"""Replay a named chaos schedule against a tiny elastic job.

Usage:
    python tools/chaos_run.py --schedule worker-kill
    python tools/chaos_run.py --schedule master-kill
    python tools/chaos_run.py --schedule @/path/to/schedule.json
    python tools/chaos_run.py --schedule '{"seed":7,"rules":[...]}'
    python tools/chaos_run.py --list

Spins up an in-process LocalJobMaster plus a one-node
ElasticTrainingAgent whose worker trains a toy counter with flash
checkpoints, with ``DLROVER_CHAOS`` armed from the requested schedule —
the same harness tests/test_chaos_schedules.py asserts against, as a
CLI for reproducing a fault pattern while debugging. Prints the job
outcome, the worker's result record, and the chaos fire summary.

Schedules containing a ``master.kill`` rule use a different harness:
the master runs as a SUBPROCESS with ``--state-dir`` (so the kill
actually severs the control plane), a supervisor restarts it with
``--restore-state`` when it dies, and the worker consumes dataset
shards through a ShardingClient — the post-run check asserts every
shard was handed out exactly once across the failover."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

WORKER = """
import json, os, time
import jax.numpy as jnp
from dlrover_tpu.common import telemetry
from dlrover_tpu.trainer.flash_checkpoint.engine import (
    ReplicatedCheckpointEngine,
)

out_dir = os.environ["CHAOS_OUT_DIR"]
total = int(os.environ.get("CHAOS_TOTAL_STEPS", "10"))
engine = ReplicatedCheckpointEngine(out_dir + "/ckpt")
restored = engine.load()
if restored is None:
    start, w = 0, jnp.zeros((4,))
else:
    start = int(restored["step"])
    w = jnp.asarray(list(restored["state"].values())[0])

for step in range(start + 1, total + 1):
    t0 = time.time()
    w = w + 1.0
    telemetry.event("step.end", step=step, dur=time.time() - t0)
    if step % 2 == 0:
        # synchronous persist: an in-flight persist would hold the shm
        # lock and make later saves skip (never reaching their fault
        # site), which would turn a chaos replay into a silent no-op
        engine.save_to_storage(step, {"w": w})
        engine.wait_for_persist(step, timeout=60)
    else:
        engine.save_to_memory(step, {"w": w})
    telemetry.flush()

with open(out_dir + "/result.json", "w") as f:
    json.dump({
        "resumed_from": start,
        "final_step": total,
        "w0": float(w[0]),
    }, f)
engine.close()
"""


def _run_in_process(out_dir: str) -> int:
    """The original harness: in-process LocalJobMaster + agent whose
    worker trains a toy counter with flash checkpoints."""
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.agent.training_agent import (
        ElasticLaunchConfig,
        ElasticTrainingAgent,
        WorkerSpec,
    )
    from dlrover_tpu.common.constants import NodeType
    from dlrover_tpu.master.master import LocalJobMaster
    from dlrover_tpu.scheduler.job import new_job_args

    master = LocalJobMaster(0, new_job_args("local", "chaos-run"))
    master.prepare()
    script = os.path.join(out_dir, "chaos_worker.py")
    with open(script, "w") as f:
        f.write(WORKER)
    config = ElasticLaunchConfig(
        min_nodes=1, max_nodes=1, nproc_per_node=1,
        monitor_interval=0.3, rdzv_timeout=60, max_restarts=3,
        log_dir=out_dir,
    )
    client = MasterClient(master.addr, 0, NodeType.WORKER)
    agent = ElasticTrainingAgent(
        config, WorkerSpec(script, (), config), client
    )
    try:
        rc = agent.run()
    finally:
        client.close()
        master.stop()

    print(f"\nagent exit code: {rc}")
    result_path = os.path.join(out_dir, "result.json")
    if os.path.exists(result_path):
        with open(result_path) as f:
            print(f"worker result: {f.read()}")
    else:
        print("worker result: MISSING (job never completed)")
    return rc


SHARD_WORKER = """
import json, os, time
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.sharding_client import ShardingClient
from dlrover_tpu.common import telemetry

out_dir = os.environ["CHAOS_OUT_DIR"]
dataset_size = int(os.environ.get("CHAOS_DATASET_SIZE", "40"))
client = MasterClient.singleton_instance()
sc = ShardingClient(
    "train", batch_size=2, num_epochs=1, dataset_size=dataset_size,
    num_minibatches_per_shard=2, master_client=client,
)
done = []
while True:
    shard = sc.fetch_shard()
    if shard is None:
        break
    t0 = time.time()
    time.sleep(0.15)  # "train" on the shard
    sc.report_batch_done()
    done.append([shard.start, shard.end])
    telemetry.event("step.end", step=len(done), dur=time.time() - t0)
    telemetry.flush()
with open(out_dir + "/result.json", "w") as f:
    json.dump({"shards": done}, f)
client.close()
"""


def _run_master_failover(schedule: dict, out_dir: str, steps: int) -> int:
    """Kill-the-master harness: the master is a SUBPROCESS persisting
    its control-plane state; a supervisor restarts it with
    ``--restore-state`` when the armed schedule kills it. The worker
    consumes dataset shards, and the post-run check asserts every shard
    was handed out exactly once across the failover — plus that the
    agent never restarted its worker."""
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.agent.training_agent import (
        ElasticLaunchConfig,
        ElasticTrainingAgent,
        WorkerSpec,
    )
    from dlrover_tpu.common.constants import NodeEnv, NodeType
    from dlrover_tpu.common.rpc import addr_connectable, find_free_port

    # the worker's shard fetches must ride the outage inside one retry
    # budget; the agent's ride-through probes fast
    os.environ.setdefault("DLROVER_RPC_MAX_ATTEMPTS", "30")
    os.environ.setdefault("DLROVER_MASTER_RIDE_POLL", "0.2")

    state_dir = os.path.join(out_dir, "master_state")
    addr_file = os.path.join(out_dir, "master_addr")
    master_log = os.path.join(out_dir, "master.log")
    port = find_free_port()
    addr = f"127.0.0.1:{port}"
    dataset_size = steps * 4  # shard size 4 (batch 2 x 2 minibatches)
    os.environ["CHAOS_DATASET_SIZE"] = str(dataset_size)
    # workers/agents re-resolve the master from this file on reconnect
    os.environ[NodeEnv.DLROVER_MASTER_ADDR_FILE] = addr_file

    env = dict(os.environ)
    env["DLROVER_TELEMETRY_ROLE"] = "master"

    def spawn(restore: bool) -> subprocess.Popen:
        cmd = [
            sys.executable, "-m", "dlrover_tpu.master.main",
            "--port", str(port), "--node_num", "1",
            "--addr-file", addr_file,
        ]
        spawn_env = dict(env)
        if restore:
            cmd += ["--restore-state", state_dir]
            # one-shot coordinator loss: a fresh process would reset
            # the rule counters and kill itself again
            spawn_env.pop("DLROVER_CHAOS", None)
        else:
            cmd += ["--state-dir", state_dir]
        with open(master_log, "ab") as log:
            return subprocess.Popen(  # noqa: S603
                cmd, env=spawn_env, stdout=log,
                stderr=subprocess.STDOUT,
            )

    proc = spawn(False)
    restarts: list[int] = []
    done = threading.Event()

    def supervise():
        nonlocal proc
        while not done.is_set():
            rc = proc.poll()
            if rc is not None and rc != 0 and not done.is_set():
                print(
                    f"master died rc={rc}; restarting with "
                    f"--restore-state {state_dir}"
                )
                restarts.append(rc)
                proc = spawn(True)
            time.sleep(0.1)

    deadline = time.time() + 30
    while not addr_connectable(addr, timeout=0.5):
        if proc.poll() not in (None, 0):
            print(f"master failed to start; see {master_log}")
            return 1
        if time.time() > deadline:
            print("master never became connectable")
            proc.kill()
            return 1
        time.sleep(0.2)
    threading.Thread(target=supervise, daemon=True).start()

    script = os.path.join(out_dir, "shard_worker.py")
    with open(script, "w") as f:
        f.write(SHARD_WORKER)
    config = ElasticLaunchConfig(
        min_nodes=1, max_nodes=1, nproc_per_node=1,
        monitor_interval=0.3, rdzv_timeout=60, max_restarts=3,
        log_dir=out_dir, master_ride_through=60,
    )
    client = MasterClient(addr, 0, NodeType.WORKER)
    agent = ElasticTrainingAgent(
        config, WorkerSpec(script, (), config), client
    )
    try:
        rc = agent.run()
    finally:
        done.set()
        client.close()
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.terminate()

    print(
        f"\nagent exit code: {rc}  worker restarts: "
        f"{agent._restart_count}  master restarts: {len(restarts)}"
    )
    result_path = os.path.join(out_dir, "result.json")
    if not os.path.exists(result_path):
        print("worker result: MISSING (job never completed)")
        return rc or 1
    with open(result_path) as f:
        covered = sorted(tuple(s) for s in json.load(f)["shards"])
    expected = [
        (i, min(i + 4, dataset_size))
        for i in range(0, dataset_size, 4)
    ]
    dupes = len(covered) - len(set(covered))
    missing = len(set(expected) - set(covered))
    print(
        f"shards handed out: {len(covered)} of {len(expected)} "
        f"(duplicated={dupes}, missing={missing})"
    )
    if dupes or missing:
        print("FAIL: shard accounting is not exactly-once")
        return rc or 1
    return rc


RESHAPE_WORKER = """
import json, os, time
import numpy as np
import jax
import jax.numpy as jnp
from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs
from dlrover_tpu.trainer.elastic.dataloader import ElasticDataLoader
from dlrover_tpu.trainer.elastic.reshape import ReshapeRequest
from dlrover_tpu.trainer.elastic.sampler import ElasticSampler

out_dir = os.environ["CHAOS_OUT_DIR"]
mode = os.environ.get("CHAOS_FLAP_MODE", "elastic")
inc = os.environ.get("CHAOS_INCARNATION", "0")
devn = int(os.environ.get("CHAOS_DEVICE_COUNT", "4"))
n_samples = int(os.environ.get("CHAOS_DATASET_SIZE", "96"))
batch = 8

rs = np.random.RandomState(0)
w_true = rs.randn(8, 1).astype(np.float32)
X = rs.randn(n_samples, 8).astype(np.float32)
Y = (X @ w_true).astype(np.float32)

# every sample fetch is logged (exactly-once accounting is asserted on
# these lines) and paced so the harness can interleave scale events
# with live training steps
log = open(os.path.join(out_dir, f"consumed.{mode}.{inc}.jsonl"), "w")

class DS:
    def __len__(self):
        return n_samples
    def __getitem__(self, i):
        log.write(f"{i}\\n")
        log.flush()
        time.sleep(0.02)
        return (X[i], Y[i])

def init_fn(rng):
    return {"w": jnp.zeros((8, 1)), "b": jnp.zeros((1,))}

def loss_fn(params, batch, rng):
    x, y = batch
    return jnp.mean((x @ params["w"] + params["b"] - y) ** 2)

axes = {"w": ("embed", None), "b": (None,)}
sampler = ElasticSampler(n_samples, num_replicas=1, rank=0, shuffle=False)
loader = ElasticDataLoader(
    DS(), batch_size=batch, sampler=sampler, config_file=""
)
args = TrainingArgs(
    output_dir=os.path.join(out_dir, f"job_{mode}"),
    micro_batch_size=batch, learning_rate=1e-2, log_steps=0,
    optimizer="sgd", num_epochs=1,
    # the elastic arm checkpoints every step so the mid-reshape kill
    # loses zero steps; the controls replay steps, not restores
    flash_checkpoint=(mode == "elastic"), save_steps=1,
    save_storage_every=10**6,
)
trainer = Trainer(loss_fn, init_fn, axes, args, train_data=loader)
trainer._adopt_accel(jax.devices()[:devn], None)

if mode == "control":
    # uninterrupted single process replaying the OBSERVED mesh schedule
    # through direct in-process reshapes — no channel, no agent, no
    # kill, no restart. Bit-identical finals prove the elasticity
    # machinery (signal/drain/ack/kill/restart/restore) is transparent.
    for i, (boundary, count) in enumerate(
        json.loads(os.environ.get("CHAOS_FLAP_PLAN", "[]"))
    ):
        trainer.args.max_steps = int(boundary)
        trainer.train()
        trainer._apply_reshape(ReshapeRequest(
            round=100 + i, world={0: 1}, total=1,
            device_count=int(count),
        ))
    trainer.args.max_steps = 0

trainer.train()
params = jax.tree.map(np.asarray, trainer.state.params)
np.savez(os.path.join(out_dir, f"params.{mode}.npz"), **params)
with open(
    os.path.join(out_dir, f"result.{mode}.{inc}.json"), "w"
) as f:
    json.dump({"final_step": trainer.global_step}, f)
trainer.close()
log.close()
"""


def _run_scale_flap(schedule: dict, out_dir: str, steps: int) -> int:
    """Scale-flap harness: one live worker subprocess, the harness
    playing the agent. Membership flaps (scale-in drain -> scale-out
    adopt) are signaled into the live worker over the reshape channel
    and must ride IN PROCESS; the armed schedule then kills the worker
    mid-reshard on the third event, and recovery must take the classic
    restart path. Asserted post-run: zero process restarts for the
    surviving worker across the flap, exactly-once dataset sample
    accounting across flap AND kill, a chaos-kill flight-recorder dump,
    and a final train state BIT-IDENTICAL to an uninterrupted control
    run replaying the same mesh schedule (plus allclose against a
    never-reshaped baseline)."""
    from dlrover_tpu.common.constants import NodeEnv

    steps = max(steps, 12)
    n_samples = steps * 8
    reshape_dir = os.path.join(out_dir, "reshape_chan")
    script = os.path.join(out_dir, "flap_worker.py")
    with open(script, "w") as f:
        f.write(RESHAPE_WORKER)

    env_base = dict(os.environ)
    repo_root = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    env_base["PYTHONPATH"] = os.pathsep.join(
        p for p in (repo_root, env_base.get("PYTHONPATH")) if p
    )
    env_base["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        "--xla_backend_optimization_level=0"
    )
    env_base["CHAOS_OUT_DIR"] = out_dir
    env_base["CHAOS_DATASET_SIZE"] = str(n_samples)
    env_base.setdefault(
        "DLROVER_TELEMETRY_DIR", os.path.join(out_dir, "telemetry")
    )

    def spawn(mode: str, inc: int, devn: int, plan=None):
        env = dict(env_base)
        env["CHAOS_FLAP_MODE"] = mode
        env["CHAOS_INCARNATION"] = str(inc)
        env["CHAOS_DEVICE_COUNT"] = str(devn)
        # separate shm/checkpoint namespaces per arm; the respawned
        # elastic incarnation SHARES its predecessor's (that is the
        # restart path's whole restore story)
        env["ELASTIC_JOB_NAME"] = f"flap_{mode}_{os.getpid()}"
        if mode == "elastic":
            env[NodeEnv.RESHAPE_DIR] = reshape_dir
        else:
            env.pop(NodeEnv.RESHAPE_DIR, None)
            env.pop("DLROVER_CHAOS", None)
        if inc > 0:
            # one-shot kill: a fresh incarnation re-arming the schedule
            # would reset the rule counters and die again
            env.pop("DLROVER_CHAOS", None)
        if plan is not None:
            env["CHAOS_FLAP_PLAN"] = json.dumps(plan)
        log = open(os.path.join(out_dir, f"worker.{mode}.{inc}.log"), "ab")
        return subprocess.Popen(  # noqa: S603
            [sys.executable, script], env=env, stdout=log,
            stderr=subprocess.STDOUT,
        )

    def consumed(mode: str, inc: int) -> list[int]:
        path = os.path.join(out_dir, f"consumed.{mode}.{inc}.jsonl")
        try:
            with open(path) as f:
                return [int(line) for line in f if line.strip()]
        except FileNotFoundError:
            return []

    def cleanup_shm():
        # the killed incarnation cannot unlink its own segments; sweep
        # every arm's job-scoped shm so repeated runs don't accumulate
        from dlrover_tpu.common.ipc import PersistentSharedMemory

        for mode in ("elastic", "control", "plain"):
            job = f"flap_{mode}_{os.getpid()}"
            for name in (
                f"dlrtpu_ckpt_{job}_0", f"dlrtpu_timer_{job}",
            ):
                try:
                    seg = PersistentSharedMemory(name=name)
                    seg.close()
                    seg.unlink()
                except (FileNotFoundError, OSError):
                    pass

    try:
        return _run_scale_flap_inner(
            out_dir, steps, n_samples, reshape_dir, spawn, consumed,
        )
    finally:
        cleanup_shm()


def _run_scale_flap_inner(
    out_dir, steps, n_samples, reshape_dir, spawn, consumed
) -> int:
    import numpy as np

    from dlrover_tpu.common import flight
    from dlrover_tpu.trainer.elastic.reshape import (
        ReshapeChannel,
        ReshapeRequest,
    )

    def wait_step(proc, inc: int, target: int, timeout: float = 180.0):
        """Wait until the elastic worker has fetched ``target`` full
        batches (== completed that many steps, fetch precedes step)."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            if len(consumed("elastic", inc)) >= target * 8:
                return True
            if proc.poll() is not None:
                return False
            time.sleep(0.05)
        return False

    def fail(msg: str) -> int:
        print(f"FAIL: {msg}")
        return 1

    telemetry_dir = os.environ.get(
        "DLROVER_TELEMETRY_DIR", os.path.join(out_dir, "telemetry")
    )
    channel = ReshapeChannel(reshape_dir)
    channel.clear()
    worker = spawn("elastic", 0, 4)
    alive = lambda: worker.poll() is None  # noqa: E731

    # --- flap: scale-in (drain) then scale-out (adopt), both in process
    if not wait_step(worker, 0, max(steps // 4, 2)):
        return fail("worker made no progress before the first flap")
    channel.signal(ReshapeRequest(
        round=2, world={0: 1}, total=1, device_count=2,
        departed={1: "drained"},
    ))
    ack2 = channel.await_ack(2, timeout=120.0, alive_fn=alive)
    if not (ack2 and ack2.get("ok")):
        return fail(f"scale-in drain was not adopted in process: {ack2}")
    channel.signal(ReshapeRequest(
        round=3, world={0: 1}, total=1, device_count=4,
    ))
    ack3 = channel.await_ack(3, timeout=120.0, alive_fn=alive)
    if not (ack3 and ack3.get("ok")):
        return fail(f"scale-out was not adopted in process: {ack3}")
    if not alive():
        return fail("worker restarted during the flap (must be zero)")
    print(
        f"flap adopted in process with zero restarts: "
        f"scale-in@step{ack2['step']} scale-out@step{ack3['step']}"
    )

    # --- third event: the armed schedule kills the worker mid-reshard
    if not wait_step(worker, 0, int(ack3["step"]) + 2):
        return fail("worker died or finished before the kill event")
    channel.signal(ReshapeRequest(
        round=4, world={0: 1}, total=1, device_count=2,
        departed={1: "drained"},
    ))
    ack4 = channel.await_ack(4, timeout=120.0, alive_fn=alive)
    if ack4 is not None:
        return fail(f"round-4 reshape should have been killed: {ack4}")
    rc = worker.wait(timeout=30)
    if rc == 0:
        return fail("worker exited clean; the mid-reshard kill never fired")
    dumps = [
        p for p in flight.list_dumps(telemetry_dir)
        if "chaos-kill" in os.path.basename(p)
    ]
    if not dumps:
        return fail("mid-reshape kill left no flight-recorder dump")
    print(f"worker killed mid-reshard (rc={rc}); flight dump: {dumps[0]}")

    # --- restart path: fresh incarnation on the round-4 world resumes
    # from the flash checkpoint and finishes the epoch
    channel.clear()
    worker = spawn("elastic", 1, 2)
    rc = worker.wait(timeout=300)
    if rc != 0:
        return fail(f"restarted worker failed rc={rc}")

    inc0, inc1 = consumed("elastic", 0), consumed("elastic", 1)
    if not inc1:
        return fail("restarted worker consumed nothing")
    # exactly-once accounting across flap AND kill: every sample
    # served exactly once across both incarnations (save_steps=1, so
    # the kill loses no step and the resume replays none)
    served = sorted(inc0 + inc1)
    if served != list(range(n_samples)):
        extra = sorted(set(inc0) & set(inc1))
        missing = sorted(set(range(n_samples)) - set(served))
        return fail(
            f"shard accounting not exactly-once: double-served="
            f"{extra[:5]} lost={missing[:5]}"
        )
    resume_step = inc1[0] // 8
    print(
        f"exactly-once: {len(inc0)}+{len(inc1)} samples, restart "
        f"resumed at step {resume_step}, 1 restart total (kill path)"
    )

    # --- controls: replay the observed mesh schedule uninterrupted
    # (bit-identity), and a never-reshaped baseline (allclose)
    plan = [
        [int(ack2["step"]), 2], [int(ack3["step"]), 4],
        [resume_step, 2],
    ]
    control = spawn("control", 0, 4, plan=plan)
    plain = spawn("plain", 0, 4)
    if control.wait(timeout=300) != 0 or plain.wait(timeout=300) != 0:
        return fail("control run failed")
    flap_p = np.load(os.path.join(out_dir, "params.elastic.npz"))
    ctrl_p = np.load(os.path.join(out_dir, "params.control.npz"))
    plain_p = np.load(os.path.join(out_dir, "params.plain.npz"))
    for k in ctrl_p.files:
        if not np.array_equal(flap_p[k], ctrl_p[k]):
            return fail(
                f"train state not bit-identical to the uninterrupted "
                f"control at leaf {k!r}"
            )
        np.testing.assert_allclose(
            flap_p[k], plain_p[k], rtol=1e-4, atol=1e-5,
            err_msg=f"flap diverged from never-reshaped baseline at {k}",
        )
    print(
        "final train state BIT-IDENTICAL to the uninterrupted control "
        "(and allclose to the never-reshaped baseline)"
    )
    return 0


WEEK_HOST = """
import json, os, time
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.common import chaos, telemetry
from dlrover_tpu.common.chaos import chaos_point
from dlrover_tpu.common.constants import NodeType, RendezvousName

rank = int(os.environ["WEEK_RANK"])
inc = int(os.environ.get("WEEK_INC", "0"))
dt = float(os.environ.get("WEEK_STEP_S", "0.05"))
slow_rank = int(os.environ.get("WEEK_SLOW_RANK", "-1"))
slow_after = float(os.environ.get("WEEK_SLOW_AFTER_S", "1e9"))
slow_factor = float(os.environ.get("WEEK_SLOW_FACTOR", "6.0"))
save_every = int(os.environ.get("WEEK_SAVE_EVERY", "5"))
out_dir = os.environ["CHAOS_OUT_DIR"]
arm = os.environ["WEEK_ARM"]
stop_file = os.path.join(out_dir, "stop." + arm)
ckpt_file = os.path.join(out_dir, "ckpt.%s.%d.json" % (arm, rank))
result_file = os.path.join(
    out_dir, "result.%s.%d.%d.json" % (arm, rank, inc)
)

client = MasterClient(
    os.environ["WEEK_MASTER_ADDR"], rank, NodeType.WORKER
)
t_start = time.time()

# toy flash checkpoint: the respawned incarnation resumes here — an
# announced preemption's pre-drain flush means ZERO replay, an
# unannounced kill replays back to the last cadence save
step = 0
if os.path.exists(ckpt_file):
    step = int(json.load(open(ckpt_file)).get("step", 0))
resumed_from = step


def stopped():
    return os.path.exists(stop_file)


def save_ckpt():
    with open(ckpt_file + ".tmp", "w") as f:
        json.dump({"step": step}, f)
    os.replace(ckpt_file + ".tmp", ckpt_file)


def finish(drained=False, evicted=False, deadline=0.0):
    with open(result_file, "w") as f:
        json.dump({
            "rank": rank, "inc": inc, "steps": step,
            "resumed_from": resumed_from,
            "drained": drained, "evicted": evicted,
            "deadline": deadline,
        }, f)
    telemetry.flush()
    client.close()


# join + poll until a formed world contains this rank
client.join_rendezvous(rank, 1, RendezvousName.ELASTIC_TRAINING)
world = None
while not stopped():
    w = client.get_comm_world(RendezvousName.ELASTIC_TRAINING, rank)
    if w and w.world and rank in w.world:
        world = w
        break
    time.sleep(0.1)
if world is None:
    finish()
    raise SystemExit(0)

round_, world_size, sync_i = world.round, len(world.world), 0
last_hb = last_ship = last_world = 0.0
evicted_out = False


def adopt(w, stall_s):
    # surviving member: adopt the new round IN PROCESS (the real
    # machinery is PR 9's reshaper; this sim prices the stall)
    global round_, world_size, sync_i
    telemetry.event(
        "elastic.reshape", round=w.round, dur=max(stall_s, 0.001)
    )
    round_, world_size, sync_i = w.round, len(w.world), 0


def excluded(w):
    # a round FORMED (round advanced) and this rank is not in it:
    # evicted. An empty world at our own round number is just a
    # dissolution in progress — keep waiting.
    return w is not None and w.round != round_ and (
        (w.world and rank not in w.world) or not w.world
    )


while not stopped():
    # announced-preemption seam: the chaos ``notice`` action fires here
    # (time-anchored via ``elapsed``) and arms the deadline kill;
    # consuming the notice buys the lead window for the brain-directed
    # drain
    chaos_point(
        "preempt.notice", rank=rank,
        elapsed=time.time() - t_start,
    )
    note = chaos.take_preempt_notice()
    if note is not None:
        deadline = float(note["deadline"])
        lead = max(deadline - time.time(), 0.0)
        telemetry.event("preempt.notice", rank=rank, lead=lead)
        directive = None
        try:
            directive = client.report_preempt_notice(
                rank, deadline, lead
            )
        except Exception:
            pass
        if directive is not None and \\
                getattr(directive, "action", "") == "drain":
            t0 = time.monotonic()
            try:
                client.drain_node(rank)
            except Exception:
                pass
            save_ckpt()  # the pre-drain flush: zero replay
            telemetry.event(
                "elastic.drained", rank=rank,
                dur=time.monotonic() - t0, deadline=deadline,
            )
            finish(drained=True, deadline=deadline)
            raise SystemExit(0)
        # directive "none" / master unreachable: keep training until
        # the armed kill lands (the unannounced fallback path)
    now = time.time()
    if now - last_hb > 0.5:
        # heartbeats drive the master's diagnosis + brain sweep
        try:
            client.report_heart_beat()
        except Exception:
            pass
        last_hb = now
    if now - last_world > 0.5:
        # steady-state membership poll: catches joins (scale-out) that
        # never stall the barrier, and our own eviction
        last_world = now
        try:
            w = client.get_comm_world(
                RendezvousName.ELASTIC_TRAINING, rank
            )
        except Exception:
            w = None
        if excluded(w):
            evicted_out = True
            break
        if w is not None and w.world and w.round != round_ and \\
                rank in w.world:
            adopt(w, 0.0)
    # lockstep step barrier through the master kv-store: a dead peer
    # never arrives, so survivors genuinely STALL until the membership
    # change propagates — the cost the predictive drain removes
    key = "week:%s:r%d:s%d" % (arm, round_, sync_i)
    t_bar = time.monotonic()
    try:
        n = client.kv_store_add(key, 1)
    except Exception:
        n = 0
    new_world = None
    while n < world_size and not stopped():
        time.sleep(0.03)
        try:
            w = client.get_comm_world(
                RendezvousName.ELASTIC_TRAINING, rank
            )
        except Exception:
            w = None
        if excluded(w):
            evicted_out = True
            break
        if w is not None and w.world and w.round != round_:
            new_world = w
            break
        try:
            n = client.kv_store_add(key, 0)
        except Exception:
            pass
    if stopped() or evicted_out:
        break
    if new_world is not None:
        if rank not in new_world.world:
            evicted_out = True
            break
        adopt(new_world, time.monotonic() - t_bar)
        continue
    this_dt = dt
    if rank == slow_rank and time.time() - t_start >= slow_after:
        this_dt = dt * slow_factor
    time.sleep(this_dt)
    step += 1
    sync_i += 1
    telemetry.event("step.end", step=step, dur=this_dt)
    telemetry.gauge_set(
        "timer.phase.recent_avg_ms", this_dt * 1e3, phase="step"
    )
    telemetry.gauge_set(
        "timer.phase.avg_ms", this_dt * 1e3, phase="step"
    )
    if step % save_every == 0:
        save_ckpt()
        telemetry.event("ckpt.save", step=step, dur=0.01)
    if time.time() - last_ship > 0.7:
        snap = telemetry.snapshot()
        if snap is not None:
            try:
                client.report_telemetry(snap)
            except Exception:
                pass
        telemetry.flush()
        last_ship = time.time()

finish(evicted=evicted_out)
"""


def run_week_arm(out_dir: str, arm: str, schedule: dict, cfg: dict) -> dict:
    """One week-in-the-life arm: an in-process master (repair brain on
    or off per ``cfg['brain']``), subprocess hosts in a kv-store
    lockstep barrier, and this harness playing the PLATFORM — spawning
    hosts, detecting unannounced deaths (simulated heartbeat timeout ->
    ``remove_alive_node``), respawning replacements, and driving the
    scale-out joiner. Returns the arm's ledger, plan summary and
    respawn accounting."""
    from dlrover_tpu.common import telemetry
    from dlrover_tpu.common.constants import NodeEnv, RendezvousName
    from dlrover_tpu.common.telemetry import JobTelemetry
    from dlrover_tpu.master.master import LocalJobMaster
    from dlrover_tpu.scheduler.job import new_job_args

    arm_dir = os.path.join(out_dir, f"week_{arm}")
    tele_dir = os.path.join(arm_dir, "telemetry")
    os.makedirs(tele_dir, exist_ok=True)
    # per-arm master AND a fresh telemetry registry: the two arms'
    # ledgers must never contaminate each other
    os.environ["DLROVER_TELEMETRY_DIR"] = tele_dir
    os.environ["DLROVER_TELEMETRY_ROLE"] = "master"
    os.environ["DLROVER_BRAIN"] = "1" if cfg.get("brain", True) else "0"
    telemetry.enable()
    master = LocalJobMaster(0, new_job_args("local", f"week-{arm}"))
    master.prepare()
    rdzv = master.rdzv_managers[RendezvousName.ELASTIC_TRAINING]
    rdzv.update_rdzv_params(
        cfg.get("min_nodes", 2), 16, cfg.get("rdzv_wait", 1.0), 1
    )

    script = os.path.join(arm_dir, "week_host.py")
    with open(script, "w") as f:
        f.write(WEEK_HOST)
    stop_file = os.path.join(arm_dir, f"stop.{arm}")
    repo_root = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )

    def spawn(rank: int, inc: int) -> subprocess.Popen:
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (repo_root, env.get("PYTHONPATH")) if p
        )
        env.update({
            "WEEK_MASTER_ADDR": master.addr,
            "WEEK_RANK": str(rank),
            "WEEK_INC": str(inc),
            "WEEK_ARM": arm,
            "WEEK_STEP_S": str(cfg.get("dt", 0.05)),
            "NODE_RANK": str(rank),
            "DLROVER_TELEMETRY_ROLE": "worker",
            "DLROVER_TELEMETRY_DIR": tele_dir,
            "CHAOS_OUT_DIR": arm_dir,
            "JAX_PLATFORMS": "cpu",
        })
        slow = cfg.get("slow") or {}
        env["WEEK_SLOW_RANK"] = str(slow.get("rank", -1))
        env["WEEK_SLOW_AFTER_S"] = str(slow.get("after_s", 1e9))
        env["WEEK_SLOW_FACTOR"] = str(slow.get("factor", 6.0))
        if inc == 0:
            env["DLROVER_CHAOS"] = json.dumps(schedule)
        else:
            # one-shot faults: a respawned incarnation re-arming the
            # schedule would reset the rule counters and die again
            env.pop("DLROVER_CHAOS", None)
        env.pop(NodeEnv.DLROVER_MASTER_ADDR_FILE, None)
        log = open(
            os.path.join(arm_dir, f"host.{rank}.{inc}.log"), "ab"
        )
        proc = subprocess.Popen(  # noqa: S603
            [sys.executable, script], env=env, stdout=log,
            stderr=subprocess.STDOUT,
        )
        log.close()
        return proc

    def result_of(rank: int, inc: int) -> dict | None:
        path = os.path.join(
            arm_dir, f"result.{arm}.{rank}.{inc}.json"
        )
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    hosts = int(cfg.get("hosts", 3))
    procs: dict[int, subprocess.Popen | None] = {}
    incs = {r: 0 for r in range(hosts)}
    respawns = {r: 0 for r in range(hosts)}
    evicted: set[int] = set()
    drained_ranks: set[int] = set()
    # rank -> (respawn_at_wall, needs_removal)
    pending: dict[int, tuple[float, bool]] = {}
    for r in range(hosts):
        procs[r] = spawn(r, 0)
    scaled = False
    t0 = time.time()
    t_end = t0 + float(cfg.get("duration_s", 26.0))
    detect_s = float(cfg.get("detect_s", 1.5))
    try:
        while time.time() < t_end:
            time.sleep(0.15)
            now = time.time()
            scale_at = cfg.get("scale_out_at_s")
            if scale_at and not scaled and now - t0 >= scale_at:
                scaled = True
                r = hosts
                incs[r] = 0
                respawns[r] = 0
                procs[r] = spawn(r, 0)
            for r, p in list(procs.items()):
                if p is None or p.poll() is None:
                    continue
                res = result_of(r, incs[r])
                procs[r] = None
                if res and res.get("evicted"):
                    # the brain shot this straggler; the platform would
                    # replace it on another host — out of scope here
                    evicted.add(r)
                    continue
                if res and res.get("drained"):
                    # graceful predictive drain: the replacement shows
                    # up once the announced deadline has passed
                    drained_ranks.add(r)
                    pending[r] = (
                        max(now, float(res.get("deadline", now)))
                        + 0.3,
                        False,
                    )
                else:
                    # unannounced death: the platform notices via
                    # heartbeat timeout, removes the node (survivors
                    # stall until then), then relaunches it
                    pending[r] = (now + detect_s, True)
            for r, (at, needs_removal) in list(pending.items()):
                if now < at:
                    continue
                del pending[r]
                if needs_removal:
                    rdzv.remove_alive_node(r)
                incs[r] += 1
                respawns[r] += 1
                procs[r] = spawn(r, incs[r])
    finally:
        with open(stop_file, "w") as f:
            f.write("stop")
        deadline = time.time() + 30
        for p in procs.values():
            if p is None:
                continue
            try:
                p.wait(timeout=max(deadline - time.time(), 1.0))
            except subprocess.TimeoutExpired:
                p.kill()
        plans = master.servicer.brain.summary()
        master.stop()
        telemetry.flush()
    report = JobTelemetry.from_dir(tele_dir).report()
    ledger = report["ledger"]
    # fleet throughput goodput: achieved steps over the ideal the
    # initial fleet could have produced in the window. The ledger's
    # collapsed utilization view ("was ANYONE productive") cannot see a
    # fleet slowed 6x by a straggler or stalled survivors overlapped by
    # the slow host's own long steps — steps/ideal can, and it is what
    # the brain's policies actually move.
    results_by_rank: dict[int, list[dict]] = {}
    for name in os.listdir(arm_dir):
        if not name.startswith(f"result.{arm}."):
            continue
        try:
            with open(os.path.join(arm_dir, name)) as f:
                res = json.load(f)
        except (OSError, ValueError):
            continue
        results_by_rank.setdefault(
            int(res.get("rank", -1)), []
        ).append(res)
    steps_by_rank: dict[int, int] = {}
    replay_by_rank: dict[int, int] = {}
    for r, results in results_by_rank.items():
        results.sort(key=lambda x: int(x.get("inc", 0)))
        steps_by_rank[r] = max(
            int(x.get("steps", 0)) for x in results
        )
        # a respawned incarnation resumed at its checkpoint: the
        # predecessor's steps past that point were replayed work
        replay_by_rank[r] = sum(
            max(
                int(prev.get("steps", 0))
                - int(cur.get("resumed_from", 0)),
                0,
            )
            for prev, cur in zip(results, results[1:])
        )
    dt = float(cfg.get("dt", 0.05))
    duration = float(cfg.get("duration_s", 26.0))
    ideal = (duration / dt) * hosts
    steps_total = sum(steps_by_rank.values())
    goodput_pct = (
        100.0 * min(steps_total / ideal, 1.0) if ideal > 0 else 0.0
    )
    return {
        "arm": arm,
        "brain": cfg.get("brain", True),
        "goodput_pct": round(goodput_pct, 3),
        "steps_total": steps_total,
        "steps_by_rank": steps_by_rank,
        "replay_by_rank": replay_by_rank,
        "dt": dt,
        "ledger_goodput_pct": round(
            ledger.get("goodput", 0.0) * 100, 3
        ),
        "total_s": round(ledger.get("total_s", 0.0), 3),
        "categories": {
            k: round(v, 3)
            for k, v in (ledger.get("categories") or {}).items()
        },
        "plans": plans,
        "respawns": respawns,
        "evicted": sorted(evicted),
        "drained": sorted(drained_ranks),
        "telemetry_dir": tele_dir,
        "timeline": [
            {
                "t": ev.get("t"), "kind": ev.get("kind"),
                "source": ev.get("source"), "dur": ev.get("dur"),
                "rank": ev.get("rank"),
            }
            for ev in report.get("timeline", ())
            if ev.get("kind") in (
                "preempt.notice", "elastic.reshape",
                "elastic.drained", "chaos.fire",
            )
        ],
    }


def _build_serving_master():
    """A servicer wired like LocalJobMaster builds it (no socket) —
    the serving harness drives its dispatch arms in-process."""
    from dlrover_tpu.common.constants import RendezvousName
    from dlrover_tpu.master.elastic_ps import ElasticPsService
    from dlrover_tpu.master.job_manager import LocalJobManager
    from dlrover_tpu.master.kvstore import KVStoreService, SyncService
    from dlrover_tpu.master.rendezvous import (
        ElasticTrainingRendezvousManager,
        NetworkCheckRendezvousManager,
    )
    from dlrover_tpu.master.servicer import MasterServicer
    from dlrover_tpu.master.shard.task_manager import TaskManager

    task_manager = TaskManager()
    job_manager = LocalJobManager(None, task_manager.speed_monitor)
    job_manager.start()
    rdzv = {
        RendezvousName.ELASTIC_TRAINING: (
            ElasticTrainingRendezvousManager()
        ),
        RendezvousName.NETWORK_CHECK: NetworkCheckRendezvousManager(),
    }
    return MasterServicer(
        task_manager=task_manager,
        job_manager=job_manager,
        rdzv_managers=rdzv,
        kv_store=KVStoreService(),
        sync_service=SyncService(),
        elastic_ps_service=ElasticPsService(),
    )


def _run_serve_kill(schedule: dict, out_dir: str, steps: int) -> int:
    """The serving-arm availability proof: an in-process master + a
    3-worker decode pool serving a seeded Poisson sweep with the
    armed schedule killing one worker mid-sweep. Asserts the ledger's
    exactly-once contract (everything completes, the victim's leases
    re-queue exactly once, nothing is dropped or double-served) and
    prints the serve_* keys (``serving/loadgen.py:summarize``)."""
    import jax

    from dlrover_tpu.common import messages as msg
    from dlrover_tpu.models import llama_init
    from dlrover_tpu.models.llama import LlamaConfig
    from dlrover_tpu.serving import loadgen
    from dlrover_tpu.serving.engine import DecodeEngine
    from dlrover_tpu.serving.worker import (
        DecodeWorker,
        LocalServingClient,
    )

    n_workers = 3
    n_requests = max(int(steps), 4) * 4
    rate_hz = 60.0
    config = LlamaConfig(
        vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        mlp_dim=64, max_seq_len=128, attn_impl="reference",
        remat=False, dtype="float32",
    )
    params = llama_init(config, jax.random.key(0))
    servicer = _build_serving_master()
    # decode steps are milliseconds here: a dead worker's leases must
    # re-queue fast enough to land inside the sweep
    servicer.serving._lease_timeout = 2.0
    servicer.serving._worker_ttl = 3.0

    workers = []
    for rank in range(n_workers):
        engine = DecodeEngine(config, params, slots=4, capacity=64)
        engine.warmup(buckets=[8, 16])
        workers.append(DecodeWorker(
            LocalServingClient(servicer, rank), engine, rank,
            source=f"decode-{rank}-{os.getpid()}",
        ))
    for w in workers:
        w.start()

    requests = loadgen.make_requests(
        n_requests, config.vocab_size, prompt_len_range=(4, 14),
        max_new_tokens=8, seed=schedule.get("seed", 41),
    )
    arrivals = loadgen.poisson_arrivals(
        n_requests, rate_hz, seed=schedule.get("seed", 41)
    )

    def submit(payload: dict) -> bool:
        return bool(servicer.report(
            "client", 0, msg.ServeSubmitRequest(**payload)
        ))

    t0 = time.monotonic()
    submitted = loadgen.run_open_loop(submit, requests, arrivals)
    deadline = time.time() + 120
    while time.time() < deadline:
        counts = servicer.serving.counts()
        if counts["done"] + counts["failed"] >= submitted:
            break
        time.sleep(0.05)
    wall_s = time.monotonic() - t0
    for w in workers:
        w.stop()

    counts = servicer.serving.counts()
    summary = servicer.serving.summary()
    finished = [f for w in workers for f in w.finished]
    keys = loadgen.summarize(submitted, finished, wall_s)
    keys["serve_goodput_pct"] = round(
        counts["done"] / submitted * 100.0, 3
    )
    result = {
        "keys": keys,
        "counts": counts,
        "summary": summary,
        "crashed": [w.rank for w in workers if w.crashed],
        "abandoned": sorted(
            rid for w in workers for rid in w.abandoned
        ),
        "wall_s": round(wall_s, 3),
    }
    with open(os.path.join(out_dir, "serve_report.json"), "w") as f:
        json.dump(result, f, indent=2)

    print("\n=== serve-kill sweep ===")
    print(f"submitted={submitted}  counts={counts}")
    print(f"crashed workers: {result['crashed']}  "
          f"abandoned in flight: {len(result['abandoned'])}")
    print(f"keys: {json.dumps(keys)}")

    failures = []
    if counts["done"] != submitted:
        failures.append(
            f"only {counts['done']}/{submitted} requests completed — "
            f"something was dropped or wedged"
        )
    if counts["failed"]:
        failures.append(f"{counts['failed']} request(s) marked failed")
    if not result["crashed"]:
        failures.append("the schedule never killed a worker")
    elif not result["abandoned"] and not counts["requeued_total"]:
        failures.append(
            "the killed worker had nothing in flight — the sweep "
            "never exercised the re-queue path"
        )
    # exactly-once re-queue: the victim's abandonments all re-queued
    # (lease expiry may also requeue off a slow-but-alive worker — the
    # stale-report guard absorbs that), and NO request was ever leased
    # beyond the cap (original + one re-queue)
    if counts["requeued_total"] < len(result["abandoned"]):
        failures.append(
            f"only {counts['requeued_total']} re-queue(s) for "
            f"{len(result['abandoned'])} abandoned request(s) — "
            f"something was silently dropped"
        )
    if counts["max_attempts_seen"] > 2:
        failures.append(
            f"a request was leased {counts['max_attempts_seen']} "
            f"times — re-queued more than once"
        )
    overlap = max(
        w.scheduler.stats()["overlap_high_water"] for w in workers
    )
    if overlap < 2:
        failures.append(
            "no two sequences ever overlapped in one decode step"
        )
    for f_ in failures:
        print(f"FAIL: {f_}")
    if not failures:
        print("serve-kill: PASS")
    return 1 if failures else 0


def _run_bad_host(schedule: dict, out_dir: str, steps: int) -> int:
    """The health-plane proof, in-process: real probes (host stand-in
    legs) against a real servicer, with the armed schedule degrading
    host 3's join-time probe and host 1's in-band re-probes.

    Asserts the full sense->gate->act loop: (1) the degraded host is
    refused at the door — it never enters a round; (2) a mid-run
    degradation becomes a ``diagnosis.hw_degraded`` verdict and a
    brain drain+reshape with ZERO survivor restarts; (3) the verdict
    survives a master failover; (4) the recovered host re-admits after
    its backoff re-probe comes back clean. Publishes the
    probe_join_overhead_s / bad_host_quarantine_s headline keys."""
    from dlrover_tpu.agent.probe import run_probe
    from dlrover_tpu.common import messages as msg
    from dlrover_tpu.common.constants import RendezvousName

    def build_master(state_dir: str):
        from dlrover_tpu.master.state_store import MasterStateStore

        servicer = _build_serving_master()
        # harness-speed backoff: seconds, not the production 30 s
        servicer.health._backoff = 0.3
        servicer.health._backoff_cap = 5.0
        # every "host" of this harness is a probe run on one shared
        # CPU, where a neighbour's load stretches a clean 35 ms leg to
        # 75 ms and a 210 ms one to 420 (2.0-2.2x: a clean host was
        # parked at the door). The schedule's degrade adds 0.4 s to a
        # leg, 9x the hbm leg and 260x the collective one, so these
        # tell the two apart where the production 2x / 25 ms cannot.
        servicer.health._slack_ms = 200.0
        servicer.health._ratio = 4.0
        servicer.health._refuse_ratio = 8.0
        store = MasterStateStore(state_dir)
        store.bind(
            task_manager=servicer.task_manager,
            rdzv_managers=servicer.rdzv_managers,
            kv_store=servicer.kv_store,
            sync_service=servicer.sync_service,
            servicer=servicer,
            port=0,
        )
        servicer.state_store = store
        return servicer, store

    def join(servicer, rank: int, report: dict) -> bool:
        return bool(servicer.report(
            "worker", rank, msg.JoinRendezvousRequest(
                node_id=rank, node_rank=rank, local_world_size=1,
                rdzv_name=RendezvousName.ELASTIC_TRAINING,
                node_ip="", probe_report=report,
            )
        ))

    def health_of(servicer, rank: int):
        return servicer.get(
            "worker", rank, msg.NodeHealthRequest(node_rank=rank)
        )

    def world_of(servicer, rank: int) -> dict:
        w = servicer.get("worker", rank, msg.CommWorldRequest(
            node_id=rank, rdzv_name=RendezvousName.ELASTIC_TRAINING,
        ))
        return dict(w.world or {})

    failures: list[str] = []
    state_dir = os.path.join(out_dir, "master_state")
    servicer, store = build_master(state_dir)
    elastic = servicer.rdzv_managers[RendezvousName.ELASTIC_TRAINING]
    elastic.update_rdzv_params(3, 3, 0.0, 1)

    # ---- phase 1: the degraded host is refused at the door ----------
    reports = {r: run_probe(r) for r in (0, 1, 2)}
    probe_join_overhead_s = max(
        r["elapsed_s"] for r in reports.values()
    )
    for r in (0, 1, 2):
        join(servicer, r, reports[r])
    join(servicer, 3, run_probe(3))  # chaos-degraded legs
    world = world_of(servicer, 0)
    print(f"phase 1: world={sorted(world)}  "
          f"host 3: {health_of(servicer, 3)}")
    if sorted(world) != [0, 1, 2]:
        failures.append(f"expected world {{0,1,2}}, got {sorted(world)}")
    verdict3 = health_of(servicer, 3)
    if verdict3.verdict not in ("refuse", "quarantine"):
        failures.append(
            f"degraded host 3 was not parked (got {verdict3.verdict!r})"
        )
    if 3 in world_of(servicer, 3):
        failures.append("degraded host 3 entered the round")
    if probe_join_overhead_s >= 5.0:
        failures.append(
            f"join probe cost {probe_join_overhead_s:.2f}s on the "
            f"CPU smoke arm (budget 5s)"
        )

    # ---- phase 2: mid-run degradation -> hw verdict -> drain --------
    elastic.update_rdzv_params(2, 3, 0.0, 1)
    t_q0 = time.monotonic()
    for _ in range(3):  # the health manager's persistence streak
        servicer.report("worker", 1, msg.HostProbeReport(
            node_rank=1, report=run_probe(1),  # chaos-degraded now
        ))
    verdicts = servicer.diagnosis.check(force=True)
    hw = verdicts.get("hw", {})
    if 1 not in hw:
        failures.append(f"no hw_degraded verdict for host 1 (got {hw})")
    deadline = time.time() + 30
    world = {}
    while time.time() < deadline:
        world = world_of(servicer, 0)
        if sorted(world) == [0, 2]:
            break
        servicer.diagnosis.check(force=True)
        time.sleep(0.05)
    bad_host_quarantine_s = time.monotonic() - t_q0
    round_ = elastic.rdzv_round()
    member_verdicts, departed = elastic.round_verdicts(round_)
    print(f"phase 2: world={sorted(world)} verdicts={member_verdicts} "
          f"departed={departed} hw={hw} "
          f"({bad_host_quarantine_s:.2f}s)")
    if sorted(world) != [0, 2]:
        failures.append(
            f"drain+reshape never re-formed {{0,2}} (got {sorted(world)})"
        )
    if departed.get(1) != "drained":
        failures.append(
            f"host 1 should depart as drained, got {departed}"
        )
    restarted = [r for r, v in member_verdicts.items() if v != "reshape"]
    if restarted:
        failures.append(
            f"survivors {restarted} got restart verdicts — reshape-"
            f"first was violated"
        )

    # ---- phase 3: the quarantine verdict survives a failover --------
    store.write_snapshot()
    servicer2, store2 = build_master(state_dir)
    store2.restore()
    elastic2 = servicer2.rdzv_managers[
        RendezvousName.ELASTIC_TRAINING
    ]
    restored3 = health_of(servicer2, 3)
    print(f"phase 3: restored verdict for host 3: {restored3}")
    if (restored3.verdict, restored3.reason, restored3.strikes) != (
        verdict3.verdict, verdict3.reason, verdict3.strikes
    ):
        failures.append(
            f"failover changed host 3's verdict: "
            f"{verdict3} -> {restored3}"
        )

    # ---- phase 4: the recovered host re-admits after backoff --------
    elastic2.update_rdzv_params(3, 3, 0.0, 1)
    for r in (0, 2):
        join(servicer2, r, run_probe(r))
    admitted = False
    deadline = time.time() + 60
    while time.time() < deadline:
        verdict = health_of(servicer2, 3)
        if verdict.verdict in ("pass", "unknown"):
            admitted = True
            break
        # wait out the backoff, then re-join with a FRESH probe —
        # exactly the agent's quarantine loop (the chaos rule's fire
        # budget runs dry, so a later probe comes back clean)
        time.sleep(max(verdict.retry_after_s, 0.05))
        join(servicer2, 3, run_probe(3))
        if health_of(servicer2, 3).verdict == "pass":
            admitted = True
            break
    world = world_of(servicer2, 3)
    print(f"phase 4: admitted={admitted} world={sorted(world)}")
    if not admitted:
        failures.append(
            "recovered host 3 never re-admitted after backoff re-probe"
        )
    if sorted(world) != [0, 2, 3]:
        failures.append(
            f"re-admitted world should be {{0,2,3}}, got {sorted(world)}"
        )

    keys = {
        "probe_join_overhead_s": round(probe_join_overhead_s, 4),
        "bad_host_quarantine_s": round(bad_host_quarantine_s, 3),
    }
    result = {
        "keys": keys,
        "health": servicer2.health.summary(),
        "failures": failures,
    }
    with open(os.path.join(out_dir, "bad_host_report.json"), "w") as f:
        json.dump(result, f, indent=2)
    print(f"keys: {json.dumps(keys)}")
    for f_ in failures:
        print(f"FAIL: {f_}")
    if not failures:
        print("bad-host: PASS")
    return 1 if failures else 0


def _run_week(schedule: dict, out_dir: str, steps: int) -> int:
    """The week-in-the-life proof: the SAME seed brain-on and
    brain-off. Announced preemption, hard kill, persistent straggler,
    scale-out; prints goodput_brain_on_pct / goodput_brain_off_pct
    / preempt_notice_saved_s and asserts the brain-on contract."""
    cfg = {
        "hosts": 3,
        "dt": 0.05,
        "duration_s": max(float(steps), 10.0) * 2.8,
        "min_nodes": 2,
        "rdzv_wait": 1.0,
        "detect_s": 1.5,
        "slow": {"rank": 2, "after_s": 9.0, "factor": 6.0},
        "scale_out_at_s": 20.0,
    }
    on = run_week_arm(out_dir, "on", schedule, {**cfg, "brain": True})
    off = run_week_arm(out_dir, "off", schedule, {**cfg, "brain": False})

    def preempt_cost(arm: dict, victim: int) -> float:
        """Seconds the announced preemption cost this arm: the worst
        survivor stall (elastic.reshape dur) inside the 10 s after the
        victim's preempt.notice event, plus the victim's replayed
        work."""
        notices = [
            ev["t"] for ev in arm["timeline"]
            if ev["kind"] == "preempt.notice"
            and ev.get("rank") == victim and ev.get("t")
        ]
        stall = 0.0
        if notices:
            t0 = min(notices)
            stall = max(
                (
                    float(ev.get("dur") or 0.0)
                    for ev in arm["timeline"]
                    if ev["kind"] == "elastic.reshape"
                    and ev.get("t") is not None
                    and t0 <= ev["t"] <= t0 + 10.0
                ),
                default=0.0,
            )
        replay = arm["replay_by_rank"].get(victim, 0) * arm["dt"]
        return stall + replay

    victim = next(
        (
            int(r.get("rank", -1))
            for r in schedule.get("rules", ())
            if r.get("action") == "notice"
        ),
        1,
    )
    saved = max(
        preempt_cost(off, victim) - preempt_cost(on, victim), 0.0
    )
    keys = {
        "goodput_brain_on_pct": on["goodput_pct"],
        "goodput_brain_off_pct": off["goodput_pct"],
        "preempt_notice_saved_s": round(saved, 3),
    }
    result = {"keys": keys, "on": on, "off": off}
    with open(os.path.join(out_dir, "week_report.json"), "w") as f:
        json.dump(result, f, indent=2)
    print("\n=== week-in-the-life ===")
    for arm in (on, off):
        print(
            f"brain={'on ' if arm['brain'] else 'off'} goodput "
            f"{arm['goodput_pct']:6.2f}%  categories={arm['categories']}"
            f"  respawns={arm['respawns']}  evicted={arm['evicted']}"
        )
    print(f"keys: {json.dumps(keys)}")

    failures = []
    done_kinds = {
        p["kind"] for p in on["plans"].get("recent", ())
        if p["state"] == "done"
    }
    if "predictive_drain" not in done_kinds:
        failures.append("no predictive_drain plan completed (brain on)")
    if "evict_straggler" not in done_kinds:
        failures.append("the persistent straggler was never evicted")
    if 2 not in on["evicted"]:
        failures.append("straggler host (rank 2) did not exit evicted")
    if 1 not in on["drained"]:
        failures.append(
            "the announced preemption (rank 1) was not pre-drained"
        )
    # zero survivor restarts on the announced preemption: only the two
    # victims (rank 0 hard kill, rank 1 preemption) may respawn
    survivors_respawned = {
        r: n for r, n in on["respawns"].items()
        if n and r not in (0, 1)
    }
    if survivors_respawned:
        failures.append(
            f"survivor host(s) restarted: {survivors_respawned}"
        )
    if on["goodput_pct"] <= off["goodput_pct"]:
        failures.append(
            f"goodput brain-on ({on['goodput_pct']}%) did not beat "
            f"brain-off ({off['goodput_pct']}%)"
        )
    for f_ in failures:
        print(f"FAIL: {f_}")
    if not failures:
        print("week-in-the-life: PASS")
    return 1 if failures else 0


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--schedule",
        help="named schedule, inline JSON, or @/path/to/schedule.json",
    )
    parser.add_argument(
        "--list", action="store_true", help="list named schedules"
    )
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument(
        "--out-dir", default="", help="work dir (default: a temp dir)"
    )
    parser.add_argument(
        "--keep", action="store_true",
        help="keep the work dir (logs, checkpoints) for inspection",
    )
    args = parser.parse_args()

    # env must be armed BEFORE dlrover_tpu imports anywhere (the chaos
    # and telemetry modules read it once at import), and before jax
    # picks a backend. This process hosts the agent AND the in-process
    # local master; its telemetry source is labeled "agent".
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("DLROVER_TELEMETRY_ROLE", "agent")
    from dlrover_tpu.common import chaos

    if args.list or not args.schedule:
        print("named schedules:")
        width = max(len(n) for n in chaos.NAMED_SCHEDULES)
        for name, sched in chaos.NAMED_SCHEDULES.items():
            desc = sched.get("desc", "")
            print(f"  {name:<{width}}  {desc}")
        print(
            "\nreplay one with --schedule <name>; full JSON via "
            "python -c 'from dlrover_tpu.common import chaos; "
            "print(chaos.NAMED_SCHEDULES[\"<name>\"])'"
        )
        return 0

    schedule = chaos.resolve_schedule(args.schedule)
    out_dir = args.out_dir or tempfile.mkdtemp(prefix="chaos_run_")
    os.makedirs(out_dir, exist_ok=True)
    os.environ["CHAOS_OUT_DIR"] = out_dir
    os.environ["CHAOS_TOTAL_STEPS"] = str(args.steps)
    os.environ["DLROVER_TPU_SOCKET_DIR"] = os.path.join(out_dir, "socks")
    os.environ["ELASTIC_JOB_NAME"] = f"chaos_run_{os.getpid()}"
    # telemetry: every process (this one + workers) leaves a snapshot so
    # the post-run goodput ledger/timeline can be assembled
    tele_dir = os.path.join(out_dir, "telemetry")
    os.environ.setdefault("DLROVER_TELEMETRY_DIR", tele_dir)
    # the worker subprocess arms itself from this env; this (agent)
    # process stays clean so master/agent control flow is unperturbed
    # unless the schedule targets agent/master sites — then arm locally
    os.environ[chaos.ENV_VAR] = json.dumps(schedule)
    agent_sites = {
        "rpc.send", "rpc.recv", "rdzv.join", "agent.spawn",
        # the serving harness runs master + decode pool in THIS process
        "serve.step", "serve.admit",
    }
    if any(
        r.get("site") in agent_sites
        # the health-plane harness runs its probes in THIS process
        or str(r.get("site", "")).startswith("probe.")
        for r in schedule.get("rules", [])
    ):
        chaos.install(schedule)

    if any(
        r.get("site") == "preempt.notice"
        for r in schedule.get("rules", [])
    ):
        # repair-brain harness: in-process master + subprocess hosts,
        # same seed brain-on vs brain-off
        rc = _run_week(schedule, out_dir, args.steps)
    elif any(
        str(r.get("site", "")).startswith("serve.")
        for r in schedule.get("rules", [])
    ):
        # serving harness: in-process master + decode pool under a
        # Poisson sweep, one worker chaos-killed mid-flight
        rc = _run_serve_kill(schedule, out_dir, args.steps)
    elif any(
        str(r.get("site", "")).startswith("probe.")
        for r in schedule.get("rules", [])
    ):
        # health-plane harness: in-process master, real probes, the
        # schedule degrading one host at the door and one mid-run
        rc = _run_bad_host(schedule, out_dir, args.steps)
    elif any(
        r.get("site") == "master.kill"
        for r in schedule.get("rules", [])
    ):
        # coordinator-loss harness: subprocess master + supervisor
        rc = _run_master_failover(schedule, out_dir, args.steps)
    elif any(
        str(r.get("site", "")).startswith("elastic.")
        for r in schedule.get("rules", [])
    ):
        # membership-flap harness: live worker + harness-driven scale
        # events over the reshape channel, restart only as fallback
        rc = _run_scale_flap(schedule, out_dir, args.steps)
    else:
        rc = _run_in_process(out_dir)

    reg = chaos.active_registry()
    if reg is not None:
        print(f"agent-side chaos fires: {reg.summary()}")
    from dlrover_tpu.common import flight, telemetry
    from dlrover_tpu.common.telemetry import JobTelemetry, format_report

    telemetry.flush()  # this (agent/master) process's snapshot
    report = JobTelemetry.from_dir(
        os.environ["DLROVER_TELEMETRY_DIR"]
    ).report()
    if report["sources"]:
        print()
        print(format_report(report, timeline_tail=30))
        if args.keep or args.out_dir:
            print(
                "\nfull report: python tools/obs_report.py --dir "
                + os.environ["DLROVER_TELEMETRY_DIR"]
                + "\nspan traces: python tools/obs_report.py --trace "
                "--dir " + os.environ["DLROVER_TELEMETRY_DIR"]
            )
    # post-mortems: kill schedules (chaos kill, SIGTERM, hang verdicts)
    # leave flight-recorder dumps — the victim's last spans/events plus
    # all-thread stacks — one file each, listed here so the post-mortem
    # is one command away
    dumps = flight.list_dumps(os.environ["DLROVER_TELEMETRY_DIR"])
    if dumps:
        print("\nflight-recorder dumps:")
        for p in dumps:
            print("  " + p)
    print(f"work dir: {out_dir}" + ("" if args.keep else " (removing)"))
    if not args.keep and not args.out_dir:
        shutil.rmtree(out_dir, ignore_errors=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
