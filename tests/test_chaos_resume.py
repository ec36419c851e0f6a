"""Chaos e2e: a worker is killed mid-training after an in-memory flash
checkpoint; the agent restarts it and the new incarnation resumes from
the shm checkpoint (which survives worker death because the agent-side
saver holds the segment) — the headline Flash Checkpoint capability
(reference fault-tolerance experiments, SURVEY §4/§6; BASELINE north
star: fast restore under injected preemption).
"""

import json

import pytest

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.training_agent import (
    ElasticLaunchConfig,
    ElasticTrainingAgent,
    WorkerSpec,
)
from dlrover_tpu.common.constants import ConfigPath, NodeType

pytestmark = pytest.mark.chaos


WORKER = """
import json, os
import jax, jax.numpy as jnp
from dlrover_tpu.trainer.flash_checkpoint.engine import (
    ReplicatedCheckpointEngine,
)

out_dir = os.environ["CHAOS_OUT_DIR"]
engine = ReplicatedCheckpointEngine(out_dir + "/ckpt")

restored = engine.load()
if restored is None:
    start, w = 0, jnp.zeros((4,))
else:
    start = int(restored["step"])
    w = jnp.asarray(list(restored["state"].values())[0])

TOTAL, CRASH_AT = 10, 5
for step in range(start + 1, TOTAL + 1):
    w = w + 1.0
    engine.save_to_memory(step, {"w": w})
    if step == CRASH_AT and restored is None:
        # injected preemption: die without any cleanup
        os._exit(13)

with open(out_dir + "/result.json", "w") as f:
    json.dump({
        "resumed_from": start,
        "final_step": TOTAL,
        "w0": float(w[0]),
    }, f)
engine.close()
"""


def test_kill_and_resume_from_shm(local_master, tmp_path, monkeypatch,
                                  isolated_ckpt_env):
    script = tmp_path / "chaos_worker.py"
    script.write_text(WORKER)
    monkeypatch.setenv("CHAOS_OUT_DIR", str(tmp_path))

    config = ElasticLaunchConfig(
        min_nodes=1,
        max_nodes=1,
        nproc_per_node=1,
        monitor_interval=0.3,
        rdzv_timeout=30,
        max_restarts=2,
        log_dir=str(tmp_path),
    )
    client = MasterClient(local_master.addr, 0, NodeType.WORKER)
    spec = WorkerSpec(str(script), (), config)
    agent = ElasticTrainingAgent(config, spec, client)
    try:
        assert agent.run() == 0
    finally:
        client.close()

    result = json.loads((tmp_path / "result.json").read_text())
    # the second incarnation must have resumed from the shm checkpoint
    # taken right before the crash — not from scratch
    assert result["resumed_from"] == 5, result
    assert result["final_step"] == 10
    # w incremented once per step with no replay: exactly 10
    assert result["w0"] == 10.0, result


# -------------------------------------------------------------------------
# the same kill, read as ONE trace: death -> first completed step
# -------------------------------------------------------------------------

TRAINER_WORKER = """
import os, signal
import numpy as np
import jax.numpy as jnp
from dlrover_tpu.trainer import init_distributed

init_distributed()
from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

first = os.environ.get("TORCHELASTIC_RESTARTS", "0") == "0"


class Data:
    def __iter__(self):
        for pulled in range(100):
            if first and pulled == 5:
                # injected preemption after the step-4 shm save
                os.kill(os.getpid(), signal.SIGKILL)
            yield np.ones((4, 4), np.float32)


trainer = Trainer(
    lambda params, batch, rng: jnp.mean((batch @ params["w"]) ** 2),
    lambda rng: {"w": jnp.ones((4, 1))},
    {"w": (None, None)},
    TrainingArgs(
        output_dir=os.environ["CHAOS_OUT_DIR"] + "/out", max_steps=8,
        log_steps=1, save_steps=4, flash_checkpoint=True,
    ),
    train_data=Data(),
)
trainer.train()
trainer.close()
"""

# the order ISSUE 39 gives the legs; ``start.script`` (the script's own
# code between two legs) may stand between any two of the worker's
RESUME_LEGS = [
    "resume.detect", "resume.report", "resume.stop", "resume.rendezvous",
    "resume.spawn", "start.exec", "start.imports", "start.backend",
    "start.trainer_init", "start.restore", "start.compile",
    "start.first_step",
]


def test_kill_yields_one_resume_trace(local_master, tmp_path, monkeypatch,
                                      isolated_ckpt_env):
    from dlrover_tpu.common import telemetry, tracing

    tele_dir = tmp_path / "telemetry"
    monkeypatch.setenv(telemetry.ENV_DIR, str(tele_dir))
    monkeypatch.setenv("CHAOS_OUT_DIR", str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    # (the progress file is one fixed path a host: not under xdist)
    monkeypatch.setattr(
        ConfigPath, "RUNTIME_METRICS", str(tmp_path / "runtime.json")
    )
    prev = telemetry.active_registry()
    telemetry.enable("agent-under-test")
    script = tmp_path / "trainer_worker.py"
    script.write_text(TRAINER_WORKER)
    config = ElasticLaunchConfig(
        min_nodes=1, max_nodes=1, nproc_per_node=1, monitor_interval=0.3,
        rdzv_timeout=30, max_restarts=2, log_dir=str(tmp_path),
    )
    client = MasterClient(local_master.addr, 0, NodeType.WORKER)
    agent = ElasticTrainingAgent(
        config, WorkerSpec(str(script), (), config), client
    )
    try:
        assert agent.run() == 0
        events = telemetry.JobTelemetry.from_dir(
            str(tele_dir)
        ).merged_events()
    finally:
        client.close()
        telemetry._REGISTRY = prev

    trees = [
        t for t in tracing.trace_trees(events)
        if any(n["event"]["name"] == "resume" for n in t["roots"])
    ]
    assert len(trees) == 1, [t["roots"] for t in trees]
    (root,) = trees[0]["roots"]
    assert len({e["source"] for e in _walk(root)}) == 2  # agent + worker
    top, legs = root["event"], [c["event"] for c in root["children"]]
    names = [e["name"] for e in legs]
    assert [n for n in names if n != "start.script"] == RESUME_LEGS, names
    # gapless: each leg begins where the one before it ended, the
    # first at the death, and together they are the root
    (died,) = [e for e in events if e["kind"] == "worker.exit"]
    start = [e["t"] - e["dur"] for e in legs]
    assert start[0] == pytest.approx(died["died_t"], abs=1e-3)
    assert top["t"] - top["dur"] == pytest.approx(died["died_t"], abs=1e-3)
    for prev_leg, begins, leg in zip(legs, start[1:], legs[1:]):
        assert abs(begins - prev_leg["t"]) < 0.05, (prev_leg, leg)
    assert sum(e["dur"] for e in legs) >= 0.97 * top["dur"]
    # the death is stamped where the kernel reports it, not where the
    # 0.3 s poll finds it
    assert 0.0 < legs[0]["dur"] <= config.monitor_interval + 0.25
    assert (top["restart"], top["exit_kind"], top["rc"]) == (1, "oom", -9)
    assert top["last_step"] >= 4 and top["last_step_t"] <= died["died_t"]
    # what was there before nests under the legs
    nested = {
        leg["event"]["name"]: {c["event"]["name"] for c in leg["children"]}
        for leg in root["children"]
    }
    assert "rdzv.round" in nested["resume.rendezvous"]
    assert "ckpt.restore.load" in nested["start.restore"]
    by_name = {e["name"]: e for e in legs}
    assert by_name["start.restore"]["status"] == "ok"
    assert "hit" in by_name["start.compile"]
    # every leg is a counter too, in the process that ran it
    snaps = telemetry.JobTelemetry.from_dir(str(tele_dir)).snapshots()
    counters = {
        c["name"]: c["value"] for s in snaps for c in s["counters"]
        if s["source"] == by_name["start.restore"]["source"]
    }
    assert counters["start.restore_s"] == pytest.approx(
        by_name["start.restore"]["dur"]
    )
    assert counters["resume_s"] == pytest.approx(top["dur"])


def _walk(node):
    yield node["event"]
    for child in node["children"]:
        yield from _walk(child)
