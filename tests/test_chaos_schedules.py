"""Seeded chaos-schedule coverage: the no-op guard, schedule
determinism, per-action unit behavior, and the three e2e recovery
scenarios the robustness claim rests on — worker kill at a chosen step,
RPC flap during rendezvous, and a torn/bit-flipped final checkpoint
falling back to the newest *verified* step (CheckFreq-style
machine-checked recovery invariants; SURVEY §4/§6).
"""

import json
import os
import time

import numpy as np
import pytest

from dlrover_tpu.common import chaos
from dlrover_tpu.common.chaos import ChaosError, ChaosRegistry

pytestmark = pytest.mark.chaos


@pytest.fixture
def disarm():
    """Always disarm the process-global registry after a test."""
    yield
    chaos.uninstall()


# -------------------------------------------------------------------------
# no-op guard: DLROVER_CHAOS unset => injection sites are inert
# -------------------------------------------------------------------------


class TestNoOpGuard:
    def test_disarmed_by_default(self):
        assert chaos.active_registry() is None

    def test_disarmed_sites_never_touch_registry_machinery(
        self, monkeypatch
    ):
        """The hot path must be a global load + None check: poison every
        registry method — a disarmed chaos_point must not reach any."""
        def boom(*_a, **_k):
            raise AssertionError("registry consulted while disarmed")

        monkeypatch.setattr(ChaosRegistry, "fire", boom)
        monkeypatch.setattr(ChaosRegistry, "transform", boom)
        chaos.chaos_point("rpc.send", verb="get")
        chaos.chaos_point("ckpt.save", step=5)
        payload = b"payload-bytes"
        # identity, not equality: no copy happens on the disarmed path
        assert chaos.chaos_transform("ckpt.write", payload) is payload

    def test_env_unset_means_no_install(self, monkeypatch):
        monkeypatch.delenv(chaos.ENV_VAR, raising=False)
        assert chaos.install_from_env() is None

    @pytest.mark.parametrize("bad", [
        "{not json",                          # invalid JSON
        '{"rules": [{"action": "drop"}]}',    # missing "site"
        '["not", "a", "dict"]',               # wrong top-level type
        '{"rules": [{"site": "s", "action": "nope"}]}',  # bad action
        "@/nonexistent/schedule.json",        # unreadable file
    ])
    def test_malformed_env_schedule_is_ignored(
        self, monkeypatch, disarm, bad
    ):
        """install_from_env runs at import time in EVERY process: no
        malformed schedule may escape as an exception and kill the job
        it was supposed to merely perturb."""
        monkeypatch.setenv(chaos.ENV_VAR, bad)
        assert chaos.install_from_env() is None
        assert chaos.active_registry() is None

    def test_rpc_roundtrip_unchanged_when_disarmed(self, local_master):
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.common.constants import NodeType

        client = MasterClient(local_master.addr, 0, NodeType.WORKER)
        try:
            assert client.ping()
            assert client.report_global_step(1)
        finally:
            client.close()


# -------------------------------------------------------------------------
# schedules: determinism + matching + actions
# -------------------------------------------------------------------------


def _drive(reg, n=64, site="rpc.send", ctx=None):
    pattern = []
    for _ in range(n):
        try:
            reg.fire(site, dict(ctx or {"verb": "get"}))
            pattern.append(0)
        except ChaosError:
            pattern.append(1)
    return pattern


class TestSchedules:
    def test_same_seed_same_fire_pattern(self):
        sched = {
            "seed": 42,
            "rules": [{"site": "rpc.send", "action": "drop", "prob": 0.4}],
        }
        a = _drive(ChaosRegistry(sched))
        b = _drive(ChaosRegistry(sched))
        assert a == b
        assert sum(a) > 0

    def test_different_seed_different_pattern(self):
        base = {"rules": [{"site": "rpc.send", "action": "drop",
                           "prob": 0.4}]}
        a = _drive(ChaosRegistry({"seed": 1, **base}))
        b = _drive(ChaosRegistry({"seed": 2, **base}))
        assert a != b

    def test_rules_draw_from_independent_streams(self):
        """Adding a second rule on another site must not perturb the
        first rule's draw sequence (per-rule RNG, not shared)."""
        one = {
            "seed": 9,
            "rules": [{"site": "a", "action": "drop", "prob": 0.5}],
        }
        two = {
            "seed": 9,
            "rules": [
                {"site": "a", "action": "drop", "prob": 0.5},
                {"site": "b", "action": "drop", "prob": 0.5},
            ],
        }
        reg = ChaosRegistry(two)
        interleaved = []
        for _ in range(32):
            try:
                reg.fire("a", {})
                interleaved.append(0)
            except ChaosError:
                interleaved.append(1)
            try:
                reg.fire("b", {})
            except ChaosError:
                pass
        assert interleaved == _drive(ChaosRegistry(one), 32, site="a",
                                     ctx={})

    def test_step_verb_msg_filters(self):
        reg = ChaosRegistry({
            "seed": 0,
            "rules": [
                {"site": "s", "action": "drop", "step": 5},
                {"site": "s", "action": "drop", "verb": "get"},
                {"site": "s", "action": "drop",
                 "msg": ["JoinRendezvousRequest"]},
            ],
        })
        reg.fire("s", {"step": 4})  # no match
        with pytest.raises(ChaosError):
            reg.fire("s", {"step": 5})
        with pytest.raises(ChaosError):
            reg.fire("s", {"verb": "get"})
        reg.fire("s", {"verb": "report"})
        with pytest.raises(ChaosError):
            reg.fire("s", {"msg": "JoinRendezvousRequest"})
        reg.fire("s", {"msg": "HeartBeat"})

    def test_after_every_max_counting(self):
        reg = ChaosRegistry({
            "seed": 0,
            "rules": [{"site": "s", "action": "drop", "after": 2,
                       "every": 2, "max": 2}],
        })
        # calls 1,2 skipped (after); 3 fires; 4 skipped (every); 5
        # fires; then max reached
        assert _drive(reg, 8, site="s", ctx={}) == [0, 0, 1, 0, 1, 0, 0, 0]

    def test_delay_action_sleeps(self, monkeypatch):
        slept = []
        monkeypatch.setattr(chaos.time, "sleep", slept.append)
        reg = ChaosRegistry({
            "rules": [{"site": "s", "action": "delay", "delay": 0.7}],
        })
        reg.fire("s", {})
        assert slept == [0.7]

    def test_tear_transform_truncates(self):
        reg = ChaosRegistry({
            "rules": [{"site": "w", "action": "tear", "frac": 0.25}],
        })
        out = reg.transform("w", b"x" * 100, {})
        assert out == b"x" * 25

    def test_bitflip_transform_flips_exactly_one_byte(self):
        sched = {
            "seed": 3,
            "rules": [{"site": "w", "action": "bitflip"}],
        }
        src = bytes(range(64))
        a = ChaosRegistry(sched).transform("w", src, {})
        b = ChaosRegistry(sched).transform("w", src, {})
        assert a == b  # seeded flip position
        assert a != src
        assert sum(x != y for x, y in zip(a, src)) == 1

    def test_fired_log_and_summary(self):
        reg = ChaosRegistry({
            "rules": [{"site": "s", "action": "drop", "max": 2}],
        })
        for _ in range(4):
            try:
                reg.fire("s", {"verb": "get"})
            except ChaosError:
                pass
        assert reg.summary() == {"s:drop": 2}

    def test_named_schedules_resolve(self):
        for name in chaos.NAMED_SCHEDULES:
            reg = ChaosRegistry(chaos.resolve_schedule(name))
            assert reg.rules, name

    def test_install_from_file(self, tmp_path, disarm):
        p = tmp_path / "sched.json"
        p.write_text(json.dumps(
            {"seed": 5, "rules": [{"site": "s", "action": "drop"}]}
        ))
        reg = chaos.install(f"@{p}")
        assert chaos.active_registry() is reg
        with pytest.raises(ChaosError):
            chaos.chaos_point("s")


# -------------------------------------------------------------------------
# e2e scenario 1: seeded worker kill at a chosen step -> resume from shm
# -------------------------------------------------------------------------


KILL_WORKER = """
import json, os
import jax.numpy as jnp
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

TOTAL = 10
for step in range(start + 1, TOTAL + 1):
    w = w + 1.0
    # the seeded schedule kills this process right AFTER the step-5
    # shm save commits (chaos site ckpt.save)
    engine.save_to_memory(step, {"w": w})

with open(out_dir + "/result.json", "w") as f:
    json.dump({
        "resumed_from": start,
        "final_step": TOTAL,
        "w0": float(w[0]),
    }, f)
engine.close()
"""


def _run_agent_job(local_master, tmp_path, script_body, max_restarts=2):
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.agent.training_agent import (
        ElasticLaunchConfig,
        ElasticTrainingAgent,
        WorkerSpec,
    )
    from dlrover_tpu.common.constants import NodeType

    script = tmp_path / "chaos_worker.py"
    script.write_text(script_body)
    config = ElasticLaunchConfig(
        min_nodes=1,
        max_nodes=1,
        nproc_per_node=1,
        monitor_interval=0.3,
        rdzv_timeout=30,
        max_restarts=max_restarts,
        log_dir=str(tmp_path),
    )
    client = MasterClient(local_master.addr, 0, NodeType.WORKER)
    agent = ElasticTrainingAgent(
        config, WorkerSpec(str(script), (), config), client
    )
    try:
        rc = agent.run()
    finally:
        client.close()
    return rc


def test_schedule_worker_kill_resumes_bit_correct(
    local_master, tmp_path, monkeypatch, isolated_ckpt_env
):
    """DLROVER_CHAOS (inherited by the worker subprocess, armed at
    import) kills the worker right after the step-5 save; the restarted
    incarnation must resume from step 5 and finish with the exact state
    an unkilled run produces."""
    monkeypatch.setenv("CHAOS_OUT_DIR", str(tmp_path))
    monkeypatch.setenv(
        chaos.ENV_VAR,
        json.dumps({
            "seed": 7,
            "rules": [{"site": "ckpt.save", "action": "kill", "step": 5}],
        }),
    )
    assert _run_agent_job(local_master, tmp_path, KILL_WORKER) == 0
    result = json.loads((tmp_path / "result.json").read_text())
    assert result["resumed_from"] == 5, result
    assert result["final_step"] == 10
    # +1.0 per step, no replay, no loss: bit-correct final state
    assert result["w0"] == 10.0, result


# -------------------------------------------------------------------------
# e2e scenario 2: RPC flap during rendezvous -> RetryPolicy rides it out
# -------------------------------------------------------------------------


FLAP_WORKER = """
import json, os
out_dir = os.environ["CHAOS_OUT_DIR"]
with open(out_dir + "/result.json", "w") as f:
    json.dump({"trained": True}, f)
"""


def test_schedule_rpc_flap_during_rendezvous(
    local_master, tmp_path, monkeypatch, disarm
):
    """A seeded schedule drops a bounded burst of the agent's rendezvous
    RPCs (client-side, in this process). The retry policy must absorb
    the flap: the world still forms and the job completes."""
    from dlrover_tpu.common import retry

    monkeypatch.setenv("CHAOS_OUT_DIR", str(tmp_path))
    # fast deterministic-budget policy for the test
    retry.set_default_rpc_policy(retry.RetryPolicy(
        max_attempts=8, base_delay=0.05, max_delay=0.2, deadline=20.0,
    ))
    try:
        # deterministic counting (every 2nd matching call, 3 drops max)
        # rather than probability: the rendezvous window is only a
        # handful of calls, and the test must be guaranteed to flap
        reg = chaos.install({
            "seed": 11,
            "rules": [{
                "site": "rpc.send",
                "action": "drop",
                "msg": ["JoinRendezvousRequest", "CommWorldRequest"],
                "every": 2,
                "max": 3,
            }],
        })
        assert _run_agent_job(local_master, tmp_path, FLAP_WORKER) == 0
        dropped = sum(
            1 for site, action, _ in reg.fired
            if site == "rpc.send" and action == "drop"
        )
        assert dropped > 0, "schedule never fired; test proves nothing"
    finally:
        retry.set_default_rpc_policy(None)
    result = json.loads((tmp_path / "result.json").read_text())
    assert result["trained"] is True


# -------------------------------------------------------------------------
# e2e scenario 3: torn final checkpoint -> verified fallback on restore
# -------------------------------------------------------------------------


@pytest.fixture
def _engine(tmp_path, isolated_ckpt_env):
    import jax.numpy as jnp  # noqa: F401 - backend up before engine

    from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
    from dlrover_tpu.trainer.flash_checkpoint.engine import (
        ReplicatedCheckpointEngine,
    )

    eng = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
    yield eng
    eng.close()
    AsyncCheckpointSaver.reset()


def _persist(eng, step):
    import jax.numpy as jnp

    assert eng.save_to_storage(step, {"w": jnp.full((4,), float(step))})
    assert eng.wait_for_persist(step), f"step {step} never persisted"


def test_schedule_torn_final_checkpoint_falls_back(
    _engine, tmp_path, disarm
):
    """Steps 4 and 6 persist clean; the seeded schedule tears the step-8
    write mid-shard. After 'node replacement' (shm gone), restore must
    verify, reject step 8, and land exactly on step 6."""
    from dlrover_tpu.agent.ckpt_saver import verify_step_dir

    _persist(_engine, 4)
    _persist(_engine, 6)
    chaos.install({
        "seed": 13,
        "rules": [{"site": "ckpt.write", "action": "tear", "step": 8}],
    })
    _persist(_engine, 8)
    chaos.uninstall()
    ckpt_dir = str(tmp_path / "ckpt")
    ok, reason = verify_step_dir(os.path.join(ckpt_dir, "checkpoint-8"))
    assert not ok and "torn" in reason
    assert verify_step_dir(os.path.join(ckpt_dir, "checkpoint-6"))[0]
    # a successful verify caches its full-payload crc work in a marker
    # (later verifiers — other hosts, repeat restores — only size-check)
    assert os.path.exists(
        os.path.join(ckpt_dir, "checkpoint-6", ".verified")
    )
    assert verify_step_dir(os.path.join(ckpt_dir, "checkpoint-6"))[0]
    # the tracker still advertises 8 — the fallback must out-vote it
    _engine._shm_handler.mark_empty()  # simulate a replaced host
    restored = _engine.load()
    assert restored["step"] == 6, restored
    np.testing.assert_array_equal(
        np.asarray(restored["state"]["w"]), np.full((4,), 6.0)
    )
    # an EXPLICITLY named corrupt checkpoint must raise, not silently
    # fall through to train-from-scratch
    with pytest.raises(ValueError, match="integrity"):
        _engine.load_from_storage(
            path=os.path.join(ckpt_dir, "checkpoint-8")
        )


def test_schedule_bitflipped_payload_falls_back(_engine, tmp_path, disarm):
    _persist(_engine, 4)
    chaos.install({
        "seed": 17,
        "rules": [{"site": "ckpt.write", "action": "bitflip", "step": 6}],
    })
    _persist(_engine, 6)
    chaos.uninstall()
    _engine._shm_handler.mark_empty()
    restored = _engine.load()
    assert restored["step"] == 4, restored
    np.testing.assert_array_equal(
        np.asarray(restored["state"]["w"]), np.full((4,), 4.0)
    )
    # explicitly naming the bit-flipped dir: shallow verify passes on
    # size, the loader's payload crc rejects it — must raise, not
    # silently return "no checkpoint"
    with pytest.raises(ValueError, match="explicitly named"):
        _engine.load_from_storage(
            path=os.path.join(str(tmp_path / "ckpt"), "checkpoint-6")
        )


def test_corrupted_manifest_falls_back(_engine, tmp_path, disarm):
    """A bit-flipped MANIFEST (not payload) must likewise disqualify the
    step: trust nothing that fails verification, restore the previous
    verified checkpoint."""
    _persist(_engine, 4)
    chaos.install({
        "seed": 19,
        "rules": [{"site": "ckpt.manifest", "action": "bitflip",
                   "step": 6}],
    })
    _persist(_engine, 6)
    chaos.uninstall()
    from dlrover_tpu.agent.ckpt_saver import verify_step_dir

    ok, reason = verify_step_dir(
        os.path.join(str(tmp_path / "ckpt"), "checkpoint-6")
    )
    assert not ok, reason
    _engine._shm_handler.mark_empty()
    restored = _engine.load()
    assert restored["step"] == 4, restored


def test_targeted_restore_also_falls_back(_engine, tmp_path, disarm):
    """The shard-wise (targeted) restore path must obey the same
    verification: it skips whole-payload CRCs during slice reads, so
    the manifest gate is its only torn-file defense."""
    import jax.numpy as jnp

    _persist(_engine, 4)
    chaos.install({
        "seed": 23,
        "rules": [{"site": "ckpt.write", "action": "tear", "step": 6}],
    })
    _persist(_engine, 6)
    chaos.uninstall()
    _engine._shm_handler.mark_empty()
    target = {"w": jnp.zeros((4,))}
    tree, step = _engine.load(target=target)
    assert step == 4
    np.testing.assert_array_equal(
        np.asarray(tree["w"]), np.full((4,), 4.0)
    )


# -------------------------------------------------------------------------
# e2e scenario 4: bad-host schedule -> health gate + drain + re-admit
# -------------------------------------------------------------------------


@pytest.mark.health
def test_schedule_bad_host_gate_drain_readmit(
    tmp_path, monkeypatch, disarm
):
    """The named bad-host schedule end-to-end via the harness's own
    acceptance checks: the join-degraded host is refused at the door
    (never enters a round), the mid-run degradation becomes an ``hw``
    verdict and a brain drain+reshape with zero survivor restarts, the
    standing verdict survives a master failover verbatim, and the
    recovered host re-admits once its backoff re-probe comes back
    clean. Also publishes the probe_join_overhead_s /
    bad_host_quarantine_s keys and asserts the < 5 s join
    budget."""
    from tools.chaos_run import _run_bad_host

    schedule = chaos.NAMED_SCHEDULES["bad-host"]
    monkeypatch.setenv(chaos.ENV_VAR, json.dumps(schedule))
    monkeypatch.setenv(
        "DLROVER_TELEMETRY_DIR", str(tmp_path / "telemetry")
    )
    chaos.install(schedule)
    assert _run_bad_host(schedule, str(tmp_path), steps=5) == 0
    report = json.loads(
        (tmp_path / "bad_host_report.json").read_text()
    )
    assert report["failures"] == []
    assert report["keys"]["probe_join_overhead_s"] < 5.0
    assert report["keys"]["bad_host_quarantine_s"] > 0
