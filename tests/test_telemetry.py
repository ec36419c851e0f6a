"""Unified telemetry layer: registry semantics (bucket edges, concurrent
counters, disabled no-op guard), snapshot-merge idempotence, goodput
ledger attribution, restore-step consensus, and the tier-1 smoke that
runs a toy elastic job under the chaos kill-at-step-5 schedule and
checks the job-wide ledger + merged timeline end to end.
"""

import json
import threading
import time

import numpy as np
import pytest

from dlrover_tpu.common import telemetry
from dlrover_tpu.common.telemetry import (
    JobTelemetry,
    TelemetryRegistry,
    goodput_ledger,
)

pytestmark = pytest.mark.telemetry


@pytest.fixture
def fresh_telemetry():
    """Swap in a fresh registry (other tests/agents pollute the
    process-global one) and restore the previous afterwards."""
    prev = telemetry.active_registry()
    reg = telemetry.enable(source="test-src")
    yield reg
    telemetry._REGISTRY = prev


# -------------------------------------------------------------------------
# registry semantics
# -------------------------------------------------------------------------


class TestRegistry:
    def test_histogram_bucket_edges(self, fresh_telemetry):
        """A value exactly on a boundary lands in that boundary's bucket
        (Prometheus ``le`` convention); beyond the last bound -> +Inf."""
        for v in (0.5, 1.0, 1.5, 2.0, 4.0, 4.0001, 100.0):
            telemetry.observe("lat", v, buckets=(1.0, 2.0, 4.0))
        snap = telemetry.snapshot()
        (hist,) = snap["histograms"]
        assert hist["bounds"] == [1.0, 2.0, 4.0]
        # le=1: {0.5, 1.0}; le=2: {1.5, 2.0}; le=4: {4.0}; inf: {4.0001, 100}
        assert hist["counts"] == [2, 2, 1, 2]
        assert hist["count"] == 7
        assert hist["sum"] == pytest.approx(113.0001)

    def test_histogram_rejects_unsorted_bounds(self):
        with pytest.raises(ValueError):
            TelemetryRegistry().observe("x", 1.0, buckets=(2.0, 1.0))

    def test_concurrent_counter_increments(self, fresh_telemetry):
        def work():
            for _ in range(1000):
                telemetry.counter_inc("hits", site="a")

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = telemetry.snapshot()
        (counter,) = snap["counters"]
        assert counter == {
            "name": "hits", "labels": {"site": "a"}, "value": 8000.0,
        }

    def test_labels_key_independent_of_kwarg_order(self, fresh_telemetry):
        telemetry.counter_inc("c", a="1", b="2")
        telemetry.counter_inc("c", b="2", a="1")
        snap = telemetry.snapshot()
        assert len(snap["counters"]) == 1
        assert snap["counters"][0]["value"] == 2.0

    def test_event_ring_bounded_with_dropped_count(self, fresh_telemetry):
        for i in range(telemetry.MAX_EVENTS + 10):
            telemetry.event("tick", i=i)
        snap = telemetry.snapshot()
        assert len(snap["events"]) == telemetry.MAX_EVENTS
        assert snap["events_dropped"] == 10
        # the tail survives, the head was dropped
        assert snap["events"][-1]["i"] == telemetry.MAX_EVENTS + 9

    def test_disabled_sites_never_touch_registry_machinery(
        self, monkeypatch
    ):
        """Poisoned-registry guard (like chaos): when disabled, every
        hook must be a module-global load + is-None branch — reaching
        ANY registry method is a bug."""
        prev = telemetry.active_registry()

        def boom(*_a, **_k):
            raise AssertionError("registry consulted while disabled")

        for name in (
            "counter_inc", "gauge_set", "observe", "event", "snapshot",
            "flush",
        ):
            monkeypatch.setattr(TelemetryRegistry, name, boom)
        telemetry.disable()
        try:
            telemetry.counter_inc("c")
            telemetry.gauge_set("g", 1.0)
            telemetry.observe("h", 0.5)
            telemetry.event("k", step=1)
            assert telemetry.snapshot() is None
            assert telemetry.flush() is None
        finally:
            telemetry._REGISTRY = prev

    def test_env_off_means_no_install(self, monkeypatch):
        prev = telemetry.active_registry()
        try:
            monkeypatch.setenv(telemetry.ENV_VAR, "0")
            assert telemetry.install_from_env() is None
            assert telemetry.active_registry() is None
        finally:
            telemetry._REGISTRY = prev

    def test_flush_noop_without_dir(self, fresh_telemetry, monkeypatch):
        monkeypatch.delenv(telemetry.ENV_DIR, raising=False)
        assert telemetry.flush() is None

    def test_flush_writes_snapshot_file(
        self, fresh_telemetry, monkeypatch, tmp_path
    ):
        monkeypatch.setenv(telemetry.ENV_DIR, str(tmp_path))
        telemetry.event("hello", step=3)
        path = telemetry.flush()
        assert path is not None
        snap = json.loads(open(path).read())
        assert snap["source"] == "test-src"
        assert snap["events"][-1]["kind"] == "hello"


# -------------------------------------------------------------------------
# merge + ledger
# -------------------------------------------------------------------------


def _snap(source, role, events, now=None):
    return {
        "format": 1, "source": source, "role": role, "pid": 1,
        "created": events[0]["t"] if events else 0.0,
        "now": now if now is not None else (
            events[-1]["t"] if events else 0.0
        ),
        "counters": [], "gauges": [], "histograms": [],
        "events": events, "events_dropped": 0,
    }


def _ev(seq, t, kind, **fields):
    return {"seq": seq, "t": t, "mono": t, "kind": kind, **fields}


class TestMergeAndLedger:
    def test_snapshot_merge_idempotent_under_reregistration(
        self, fresh_telemetry
    ):
        telemetry.event("a", step=1)
        telemetry.event("b", step=2)
        snap = telemetry.snapshot()
        jt = JobTelemetry()
        assert jt.update(snap)
        first = jt.report()
        # the agent re-registers and re-sends the SAME snapshot: nothing
        # may double-count
        assert jt.update(json.loads(json.dumps(snap)))
        second = jt.report()
        assert first["timeline"] == second["timeline"]
        assert first["ledger"] == second["ledger"]
        assert len(second["timeline"]) == 2

    def test_stale_resend_cannot_roll_back(self):
        jt = JobTelemetry()
        old = _snap("w", "worker", [_ev(1, 100.0, "x")], now=101.0)
        new = _snap("w", "worker",
                    [_ev(1, 100.0, "x"), _ev(2, 102.0, "y")], now=103.0)
        assert jt.update(new)
        assert not jt.update(old)  # re-registered agent sends stale state
        assert len(jt.merged_events()) == 2

    def test_counters_sum_across_sources(self):
        jt = JobTelemetry()
        for src in ("a", "b"):
            snap = _snap(src, "worker", [_ev(1, 1.0, "x")])
            snap["counters"] = [
                {"name": "hits", "labels": {}, "value": 3.0}
            ]
            jt.update(snap)
        (c,) = jt.metrics_rollup()["counters"]
        assert c["value"] == 6.0

    def test_histograms_merge_bucketwise(self):
        jt = JobTelemetry()
        for src in ("a", "b"):
            snap = _snap(src, "worker", [_ev(1, 1.0, "x")])
            snap["histograms"] = [{
                "name": "lat", "labels": {}, "bounds": [1.0, 2.0],
                "counts": [1, 2, 3], "sum": 10.0, "count": 6,
            }]
            jt.update(snap)
        (h,) = jt.metrics_rollup()["histograms"]
        assert h["counts"] == [2, 4, 6]
        assert h["count"] == 12

    def test_ledger_kill_rendezvous_restore_attribution(self):
        """Simulated kill -> rendezvous -> restore -> resume: every
        second of the span lands in exactly one category and the
        categories sum to the span."""
        t0 = 1000.0
        worker_a = _snap("worker-0-100", "worker", [
            _ev(1, t0 + 1.0, "step.end", step=1, dur=1.0),
            _ev(2, t0 + 2.0, "step.end", step=2, dur=1.0),
            _ev(3, t0 + 2.2, "ckpt.save", step=2, dur=0.2),
            _ev(4, t0 + 2.2, "chaos.fire", site="ckpt.save", action="kill"),
        ])
        agent = _snap("agent-0-1", "agent", [
            _ev(1, t0 + 3.2, "rdzv.wait", dur=0.6, round=2),
        ])
        worker_b = _snap("worker-0-101", "worker", [
            _ev(1, t0 + 4.0, "ckpt.restore", step=2, dur=0.5,
                source_kind="shm"),
            _ev(2, t0 + 5.5, "compile", step=3, dur=1.5),
            _ev(3, t0 + 6.5, "step.end", step=4, dur=1.0),
        ])
        ledger = goodput_ledger([worker_a, agent, worker_b])
        cats = ledger["categories"]
        assert ledger["total_s"] == pytest.approx(6.5)
        assert sum(cats.values()) == pytest.approx(ledger["total_s"])
        assert cats["productive"] == pytest.approx(3.0)
        assert cats["checkpoint"] == pytest.approx(0.2)
        assert cats["rendezvous"] == pytest.approx(0.6)
        assert cats["compile"] == pytest.approx(1.5)
        # the kill->restart gap is restart time except where rendezvous
        # claimed it: gap is [2.2, 4.0] = 1.8s, rdzv covers 0.6s, and the
        # restore interval [3.5, 4.0] lies inside the gap -> 1.2s restart
        assert cats["restart"] == pytest.approx(1.2)
        assert cats["idle"] == pytest.approx(0.0)

    def test_ledger_empty(self):
        ledger = goodput_ledger([])
        assert ledger["total_s"] == 0.0

    def test_async_persist_not_charged_to_goodput(self):
        """The agent daemon's shm->storage copy overlaps training; it
        must not appear as lost wall-clock."""
        snap = _snap("agent-0-1", "agent", [
            _ev(1, 10.0, "ckpt.persist", step=2, dur=5.0),
        ])
        ledger = goodput_ledger([snap])
        assert ledger["categories"]["checkpoint"] == 0.0


# -------------------------------------------------------------------------
# guard + retry + rpc instrumentation
# -------------------------------------------------------------------------


class TestInstrumentation:
    def test_noncritical_guard_degrade_recover_events(
        self, fresh_telemetry
    ):
        from dlrover_tpu.common.retry import NonCriticalGuard

        guard = NonCriticalGuard(
            "test-guard", max_consecutive_failures=2, cooldown=0.01
        )

        def fail():
            raise ConnectionError("down")

        guard.run(fail)
        guard.run(fail)  # trips
        assert guard.disabled
        snap = telemetry.snapshot()
        kinds = [e["kind"] for e in snap["events"]]
        assert "guard.degrade" in kinds
        gauge = {
            (g["name"], g["labels"].get("name")): g["value"]
            for g in snap["gauges"]
        }
        assert gauge[("guard.degraded", "test-guard")] == 1.0

        time.sleep(0.02)
        assert guard.run(lambda: "ok") == "ok"  # half-open probe succeeds
        snap = telemetry.snapshot()
        kinds = [e["kind"] for e in snap["events"]]
        assert "guard.recover" in kinds
        gauge = {
            (g["name"], g["labels"].get("name")): g["value"]
            for g in snap["gauges"]
        }
        assert gauge[("guard.degraded", "test-guard")] == 0.0

    def test_rpc_latency_histogram_recorded(
        self, fresh_telemetry, local_master
    ):
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.common.constants import NodeType

        client = MasterClient(local_master.addr, 0, NodeType.WORKER)
        try:
            assert client.report_global_step(1)
            assert client.ping()
        finally:
            client.close()
        snap = telemetry.snapshot()
        rpc_hists = [
            h for h in snap["histograms"] if h["name"] == "rpc.client.seconds"
        ]
        assert rpc_hists
        by_msg = {h["labels"].get("msg") for h in rpc_hists}
        assert "GlobalStep" in by_msg

    def test_retry_exhaustion_counted(self, fresh_telemetry):
        from dlrover_tpu.common.retry import RetryPolicy, run_with_retry

        def always_down():
            raise ConnectionError("nope")

        with pytest.raises(ConnectionError):
            run_with_retry(
                always_down,
                RetryPolicy(max_attempts=2, base_delay=0.0, jitter=False),
                op="test",
            )
        snap = telemetry.snapshot()
        counters = {
            (c["name"], c["labels"].get("op")): c["value"]
            for c in snap["counters"]
        }
        assert counters[("retry.attempt_failed", "test")] == 2.0
        assert counters[("retry.exhausted", "test")] == 1.0

    def test_chaos_fires_are_evented(self, fresh_telemetry):
        from dlrover_tpu.common import chaos
        from dlrover_tpu.common.chaos import ChaosError, ChaosRegistry

        reg = ChaosRegistry({
            "rules": [{"site": "s", "action": "drop", "max": 1}],
        })
        with pytest.raises(ChaosError):
            reg.fire("s", {"verb": "get"})
        snap = telemetry.snapshot()
        fires = [e for e in snap["events"] if e["kind"] == "chaos.fire"]
        assert fires and fires[0]["site"] == "s"
        counters = {c["name"] for c in snap["counters"]}
        assert "chaos.fires" in counters
        assert chaos.active_registry() is None  # never armed globally


def test_trainer_emits_compile_then_step_events(
    tmp_path, isolated_ckpt_env, fresh_telemetry
):
    """The first train_step of an incarnation is attributed to compile;
    the rest are productive step.end intervals."""
    import jax.numpy as jnp

    from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

    def init_fn(rng):
        return {"w": jnp.zeros((4, 1))}

    def loss_fn(params, batch, rng):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    rs = np.random.RandomState(0)
    data = [
        (rs.randn(4, 4).astype(np.float32),
         rs.randn(4, 1).astype(np.float32))
        for _ in range(6)
    ]
    args = TrainingArgs(
        output_dir=str(tmp_path / "out"), max_steps=5,
        flash_checkpoint=False, log_steps=0,
    )
    trainer = Trainer(
        loss_fn, init_fn, {"w": (None, None)}, args, train_data=data
    )
    trainer.train()
    trainer.close()
    snap = telemetry.snapshot()
    kinds = [e["kind"] for e in snap["events"]]
    assert kinds.count("compile") == 1
    assert kinds.count("step.end") == 4
    assert kinds.index("compile") < kinds.index("step.end")
    # one clock: the steady steps' gauge, no second copy of it
    assert {g["name"] for g in snap["gauges"]} >= {"train.step.last_s"}
    steps = [e["step"] for e in snap["events"] if e["kind"] == "step.end"]
    assert steps == [2, 3, 4, 5]


# -------------------------------------------------------------------------
# restore-step consensus (ROADMAP open item)
# -------------------------------------------------------------------------


class TestRestoreConsensus:
    def _manager(self, n=2):
        from dlrover_tpu.master.rendezvous import (
            ElasticTrainingRendezvousManager,
        )

        mgr = ElasticTrainingRendezvousManager()
        mgr.update_rdzv_params(
            min_nodes=n, max_nodes=n, waiting_timeout=30, node_unit=1
        )
        return mgr

    def test_newest_common_step_broadcast(self):
        """Consensus = the newest step EVERY member can load — never a
        step some host lacks (min-of-newest would force host 1 to a
        step it never claimed to have)."""
        mgr = self._manager()
        mgr.join_rendezvous(0, 1, verified_ckpt_steps=[4, 6, 8])
        mgr.join_rendezvous(1, 1, verified_ckpt_steps=[4, 6])
        _round, _g, world, _coord = mgr.get_comm_world(0)
        assert world
        assert mgr.consensus_restore_step() == 6

    def test_no_common_step_means_no_forcing(self):
        mgr = self._manager()
        mgr.join_rendezvous(0, 1, verified_ckpt_steps=[8])
        mgr.join_rendezvous(1, 1, verified_ckpt_steps=[6])
        mgr.get_comm_world(0)
        assert mgr.consensus_restore_step() == -1

    def test_scalar_only_report_is_singleton_set(self):
        """Older clients report only the newest step; two hosts at the
        same step still reach consensus."""
        mgr = self._manager()
        mgr.join_rendezvous(0, 1, verified_ckpt_step=5)
        mgr.join_rendezvous(1, 1, verified_ckpt_step=5)
        mgr.get_comm_world(0)
        assert mgr.consensus_restore_step() == 5

    def test_no_consensus_when_any_host_lacks_checkpoint(self):
        mgr = self._manager()
        mgr.join_rendezvous(0, 1, verified_ckpt_steps=[8])
        mgr.join_rendezvous(1, 1)  # fresh host: nothing verified
        mgr.get_comm_world(0)
        assert mgr.consensus_restore_step() == -1

    def test_rejoin_refreshes_verified_steps(self):
        mgr = self._manager()
        mgr.join_rendezvous(0, 1, verified_ckpt_steps=[4])
        mgr.join_rendezvous(1, 1, verified_ckpt_steps=[4])
        mgr.get_comm_world(0)
        assert mgr.consensus_restore_step() == 4
        # both hosts checkpointed further and re-rendezvous
        mgr.join_rendezvous(0, 1, verified_ckpt_steps=[4, 7, 9])
        mgr.join_rendezvous(1, 1, verified_ckpt_steps=[4, 7])
        mgr.get_comm_world(0)
        assert mgr.consensus_restore_step() == 7

    def test_servicer_threads_step_through_comm_world(self, local_master):
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.common.constants import NodeType, RendezvousName

        client = MasterClient(local_master.addr, 0, NodeType.WORKER)
        try:
            assert client.join_rendezvous(
                0, 1, RendezvousName.ELASTIC_TRAINING,
                verified_ckpt_step=5, verified_ckpt_steps=[3, 5],
            )
            world = client.get_comm_world(
                RendezvousName.ELASTIC_TRAINING, 0
            )
            assert world.world
            assert world.restore_step == 5
        finally:
            client.close()

    def test_engine_respects_consensus_env(
        self, tmp_path, monkeypatch, isolated_ckpt_env, fresh_telemetry
    ):
        """Host-local newest is step 8 (shm); the master-brokered min is
        6 — the engine must restore 6 from storage, skip the newer shm
        state, and record that consensus forced it below local newest."""
        import jax.numpy as jnp

        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
        from dlrover_tpu.common.constants import NodeEnv
        from dlrover_tpu.trainer.flash_checkpoint.engine import (
            ReplicatedCheckpointEngine,
        )

        eng = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        try:
            for step in (4, 6):
                assert eng.save_to_storage(
                    step, {"w": jnp.full((4,), float(step))}
                )
                assert eng.wait_for_persist(step, timeout=60)
            assert eng.save_to_memory(8, {"w": jnp.full((4,), 8.0)})

            restored = eng.load()  # no consensus: newest (shm) wins
            assert restored["step"] == 8

            monkeypatch.setenv(NodeEnv.RESTORE_STEP, "6")
            restored = eng.load()
            assert restored["step"] == 6
            np.testing.assert_array_equal(
                np.asarray(restored["state"]["w"]), np.full((4,), 6.0)
            )
            snap = telemetry.snapshot()
            forced = [
                e for e in snap["events"]
                if e["kind"] == "ckpt.consensus.forced"
            ]
            assert forced and forced[-1]["step"] == 6
            assert forced[-1]["local_newest"] == 8

            # a consensus step this host CANNOT load must raise — a
            # quiet restore of an older step would split the world
            monkeypatch.setenv(NodeEnv.RESTORE_STEP, "7")
            with pytest.raises(ValueError, match="consensus"):
                eng.load()
        finally:
            eng.close()
            AsyncCheckpointSaver.reset()

    def test_newest_verified_step_scan(self, tmp_path, isolated_ckpt_env):
        import jax.numpy as jnp

        from dlrover_tpu.agent.ckpt_saver import (
            AsyncCheckpointSaver,
            newest_verified_step,
        )
        from dlrover_tpu.trainer.flash_checkpoint.engine import (
            ReplicatedCheckpointEngine,
        )

        ckpt_dir = str(tmp_path / "ckpt")
        assert newest_verified_step(ckpt_dir) == -1
        eng = ReplicatedCheckpointEngine(ckpt_dir)
        try:
            for step in (4, 6):
                assert eng.save_to_storage(
                    step, {"w": jnp.full((4,), float(step))}
                )
                assert eng.wait_for_persist(step, timeout=60)
            assert newest_verified_step(ckpt_dir) == 6
            # tear the newest shard: the scan must fall back to 4
            import glob
            import os

            (shard,) = glob.glob(
                os.path.join(ckpt_dir, "checkpoint-6", "*.dlck")
            )
            with open(shard, "r+b") as f:
                f.truncate(os.path.getsize(shard) // 2)
            assert newest_verified_step(ckpt_dir) == 4
        finally:
            eng.close()
            AsyncCheckpointSaver.reset()


# -------------------------------------------------------------------------
# tier-1 smoke: toy elastic job + chaos kill, ledger end to end
# -------------------------------------------------------------------------


SMOKE_WORKER = """
import json, os, time
import jax.numpy as jnp
from dlrover_tpu.common import telemetry
from dlrover_tpu.trainer.flash_checkpoint.engine import (
    ReplicatedCheckpointEngine,
)

out_dir = os.environ["SMOKE_OUT_DIR"]
engine = ReplicatedCheckpointEngine(out_dir + "/ckpt")
restored = engine.load()
if restored is None:
    start, w = 0, jnp.zeros((4,))
else:
    start = int(restored["step"])
    w = jnp.asarray(list(restored["state"].values())[0])

TOTAL, STEP_S = 10, 0.02
for step in range(start + 1, TOTAL + 1):
    t0 = time.time()
    time.sleep(STEP_S)  # simulated device work
    w = w + 1.0
    telemetry.event("step.end", step=step, dur=time.time() - t0)
    if step % 2 == 0:
        # persisted steps give the restart a verified storage fallback
        engine.save_to_storage(step, {"w": w})
        engine.wait_for_persist(step, timeout=60)
    else:
        # the worker-kill schedule fires at the step-5 shm save
        engine.save_to_memory(step, {"w": w})
    telemetry.flush()

with open(out_dir + "/result.json", "w") as f:
    json.dump({"resumed_from": start, "final_step": TOTAL,
               "w0": float(w[0])}, f)
engine.close()
"""


def test_smoke_elastic_job_goodput_ledger(
    local_master, tmp_path, monkeypatch, isolated_ckpt_env,
    fresh_telemetry,
):
    """The acceptance scenario: a chaos worker-kill run whose merged
    telemetry yields a ledger summing to total wall-clock (+-2%), with
    nonzero rendezvous and restore time, and a timeline ordering
    kill -> rendezvous -> consensus restore step -> resume."""
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.agent.training_agent import (
        ElasticLaunchConfig,
        ElasticTrainingAgent,
        WorkerSpec,
    )
    from dlrover_tpu.common import chaos
    from dlrover_tpu.common.constants import NodeType

    tele_dir = tmp_path / "telemetry"
    monkeypatch.setenv(telemetry.ENV_DIR, str(tele_dir))
    monkeypatch.setenv("SMOKE_OUT_DIR", str(tmp_path))
    monkeypatch.setenv(
        chaos.ENV_VAR,
        json.dumps({
            "seed": 7,
            "rules": [{"site": "ckpt.save", "action": "kill", "step": 5}],
        }),
    )

    script = tmp_path / "smoke_worker.py"
    script.write_text(SMOKE_WORKER)
    config = ElasticLaunchConfig(
        min_nodes=1, max_nodes=1, nproc_per_node=1,
        monitor_interval=0.3, rdzv_timeout=30, max_restarts=2,
        log_dir=str(tmp_path),
    )
    client = MasterClient(local_master.addr, 0, NodeType.WORKER)
    agent = ElasticTrainingAgent(
        config, WorkerSpec(str(script), (), config), client
    )
    try:
        assert agent.run() == 0
    finally:
        client.close()

    result = json.loads((tmp_path / "result.json").read_text())
    assert result["resumed_from"] == 5, result
    assert result["w0"] == 10.0, result

    # the master/agent process flushed too (agent.run finally-block)
    report = JobTelemetry.from_dir(str(tele_dir)).report()
    assert len(report["sources"]) >= 3  # 2 worker incarnations + agent

    ledger = report["ledger"]
    cats = ledger["categories"]
    assert ledger["total_s"] > 0
    assert sum(cats.values()) == pytest.approx(
        ledger["total_s"], rel=0.02
    )
    assert cats["productive"] > 0
    assert cats["rendezvous"] > 0, cats
    assert cats["restart"] > 0, cats
    assert cats["checkpoint"] > 0, cats

    timeline = report["timeline"]

    def first_index(pred, after=-1):
        for i, ev in enumerate(timeline):
            if i > after and pred(ev):
                return i
        raise AssertionError(
            f"event missing in timeline: {[e['kind'] for e in timeline]}"
        )

    i_kill = first_index(
        lambda e: e["kind"] == "chaos.fire" and e.get("action") == "kill"
    )
    i_join = first_index(
        lambda e: e["kind"] == "rdzv.join", after=i_kill
    )
    i_complete = first_index(
        lambda e: e["kind"] == "rdzv.complete"
        and e.get("restore_step", -1) >= 0
    )
    i_restore = first_index(
        lambda e: e["kind"] == "ckpt.restore" and e.get("step") == 5
    )
    i_resume = first_index(
        lambda e: e["kind"] == "step.end" and e.get("step", 0) > 5
    )
    assert i_kill < i_join < i_complete < i_restore < i_resume, [
        (i_kill, i_join, i_complete, i_restore, i_resume)
    ]
    # consensus: shm step 5 outranks the persisted step 4; the master
    # broadcast min-across-hosts == 5 and the restore landed exactly there
    complete = timeline[i_complete]
    assert complete["restore_step"] == 5
    restore = timeline[i_restore]
    assert restore.get("consensus") == 5
