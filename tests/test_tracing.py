"""Distributed tracing, straggler/hang diagnosis, and the flight
recorder: span nesting + cross-process propagation (through RPC retry,
reconnect, and master failover), histogram quantiles, TimerRing
exporter round-trip, DiagnosisManager verdicts with blamed phases, the
check_straggler / exclude_straggler end-to-end path, and crash-time
flight dumps (chaos kill, SIGTERM, hang detector, received diagnosis).
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from dlrover_tpu.common import telemetry, tracing
from dlrover_tpu.common.telemetry import JobTelemetry, hist_quantile

pytestmark = pytest.mark.diagnosis


@pytest.fixture
def fresh_telemetry():
    prev = telemetry.active_registry()
    reg = telemetry.enable(source="test-0-1")
    yield reg
    telemetry._REGISTRY = prev


def _span_events(snap):
    return [e for e in snap["events"] if e["kind"] == tracing.SPAN_EVENT]


# -------------------------------------------------------------------------
# legs: a root cut into consecutive children, across a Popen
# -------------------------------------------------------------------------

_CHILD = """
import json, sys
from dlrover_tpu.common import telemetry, tracing

with tracing.span("child.work"):
    pass
legs = tracing.startup()
json.dump({
    "events": telemetry.snapshot()["events"],
    "root": [legs.name, legs.trace, legs.span, legs.t0, legs.closes_root],
    "open": legs.open_name, "import_t": telemetry.IMPORT_T,
}, sys.stdout)
"""


def _run_child(env_trace=None):
    env = dict(os.environ)
    env.pop(telemetry.ENV_TRACE, None)
    env.pop(telemetry.ENV_DIR, None)
    if env_trace is not None:
        env[telemetry.ENV_TRACE] = env_trace
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo
    spawn_t = time.time()
    out = subprocess.run(
        [sys.executable, "-c", _CHILD], env=env, capture_output=True,
        text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return spawn_t, json.loads(out.stdout)


class TestLegs:
    def test_legs_tile_the_root_and_each_is_a_counter(
        self, fresh_telemetry
    ):
        t0 = time.time() - 5.0
        legs = tracing.Legs("resume", t0, labels={"restart": 2})
        legs.advance("resume.detect")
        legs.advance("resume.report", t=t0 + 4.0)  # in retrospect
        with legs.leg("resume.rendezvous"):
            with tracing.span("rdzv.round"):
                pass
            inside = tracing.current()
        assert tracing.current() is None
        legs.advance("resume.spawn")
        legs.close(outcome="ok")
        legs.close()  # closed is closed
        assert not legs.advance("resume.more")
        snap = fresh_telemetry.snapshot()
        spans = {e["name"]: e for e in _span_events(snap)}
        root = spans["resume"]
        assert root["parent"] == "" and root["restart"] == 2
        assert root["t"] - root["dur"] == pytest.approx(t0)
        order = ["resume.detect", "resume.report", "resume.rendezvous",
                 "resume.spawn"]
        children = [spans[n] for n in order]
        assert all(c["parent"] == root["span"] for c in children)
        assert all(c["trace"] == root["trace"] for c in children)
        assert children[0]["dur"] == pytest.approx(4.0)
        # each begins where the one before it ended; together the root
        for before, after in zip(children, children[1:]):
            assert after["t"] - after["dur"] == pytest.approx(before["t"])
        assert sum(c["dur"] for c in children) == pytest.approx(root["dur"])
        # a block leg is the ambient parent of what it holds
        assert inside == {"trace": legs.trace,
                          "span": spans["resume.rendezvous"]["span"]}
        assert spans["rdzv.round"]["parent"] == inside["span"]
        counters = {c["name"]: c["value"] for c in snap["counters"]}
        for name in (*order, "resume"):
            assert counters[name + "_s"] == pytest.approx(spans[name]["dur"])

    def test_order_is_kept_and_the_filler_takes_the_gaps(
        self, fresh_telemetry
    ):
        legs = tracing.Legs(
            "launch", order=tracing.START_LEGS, filler=tracing.START_FILLER,
        )
        assert legs.advance("start.exec")
        assert legs.advance("start.backend")       # may skip ahead
        assert not legs.advance("start.imports")   # never back
        assert not legs.advance("start.backend")   # nor twice
        with legs.leg("start.trainer_init"):
            pass
        assert legs.open_name == tracing.START_FILLER
        with legs.leg("start.trainer_init") as again:
            assert again is None                   # a second Trainer
        legs.close()
        names = [e["name"] for e in _span_events(fresh_telemetry.snapshot())]
        assert names == ["start.exec", "start.backend",
                         "start.trainer_init", "start.script", "launch"]

    def test_context_survives_a_popen(self, fresh_telemetry):
        legs = tracing.Legs("resume", labels={"restart": 1})
        with legs.leg("resume.spawn"):
            spawn_t, child = _run_child(legs.export(closes_root=True))
        name, trace, span, t0, closes_root = child["root"]
        assert (name, trace, span) == ("resume", legs.trace, legs.span)
        assert t0 == pytest.approx(legs.t0) and closes_root
        spans = {e["name"]: e for e in child["events"]
                 if e["kind"] == tracing.SPAN_EVENT}
        # its first leg: a child of the SAME root, from the spawn's
        # instant to the package's first import line
        exec_leg = spans["start.exec"]
        assert exec_leg["trace"] == legs.trace
        assert exec_leg["parent"] == legs.span
        assert exec_leg["t"] == pytest.approx(child["import_t"])
        assert exec_leg["t"] - exec_leg["dur"] == pytest.approx(
            spawn_t, abs=0.05
        )
        # and a span of its own lies in the parent's trace, under the
        # leg that was open (the adopted context is ambient)
        work = spans["child.work"]
        assert work["trace"] == legs.trace and work["parent"]
        assert child["open"] == "start.imports"
        assert work["parent"] not in (legs.span, exec_leg["span"])

    def test_a_payload_child_starts_under_the_span_that_waits_for_it(
        self, fresh_telemetry
    ):
        with tracing.span("rdzv.probe") as waiting:
            env = tracing.export_ambient({telemetry.ENV_TRACE: "stale"})
            _t, child = _run_child(env[telemetry.ENV_TRACE])
        (exec_leg,) = [e for e in child["events"]
                       if e.get("name") == "start.exec"]
        assert exec_leg["trace"] == waiting.trace
        assert exec_leg["parent"] == waiting.span
        assert not child["root"][4]  # the child closes no root
        # outside any span nothing is handed on, not even what this
        # process was handed itself
        assert tracing.export_ambient({telemetry.ENV_TRACE: "stale"}) == {}

    def test_a_process_nobody_launched_roots_its_own_at_its_start(self):
        spawn_t, child = _run_child()
        name, _trace, _span, t0, closes_root = child["root"]
        assert name == "launch" and closes_root
        # /proc/self/stat's start time: after the spawn, before the import
        assert spawn_t - 0.05 <= t0 <= child["import_t"]
        (exec_leg,) = [e for e in child["events"]
                       if e.get("name") == "start.exec"]
        assert exec_leg["t"] - exec_leg["dur"] == pytest.approx(t0)
        # nothing is ambient there: its spans root their own traces
        (work,) = [e for e in child["events"]
                   if e.get("name") == "child.work"]
        assert work["parent"] == "" and work["trace"] != exec_leg["trace"]

    def test_a_malformed_trace_variable_is_no_trace(self):
        _t, child = _run_child("{not json")
        assert child["root"][0] == "launch"

    def test_root_legs_lists_each_resume_by_leg(self, fresh_telemetry):
        for restart in (1, 2):
            legs = tracing.Legs("resume", labels={"restart": restart})
            legs.advance("resume.detect")
            legs.advance("resume.stop")
            legs.close()
        tracing.Legs("launch").close()
        jt = JobTelemetry()
        jt.update(fresh_telemetry.snapshot())
        found = tracing.root_legs(jt.merged_events())
        assert [r["root"]["restart"] for r in found] == [1, 2]
        assert [[e["name"] for e in r["legs"]] for r in found] == [
            ["resume.detect", "resume.stop"]
        ] * 2
        text = telemetry.format_report(
            {**jt.report(), "resume_legs": found}
        )
        ledger = text.split("=== event timeline")[0].splitlines()
        at = next(i for i, l in enumerate(ledger) if l.endswith("restart"))
        assert "resume.detect" in ledger[at + 2]


# -------------------------------------------------------------------------
# span semantics
# -------------------------------------------------------------------------


class TestSpans:
    def test_nesting_parents_and_shared_trace(self, fresh_telemetry):
        with tracing.span("root") as root:
            assert tracing.current() == {
                "trace": root.trace, "span": root.span,
            }
            with tracing.span("child") as child:
                assert child.trace == root.trace
                assert child.parent == root.span
        assert tracing.current() is None
        spans = {e["name"]: e for e in _span_events(telemetry.snapshot())}
        assert spans["child"]["parent"] == spans["root"]["span"]
        assert spans["root"]["parent"] == ""
        assert spans["root"]["dur"] >= spans["child"]["dur"] >= 0

    def test_exception_marks_error_and_restores_context(
        self, fresh_telemetry
    ):
        with pytest.raises(RuntimeError):
            with tracing.span("boom"):
                raise RuntimeError("x")
        assert tracing.current() is None
        (ev,) = _span_events(telemetry.snapshot())
        assert ev["status"] == "error"

    def test_attach_adopts_wire_context(self, fresh_telemetry):
        wire = {"trace": "t" * 16, "span": "s" * 16}
        with tracing.attach(wire):
            with tracing.span("served") as sp:
                assert sp.trace == wire["trace"]
                assert sp.parent == wire["span"]
        assert tracing.current() is None

    def test_attach_tolerates_malformed_context(self, fresh_telemetry):
        for bad in (None, {}, {"trace": "x"}, "junk", 7):
            with tracing.attach(bad):
                with tracing.span("s") as sp:
                    assert sp.parent == ""

    def test_labels_ride_the_event(self, fresh_telemetry):
        with tracing.span("ckpt", step=5) as sp:
            sp.annotate(mb=12.5)
        (ev,) = _span_events(telemetry.snapshot())
        assert ev["step"] == 5 and ev["mb"] == 12.5

    def test_disabled_telemetry_still_propagates(self):
        prev = telemetry.active_registry()
        telemetry.disable()
        try:
            with tracing.span("root") as root:
                assert tracing.wire_context()["trace"] == root.trace
        finally:
            telemetry._REGISTRY = prev


# -------------------------------------------------------------------------
# cross-process propagation (retry / reconnect / failover)
# -------------------------------------------------------------------------


class _EchoService:
    """get() opens a server-side span and returns its identity."""

    def __init__(self, name="server.handle"):
        self.name = name

    def get(self, node_type, node_id, message):
        with tracing.span(self.name) as sp:
            return {"trace": sp.trace, "parent": sp.parent}

    def report(self, node_type, node_id, message):
        return True


def _start_server(name="server.handle"):
    from dlrover_tpu.common.rpc import RpcServer

    server = RpcServer(0, _EchoService(name))
    server.start()
    return server


class TestPropagation:
    def test_span_crosses_the_rpc_boundary(self, fresh_telemetry):
        from dlrover_tpu.common.rpc import RpcClient

        server = _start_server()
        client = RpcClient(f"127.0.0.1:{server.port}")
        try:
            with tracing.span("client.op") as root:
                got = client.get("w", 0, "x")
            assert got["trace"] == root.trace
            assert got["parent"] == root.span
        finally:
            client.close()
            server.stop()

    def test_no_active_span_sends_plain_envelope(self, fresh_telemetry):
        from dlrover_tpu.common.rpc import RpcClient

        server = _start_server()
        client = RpcClient(f"127.0.0.1:{server.port}")
        try:
            got = client.get("w", 0, "x")
            assert got["parent"] == ""  # server span is a trace root
        finally:
            client.close()
            server.stop()

    def test_parent_survives_rpc_retry(self, fresh_telemetry):
        """An injected first-attempt drop forces the retry path; the
        retried attempt must carry the SAME parent (context is captured
        per logical call, not per attempt)."""
        from dlrover_tpu.common import chaos
        from dlrover_tpu.common.rpc import RpcClient

        server = _start_server()
        client = RpcClient(f"127.0.0.1:{server.port}")
        chaos.install({
            "seed": 3,
            "rules": [{"site": "rpc.send", "action": "drop", "max": 1}],
        })
        try:
            os.environ["DLROVER_RPC_BASE_DELAY"] = "0.01"
            with tracing.span("client.op") as root:
                got = client.get("w", 0, "x")
            assert got["trace"] == root.trace
            assert got["parent"] == root.span
            assert chaos.active_registry().summary() == {
                "rpc.send:drop": 1
            }
        finally:
            os.environ.pop("DLROVER_RPC_BASE_DELAY", None)
            chaos.uninstall()
            client.close()
            server.stop()

    def test_parent_survives_master_failover(self, fresh_telemetry):
        """The context lives in the caller, never in master state: a
        replacement master (new process in prod; new server here)
        parents its spans under the same client span, so children are
        never orphaned by a failover mid-trace."""
        from dlrover_tpu.common.rpc import RpcClient

        first = _start_server("incarnation.one")
        addr = {"v": f"127.0.0.1:{first.port}"}
        client = RpcClient(addr["v"], addr_resolver=lambda: addr["v"])
        try:
            with tracing.span("client.op") as root:
                got1 = client.get("w", 0, "x")
                first.stop()
                second = _start_server("incarnation.two")
                addr["v"] = f"127.0.0.1:{second.port}"
                os.environ["DLROVER_RPC_BASE_DELAY"] = "0.01"
                try:
                    got2 = client.get("w", 0, "x")
                finally:
                    os.environ.pop("DLROVER_RPC_BASE_DELAY", None)
            assert got1["parent"] == root.span
            assert got2["parent"] == root.span
            assert got1["trace"] == got2["trace"] == root.trace
        finally:
            client.close()
            second.stop()

    def test_server_histograms_recorded_per_verb(self, fresh_telemetry):
        from dlrover_tpu.common.rpc import RpcClient

        server = _start_server()
        client = RpcClient(f"127.0.0.1:{server.port}")
        try:
            client.get("w", 0, "x")
            client.report("w", 0, "y")
        finally:
            client.close()
            server.stop()
        hists = {
            (h["labels"]["verb"], h["labels"]["msg"])
            for h in telemetry.snapshot()["histograms"]
            if h["name"] == "master.rpc.seconds"
        }
        assert ("get", "str") in hists and ("report", "str") in hists

    def test_chaos_fire_tagged_with_active_span(self, fresh_telemetry):
        from dlrover_tpu.common.chaos import ChaosRegistry

        reg = ChaosRegistry({
            "rules": [{"site": "s", "action": "delay", "delay": 0.0}],
        })
        with tracing.span("restore") as sp:
            reg.fire("s", {"step": 1})
        (fire,) = [
            e for e in telemetry.snapshot()["events"]
            if e["kind"] == "chaos.fire"
        ]
        assert fire["trace"] == sp.trace
        assert fire["span"] == sp.span


# -------------------------------------------------------------------------
# quantiles
# -------------------------------------------------------------------------


class TestQuantiles:
    def test_linear_interpolation_within_bucket(self):
        # 100 obs uniformly attributed to (0, 1]: p50 -> 0.5
        assert hist_quantile([1.0], [100, 0], 0.5) == pytest.approx(0.5)
        # two buckets (0,1], (1,2] with 50/50: p75 lands mid second
        assert hist_quantile(
            [1.0, 2.0], [50, 50, 0], 0.75
        ) == pytest.approx(1.5)

    def test_interpolates_from_previous_bound(self):
        # all mass in (10, 20]: p0.. near 10, p100 -> 20
        assert hist_quantile([10.0, 20.0], [0, 10, 0], 0.0) >= 10.0
        assert hist_quantile(
            [10.0, 20.0], [0, 10, 0], 1.0
        ) == pytest.approx(20.0)

    def test_inf_bucket_clamps_to_last_bound(self):
        assert hist_quantile([1.0, 2.0], [0, 0, 5], 0.99) == 2.0

    def test_empty_is_nan(self):
        import math

        assert math.isnan(hist_quantile([1.0], [0, 0], 0.5))

    def test_degenerate_inputs(self):
        """The operator-facing quantile must stay finite and bounded on
        every degenerate shape: empty counts list, no bounds at all,
        every observation past the last finite bound, and the q=0/q=1
        edges (clamped, never extrapolated)."""
        import math

        # empty/zero counts and empty bounds: NaN, never a crash
        assert math.isnan(hist_quantile([1.0, 2.0], [], 0.5))
        assert math.isnan(hist_quantile([], [], 0.5))
        assert math.isnan(hist_quantile([], [5], 0.5))  # no finite bound
        # EVERYTHING in the +Inf overflow bucket: every quantile clamps
        # to the last finite bound (there is no upper edge to
        # interpolate toward)
        for q in (0.0, 0.25, 0.5, 0.99, 1.0):
            assert hist_quantile([1.0, 4.0], [0, 0, 7], q) == 4.0
        # q=0 -> the LOWER edge of the first nonempty bucket; q=1 ->
        # the upper edge of the last nonempty one
        assert hist_quantile(
            [1.0, 2.0, 4.0], [0, 5, 0, 0], 0.0
        ) == pytest.approx(1.0)
        assert hist_quantile(
            [1.0, 2.0, 4.0], [0, 5, 0, 0], 1.0
        ) == pytest.approx(2.0)
        # out-of-range q is clamped into [0, 1], not extrapolated
        assert hist_quantile([1.0], [10, 0], -0.5) == pytest.approx(0.0)
        assert hist_quantile([1.0], [10, 0], 2.0) == pytest.approx(1.0)

    def test_sum_bucket_counts_merges_and_skips_mismatched(self):
        from dlrover_tpu.common.telemetry import sum_bucket_counts

        bounds, counts = sum_bucket_counts([
            {"bounds": [1.0, 2.0], "counts": [1, 2, 3]},
            {"bounds": [1.0, 2.0], "counts": [4, 5, 6]},
            {"bounds": [9.0], "counts": [7, 7]},  # mismatched: skipped
        ])
        assert bounds == [1.0, 2.0]
        assert counts == [5, 7, 9]
        assert sum_bucket_counts([]) == (None, None)

    def test_snapshot_best_effort_survives_a_held_lock(
        self, fresh_telemetry
    ):
        """The flight recorder's signal-context path: a bounded lock
        acquire, then a lockless read — never a self-deadlock on the
        non-reentrant registry lock."""
        telemetry.event("before", step=1)
        reg = telemetry.active_registry()
        assert reg._lock.acquire()  # simulate an interrupted hook
        try:
            t0 = time.monotonic()
            snap = telemetry.snapshot_best_effort(lock_timeout=0.05)
            assert time.monotonic() - t0 < 2.0
            assert snap is not None
            assert any(e["kind"] == "before" for e in snap["events"])
        finally:
            reg._lock.release()

    def test_registry_histograms_round_trip(self, fresh_telemetry):
        for v in (0.001, 0.002, 0.004, 0.5):
            telemetry.observe("lat", v)
        (h,) = telemetry.snapshot()["histograms"]
        p99 = hist_quantile(h["bounds"], h["counts"], 0.99)
        assert 0.25 < p99 <= 0.5

    def test_format_report_renders_quantile_columns(
        self, fresh_telemetry
    ):
        from dlrover_tpu.common.telemetry import format_report

        telemetry.observe("lat", 0.002)
        jt = JobTelemetry()
        jt.update(telemetry.snapshot())
        out = format_report(jt.report())
        assert "p50" in out and "p95" in out and "p99" in out


# -------------------------------------------------------------------------
# TimerRing exporter round-trip + per-phase gauges
# -------------------------------------------------------------------------


class TestTimerExporter:
    def test_aggregation_round_trip_and_gauges(
        self, tmp_path, isolated_ckpt_env, fresh_telemetry
    ):
        from dlrover_tpu.agent.monitor import TimerRingExporter
        from dlrover_tpu.trainer.timer import StepTimer, Tag

        timer = StepTimer()
        try:
            now = time.time_ns()
            for dur_ms in (100, 120):
                timer.record(Tag.STEP, now, dur_ms * 1_000_000)
            timer.record(Tag.DATA_WAIT, now, 30 * 1_000_000)
            out_path = str(tmp_path / "timer_stats.json")
            exporter = TimerRingExporter(out_path=out_path)
            exporter._timer = timer
            stats = exporter.export_once()
            assert stats["step"]["count"] == 2
            assert stats["step"]["avg_ms"] == pytest.approx(110.0)
            assert stats["step"]["max_ms"] == pytest.approx(120.0)
            assert stats["data_wait"]["avg_ms"] == pytest.approx(30.0)
            # the on-disk JSON round-trips the same aggregates
            assert json.load(open(out_path)) == stats
            # ... and the per-phase gauges landed in the registry (the
            # payload the agent relays and the diagnosis consumes)
            gauges = {
                (g["name"], g["labels"].get("phase")): g["value"]
                for g in telemetry.snapshot()["gauges"]
            }
            assert gauges[
                ("timer.phase.recent_avg_ms", "step")
            ] == pytest.approx(110.0)
            assert gauges[
                ("timer.phase.avg_ms", "data_wait")
            ] == pytest.approx(30.0)
            # drained ring: a second export keeps lifetime aggregates
            stats2 = exporter.export_once()
            assert stats2["step"]["count"] == 2
        finally:
            timer.close()

    def test_step_timer_time_emits_phase_span(
        self, isolated_ckpt_env, fresh_telemetry
    ):
        from dlrover_tpu.trainer.timer import StepTimer, Tag

        timer = StepTimer()
        try:
            with timer.time(Tag.DATA_WAIT):
                pass
            (ev,) = _span_events(telemetry.snapshot())
            assert ev["name"] == "phase.data_wait"
            assert timer.drain()[0][0] == Tag.DATA_WAIT
        finally:
            timer.close()


# -------------------------------------------------------------------------
# diagnosis: stragglers + hangs
# -------------------------------------------------------------------------


def _agent_snap(rank, phases, now, role="agent"):
    return {
        "format": 1, "source": f"{role}-{rank}-1", "role": role,
        "pid": 1, "created": 0.0, "now": now,
        "counters": [], "histograms": [], "events": [],
        "events_dropped": 0,
        "gauges": [
            {
                "name": "timer.phase.recent_avg_ms",
                "labels": {"phase": p}, "value": v,
            }
            for p, v in phases.items()
        ],
    }


def _worker_snap(rank, steps, now):
    """steps: list of (t, step, dur)."""
    return {
        "format": 1, "source": f"worker-{rank}-9", "role": "worker",
        "pid": 9, "created": 0.0, "now": now,
        "counters": [], "gauges": [], "histograms": [],
        "events_dropped": 0,
        "events": [
            {"seq": i + 1, "t": t, "mono": t, "kind": "step.end",
             "step": s, "dur": d}
            for i, (t, s, d) in enumerate(steps)
        ],
    }


class TestDiagnosis:
    def _manager(self, snaps, **kw):
        from dlrover_tpu.master.diagnosis import DiagnosisManager

        jt = JobTelemetry()
        for s in snaps:
            assert jt.update(s)
        return DiagnosisManager(jt, **kw)

    def test_straggler_flagged_with_blamed_phase(self, fresh_telemetry):
        now = time.time()
        snaps = [
            _agent_snap(r, {"step": 100.0, "data_wait": 5.0}, now)
            for r in range(3)
        ] + [
            _agent_snap(3, {"step": 260.0, "data_wait": 170.0}, now)
        ]
        mgr = self._manager(snaps)
        verdict = mgr.check(force=True)
        assert list(verdict["stragglers"]) == [3]
        info = verdict["stragglers"][3]
        assert info["phase"] == "data_wait"
        assert info["ratio"] > 2.0
        kinds = [
            e["kind"] for e in telemetry.snapshot()["events"]
        ]
        assert "diagnosis.straggler" in kinds

    def test_compute_blame_when_no_subphase_stands_out(self):
        now = time.time()
        snaps = [
            _agent_snap(r, {"step": 100.0, "data_wait": 5.0}, now)
            for r in range(3)
        ] + [
            # slow step, normal data_wait: the jitted step itself (bad
            # chip / contention) is to blame
            _agent_snap(3, {"step": 300.0, "data_wait": 5.0}, now)
        ]
        mgr = self._manager(snaps)
        assert mgr.detect_stragglers()[3]["phase"] == "compute"

    def test_ckpt_blame(self):
        now = time.time()
        snaps = [
            _agent_snap(
                r, {"step": 100.0, "ckpt_shm": 10.0}, now
            )
            for r in range(3)
        ] + [
            _agent_snap(3, {"step": 280.0, "ckpt_shm": 190.0}, now)
        ]
        mgr = self._manager(snaps)
        assert mgr.detect_stragglers()[3]["phase"] == "ckpt"

    def test_healthy_fleet_flags_nobody(self):
        now = time.time()
        snaps = [
            _agent_snap(r, {"step": 100.0 + r, "data_wait": 5.0}, now)
            for r in range(4)
        ]
        mgr = self._manager(snaps)
        assert mgr.detect_stragglers() == {}

    def test_two_hosts_use_faster_as_baseline(self):
        now = time.time()
        snaps = [
            _agent_snap(0, {"step": 100.0}, now),
            _agent_snap(1, {"step": 250.0}, now),
        ]
        mgr = self._manager(snaps)
        assert list(mgr.detect_stragglers()) == [1]

    def test_hang_detected_from_stale_step_end(self, fresh_telemetry):
        now = time.time()
        snaps = [
            _worker_snap(
                0,
                [(now - 3 + 0.5 * i, i, 0.5) for i in range(5)],
                now,
            ),
            _worker_snap(
                1, [(now - 120, 3, 0.5)], now,
            ),
        ]
        mgr = self._manager(snaps, hang_floor_s=10.0)
        verdict = mgr.check(force=True)
        assert list(verdict["hangs"]) == [1]
        assert verdict["hangs"][1]["stalled_s"] > 100
        assert verdict["hangs"][1]["last_step"] == 3
        kinds = [e["kind"] for e in telemetry.snapshot()["events"]]
        assert "diagnosis.hang" in kinds

    def test_never_stepped_host_is_not_a_hang(self):
        now = time.time()
        snaps = [
            _worker_snap(0, [(now - 1, 5, 0.5)], now),
            _worker_snap(1, [], now),  # still compiling/restoring
        ]
        mgr = self._manager(snaps, hang_floor_s=1.0)
        assert mgr.detect_hangs(now) == {}

    def test_recovery_emits_clear_event(self, fresh_telemetry):
        now = time.time()
        jt = JobTelemetry()
        jt.update(_worker_snap(0, [(now - 1, 9, 0.5)], now))
        jt.update(_worker_snap(1, [(now - 120, 3, 0.5)], now))
        from dlrover_tpu.master.diagnosis import DiagnosisManager

        mgr = DiagnosisManager(jt, hang_floor_s=10.0)
        assert list(mgr.check(force=True)["hangs"]) == [1]
        # host 1 resumes stepping
        jt.update(_worker_snap(1, [(now - 120, 3, 0.5),
                                   (now - 0.5, 4, 0.5)], now + 1))
        assert mgr.check(force=True)["hangs"] == {}
        kinds = [e["kind"] for e in telemetry.snapshot()["events"]]
        assert "diagnosis.clear" in kinds

    def test_fresh_global_step_vetoes_stale_telemetry_hang(self):
        """The telemetry file is only as fresh as the worker's flush
        cadence; the per-step GlobalStep stamps are fresher — a host
        whose speed-monitor progress is recent must NOT be flagged off
        a stale snapshot."""
        from dlrover_tpu.master.diagnosis import DiagnosisManager
        from dlrover_tpu.master.monitor import SpeedMonitor

        now = time.time()
        jt = JobTelemetry()
        jt.update(_worker_snap(0, [(now - 1, 9, 0.5)], now))
        # rank 1's snapshot is 120s stale (sparse flusher) ...
        jt.update(_worker_snap(1, [(now - 120, 3, 0.5)], now))
        sm = SpeedMonitor()
        # ... but its GlobalStep reports kept flowing
        sm.collect_global_step(8, now - 2, node=("worker", 1))
        sm.collect_global_step(9, now - 1, node=("worker", 0))
        mgr = DiagnosisManager(jt, speed_monitor=sm, hang_floor_s=10.0)
        assert mgr.detect_hangs(now) == {}

    def test_everyone_stalled_is_job_level_not_per_node(self):
        """A fleet-wide pause (recompile, sync checkpoint, rendezvous)
        stalls every host at once: that is SpeedMonitor's job-level
        all_worker_hanged signal, not N per-node hang verdicts (which
        would trigger N flight dumps)."""
        now = time.time()
        snaps = [
            _worker_snap(r, [(now - 120, 3, 0.5)], now)
            for r in range(3)
        ]
        mgr = self._manager(snaps, hang_floor_s=10.0)
        assert mgr.detect_hangs(now) == {}
        # a single survivor stalling alone IS a per-node verdict
        snaps2 = [
            _worker_snap(0, [(now - 1, 9, 0.5)], now),
            _worker_snap(1, [(now - 120, 3, 0.5)], now),
        ]
        mgr2 = self._manager(snaps2, hang_floor_s=10.0)
        assert list(mgr2.detect_hangs(now)) == [1]

    def test_speed_monitor_tracks_per_node_progress(self):
        from dlrover_tpu.master.monitor import SpeedMonitor

        sm = SpeedMonitor()
        old = time.time() - 100
        sm.collect_global_step(5, old, node=("worker", 1))
        sm.collect_global_step(6, time.time(), node=("worker", 0))
        progress = sm.node_progress()
        assert progress[("worker", 1)][1] == 5
        assert sm.stalled_nodes(window=50) == [("worker", 1)]
        # everyone stalled -> job-level signal, not per-node blame
        sm2 = SpeedMonitor()
        sm2.collect_global_step(1, old, node=("worker", 0))
        sm2.collect_global_step(1, old, node=("worker", 1))
        assert sm2.stalled_nodes(window=50) == []

    def test_servicer_merges_diagnosis_into_check_straggler(
        self, fresh_telemetry
    ):
        from dlrover_tpu.common import messages as msg
        from dlrover_tpu.common.constants import RendezvousName
        from dlrover_tpu.master.rendezvous import (
            NetworkCheckRendezvousManager,
        )
        from dlrover_tpu.master.servicer import MasterServicer

        servicer = MasterServicer(
            rdzv_managers={
                RendezvousName.NETWORK_CHECK: (
                    NetworkCheckRendezvousManager()
                ),
            }
        )
        now = time.time()
        for r in range(3):
            servicer.telemetry.update(
                _agent_snap(r, {"step": 100.0, "data_wait": 5.0}, now)
            )
        servicer.telemetry.update(
            _agent_snap(3, {"step": 260.0, "data_wait": 170.0}, now)
        )
        res = servicer.get("worker", 0, msg.StragglerExistRequest())
        assert 3 in res.nodes
        assert "3:data_wait" in res.reason
        diag = servicer.get("worker", 0, msg.DiagnosisRequest())
        assert 3 in diag.stragglers
        assert diag.stragglers[3]["phase"] == "data_wait"


# -------------------------------------------------------------------------
# check_straggler / exclude_straggler end to end
# -------------------------------------------------------------------------


def test_exclude_straggler_end_to_end(
    local_master_2nodes, monkeypatch,
):
    """Two node-check agents probe through a real master; the injected
    slow host is flagged by check_straggler and excludes itself, the
    fast host passes — the full reference --exclude-straggler flow."""
    from dlrover_tpu.agent import node_check as node_check_mod
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.agent.training_agent import (
        ElasticLaunchConfig,
        NodeCheckElasticAgent,
    )
    from dlrover_tpu.common.constants import NodeType

    elapsed_by_thread = {"nc-0": 0.1, "nc-1": 2.0}

    def fake_check(*_a, **_k):
        return True, elapsed_by_thread[threading.current_thread().name]

    monkeypatch.setattr(
        node_check_mod, "run_node_check_child", fake_check
    )

    results = {}

    def run_agent(rank):
        config = ElasticLaunchConfig(
            min_nodes=2, max_nodes=2, nproc_per_node=1,
            node_rank=rank, rdzv_timeout=30, exclude_straggler=True,
        )
        client = MasterClient(
            local_master_2nodes.addr, rank, NodeType.WORKER
        )
        try:
            agent = NodeCheckElasticAgent(config, client, rounds=2)
            results[rank] = agent.run()
        finally:
            client.close()

    threads = [
        threading.Thread(target=run_agent, args=(r,), name=f"nc-{r}")
        for r in range(2)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert results == {0: True, 1: False}, results


# -------------------------------------------------------------------------
# flight recorder
# -------------------------------------------------------------------------


class TestFlightRecorder:
    def test_dump_contains_spans_and_stacks(
        self, tmp_path, monkeypatch, fresh_telemetry
    ):
        from dlrover_tpu.common import flight

        monkeypatch.setenv(telemetry.ENV_DIR, str(tmp_path))
        with tracing.span("last.thing", step=7):
            pass
        path = flight.dump("unit-test", extra_field=1)
        assert path is not None and os.path.exists(path)
        record = json.load(open(path))
        assert record["reason"] == "unit-test"
        assert record["extra_field"] == 1
        names = [
            e.get("name") for e in record["events"]
            if e["kind"] == "span"
        ]
        assert "last.thing" in names
        assert "Thread" in record["stacks"]
        assert "MainThread" in record["stacks"]
        assert flight.list_dumps(str(tmp_path)) == [path]

    def test_dump_noop_without_telemetry_dir(
        self, monkeypatch, fresh_telemetry
    ):
        from dlrover_tpu.common import flight

        monkeypatch.delenv(telemetry.ENV_DIR, raising=False)
        assert flight.dump("nowhere") is None

    def test_hang_detector_expiry_dumps(
        self, tmp_path, monkeypatch, fresh_telemetry
    ):
        from dlrover_tpu.common import flight
        from dlrover_tpu.trainer.fault_tolerance import HangingDetector

        monkeypatch.setenv(telemetry.ENV_DIR, str(tmp_path))
        det = HangingDetector(timeout=0.05, check_interval=0.05)
        det.start()
        try:
            deadline = time.time() + 5
            while not flight.list_dumps(str(tmp_path)):
                assert time.time() < deadline, "no dump within 5s"
                time.sleep(0.05)
        finally:
            det.stop()
        (path,) = flight.list_dumps(str(tmp_path))
        record = json.load(open(path))
        assert record["reason"] == "hang-detector"
        assert record["stalled_s"] >= 0.05

    def test_received_hang_diagnosis_dumps_once_per_episode(
        self, tmp_path, monkeypatch, fresh_telemetry
    ):
        from dlrover_tpu.common import flight
        from dlrover_tpu.common import messages as msg
        from dlrover_tpu.agent.training_agent import (
            ElasticLaunchConfig,
            ElasticTrainingAgent,
            WorkerSpec,
        )

        monkeypatch.setenv(telemetry.ENV_DIR, str(tmp_path))

        class StubClient:
            master_addr = "127.0.0.1:0"
            node_id = 0
            hangs: dict = {}

            def get_diagnosis(self):
                return msg.DiagnosisResult(hangs=dict(self.hangs))

        client = StubClient()
        config = ElasticLaunchConfig(node_rank=0)
        agent = ElasticTrainingAgent(
            config, WorkerSpec("x.py", (), config), client
        )
        dumped = []
        monkeypatch.setattr(
            flight, "dump", lambda reason, **kw: dumped.append(reason)
        )
        agent._poll_diagnosis()
        assert dumped == []  # no verdict, no dump
        client.hangs = {0: {"stalled_s": 120.0, "last_step": 9}}
        agent._poll_diagnosis()
        agent._poll_diagnosis()
        assert dumped == ["hang-diagnosis"]  # one per episode
        client.hangs = {}
        agent._poll_diagnosis()
        client.hangs = {0: {"stalled_s": 500.0, "last_step": 9}}
        agent._poll_diagnosis()
        assert dumped == ["hang-diagnosis", "hang-diagnosis"]

    def test_sigterm_dumps_then_dies_with_default_code(self, tmp_path):
        """The worker-preemption path: SIGTERM leaves a flight record
        AND the exit code stays -SIGTERM (the agent's taxonomy depends
        on it)."""
        script = (
            "import os, signal, time\n"
            "from dlrover_tpu.common import flight, telemetry, tracing\n"
            "flight.install()\n"
            "with tracing.span('about.to.die'):\n"
            "    pass\n"
            "os.kill(os.getpid(), signal.SIGTERM)\n"
            "time.sleep(10)\n"
        )
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            DLROVER_TELEMETRY_DIR=str(tmp_path),
            DLROVER_TELEMETRY_ROLE="worker",
        )
        proc = subprocess.run(
            [sys.executable, "-c", script], env=env, timeout=60,
            capture_output=True,
        )
        assert proc.returncode == -signal.SIGTERM, proc.stderr.decode()
        (path,) = [
            p for p in os.listdir(tmp_path / "flight")
        ]
        record = json.load(open(tmp_path / "flight" / path))
        assert record["reason"] == "sigterm"
        names = [
            e.get("name") for e in record["events"]
            if e["kind"] == "span"
        ]
        assert "about.to.die" in names

    def test_chaos_kill_dumps_victims_last_spans(self, tmp_path):
        """The acceptance bullet: a chaos kill leaves a post-mortem
        with the victim's last spans + thread stacks."""
        script = (
            "from dlrover_tpu.common import tracing\n"
            "from dlrover_tpu.common.chaos import chaos_point\n"
            "with tracing.span('train.step', step=5):\n"
            "    with tracing.span('ckpt.save', step=5):\n"
            "        chaos_point('ckpt.save', step=5)\n"
        )
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            DLROVER_TELEMETRY_DIR=str(tmp_path),
            DLROVER_TELEMETRY_ROLE="worker",
            DLROVER_CHAOS=json.dumps({
                "seed": 7,
                "rules": [
                    {"site": "ckpt.save", "action": "kill", "step": 5},
                ],
            }),
        )
        proc = subprocess.run(
            [sys.executable, "-c", script], env=env, timeout=60,
            capture_output=True,
        )
        assert proc.returncode == 137, proc.stderr.decode()
        from dlrover_tpu.common import flight

        (path,) = flight.list_dumps(str(tmp_path))
        record = json.load(open(path))
        assert record["reason"] == "chaos-kill"
        assert record["site"] == "ckpt.save"
        # the kill fired INSIDE the ckpt.save span, before its exit —
        # the surrounding spans are on the ring from earlier activity
        # only if they closed; what must be present is the chaos.fire
        # event tagged with the exact span it perturbed
        fires = [
            e for e in record["events"] if e["kind"] == "chaos.fire"
        ]
        assert fires and fires[0]["span"], fires
        assert "Thread" in record["stacks"]

    def test_install_chains_and_uninstall_restores(self, monkeypatch):
        from dlrover_tpu.common import flight

        if threading.current_thread() is not threading.main_thread():
            pytest.skip("signal API needs the main thread")
        # an earlier test may have run an agent/trainer that installed
        # the process-global handlers; unwind to a clean slate so this
        # test exercises a fresh install->uninstall cycle
        flight.uninstall()
        seen = []
        prev = signal.signal(
            signal.SIGTERM, lambda *_: seen.append("prev")
        )
        try:
            assert flight.install()
            assert flight.install()  # idempotent
            handler = signal.getsignal(signal.SIGTERM)
            assert handler is flight._handler
            flight.uninstall()
            restored = signal.getsignal(signal.SIGTERM)
            restored(signal.SIGTERM, None)
            assert seen == ["prev"]
        finally:
            flight.uninstall()
            signal.signal(signal.SIGTERM, prev)


# -------------------------------------------------------------------------
# obs_report surfaces: trace view + control plane
# -------------------------------------------------------------------------


class TestReportSurfaces:
    def test_trace_render_nests_cross_source_children(self):
        from dlrover_tpu.common.tracing import format_trace, trace_trees

        t0 = 1000.0
        events = [
            {"seq": 1, "t": t0 + 1.0, "kind": "span", "name": "child",
             "trace": "T", "span": "b", "parent": "a", "dur": 0.4,
             "status": "ok", "source": "master-0-1"},
            {"seq": 2, "t": t0 + 2.0, "kind": "span",
             "name": "rdzv.round", "trace": "T", "span": "a",
             "parent": "", "dur": 1.9, "status": "ok",
             "source": "agent-0-1"},
            {"seq": 3, "t": t0 + 5.0, "kind": "step.end", "step": 1},
        ]
        (tree,) = trace_trees(events)
        assert tree["spans"] == 2
        (root,) = tree["roots"]
        assert root["event"]["name"] == "rdzv.round"
        assert root["children"][0]["event"]["name"] == "child"
        out = format_trace(events)
        root_line = next(l for l in out.splitlines() if "rdzv.round" in l)
        child_line = next(l for l in out.splitlines() if "child" in l)
        assert "<agent-0-1>" in root_line
        assert "<master-0-1>" in child_line
        # the child renders indented one level deeper than the root
        assert child_line.index("child") > root_line.index("rdzv.round")

    def test_orphaned_span_promoted_to_root(self):
        from dlrover_tpu.common.tracing import trace_trees

        events = [
            {"seq": 1, "t": 1.0, "kind": "span", "name": "orphan",
             "trace": "T", "span": "x", "parent": "gone", "dur": 0.1,
             "status": "ok"},
        ]
        (tree,) = trace_trees(events)
        assert tree["roots"][0]["event"]["name"] == "orphan"

    def test_cross_host_rendezvous_trace_through_real_master(
        self, local_master, tmp_path, monkeypatch, fresh_telemetry
    ):
        """The acceptance bullet: one rendezvous round renders as a
        single cross-host span tree with correct parent/child nesting
        (client root -> master-side join/form children)."""
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.agent.training_agent import (
            MasterRendezvousHandler,
        )
        from dlrover_tpu.common.constants import NodeType, RendezvousName
        from dlrover_tpu.common.tracing import trace_trees

        client = MasterClient(local_master.addr, 0, NodeType.WORKER)
        try:
            handler = MasterRendezvousHandler(
                RendezvousName.ELASTIC_TRAINING, 0, client, 1,
                timeout=30,
            )
            handler.next_rendezvous()
        finally:
            client.close()
        # this test process hosts BOTH sides (in-process master), so
        # one registry holds the whole trace
        events = telemetry.snapshot()["events"]
        trees = {
            n["event"]["name"]: t
            for t in trace_trees(events)
            for n in t["roots"]
        }
        round_tree = trees["rdzv.round"]
        (root,) = round_tree["roots"]
        child_names = {
            c["event"]["name"] for c in root["children"]
        }
        assert "rdzv.join.handle" in child_names
        assert "rdzv.form_round" in child_names
        for child in root["children"]:
            assert child["event"]["trace"] == root["event"]["trace"]
            assert child["event"]["parent"] == root["event"]["span"]

    def test_control_plane_summary_from_dir(
        self, local_master, tmp_path, monkeypatch, fresh_telemetry
    ):
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.common.constants import NodeType, RendezvousName
        from tools.obs_report import build_report

        monkeypatch.setenv(telemetry.ENV_DIR, str(tmp_path))
        client = MasterClient(local_master.addr, 0, NodeType.WORKER)
        try:
            client.join_rendezvous(
                0, 1, RendezvousName.ELASTIC_TRAINING
            )
            client.report_global_step(1)
            telemetry.event("step.end", step=1, dur=1.0)
            telemetry.flush()
        finally:
            client.close()
        report = build_report(telemetry_dir=str(tmp_path))
        control = report["control_plane"]
        assert control["master_rpc_calls"] >= 2
        assert control["master_rpc_p99_ms"] > 0
        assert control["joins_total"] == 1
        assert control["joins_per_sec"] >= 0
        assert "rpc_get_p99_ms" in control or "rpc_report_p99_ms" in control


# -------------------------------------------------------------------------
# spans on the profiler's clock
# -------------------------------------------------------------------------


class TestProfilerAnnotations:
    def test_span_lands_in_a_profiler_session(
        self, profiled_spans, fresh_telemetry
    ):
        def body():
            with tracing.span("unit.outer", step=3, why="text"):
                with tracing.annotation("unit.inner", bytes=7):
                    time.sleep(0.002)

        before = len(telemetry.snapshot()["events"])
        spans = {s["name"]: s for s in profiled_spans(body)}
        outer, inner = spans["unit.outer"], spans["unit.inner"]
        # on one thread and one clock, the inner inside the outer
        assert outer["thread"] == inner["thread"]
        assert outer["start_ns"] <= inner["start_ns"]
        assert inner["start_ns"] + inner["dur_ns"] <= \
            outer["start_ns"] + outer["dur_ns"]
        assert inner["dur_ns"] >= 2e6
        # numbers travel as the event's stats, text does not
        assert outer["stats"] == {"step": 3}
        assert inner["stats"] == {"bytes": 7}
        # the ring got the span's one event and nothing for the
        # trace-only annotation
        new = telemetry.snapshot()["events"][before:]
        assert [(e["kind"], e["name"]) for e in new] == \
            [("span", "unit.outer")]
        assert new[0]["why"] == "text"

    def test_no_session_no_trace_and_the_ring_is_unchanged(
        self, fresh_telemetry
    ):
        with tracing.annotation("unit.quiet", n=1):
            pass
        assert telemetry.snapshot()["events"] == []

    def test_a_process_without_jax_stays_without_it(self):
        """The agent and the master open spans and never import JAX."""
        script = (
            "import sys\n"
            "from dlrover_tpu.common import tracing\n"
            "with tracing.span('rdzv.round', round=1):\n"
            "    with tracing.annotation('rdzv.inner'):\n"
            "        pass\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", script], timeout=60,
            capture_output=True,
        )
        assert proc.returncode == 0, proc.stderr.decode()
