"""Live metrics plane: per-gauge series rings, delta-encoded shipping
(equivalence with full snapshots under re-registration and master
failover), the master's tiered metrics store, the SLO watchdog, the
read-only HTTP plane, per-step trainer MFU/HBM gauges, and the
chaos-exercised end-to-end smoke from the acceptance criteria.
"""

import json
import re
import time
import urllib.request

import numpy as np
import pytest

from dlrover_tpu.common import messages as msg
from dlrover_tpu.common import telemetry
from dlrover_tpu.common.telemetry import (
    MAX_EVENTS,
    SERIES_MAXLEN,
    JobTelemetry,
    TelemetryRegistry,
    apply_delta,
    snapshot_delta,
)
from dlrover_tpu.master.metrics_store import MetricsStore, SloWatchdog

pytestmark = pytest.mark.metrics


@pytest.fixture
def fresh_telemetry(monkeypatch):
    """Fresh process-global registry labeled as a worker (diagnosis
    and the goodput ledger key on the role/source convention)."""
    monkeypatch.setenv(telemetry.ENV_ROLE, "worker")
    monkeypatch.delenv(telemetry.ENV_DIR, raising=False)
    prev = telemetry.active_registry()
    reg = telemetry.enable()
    yield reg
    telemetry._REGISTRY = prev


def _roundtrip(snap):
    return json.loads(json.dumps(snap))


# -------------------------------------------------------------------------
# series rings
# -------------------------------------------------------------------------


class TestSeriesRings:
    def test_gauge_sets_append_stamped_points(self):
        reg = TelemetryRegistry("w-0-1")
        reg.gauge_set("g", 1.0)
        reg.gauge_set("g", 2.0)
        reg.gauge_set("h", 5.0, device="0")
        snap = reg.snapshot()
        by_name = {(s["name"], tuple(s["labels"].items())): s["points"]
                   for s in snap["series"]}
        pts = by_name[("g", ())]
        assert [p[3] for p in pts] == [1.0, 2.0]
        # monotonically increasing sample seq, wall + mono stamps
        assert pts[0][0] < pts[1][0]
        assert pts[0][1] <= pts[1][1] and pts[0][2] <= pts[1][2]
        assert by_name[("h", (("device", "0"),))][0][3] == 5.0
        assert snap["sample_seq"] == 3

    def test_ring_bounded(self):
        reg = TelemetryRegistry("w-0-1")
        for i in range(SERIES_MAXLEN + 50):
            reg.gauge_set("g", float(i))
        pts = reg.snapshot()["series"][0]["points"]
        assert len(pts) == SERIES_MAXLEN
        assert pts[-1][3] == SERIES_MAXLEN + 49  # newest kept


# -------------------------------------------------------------------------
# delta-encoded shipping
# -------------------------------------------------------------------------


class TestDeltaShipping:
    def _mutate(self, reg, i):
        reg.counter_inc("c", 1.0)
        reg.gauge_set("g", float(i))
        reg.observe("h", 0.1 * (i + 1))
        reg.event("step.end", step=i, dur=0.01)

    def test_delta_merge_equals_full_merge(self):
        """The core contract: shipping deltas every round produces the
        SAME master-side merged state as shipping full snapshots."""
        reg = TelemetryRegistry("worker-0-1")
        jt_full, jt_delta = JobTelemetry(), JobTelemetry()
        prev = None
        for i in range(5):
            self._mutate(reg, i)
            snap = _roundtrip(reg.snapshot())
            assert jt_full.update(_roundtrip(snap))
            payload = (
                snap if prev is None else snapshot_delta(prev, snap)
            )
            assert jt_delta.update(_roundtrip(payload))
            prev = snap
        assert jt_delta.snapshots() == jt_full.snapshots()

    def test_delta_carries_only_changes(self):
        reg = TelemetryRegistry("worker-0-1")
        reg.counter_inc("stable", 1.0)
        reg.gauge_set("stable_g", 1.0)
        base = _roundtrip(reg.snapshot())
        reg.counter_inc("hot", 1.0)
        reg.event("only.new", x=1)
        delta = snapshot_delta(base, _roundtrip(reg.snapshot()))
        assert [c["name"] for c in delta["counters"]] == ["hot"]
        assert delta["gauges"] == [] and delta["histograms"] == []
        assert [e["kind"] for e in delta["events"]] == ["only.new"]

    def test_unknown_base_rejected_full_fallback(self):
        """Master failover onto older (or no) state: the delta chain
        breaks, update() says no, and a full re-send converges."""
        reg = TelemetryRegistry("worker-0-1")
        self._mutate(reg, 0)
        s1 = _roundtrip(reg.snapshot())
        self._mutate(reg, 1)
        s2 = _roundtrip(reg.snapshot())
        delta = snapshot_delta(s1, s2)
        empty = JobTelemetry()
        assert not empty.update(_roundtrip(delta))
        stale = JobTelemetry()
        old = dict(s1)
        old["now"] = s1["now"] - 10.0  # restored pre-ack snapshot
        assert stale.update(old)
        assert not stale.update(_roundtrip(delta))
        # full fallback converges both
        assert empty.update(_roundtrip(s2))
        assert stale.update(_roundtrip(s2))
        assert empty.snapshots() == stale.snapshots()

    def test_reregistration_full_resend_idempotent(self):
        reg = TelemetryRegistry("worker-0-1")
        self._mutate(reg, 0)
        snap = _roundtrip(reg.snapshot())
        jt = JobTelemetry()
        assert jt.update(_roundtrip(snap))
        before = jt.snapshots()
        assert not jt.update(
            dict(snap, now=snap["now"] - 1)
        )  # stale re-send
        jt.update(_roundtrip(snap))  # same-state re-send
        assert jt.snapshots() == before

    def test_cross_source_delta_raises(self):
        a = _roundtrip(TelemetryRegistry("worker-0-1").snapshot())
        b = _roundtrip(TelemetryRegistry("worker-1-2").snapshot())
        with pytest.raises(ValueError):
            snapshot_delta(a, b)

    def test_merged_bounds_match_source_bounds(self):
        """apply_delta trims merged events/series to the registry's own
        bounds, so a long delta chain cannot grow past what a full
        snapshot would hold."""
        reg = TelemetryRegistry("worker-0-1")
        reg.event("e", i=-1)
        reg.gauge_set("g", -1.0)
        prev = _roundtrip(reg.snapshot())
        merged = prev
        for i in range(3):
            for j in range(SERIES_MAXLEN // 2):
                reg.gauge_set("g", float(i * 1000 + j))
                reg.event("e", i=i * 1000 + j)
            cur = _roundtrip(reg.snapshot())
            merged = apply_delta(merged, snapshot_delta(prev, cur))
            prev = cur
        assert merged == prev
        assert len(merged["series"][0]["points"]) == SERIES_MAXLEN
        assert len(merged["events"]) <= MAX_EVENTS

    def test_reporter_ships_delta_then_full_on_reject(
        self, fresh_telemetry,
    ):
        """TelemetryReporter-level behavior: second tick is a delta,
        an unchanged registry ships nothing, a rejected delta falls
        back to a full re-send next tick."""
        from dlrover_tpu.agent.monitor import TelemetryReporter

        shipped = []
        jt = JobTelemetry()
        accept = {"ok": True}

        class FakeClient:
            def report_telemetry(self, payload):
                shipped.append(_roundtrip(payload))
                if not accept["ok"]:
                    return False
                return jt.update(_roundtrip(payload))

        reporter = TelemetryReporter(FakeClient(), interval=999)
        telemetry.counter_inc("c", 1.0)
        telemetry.gauge_set("g", 1.0)
        reporter.report_once()
        assert len(shipped) == 1 and not shipped[0].get("delta")
        telemetry.gauge_set("g", 2.0)
        reporter.report_once()
        assert len(shipped) == 2 and shipped[1].get("delta")
        assert [g["name"] for g in shipped[1]["gauges"]] == ["g"]
        # nothing changed -> nothing shipped
        reporter.report_once()
        assert len(shipped) == 2
        # master loses the base: delta rejected, next tick full
        telemetry.gauge_set("g", 3.0)
        accept["ok"] = False
        reporter.report_once()
        assert shipped[-1].get("delta")
        accept["ok"] = True
        telemetry.gauge_set("g", 4.0)
        reporter.report_once()
        assert not shipped[-1].get("delta")
        # converged: the master holds exactly the local cumulative state
        src = telemetry.snapshot()["source"]
        assert jt.snapshots()[0] == reporter._acked[src]


# -------------------------------------------------------------------------
# metrics store: tiered downsampling
# -------------------------------------------------------------------------


def _series_snap(source, name, points, labels=None):
    return {
        "source": source,
        "now": points[-1][1] if points else 0.0,
        "series": [
            {"name": name, "labels": labels or {}, "points": points}
        ],
    }


class TestMetricsStore:
    def test_raw_query_and_idempotent_reingest(self):
        store = MetricsStore()
        pts = [[i + 1, 100.0 + i, 0.0, float(i)] for i in range(10)]
        snap = _series_snap("w-0-1", "train.step.last_s", pts)
        assert store.ingest_snapshot(snap) == 10
        assert store.ingest_snapshot(snap) == 0  # same sseq: no-op
        (series,) = store.query("train.step.last_s")
        assert series["points"] == [[100.0 + i, float(i)]
                                    for i in range(10)]

    def test_downsampled_consistent_with_raw(self):
        """Acceptance: tier aggregates must agree with the raw ledger —
        per 10 s/1 min bucket, count/sum/min/max/last recomputed from
        the raw points match the stored aggregates exactly."""
        store = MetricsStore()
        rng = np.random.RandomState(0)
        t0 = 1000.0
        pts = []
        for i in range(200):
            t0 += rng.uniform(0.2, 1.5)
            pts.append([i + 1, t0, 0.0, float(rng.uniform(0, 10))])
        store.ingest_snapshot(_series_snap("w-0-1", "m", pts))
        (raw,) = store.query("m", resolution="raw")
        for res, step in (("10s", 10.0), ("1m", 60.0)):
            (agg,) = store.query("m", resolution=res)
            buckets = {}
            for t, v in raw["points"]:
                buckets.setdefault((t // step) * step, []).append(v)
            assert len(agg["points"]) == len(buckets)
            for bt0, count, total, lo, hi, last in agg["points"]:
                vals = buckets[bt0]
                assert count == len(vals)
                assert total == pytest.approx(sum(vals))
                assert lo == min(vals) and hi == max(vals)
                assert last == vals[-1]

    def test_bounded_memory(self):
        store = MetricsStore(raw_maxlen=16)
        pts = [[i + 1, float(i), 0.0, float(i)] for i in range(100)]
        store.ingest_snapshot(_series_snap("w", "m", pts))
        (raw,) = store.query("m")
        assert len(raw["points"]) == 16
        assert raw["points"][-1] == [99.0, 99.0]
        # 10s tier bounded by its own ring length
        (agg,) = store.query("m", resolution="10s")
        assert len(agg["points"]) <= 360

    def test_export_restore_roundtrip_keeps_dedup_marks(self):
        store = MetricsStore()
        pts = [[i + 1, 10.0 * i, 0.0, float(i)] for i in range(20)]
        snap = _series_snap("w-0-1", "m", pts)
        store.ingest_snapshot(snap)
        state = json.loads(json.dumps(store.export_state()))
        restored = MetricsStore()
        restored.restore_state(state)
        assert restored.query("m") == store.query("m")
        assert restored.query("m", resolution="1m") == store.query(
            "m", resolution="1m"
        )
        # a full re-send after failover adds nothing (high-water kept)
        assert restored.ingest_snapshot(snap) == 0

    def test_series_cap_evicts_stalest_source(self):
        """Every worker restart is a new source; without the cap a
        long elastic job accumulates dead series forever. The stalest
        series (oldest newest-point) is the one evicted."""
        store = MetricsStore(max_series=3)
        for i, src in enumerate(("w-0-1", "w-0-2", "w-0-3")):
            store.ingest_snapshot(_series_snap(
                src, "m", [[1, 100.0 + i, 0.0, 1.0]]
            ))
        store.ingest_snapshot(_series_snap(
            "w-0-4", "m", [[1, 200.0, 0.0, 2.0]]
        ))
        sources = {e["source"] for e in store.names()}
        assert sources == {"w-0-2", "w-0-3", "w-0-4"}

    def test_source_and_resolution_filters(self):
        store = MetricsStore()
        store.ingest_snapshot(_series_snap("a", "m", [[1, 1.0, 0, 5.0]]))
        store.ingest_snapshot(_series_snap("b", "m", [[1, 1.0, 0, 7.0]]))
        assert len(store.query("m")) == 2
        (only_b,) = store.query("m", source="b")
        assert only_b["points"] == [[1.0, 7.0]]
        assert store.latest("m") == {"a": 5.0, "b": 7.0}
        with pytest.raises(ValueError):
            store.query("m", resolution="5s")


# -------------------------------------------------------------------------
# SLO watchdog
# -------------------------------------------------------------------------


def _feed_steps(store, durs, source="worker-0-1", name="train.step.last_s"):
    pts = [
        [i + 1, 1000.0 + i, 0.0, float(d)] for i, d in enumerate(durs)
    ]
    store.ingest_snapshot(_series_snap(source, name, pts))


class TestSloWatchdog:
    def test_step_time_regression_breach_and_clear(self, fresh_telemetry):
        store = MetricsStore()
        jt = JobTelemetry()
        dog = SloWatchdog(store, jt, window=4)
        _feed_steps(store, [0.01] * 12)
        assert dog.check() == {}
        _feed_steps(store, [0.01] * 12 + [0.05] * 4)
        breaches = dog.check()
        (key,) = breaches
        assert key == "step_time:worker-0-1"
        assert breaches[key]["rule"] == "step_time_regression"
        assert breaches[key]["ratio"] > 1.5
        kinds = [e["kind"] for e in telemetry.snapshot()["events"]]
        assert "slo.breach" in kinds
        # recovery: fast steps push the slow window out
        _feed_steps(store, [0.01] * 12 + [0.05] * 4 + [0.01] * 40)
        assert dog.check() == {}
        kinds = [e["kind"] for e in telemetry.snapshot()["events"]]
        assert "slo.clear" in kinds

    def test_mfu_drop_breach(self, fresh_telemetry):
        store = MetricsStore()
        dog = SloWatchdog(store, JobTelemetry(), window=4)
        _feed_steps(
            store, [0.5] * 12 + [0.1] * 4, name="train.mfu",
        )
        breaches = dog.check()
        assert "mfu:worker-0-1" in breaches
        assert breaches["mfu:worker-0-1"]["rule"] == "mfu_drop"

    def test_goodput_breach_names_dominant_loss(self, fresh_telemetry):
        jt = JobTelemetry()
        now = time.time()
        jt.update({
            "source": "worker-0-1", "role": "worker", "now": now,
            "events": [
                {"seq": 1, "t": now - 100, "kind": "step.end",
                 "dur": 5.0},
                {"seq": 2, "t": now, "kind": "ckpt.save", "dur": 60.0},
            ],
        })
        dog = SloWatchdog(
            MetricsStore(), jt, goodput_min=0.5,
            goodput_min_runtime_s=0.0,
        )
        breaches = dog.check(now=now)
        assert breaches["goodput"]["rule"] == "goodput_below_threshold"
        assert breaches["goodput"]["dominant_loss"] == "checkpoint"

    def test_events_dropped_breaches_only_while_growing(
        self, fresh_telemetry,
    ):
        """The counter is cumulative and never resets: the breach must
        track ACTIVE loss (growth between sweeps), or one early burst
        stays red for the rest of the job."""
        jt = JobTelemetry()

        def report(dropped, now):
            jt.update({
                "source": "worker-0-1", "now": now,
                "events_dropped": dropped, "events": [],
            })

        dog = SloWatchdog(
            MetricsStore(), jt, goodput_min_runtime_s=1e9,
        )
        report(3, 1.0)
        assert dog.check() == {}  # no prior sweep: growth unknown
        report(7, 2.0)
        breaches = dog.check()
        key = "events_dropped:worker-0-1"
        assert breaches[key]["dropped_since_last_sweep"] == 4
        # loss stopped (counter flat): the breach clears
        report(7, 3.0)
        assert dog.check() == {}
        kinds = [e["kind"] for e in telemetry.snapshot()["events"]]
        assert "slo.breach" in kinds and "slo.clear" in kinds


# -------------------------------------------------------------------------
# HTTP plane
# -------------------------------------------------------------------------


_PROM_LINE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)$"
)


def parse_prometheus(text: str) -> dict:
    """name -> [(labels_str, value)] — raises on any malformed line,
    which is the 'parseable exposition format' assertion."""
    samples: dict = {}
    for line in text.splitlines():
        if not line.strip() or line.startswith("#"):
            continue
        m = _PROM_LINE.match(line)
        assert m, f"malformed exposition line: {line!r}"
        value = float(m.group(3))  # must parse as a number
        samples.setdefault(m.group(1), []).append(
            (m.group(2) or "", value)
        )
    return samples


def _http_json(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as resp:
        return json.loads(resp.read().decode())


class TestHttpPlane:
    @pytest.fixture
    def servicer_with_data(self, fresh_telemetry):
        from dlrover_tpu.master.servicer import MasterServicer

        svc = MasterServicer()
        reg = TelemetryRegistry("worker-0-42")
        reg.role = "worker"
        for i in range(20):
            reg.gauge_set("train.step.last_s", 0.01)
            reg.gauge_set("train.mfu", 0.4)
            reg.counter_inc("steps")
            reg.observe("lat", 0.1, buckets=(0.05, 0.2))
            reg.event("step.end", step=i, dur=0.01)
        svc.report(
            "worker", 0,
            msg.TelemetrySnapshot(payload=_roundtrip(reg.snapshot())),
        )
        return svc

    @pytest.fixture
    def plane(self, servicer_with_data):
        from dlrover_tpu.master.http_plane import MasterHttpPlane

        plane = MasterHttpPlane(servicer_with_data)
        plane.start()
        yield plane
        plane.stop()

    def test_metrics_page_parseable(self, plane):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{plane.port}/metrics", timeout=10
        ) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            text = resp.read().decode()
            samples = parse_prometheus(text)
        # every emitted family is announced with # HELP and # TYPE
        # lines BEFORE its first sample (the exposition-format
        # contract scrapers rely on)
        announced_help, announced_type = set(), set()
        for line in text.splitlines():
            if line.startswith("# HELP "):
                announced_help.add(line.split()[2])
            elif line.startswith("# TYPE "):
                parts = line.split()
                announced_type.add(parts[2])
                assert parts[3] in ("counter", "gauge", "histogram")
            elif line.strip():
                family = re.match(r"[a-zA-Z_:][a-zA-Z0-9_:]*", line)
                base = family.group(0)
                for suffix in ("_bucket", "_sum", "_count"):
                    if base.endswith(suffix) and (
                        base[: -len(suffix)] in announced_type
                    ):
                        base = base[: -len(suffix)]
                        break
                assert base in announced_help, f"no # HELP for {line!r}"
                assert base in announced_type, f"no # TYPE for {line!r}"
        assert ('{source="worker-0-42"}', 0.4) in samples[
            "dlrtpu_train_mfu"
        ]
        assert samples["dlrtpu_steps_total"][0][1] == 20.0
        # histogram: cumulative le buckets + sum/count
        buckets = dict(samples["dlrtpu_lat_bucket"])
        assert buckets['{le="+Inf"}'] == 20.0
        assert buckets['{le="0.2"}'] == 20.0
        assert buckets['{le="0.05"}'] == 0.0
        assert samples["dlrtpu_lat_count"][0][1] == 20.0
        assert "dlrtpu_goodput_ratio" in samples

    def test_report_and_series_json(self, plane):
        rep = _http_json(plane.port, "/report.json")
        assert "worker-0-42" in rep["sources"]
        assert "snapshots" not in rep
        assert "slo" in rep and "diagnosis" in rep
        ser = _http_json(
            plane.port, "/series.json?name=train.mfu&res=10s"
        )
        assert ser["series"][0]["points"][0][1] == 20  # bucket count
        names = _http_json(plane.port, "/series.json")
        assert any(
            n["name"] == "train.step.last_s" for n in names["names"]
        )

    def test_dashboard_served_and_404(self, plane):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{plane.port}/", timeout=10
        ) as resp:
            body = resp.read().decode()
        assert "dlrover_tpu live" in body and "/series.json" in body
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                f"http://127.0.0.1:{plane.port}/etc/passwd", timeout=10
            )
        assert err.value.code == 404


# -------------------------------------------------------------------------
# obs_report: sparklines + events_dropped warning + live render
# -------------------------------------------------------------------------


class TestObsReportLive:
    def test_sparkline_shapes(self):
        from tools.obs_report import sparkline

        assert sparkline([]) == ""
        assert sparkline([1.0, 1.0]) == "▁▁"
        line = sparkline([0, 1, 2, 3, 4, 5, 6, 7])
        assert line[0] == "▁" and line[-1] == "█"
        assert len(sparkline(list(range(500)), width=48)) == 48

    def test_events_dropped_warning_fires(self, capsys):
        from tools.obs_report import warn_events_dropped

        assert not warn_events_dropped({"events_dropped": {}})
        assert warn_events_dropped(
            {"events_dropped": {"worker-0-1": 7}}
        )
        err = capsys.readouterr().err
        assert "DROPPED" in err and "worker-0-1: 7" in err
        assert "INCOMPLETE" in err

    def test_render_live_frame(self):
        from tools.obs_report import render_live

        report = {
            "ledger": {
                "total_s": 100.0, "goodput": 0.8,
                "categories": {"productive": 80.0, "idle": 20.0},
            },
            "timeline": [
                {"t": time.time(), "kind": "slo.breach",
                 "source": "master-0-1"},
            ],
        }
        series = {
            "train.step.last_s": [{
                "source": "worker-0-1",
                "points": [[0, 0.01], [1, 0.02]],
            }],
            "train.mfu": [],
        }
        frame = render_live(
            report, series, {"goodput": {"rule": "goodput", "x": 1}},
        )
        assert "goodput  80.0%" in frame
        assert "worker-0-1" in frame and "ms" in frame
        assert "SLO BREACHES" in frame and "slo.breach" in frame


# -------------------------------------------------------------------------
# trainer gauges: train.mfu from the step clock and the FLOP count
# -------------------------------------------------------------------------


def _token_problem(vocab=32, dim=4, bs=4, seq=8, n=16):
    import jax.numpy as jnp

    def init_fn(rng):
        return {"emb": jnp.zeros((vocab, dim))}

    def loss_fn(params, batch, rng):
        tok = batch["tokens"]
        return jnp.mean(params["emb"][tok] ** 2) + 1e-6 * jnp.sum(
            params["emb"] ** 2
        )

    axes = {"emb": (None, None)}
    rs = np.random.RandomState(0)
    batches = [
        {"tokens": rs.randint(0, vocab, (bs, seq)).astype(np.int32)}
        for _ in range(n)
    ]
    return loss_fn, init_fn, axes, batches


class TestTrainerMfu:
    def test_live_mfu_uses_the_callers_flops_per_token(
        self, tmp_path, fresh_telemetry, monkeypatch,
    ):
        """Per-step ``train.mfu`` is ``common/mfu.mfu`` over the
        caller's count (``model_flops_per_token``, here dense plus a
        causal-attention term) and the step clock's seconds."""
        from dlrover_tpu.common import mfu as mfu_mod
        from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

        # the CPU has no row in the peak table: the test supplies one
        monkeypatch.setitem(mfu_mod.PEAK_FLOPS, "cpu", 1e12)
        vocab, dim, bs, seq = 32, 4, 4, 8
        tokens = bs * seq
        params = vocab * dim
        flops_step = 6.0 * params * tokens \
            + 12.0 * 2 * dim * tokens * seq / 2
        loss_fn, init_fn, axes, batches = _token_problem(
            vocab, dim, bs, seq
        )
        args = TrainingArgs(
            output_dir=str(tmp_path / "out"), max_steps=8, log_steps=0,
            flash_checkpoint=False,
            model_flops_per_token=flops_step / tokens,
        )
        trainer = Trainer(loss_fn, init_fn, axes, args,
                          train_data=batches)
        trainer.train()
        snap = telemetry.snapshot()
        series = {s["name"]: s["points"] for s in snap["series"]}
        mfu_pts = series["train.mfu"]
        dur_pts = series["train.step.last_s"]
        assert len(mfu_pts) == 7  # 8 steps minus the compile step
        for mp, dp in zip(mfu_pts, dur_pts):
            offline = mfu_mod.mfu(flops_step, dp[3], 8e12)
            assert mp[3] == pytest.approx(offline, rel=1e-9)
        # steady-state only: the compile step contributes no sample
        events = [e for e in snap["events"] if e["kind"] == "compile"]
        assert len(events) == 1
        assert len(dur_pts) == 7
        # the host-arena gauge emits EVERY step, independent of the
        # backend's device memory_stats support
        assert len(series["ckpt.arena.pooled_bytes"]) == 8

    def test_default_flops_estimate_is_dense(
        self, tmp_path, fresh_telemetry, monkeypatch,
    ):
        from dlrover_tpu.common import mfu as mfu_mod
        from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

        monkeypatch.setitem(mfu_mod.PEAK_FLOPS, "cpu", 1e12)
        loss_fn, init_fn, axes, batches = _token_problem()
        args = TrainingArgs(
            output_dir=str(tmp_path / "out"), max_steps=4, log_steps=0,
            flash_checkpoint=False,
        )
        trainer = Trainer(loss_fn, init_fn, axes, args,
                          train_data=batches)
        assert trainer._flops_per_token == 6.0 * 32 * 4
        trainer.train()
        snap = telemetry.snapshot()
        series = {s["name"]: s["points"] for s in snap["series"]}
        mp, dp = series["train.mfu"][-1], series["train.step.last_s"][-1]
        assert mp[3] == pytest.approx(
            mfu_mod.mfu(6.0 * 32 * 4 * 32, dp[3], 8e12), rel=1e-9
        )

    def test_peak_comes_from_the_device_kind_table(self):
        """One table keyed by device_kind: the v5e row is the published
        bf16 peak, the CPU has no peak, an accelerator that is not in
        the table is an error and never a default."""
        import types

        from dlrover_tpu.common import mfu as mfu_mod

        def dev(platform, kind):
            return types.SimpleNamespace(platform=platform, device_kind=kind)

        assert mfu_mod.peak_flops(dev("tpu", "TPU v5 lite")) == 197e12
        assert mfu_mod.peak_flops(dev("cpu", "cpu")) is None
        with pytest.raises(ValueError, match="TPU v99"):
            mfu_mod.peak_flops(dev("tpu", "TPU v99"))
        assert mfu_mod.mfu(1e10, 0.01, 1e12) == pytest.approx(1.0)

    def test_no_mfu_gauge_without_a_peak(self, tmp_path, fresh_telemetry):
        """A CPU run never writes a number under the device metric's
        name: no peak, no ``train.mfu`` series."""
        from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

        loss_fn, init_fn, axes, batches = _token_problem()
        args = TrainingArgs(
            output_dir=str(tmp_path / "out"), max_steps=3, log_steps=0,
            flash_checkpoint=False,
        )
        Trainer(loss_fn, init_fn, axes, args, train_data=batches).train()
        names = {s["name"] for s in telemetry.snapshot()["series"]}
        assert "train.step.last_s" in names
        assert "train.mfu" not in names


# -------------------------------------------------------------------------
# end to end: chaos-exercised job -> live /metrics -> SLO breach
# -------------------------------------------------------------------------


class TestLiveMetricsPlaneEndToEnd:
    def test_smoke_live_plane(
        self, local_master, tmp_path, fresh_telemetry, isolated_ckpt_env,
        monkeypatch,
    ):
        """The acceptance scenario, in process: a chaos-exercised
        training job ships delta-encoded telemetry to a real master
        over RPC; mid-run the HTTP plane serves a parseable Prometheus
        /metrics page; the store's downsampled series agree with the
        raw ones; an injected step-time regression raises an
        ``slo.breach`` diagnosis verdict; and the master's merged
        state is byte-equal to the worker's cumulative snapshot
        through re-registration and a simulated failover."""
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.agent.monitor import TelemetryReporter
        from dlrover_tpu.common import chaos, mfu
        from dlrover_tpu.master.http_plane import MasterHttpPlane
        from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

        # the CPU has no row in the peak table: the test supplies one
        monkeypatch.setitem(mfu.PEAK_FLOPS, "cpu", 1e12)
        svc = local_master.servicer
        plane = MasterHttpPlane(svc)
        plane.start()
        client = MasterClient(local_master.addr, 0, "worker")
        reporter = TelemetryReporter(client, interval=999)
        # chaos-exercise the run: a seeded delay on the shm-save seam
        # fires during training and lands chaos.fire events in the
        # shipped timeline
        chaos.install({
            "seed": 3,
            "rules": [{
                "site": "ckpt.save", "action": "delay", "delay": 0.01,
            }],
        })
        delay = {"s": 0.0}

        def prestep(state, batch):
            if delay["s"]:
                time.sleep(delay["s"])
            return state, batch

        loss_fn, init_fn, axes, batches = _token_problem(n=64)
        args = TrainingArgs(
            output_dir=str(tmp_path / "out"), max_steps=24,
            log_steps=0, save_steps=8, flash_checkpoint=True,
        )
        trainer = Trainer(
            loss_fn, init_fn, axes, args, train_data=batches,
            prestep=prestep,
        )
        try:
            trainer.train()          # phase 1: healthy baseline
            reporter.report_once()
            source = telemetry.snapshot()["source"]

            # --- mid-run: Prometheus page parseable, store consistent
            with urllib.request.urlopen(
                f"http://127.0.0.1:{plane.port}/metrics", timeout=10
            ) as resp:
                samples = parse_prometheus(resp.read().decode())
            assert "dlrtpu_train_step_last_s" in samples
            assert "dlrtpu_train_mfu" in samples
            (raw,) = svc.metrics_store.query(
                "train.step.last_s", source=source
            )
            (agg,) = svc.metrics_store.query(
                "train.step.last_s", source=source, resolution="10s"
            )
            assert sum(p[1] for p in agg["points"]) == len(raw["points"])
            assert sum(p[2] for p in agg["points"]) == pytest.approx(
                sum(v for _t, v in raw["points"])
            )
            # chaos fired and its events rode the relay
            merged_kinds = {
                e["kind"]
                for s in svc.telemetry.snapshots()
                for e in s.get("events", ())
            }
            assert "chaos.fire" in merged_kinds

            # --- delta equivalence: master holds exactly the acked
            # cumulative snapshot (shipping was delta after tick 1)
            assert any(
                s["source"] == source
                and s == reporter._acked[source]
                for s in svc.telemetry.snapshots()
            )

            # --- phase 2: inject a step-time regression, several times
            # the toy's true step (its compute included, since a step is
            # timed at its completion) even on a loaded test host
            delay["s"] = 0.2
            args.max_steps = 40
            trainer.train()
            reporter.report_once()
            verdicts = svc.diagnosis.check(force=True)
            assert any(
                k.startswith("step_time:") for k in verdicts["slo"]
            ), verdicts["slo"]
            res = svc.get("worker", 0, msg.DiagnosisRequest())
            assert res.slo
            rep = _http_json(plane.port, "/report.json")
            assert any(
                e["kind"] == "slo.breach" for e in rep["timeline"]
            )
            assert rep["slo"]

            # --- re-registration: full re-send converges to the same
            # merged state
            reporter.reset_shipped()
            reporter.report_once()
            held = next(
                s for s in svc.telemetry.snapshots()
                if s["source"] == source
            )
            assert held == reporter._acked[source]

            # --- failover: master loses this source's base; the next
            # delta is rejected and the full fallback converges
            telemetry.gauge_set("post.failover", 1.0)
            with svc.telemetry._lock:
                svc.telemetry._snaps.pop(source)
            reporter.report_once()   # delta rejected (base unknown)
            assert source not in {
                s["source"] for s in svc.telemetry.snapshots()
            }
            reporter.report_once()   # full re-send
            held = next(
                s for s in svc.telemetry.snapshots()
                if s["source"] == source
            )
            assert held == reporter._acked[source]
            assert any(
                g["name"] == "post.failover" for g in held["gauges"]
            )
        finally:
            chaos.uninstall()
            delay["s"] = 0.0
            client.close()
            plane.stop()
