"""Deep profiling plane: the shared trace summarizer, the always-on
device-time sampler + op-cost baselines, the capture channel/ledger
(exactly-once, rate-limited, failover-durable), the merged Perfetto
timeline, flight-recorder series tails, and the acceptance smoke:
an injected 6x step-time regression -> SLO breach -> deep capture on
the blamed host -> /captures.json artifact whose attribution names the
inflated op category -> merged host+device timeline.
"""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from dlrover_tpu.common import messages as msg
from dlrover_tpu.common import profiling, telemetry, trace_summary
from dlrover_tpu.master.capture import CaptureManager, _slo_rank

pytestmark = pytest.mark.profiling


@pytest.fixture
def fresh_telemetry(monkeypatch):
    monkeypatch.setenv(telemetry.ENV_ROLE, "worker")
    monkeypatch.delenv(telemetry.ENV_DIR, raising=False)
    prev = telemetry.active_registry()
    reg = telemetry.enable()
    yield reg
    telemetry._REGISTRY = prev


def wait_until(cond, timeout=60.0, poll=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(poll)
    return cond()


class FakeBackend:
    """Profiler-backend seam: records windows, captures nothing."""

    def __init__(self, fail_start=False):
        self.active = None
        self.windows = []
        self.fail_start = fail_start

    def start(self, log_dir):
        if self.fail_start:
            return False
        os.makedirs(log_dir, exist_ok=True)
        self.active = log_dir
        return True

    def stop(self, block_on=None):
        self.windows.append(self.active)
        self.active = None


def make_sampler(tmp_path, parse_fn, sample_steps=4, channel=None,
                 backend=None, name="b.json", overhead_pct=0.0):
    # overhead_pct=0 pins the FIXED cadence (deterministic tests); the
    # cost governor has its own test below
    s = profiling.DeviceTimeSampler(
        str(tmp_path / "prof"),
        sample_steps=sample_steps,
        parse_fn=parse_fn,
        baseline=profiling.OpCostBaseline(str(tmp_path / name)),
        capture_channel=channel,
        backend=backend or FakeBackend(),
        artifact_root=str(tmp_path / "captures"),
        overhead_pct=overhead_pct,
    )
    s.set_context("fp0", "data=1,fsdp=1")
    return s


def wait_parsed(sampler, timeout=10.0):
    """No window opens while the parse thread converts the last one:
    a loop that steps faster than the thread starts waits for it."""
    deadline = time.time() + timeout
    while sampler._parsing and time.time() < deadline:
        time.sleep(0.001)
    assert not sampler._parsing


def drive(sampler, first, last):
    for step in range(first, last + 1):
        sampler.on_step_start(step)
        sampler.on_step_end(step, 0.001)
        wait_parsed(sampler)


# -------------------------------------------------------------------------
# shared trace summarizer
# -------------------------------------------------------------------------


class TestTraceSummary:
    def test_canonical_mapping(self):
        cc = trace_summary.canonical_category
        assert cc("%dot") == "matmul"
        assert cc("convolution fusion") == "convolution"
        assert cc("all-gather fusion") == "all-gather"
        assert cc("collective permute") == "collective-permute"
        assert cc("reduce-scatter") == "reduce-scatter"
        assert cc("all-to-all") == "all-to-all"
        assert cc("infeed") == "infeed-outfeed"
        assert cc("host compute") == "host"
        assert cc("loop fusion") == "fusion"
        assert cc("mystery-op") == "other"
        assert cc("") == "other"
        for cat in trace_summary.CANONICAL_CATEGORIES:
            assert cc(cat) == cat, cat

    def test_canonical_breakdown_sums_buckets(self):
        out = trace_summary.canonical_breakdown({
            "loop fusion": 1.0, "output fusion": 2.0, "%dot": 5.0,
        })
        assert out == {"fusion": 3.0, "matmul": 5.0}
        assert trace_summary.canonical_breakdown({}) == {}

    def test_summarize_none_without_traces(self, tmp_path):
        assert trace_summary.summarize(str(tmp_path)) is None

    def test_parse_profile_cli_missing_dir(self, tmp_path, capsys):
        from tools.parse_profile import main

        rc = main([str(tmp_path / "nope")])
        assert rc == 1
        assert "does not exist" in capsys.readouterr().err

    def test_parse_profile_cli_empty_dir(self, tmp_path, capsys):
        from tools.parse_profile import main

        rc = main([str(tmp_path)])
        assert rc == 1
        assert "no *.xplane.pb traces" in capsys.readouterr().err

    def test_parse_profile_cli_unparseable_is_message_not_traceback(
        self, tmp_path, capsys,
    ):
        """A present-but-unreadable trace (or a missing toolchain)
        exits 2 with one clear line — never a stack trace."""
        from tools.parse_profile import main

        (tmp_path / "junk.xplane.pb").write_bytes(b"\x00garbage")
        rc = main([str(tmp_path)])
        err = capsys.readouterr().err
        assert rc == 2
        assert "Traceback" not in err
        assert "xprof" in err or "could not parse" in err


# -------------------------------------------------------------------------
# op-cost baseline
# -------------------------------------------------------------------------


class TestOpCostBaseline:
    def test_seed_then_ewma(self, tmp_path):
        b = profiling.OpCostBaseline(str(tmp_path / "b.json"))
        key = b.key("fp", "data=2")
        base, reg = b.update(key, {"matmul": 10.0})
        assert base == {"matmul": 10.0} and not reg
        base, reg = b.update(key, {"matmul": 12.0})  # within ratio
        assert not reg
        assert base["matmul"] == pytest.approx(
            0.75 * 10.0 + 0.25 * 12.0
        )

    def test_regression_freezes_baseline(self, tmp_path):
        b = profiling.OpCostBaseline(str(tmp_path / "b.json"))
        key = b.key("fp", "m")
        b.update(key, {"collective-permute": 2.0, "matmul": 10.0})
        base, reg = b.update(
            key, {"collective-permute": 9.0, "matmul": 10.0}
        )
        assert reg
        # frozen: the anomaly did not erode the healthy past
        assert base["collective-permute"] == 2.0
        diff = b.diff(key, {"collective-permute": 9.0, "matmul": 10.0})
        assert diff[0]["category"] == "collective-permute"
        assert diff[0]["delta_pct"] == pytest.approx(350.0)

    def test_keys_are_independent(self, tmp_path):
        b = profiling.OpCostBaseline(str(tmp_path / "b.json"))
        b.update(b.key("fp", "data=1"), {"matmul": 1.0})
        b.update(b.key("fp", "data=2"), {"matmul": 100.0})
        assert b.get(b.key("fp", "data=1")) == {"matmul": 1.0}
        assert b.get(b.key("other", "data=1")) is None

    def test_persistence_roundtrip(self, tmp_path):
        path = str(tmp_path / "b.json")
        b = profiling.OpCostBaseline(path)
        key = b.key("fp", "m")
        b.update(key, {"matmul": 3.0})
        reloaded = profiling.OpCostBaseline(path)
        assert reloaded.get(key) == {"matmul": 3.0}

    def test_diff_skips_noise_and_handles_new(self, tmp_path):
        b = profiling.OpCostBaseline(str(tmp_path / "b.json"))
        key = b.key("fp", "m")
        b.update(key, {"matmul": 5.0, "copy": 0.001})
        diff = b.diff(key, {"matmul": 5.0, "copy": 0.002, "host": 1.0})
        cats = {d["category"] for d in diff}
        assert "copy" not in cats          # sub-threshold noise
        host = next(d for d in diff if d["category"] == "host")
        assert host["delta_pct"] is None   # new category: no baseline
        assert diff[0]["category"] == "host"  # new sorts first

    def test_fingerprint_and_mesh_key(self):
        import jax.numpy as jnp

        p1 = {"a": jnp.zeros((2, 3)), "b": jnp.zeros(4)}
        p2 = {"a": jnp.zeros((2, 3)), "b": jnp.zeros(5)}
        f1 = profiling.model_fingerprint(p1)
        assert f1 == profiling.model_fingerprint(
            {"a": jnp.ones((2, 3)), "b": jnp.ones(4)}
        )  # values don't matter, structure does
        assert f1 != profiling.model_fingerprint(p2)
        import jax

        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

        mesh = build_mesh(
            MeshConfig(data=1, fsdp=1), devices=jax.devices()[:1]
        )
        key = profiling.mesh_shape_key(mesh)
        assert "=" in key and "fsdp=1" in key


# -------------------------------------------------------------------------
# capture channel
# -------------------------------------------------------------------------


class TestCaptureChannel:
    def test_roundtrip(self, tmp_path):
        ch = profiling.CaptureChannel(str(tmp_path / "c"))
        assert not ch.worker_ready()
        ch.mark_ready()
        assert ch.worker_ready()
        assert ch.poll("") is None
        ch.signal(profiling.CaptureRequest(
            capture_id="cap-1", steps=3, reason="slo"
        ))
        req = ch.poll("")
        assert req.capture_id == "cap-1" and req.steps == 3
        assert ch.poll("cap-1") is None  # consumed id never re-served
        ch.ack("cap-1", True, artifact="/a", summary={"x": 1})
        ack = ch.read_ack("cap-1")
        assert ack["ok"] and ack["artifact"] == "/a"
        assert ch.read_ack("cap-2") is None
        assert ch.await_ack("cap-1", 1.0) is not None

    def test_await_ack_worker_death(self, tmp_path):
        ch = profiling.CaptureChannel(str(tmp_path / "c"))
        assert ch.await_ack("cap-1", 5.0, alive_fn=lambda: False) is None

    def test_clear(self, tmp_path):
        ch = profiling.CaptureChannel(str(tmp_path / "c"))
        ch.mark_ready()
        ch.signal(profiling.CaptureRequest(capture_id="cap-1"))
        ch.clear()
        assert not ch.worker_ready() and ch.poll("") is None


# -------------------------------------------------------------------------
# device-time sampler
# -------------------------------------------------------------------------


class TestDeviceTimeSampler:
    def test_sampling_cadence(self, tmp_path, fresh_telemetry):
        backend = FakeBackend()
        s = make_sampler(
            tmp_path, lambda d, n: {"matmul": 1.0}, sample_steps=4,
            backend=backend,
        )
        drive(s, 1, 12)
        s.close()
        assert len(backend.windows) == 3  # steps 4, 8, 12

    def test_gauges_and_baseline_published(
        self, tmp_path, fresh_telemetry,
    ):
        s = make_sampler(
            tmp_path,
            lambda d, n: {"%dot": 3.0, "loop fusion": 1.5},
            sample_steps=2,
        )
        drive(s, 1, 4)
        assert wait_until(lambda: s.baseline.get(s.baseline_key))
        s.close()
        snap = telemetry.snapshot()
        gauges = {
            (g["name"], tuple(sorted(g["labels"].items()))): g["value"]
            for g in snap["gauges"]
        }
        assert gauges[(
            profiling.OPTIME_GAUGE, (("category", "matmul"),)
        )] == 3.0
        assert gauges[(
            profiling.OPTIME_GAUGE, (("category", "fusion"),)
        )] == 1.5
        assert gauges[("device.optime.total_ms", ())] == 4.5
        assert s.baseline.get(s.baseline_key) == {
            "matmul": 3.0, "fusion": 1.5,
        }

    def test_regression_event_and_frozen_baseline(
        self, tmp_path, fresh_telemetry,
    ):
        vals = {"cp": 2.0}
        s = make_sampler(
            tmp_path,
            lambda d, n: {"collective-permute": vals["cp"]},
            sample_steps=2,
        )
        drive(s, 1, 2)
        assert wait_until(lambda: s.baseline.get(s.baseline_key))
        vals["cp"] = 12.0
        drive(s, 3, 4)
        assert wait_until(lambda: any(
            e["kind"] == "device.optime.regression"
            for e in telemetry.snapshot()["events"]
        ))
        s.close()
        ev = next(
            e for e in telemetry.snapshot()["events"]
            if e["kind"] == "device.optime.regression"
        )
        assert ev["category"] == "collective-permute"
        assert ev["delta_pct"] == pytest.approx(500.0)
        # frozen baseline keeps the healthy value
        assert s.baseline.get(s.baseline_key) == {
            "collective-permute": 2.0,
        }

    def test_vanished_category_gauge_zeroed(
        self, tmp_path, fresh_telemetry,
    ):
        """A category absent from the next sample drops to 0 instead
        of freezing at its last value on /metrics forever."""
        vals = {"cats": {"collective-permute": 5.0, "matmul": 1.0}}
        s = make_sampler(
            tmp_path, lambda d, n: dict(vals["cats"]), sample_steps=2,
        )
        drive(s, 1, 2)
        assert wait_until(lambda: s.baseline.get(s.baseline_key))
        vals["cats"] = {"matmul": 1.0}  # the collective vanished
        drive(s, 3, 4)

        def cp_gauge():
            for g in telemetry.snapshot()["gauges"]:
                if (
                    g["name"] == profiling.OPTIME_GAUGE
                    and g["labels"].get("category")
                    == "collective-permute"
                ):
                    return g["value"]
            return None

        assert wait_until(lambda: cp_gauge() == 0.0), cp_gauge()
        s.close()

    def test_poll_is_stat_only_after_consumption(self, tmp_path):
        """The per-step cost contract: an already-consumed request
        file is never re-opened/re-parsed, only stat'ed."""
        ch = profiling.CaptureChannel(str(tmp_path / "c"))
        ch.signal(profiling.CaptureRequest(capture_id="cap-1"))
        assert ch.poll("").capture_id == "cap-1"
        assert ch.poll("cap-1") is None  # parses once, caches
        import unittest.mock as mock

        with mock.patch(
            "dlrover_tpu.common.profiling._read_json",
            side_effect=AssertionError("re-parsed a consumed request"),
        ):
            for _ in range(5):
                assert ch.poll("cap-1") is None
        # a NEW request (fresh mtime) is parsed again
        time.sleep(0.01)
        ch.signal(profiling.CaptureRequest(capture_id="cap-2"))
        assert ch.poll("cap-1").capture_id == "cap-2"

    def test_cost_governor_stretches_gap(
        self, tmp_path, fresh_telemetry,
    ):
        """An expensive window on a fast-stepping job pushes the next
        sample out until the steady-state overhead fits the budget —
        sample_steps is a floor, not a promise."""

        class CostlyBackend(FakeBackend):
            def start(self, log_dir):
                time.sleep(0.005)  # a 5 ms window cost
                return super().start(log_dir)

        backend = CostlyBackend()
        s = make_sampler(
            tmp_path, lambda d, n: {"matmul": 1.0}, sample_steps=2,
            backend=backend, overhead_pct=2.0,
        )
        # fast steps: 1 ms each -> budget 20 us/step -> a 5 ms window
        # needs a gap of ~250 steps
        drive(s, 1, 60)
        assert len(backend.windows) == 1  # the step-2 window only
        assert s._next_sample >= 2 + int(
            s.last_window_cost_s / (0.02 * 0.001)
        )
        assert s.last_window_cost_s >= 0.005
        s.close()
        snap = telemetry.snapshot()
        gauges = {g["name"] for g in snap["gauges"]}
        assert "device.optime.sample_gap" in gauges
        assert "device.optime.window_cost_ms" in gauges

    def test_sample_cost_counts_hooks_and_slower_steps(
        self, tmp_path, fresh_telemetry,
    ):
        """``prof.sample.cost_s``: the step thread's time in the two
        hooks plus what the window's step took beyond an EWMA step, on
        the Trainer's own clock (the ``dur_s`` it hands in)."""

        class CostlyBackend(FakeBackend):
            def start(self, log_dir):
                time.sleep(0.005)
                return super().start(log_dir)

            def stop(self, block_on=None):
                time.sleep(0.003)
                super().stop(block_on)

        s = make_sampler(
            tmp_path, lambda d, n: {"matmul": 1.0}, sample_steps=4,
            backend=CostlyBackend(),
        )
        drive(s, 1, 3)              # EWMA: 1 ms steps
        s.on_step_start(4)
        s.on_step_end(3, 0.001)     # an older step closes: no part of it
        s.on_step_end(4, 0.007)     # the sampled step ran 6 ms slower
        wait_parsed(s)
        s.close()
        assert s.last_window_cost_s >= 0.005 + 0.003 + 0.006
        assert s.last_window_cost_s < 0.05
        snap = telemetry.snapshot()
        counters = {c["name"]: c["value"] for c in snap["counters"]}
        assert counters["prof.sample.cost_s"] == pytest.approx(
            s.last_window_cost_s
        )
        assert counters["prof.samples"] == 1
        # what the hooks and the parse thread did is on the ring, with
        # their seconds, for the trace that shows a stalled loop
        spans = {
            e["name"]: e for e in snap["events"] if e["kind"] == "span"
        }
        assert spans["prof.sample.start"]["dur"] >= 0.005
        assert spans["prof.sample.stop"]["dur"] >= 0.003
        # each increment of the counter rides on its stop span
        assert spans["prof.sample.stop"]["cost_s"] == pytest.approx(
            s.last_window_cost_s
        )
        assert spans["prof.parse"]["window"] == "sample"

    def test_no_window_while_the_last_one_is_parsed(
        self, tmp_path, fresh_telemetry,
    ):
        """A second profiler session beside a running conversion
        stalled the training loop for seconds on the chip: a due sample
        is skipped and re-armed at the floor cadence, a deep capture
        waits."""
        release = threading.Event()

        def slow_parse(d, n):
            assert release.wait(10)
            return {"matmul": 1.0}

        backend = FakeBackend()
        ch = profiling.CaptureChannel(str(tmp_path / "chan"))
        s = make_sampler(
            tmp_path, slow_parse, sample_steps=2, backend=backend,
            channel=ch,
        )
        for step in (1, 2):
            s.on_step_start(step)
            s.on_step_end(step, 0.001)
        assert len(backend.windows) == 1 and s._parsing == 1
        for step in (3, 4):
            s.on_step_start(step)
            assert backend.active is None
            s.on_step_end(step, 0.001)
        assert s._next_sample == 4 + 2      # skipped at 4, floor cadence
        ch.signal(profiling.CaptureRequest(capture_id="cap-1", steps=1))
        s.on_step_start(5)
        assert backend.active is None       # the capture waits too
        s.on_step_end(5, 0.001)
        release.set()
        wait_parsed(s)
        s.on_step_start(6)                  # the capture goes first
        assert backend.active and "cap-1" in backend.active
        s.on_step_end(6, 0.001)
        wait_parsed(s)
        s.close()
        assert len(backend.windows) == 2

    def test_governor_off_keeps_fixed_cadence(
        self, tmp_path, fresh_telemetry,
    ):
        backend = FakeBackend()
        s = make_sampler(
            tmp_path, lambda d, n: {"matmul": 1.0}, sample_steps=3,
            backend=backend, overhead_pct=0.0,
        )
        drive(s, 1, 9)
        s.close()
        assert len(backend.windows) == 3  # steps 3, 6, 9

    def test_disabled_modes(self, tmp_path, fresh_telemetry):
        backend = FakeBackend()
        s = make_sampler(
            tmp_path, lambda d, n: {}, sample_steps=0, backend=backend,
        )
        assert not s.sampling_enabled
        drive(s, 1, 20)
        s.close()
        assert backend.windows == []
        # no parse path at all (no parse_fn, no xprof) -> disabled
        if not trace_summary.toolchain_available():
            s2 = profiling.DeviceTimeSampler(
                str(tmp_path / "p2"), sample_steps=4,
                backend=FakeBackend(),
                baseline=profiling.OpCostBaseline(
                    str(tmp_path / "b2.json")
                ),
                capture_channel=None,
            )
            assert not s2.sampling_enabled
            s2.close()

    def test_two_parse_failures_disable_sampling(
        self, tmp_path, fresh_telemetry,
    ):
        calls = {"n": 0}

        def bad_parse(d, n):
            calls["n"] += 1
            raise ValueError("boom")

        backend = FakeBackend()
        s = make_sampler(
            tmp_path, bad_parse, sample_steps=1, backend=backend,
        )
        drive(s, 1, 2)  # exactly two windows -> two failures
        assert wait_until(lambda: not s.sampling_enabled)
        windows_then = len(backend.windows)
        drive(s, 3, 10)
        s.close()
        assert calls["n"] == 2
        assert len(backend.windows) == windows_then

    def test_deep_capture_via_channel(self, tmp_path, fresh_telemetry):
        telemetry.event("span", name="train.step", dur=0.01,
                        trace="t", span="s", parent="")
        ch = profiling.CaptureChannel(str(tmp_path / "chan"))
        s = make_sampler(
            tmp_path, lambda d, n: {"collective-permute": 30.0},
            sample_steps=0, channel=ch,
        )
        assert ch.worker_ready()  # sampler advertised its watcher
        s.baseline.update(
            s.baseline_key, {"collective-permute": 2.0}
        )
        ch.signal(profiling.CaptureRequest(
            capture_id="cap-7", steps=2, reason="slo:test"
        ))
        drive(s, 5, 8)
        ack = ch.await_ack("cap-7", 10.0)
        s.close()
        assert ack is not None and ack["ok"], ack
        summary = ack["summary"]
        assert summary["start_step"] == 5 and summary["end_step"] == 6
        assert summary["attribution"][0]["category"] == (
            "collective-permute"
        )
        assert summary["attribution"][0]["delta_pct"] == pytest.approx(
            1400.0
        )
        art = ack["artifact"]
        assert {
            "flight.json", "summary.json", "timeline.perfetto.json",
        } <= set(os.listdir(art))
        timeline = json.load(
            open(os.path.join(art, "timeline.perfetto.json"))
        )
        cats = {e.get("cat") for e in timeline["traceEvents"]}
        assert "host" in cats and "device" in cats
        flight_rec = json.load(
            open(os.path.join(art, "flight.json"))
        )
        assert flight_rec["stacks"] and "series" in flight_rec

    def test_capture_runs_even_without_parse_path(
        self, tmp_path, fresh_telemetry,
    ):
        """Sampling needs a parser; a DEEP capture is worth shipping
        even unparsed (trace + spans + stacks)."""
        ch = profiling.CaptureChannel(str(tmp_path / "chan"))
        s = profiling.DeviceTimeSampler(
            str(tmp_path / "prof"), sample_steps=0, parse_fn=None,
            baseline=profiling.OpCostBaseline(
                str(tmp_path / "b.json")
            ),
            capture_channel=ch, backend=FakeBackend(),
            artifact_root=str(tmp_path / "captures"),
        )
        ch.signal(profiling.CaptureRequest(capture_id="cap-1", steps=1))
        drive(s, 1, 2)
        ack = ch.await_ack("cap-1", 15.0)
        s.close()
        assert ack is not None and ack["ok"]
        assert ack["summary"]["categories"] == {}

    def test_profiler_start_failure_acks_failure(
        self, tmp_path, fresh_telemetry,
    ):
        ch = profiling.CaptureChannel(str(tmp_path / "chan"))
        s = make_sampler(
            tmp_path, lambda d, n: {}, sample_steps=0, channel=ch,
            backend=FakeBackend(fail_start=True),
        )
        ch.signal(profiling.CaptureRequest(capture_id="cap-1"))
        drive(s, 1, 2)
        ack = ch.await_ack("cap-1", 5.0)
        s.close()
        assert ack is not None and not ack["ok"]
        assert "start failed" in ack["error"]

    def test_real_jax_backend_one_window(
        self, tmp_path, fresh_telemetry,
    ):
        """One sampled window through the REAL jax.profiler: the
        xplane lands on disk and the parse thread sees it."""
        import jax
        import jax.numpy as jnp

        seen = {}

        def parse_fn(trace_dir, steps):
            assert profiling.DeviceTimeSampler._await_xplane(
                trace_dir, timeout=10.0
            ), "xplane never appeared"
            seen["paths"] = trace_summary.xplane_paths(trace_dir)
            return {"matmul": 1.0}

        s = profiling.DeviceTimeSampler(
            str(tmp_path / "prof"), sample_steps=2, parse_fn=parse_fn,
            baseline=profiling.OpCostBaseline(
                str(tmp_path / "b.json")
            ),
            capture_channel=None,
            artifact_root=str(tmp_path / "captures"),
        )
        s.set_context("fp", "devices=1")
        x = jnp.zeros((8, 8))
        step = jax.jit(lambda a: a + 1)
        step(x).block_until_ready()
        for i in range(1, 3):
            s.on_step_start(i)
            y = step(x)
            s.on_step_end(i, 0.001, block_on=y)
        assert wait_until(lambda: "paths" in seen, timeout=15.0)
        s.close()
        assert seen["paths"], "trace file missing"


# -------------------------------------------------------------------------
# capture manager (ledger discipline)
# -------------------------------------------------------------------------


class TestCaptureManager:
    def test_one_in_flight_and_cooldown(self, fresh_telemetry):
        cm = CaptureManager(cooldown_s=3600.0)
        ack = cm.request(0, reason="r1")
        assert ack["accepted"]
        refused = cm.request(1, reason="r2")
        assert not refused["accepted"]
        assert "in flight" in refused["reason"]
        d = cm.poll_directive(0)
        assert cm.report_result(d["capture_id"], 0, True)
        # host 0 now in cooldown; host 1 free
        refused = cm.request(0)
        assert not refused["accepted"] and "cooldown" in refused["reason"]
        assert cm.request(1)["accepted"]

    def test_directive_idempotent_reserve_and_exactly_once(
        self, fresh_telemetry,
    ):
        cm = CaptureManager(cooldown_s=0.0)
        cm.request(3, reason="slo")
        assert cm.poll_directive(0) == {}  # wrong host gets nothing
        d1 = cm.poll_directive(3)
        d2 = cm.poll_directive(3)
        assert d1["capture_id"] == d2["capture_id"]
        # wrong-host report dropped; first real report lands; dup dropped
        assert not cm.report_result(d1["capture_id"], 9, True)
        assert cm.report_result(
            d1["capture_id"], 3, True, artifact="/a",
            summary={"attribution": [
                {"category": "matmul", "delta_pct": 38.0,
                 "current_ms": 2, "baseline_ms": 1.4},
            ]},
        )
        assert not cm.report_result(d1["capture_id"], 3, True)
        assert cm.poll_directive(3) == {}  # done: never re-served
        rec = cm.list()[0]
        assert rec["state"] == "done" and rec["artifact"] == "/a"

    def test_expiry_frees_the_slot(self, fresh_telemetry):
        cm = CaptureManager(cooldown_s=0.0, directive_ttl_s=10.0)
        t0 = 1000.0
        cm.request(0, now=t0)
        cm.poll_directive(0, now=t0 + 1)
        # unexecuted past the TTL: failed, slot freed
        assert cm.poll_directive(0, now=t0 + 20) == {}
        rec = cm.list(now=t0 + 20)[0]
        assert rec["state"] == "failed" and "expired" in rec["error"]
        assert cm.request(1, now=t0 + 21)["accepted"]

    def test_on_sweep_triggers_from_verdicts(self, fresh_telemetry):
        cm = CaptureManager(cooldown_s=0.0)
        cm.on_sweep({
            "stragglers": {2: {"phase": "compute", "ratio": 3.0}},
            "hangs": {},
            "slo": {},
        })
        d = cm.poll_directive(2)
        assert d and "straggler:compute" in d["reason"]
        cm.report_result(d["capture_id"], 2, True)
        # an SLO breach naming a host triggers too (rank parsed from
        # the source name); goodput/global rules do not
        cm.on_sweep({
            "stragglers": {}, "hangs": {},
            "slo": {
                "goodput": {"rule": "goodput_below_threshold"},
                "step_time:worker-5-123": {
                    "rule": "step_time_regression", "ratio": 6.0,
                },
            },
        })
        d = cm.poll_directive(5)
        assert d and "slo:step_time_regression" in d["reason"]

    def test_slo_rank_parse(self):
        assert _slo_rank("step_time:worker-5-123") == 5
        assert _slo_rank("mfu:worker-0-99") == 0
        assert _slo_rank("goodput") is None
        assert _slo_rank("step_time:tool") is None

    def test_disabled_manager_refuses(self, fresh_telemetry):
        cm = CaptureManager(enabled=False)
        assert not cm.request(0)["accepted"]
        cm.on_sweep({"stragglers": {0: {}}, "hangs": {}, "slo": {}})
        assert cm.list() == []


# -------------------------------------------------------------------------
# capture ledger failover (test_master_failover style)
# -------------------------------------------------------------------------


def _servicer_with_store(state_dir, restore=False):
    from dlrover_tpu.master.servicer import MasterServicer
    from dlrover_tpu.master.state_store import MasterStateStore

    svc = MasterServicer()
    store = MasterStateStore(str(state_dir))
    store.bind(servicer=svc)
    svc.state_store = store
    if restore:
        store.restore()
    return svc, store


class TestCaptureFailover:
    def test_wal_only_reserves_identical_directive(
        self, tmp_path, fresh_telemetry,
    ):
        """Master killed between decision and execution, NO snapshot:
        WAL replay re-serves the identical directive exactly once."""
        svc, store = _servicer_with_store(tmp_path)
        ack = svc.get("worker", 0, msg.ProfileCaptureRequest(
            node_rank=2, reason="slo:step_time",
        ))
        assert ack.accepted
        d = svc.capture.poll_directive(2)
        # crash here (no snapshot written): recovery is WAL-only
        svc2, _store2 = _servicer_with_store(tmp_path, restore=True)
        d2 = svc2.capture.poll_directive(2)
        assert d2["capture_id"] == d["capture_id"]
        assert d2["reason"] == "slo:step_time"
        # still one in flight: a new request is refused
        assert not svc2.capture.request(3)["accepted"]
        # and the id counter moved forward: a later capture gets a
        # FRESH id, never a reused one
        svc2.capture.report_result(d2["capture_id"], 2, True)
        ack2 = svc2.capture.request(3)
        assert ack2["accepted"]
        assert ack2["capture_id"] != d["capture_id"]

    def test_snapshot_restore_and_done_not_reserved(
        self, tmp_path, fresh_telemetry,
    ):
        svc, store = _servicer_with_store(tmp_path)
        svc.capture.request(1, reason="operator")
        d = svc.capture.poll_directive(1)
        store.write_snapshot()
        svc2, store2 = _servicer_with_store(tmp_path, restore=True)
        assert svc2.capture.poll_directive(1)["capture_id"] == (
            d["capture_id"]
        )
        svc2.capture.report_result(
            d["capture_id"], 1, True, artifact="/a",
        )
        store2.write_snapshot()
        svc3, _ = _servicer_with_store(tmp_path, restore=True)
        assert svc3.capture.poll_directive(1) == {}
        rec = next(
            r for r in svc3.capture.list() if r["id"] == d["capture_id"]
        )
        assert rec["state"] == "done" and rec["artifact"] == "/a"
        # cooldown survives the failover too
        assert "cooldown" in svc3.capture.request(1)["reason"]


# -------------------------------------------------------------------------
# merged Perfetto timeline
# -------------------------------------------------------------------------


class TestPerfettoMerge:
    def test_host_and_device_slices(self):
        events = [
            {"t": 100.5, "kind": "span", "name": "train.step",
             "dur": 0.5, "source": "worker-0-1", "step": 7},
            {"t": 100.2, "kind": "span", "name": "shard.dispatch",
             "dur": 0.1, "source": "master-0-2"},
            {"t": 100.6, "kind": "slo.breach", "source": "master-0-2"},
        ]
        merged = profiling.merge_perfetto(
            events,
            device_categories={"matmul": 6.0, "fusion": 2.0},
            device_window=(100.0, 100.4),
        )
        evs = merged["traceEvents"]
        json.dumps(merged)  # serializable
        host = [e for e in evs if e.get("cat") == "host"]
        device = [e for e in evs if e.get("cat") == "device"]
        meta = [e for e in evs if e["ph"] == "M"]
        assert {e["name"] for e in host} == {
            "train.step", "shard.dispatch", "slo.breach",
        }
        assert all(e["ts"] >= 0 for e in evs if "ts" in e)
        span = next(e for e in host if e["name"] == "train.step")
        assert span["ph"] == "X" and span["dur"] == pytest.approx(5e5)
        assert span["args"]["step"] == 7
        instant = next(e for e in host if e["name"] == "slo.breach")
        assert instant["ph"] == "i"
        # device slices proportional to the category mix, inside the
        # window, widest first
        assert [e["name"] for e in device] == ["matmul", "fusion"]
        assert sum(e["dur"] for e in device) == pytest.approx(4e5)
        assert device[0]["dur"] == pytest.approx(3 * device[1]["dur"])
        names = {m["args"]["name"] for m in meta}
        assert {"worker-0-1", "master-0-2", "device"} <= names

    def test_real_device_trace_rebased_into_window(self):
        """xprof events carry their own trace-start timebase: they
        must be REBASED into the host timeline (anchored at the
        capture window), not copied verbatim to t=0."""
        merged = profiling.merge_perfetto(
            [{"t": 101.0, "kind": "span", "name": "s", "dur": 0.5,
              "source": "w"}],
            device_window=(100.8, 101.0),
            device_trace_events=[
                {"ph": "X", "name": "fusion.123", "ts": 10, "dur": 5,
                 "pid": 99, "tid": 7},
                {"ph": "X", "name": "fusion.124", "ts": 30, "dur": 5,
                 "pid": 99, "tid": 7},
            ],
        )
        dev = sorted(
            (e for e in merged["traceEvents"]
             if e.get("cat") == "device"),
            key=lambda e: e["ts"],
        )
        assert [e["name"] for e in dev] == ["fusion.123", "fusion.124"]
        assert dev[0]["tid"] == 7  # device-internal lanes preserved
        # host t0 = 100.5 (span start); window opens 0.3 s later: the
        # earliest device event sits AT the window start, relative
        # spacing preserved
        assert dev[0]["ts"] == pytest.approx(0.3e6)
        assert dev[1]["ts"] - dev[0]["ts"] == pytest.approx(20.0)

    def test_real_device_trace_no_window_anchors_at_t0(self):
        merged = profiling.merge_perfetto(
            [{"t": 1.0, "kind": "span", "name": "s", "dur": 0.5,
              "source": "w"}],
            device_trace_events=[
                {"ph": "X", "name": "op", "ts": 1234, "dur": 5},
            ],
        )
        (dev,) = [
            e for e in merged["traceEvents"]
            if e.get("cat") == "device"
        ]
        assert dev["ts"] == 0.0

    def test_empty_inputs(self):
        merged = profiling.merge_perfetto([])
        assert merged["traceEvents"][-1]["ph"] == "M"


# -------------------------------------------------------------------------
# flight recorder: series tails
# -------------------------------------------------------------------------


class TestFlightSeriesTail:
    def test_dump_carries_series_tails(self, tmp_path, monkeypatch):
        from dlrover_tpu.common import flight

        monkeypatch.setenv(telemetry.ENV_DIR, str(tmp_path))
        prev = telemetry.active_registry()
        telemetry.enable("worker-0-7")
        try:
            for i in range(80):
                telemetry.gauge_set("train.step.last_s", 0.01 * i)
                telemetry.gauge_set("train.mfu", 0.4)
            path = flight.dump("test-reason")
            assert path is not None
            record = json.load(open(path))
            series = {s["name"]: s["points"] for s in record["series"]}
            assert len(series["train.step.last_s"]) == (
                telemetry.SERIES_TAIL_POINTS
            )
            # the NEWEST points: the quantitative lead-up to the crash
            assert series["train.step.last_s"][-1][3] == pytest.approx(
                0.79
            )
            assert len(series["train.mfu"]) == (
                telemetry.SERIES_TAIL_POINTS
            )
        finally:
            telemetry._REGISTRY = prev

    def test_series_tail_helper(self):
        tail = telemetry.series_tail(
            [
                {"name": "g", "labels": {},
                 "points": [[i, 0, 0, i] for i in range(100)]},
                {"name": "empty", "labels": {}, "points": []},
            ],
            n=5,
        )
        assert len(tail) == 1  # empty series dropped
        assert [p[0] for p in tail[0]["points"]] == [
            95, 96, 97, 98, 99,
        ]


# -------------------------------------------------------------------------
# obs_report front door
# -------------------------------------------------------------------------


class TestObsReportCapture:
    def test_refused_capture_exits_nonzero(
        self, local_master, fresh_telemetry, capsys,
    ):
        from tools.obs_report import run_capture

        rc = run_capture(local_master.addr, -1, wait=5.0)
        assert rc == 1
        assert "refused" in capsys.readouterr().err

    def test_capture_roundtrip_via_tool(
        self, local_master, fresh_telemetry, capsys,
    ):
        from tools.obs_report import run_capture

        svc = local_master.servicer
        done = threading.Event()

        def executor():
            deadline = time.time() + 20
            while time.time() < deadline:
                d = svc.capture.poll_directive(0)
                if d:
                    svc.capture.report_result(
                        d["capture_id"], 0, True, artifact="/art",
                        summary={"attribution": [
                            {"category": "collective-permute",
                             "current_ms": 2.76, "baseline_ms": 2.0,
                             "delta_pct": 38.0},
                        ]},
                    )
                    done.set()
                    return
                time.sleep(0.05)

        t = threading.Thread(target=executor, daemon=True)
        t.start()
        rc = run_capture(local_master.addr, 0, wait=20.0, poll=0.05)
        t.join(timeout=20)
        assert done.is_set()
        assert rc == 0
        out = capsys.readouterr().out
        assert "collective-permute" in out and "+38.0%" in out

    def test_write_perfetto(self, tmp_path):
        from tools.obs_report import write_perfetto

        report = {"timeline": [
            {"t": 5.0, "kind": "span", "name": "rdzv.round",
             "dur": 1.0, "source": "agent-0-1"},
        ]}
        out = write_perfetto(report, str(tmp_path / "t.json"))
        merged = json.load(open(out))
        assert any(
            e.get("name") == "rdzv.round"
            for e in merged["traceEvents"]
        )

    def test_profiling_summary_section(self):
        from tools.obs_report import _profiling_summary

        metrics = {
            "gauges": [
                {"name": "device.optime_ms",
                 "labels": {"category": "matmul"}, "value": 3.0},
                {"name": "device.optime.total_ms", "labels": {},
                 "value": 4.5},
                {"name": "train.mfu", "labels": {}, "value": 0.4},
            ],
            "counters": [
                {"name": "prof.samples", "labels": {}, "value": 7},
                {"name": "steps", "labels": {}, "value": 100},
            ],
        }
        timeline = [
            {"t": 1.0, "kind": "device.optime.regression",
             "category": "matmul", "delta_pct": 80.0},
            {"t": 2.0, "kind": "step.end"},
        ]
        out = _profiling_summary(metrics, timeline)
        assert out["metrics"][
            "device.optime_ms{category=matmul}"
        ] == 3.0
        assert out["metrics"]["prof.samples"] == 7
        assert "train.mfu" not in out["metrics"]
        assert [e["kind"] for e in out["events"]] == [
            "device.optime.regression",
        ]
        assert _profiling_summary({}, []) == {}


# -------------------------------------------------------------------------
# end to end: regression -> breach -> capture -> artifact -> timeline
# -------------------------------------------------------------------------


def _token_problem(vocab=32, dim=4, bs=4, seq=8, n=96):
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


def _http_json(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as resp:
        return json.loads(resp.read().decode())


class TestDeepProfilingEndToEnd:
    def test_smoke_regression_to_capture(
        self, local_master, tmp_path, fresh_telemetry, monkeypatch,
    ):
        """The acceptance scenario, in process: an injected 6x
        step-time regression produces — with no human action — an SLO
        breach, a deep-capture directive for the blamed host, an
        executed capture whose attribution names the inflated op
        category vs the stored baseline, a /captures.json entry, and a
        merged Perfetto timeline holding host spans AND device ops."""
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.agent.monitor import TelemetryReporter
        from dlrover_tpu.master.http_plane import MasterHttpPlane
        from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

        svc = local_master.servicer
        plane = MasterHttpPlane(svc)
        plane.start()
        client = MasterClient(local_master.addr, 0, "worker")
        reporter = TelemetryReporter(client, interval=999)
        delay = {"s": 0.0}

        def prestep(state, batch):
            # a step is timed at its completion, so the toy's own
            # compute is in it, and on a loaded test host that wobbles
            # between 3 and 70 ms: a floor under the healthy step keeps
            # the watchdog's 1.5x quiet, and the injected delay is
            # several times the worst of it
            time.sleep(0.03 + delay["s"])
            return state, batch

        # the injected anomaly reads as collective-permute time: the
        # fake parse backend prices the delay into that category, so
        # the attribution must NAME it against the healthy baseline
        def parse_fn(trace_dir, steps):
            return {
                "collective-permute": 2.0 + delay["s"] * 1e3,
                "matmul": 1.0,
            }

        loss_fn, init_fn, axes, batches = _token_problem()
        args = TrainingArgs(
            output_dir=str(tmp_path / "out"), max_steps=24,
            log_steps=0, flash_checkpoint=False,
        )
        trainer = Trainer(
            loss_fn, init_fn, axes, args, train_data=batches,
            prestep=prestep,
        )
        # swap in the harness sampler: fake capture backend + the
        # synthetic parser (no xprof in this environment), sampling
        # every 4 steps, capture channel like the agent would export
        channel = profiling.CaptureChannel(str(tmp_path / "chan"))
        trainer._prof.close()
        trainer._prof = profiling.DeviceTimeSampler(
            str(tmp_path / "prof"), sample_steps=4, parse_fn=parse_fn,
            baseline=profiling.OpCostBaseline(
                str(tmp_path / "baseline.json")
            ),
            capture_channel=channel, backend=FakeBackend(),
            artifact_root=str(tmp_path / "captures"),
            overhead_pct=0.0,  # fixed cadence: deterministic smoke
        )
        trainer._refresh_prof_context()
        try:
            # --- phase 1: healthy baseline (samples seed the op-cost
            # baseline; step times seed the SLO rolling windows)
            trainer.train()
            assert wait_until(
                lambda: trainer._prof.baseline.get(
                    trainer._prof.baseline_key
                )
            )
            reporter.report_once()
            source = telemetry.snapshot()["source"]
            assert svc.diagnosis.check(force=True)["slo"] == {}
            baseline_cp = trainer._prof.baseline.get(
                trainer._prof.baseline_key
            )["collective-permute"]
            assert baseline_cp == pytest.approx(2.0)

            # --- phase 2: inject the regression, ship telemetry
            delay["s"] = 0.2
            args.max_steps = 40
            trainer.train()

            def slow_sample_parsed():
                snap = telemetry.snapshot()
                return any(
                    g["name"] == profiling.OPTIME_GAUGE
                    and g["labels"].get("category")
                    == "collective-permute"
                    and g["value"] == pytest.approx(202.0)
                    for g in snap["gauges"]
                )

            assert wait_until(slow_sample_parsed)
            reporter.report_once()

            # SLO breach names the host...
            verdicts = svc.diagnosis.check(force=True)
            assert any(
                k == f"step_time:{source}" for k in verdicts["slo"]
            ), verdicts["slo"]
            # ...and the capture manager turned it into a directive
            # for the blamed host with NO human action
            directive = dict(client.get_diagnosis().capture)
            assert directive.get("capture_id"), (
                svc.capture.list(), verdicts,
            )
            assert "slo:step_time_regression" in directive["reason"]

            # --- the agent half: relay into the worker, wait, report
            executor = threading.Thread(
                target=profiling.execute_capture,
                args=(directive, channel,
                      lambda cid, ok, artifact, summary, error:
                      client.report_capture_result(
                          cid, 0, ok, artifact=artifact,
                          summary=summary, error=error,
                      )),
                kwargs={"timeout": 60.0},
                daemon=True,
            )
            executor.start()
            args.max_steps = 48
            trainer.train()  # the worker executes the capture window
            executor.join(timeout=60)
            assert not executor.is_alive()

            # --- artifact indexed on /captures.json with the
            # attribution diff naming the inflated category
            payload = _http_json(plane.port, "/captures.json")
            rec = next(
                r for r in payload["captures"]
                if r["id"] == directive["capture_id"]
            )
            assert rec["state"] == "done", rec
            attribution = rec["summary"]["attribution"]
            assert attribution[0]["category"] == "collective-permute"
            assert attribution[0]["delta_pct"] > 300
            one = _http_json(
                plane.port,
                f"/captures.json?id={directive['capture_id']}",
            )
            assert len(one["captures"]) == 1

            # --- the merged Perfetto timeline holds host spans AND
            # device ops
            timeline = json.load(open(os.path.join(
                rec["artifact"], "timeline.perfetto.json"
            )))
            cats = {
                e.get("cat") for e in timeline["traceEvents"]
            }
            assert "host" in cats and "device" in cats
            host_names = {
                e["name"] for e in timeline["traceEvents"]
                if e.get("cat") == "host"
            }
            assert "train.dispatch" in host_names
            device_names = {
                e["name"] for e in timeline["traceEvents"]
                if e.get("cat") == "device"
            }
            assert "collective-permute" in device_names

            # --- always-on accounting on /metrics: the
            # dlrtpu_device_optime_ms family, HELP/TYPE announced,
            # per-category samples parseable
            with urllib.request.urlopen(
                f"http://127.0.0.1:{plane.port}/metrics", timeout=10
            ) as resp:
                text = resp.read().decode()
            assert (
                "# HELP dlrtpu_device_optime_ms " in text
            )
            assert "# TYPE dlrtpu_device_optime_ms gauge" in text
            from tests.test_metrics_plane import parse_prometheus

            samples = parse_prometheus(text)
            optime = dict(samples["dlrtpu_device_optime_ms"])
            cp = next(
                v for k, v in optime.items()
                if 'category="collective-permute"' in k
            )
            assert cp == pytest.approx(202.0)
            assert any(
                'state="done"' in k
                for k, _v in samples["dlrtpu_prof_captures"]
            )

            # the regression event rode the relay into the master's
            # merged timeline
            rep = _http_json(plane.port, "/report.json")
            kinds = {e["kind"] for e in rep["timeline"]}
            assert "device.optime.regression" in kinds
            assert rep["captures"]["states"].get("done") == 1
        finally:
            delay["s"] = 0.0
            trainer.close()
            client.close()
            plane.stop()
