"""trace_reduce.py against answers worked out by hand: a small trace
written out below, and (``recorded_trace.json``) a trimmed copy of one
sync interval recorded on the chip."""

import json
import os

import pytest
import readers
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1000.0  # the hand-made trace is written in microseconds


def _ns(events):
    return [[n, s * US, d * US] for n, s, d in events]


HAND = {
    "devices": {"/device:TPU:0": {
        "XLA Ops": _ns([
            # step 1: a loop of 60 us holding two bodies, then a kernel
            ["while.1", 100, 60], ["fusion.2", 105, 20], ["fusion.2", 130, 25],
            ["flash_fwd", 160, 30],
            # idle 190..250, then step 2
            ["while.1", 250, 60], ["fusion.2", 255, 20], ["fusion.2", 280, 25],
            ["flash_fwd", 310, 30],
            # outside the window: not counted
            ["fusion.9", 900, 50],
        ]),
        "XLA Modules": _ns([
            ["jit_train_step(1)", 100, 90], ["jit_train_step(1)", 250, 90],
            ["jit_other", 900, 50],
        ]),
    }},
    "host": _ns([
        ["bench.window", 50, 350],           # 50..400
        ["bench.log_flush", 40, 50],         # 40..90: covers 50..90 of gap 1
        ["bench.save_checkpoint", 200, 40],  # 200..240 inside gap 190..250
        ["other.thing", 0, 1000],            # not one of the runner's
    ]),
}


def test_by_hand():
    got = trace_reduce.reduce(HAND)
    assert got["devices"] == 1
    assert got["window_s"] == pytest.approx(350e-6)
    # busy: 100..190 and 250..340
    assert got["busy_s"] == pytest.approx(180e-6)
    # self time: the loop's 60 minus its bodies' 45, twice
    assert got["ops_s"]["while.1"] == pytest.approx(30e-6)
    assert got["ops_s"]["fusion.2"] == pytest.approx(90e-6)
    assert got["ops_s"]["flash_fwd"] == pytest.approx(60e-6)
    assert "fusion.9" not in got["ops_s"]
    assert got["modules_s"] == {"jit_train_step(1)": pytest.approx(180e-6)}
    assert got["modules_count"] == {"jit_train_step(1)": 2}
    assert got["device_ops"][0] == ["fusion.2", pytest.approx(90e-6)]
    # gaps: 50..100 (40 under log_flush, 10 other), 190..250 (40 under
    # save_checkpoint, 20 other), 340..400 (other)
    gaps = dict(got["idle_gaps"])
    assert gaps == {
        "log_flush": pytest.approx(40e-6),
        "save_checkpoint": pytest.approx(40e-6),
        "other": pytest.approx(90e-6),
    }
    assert sum(gaps.values()) == pytest.approx(
        got["window_s"] - got["busy_s"]
    )


def test_readers_on_the_hand_trace():
    record = {"trace": trace_reduce.reduce(HAND), "traced_steps": 2}
    step = {"reader": {"name": "trace_op_ms", "line": "modules",
                       "pattern": "train_step"}}
    kernel = {"reader": {"name": "trace_op_ms", "line": "ops",
                         "pattern": "^flash"}}
    absent = {"reader": {"name": "trace_op_ms", "line": "ops",
                         "pattern": "no_such_op"}}
    idle = {"reader": {"name": "trace_idle"}}
    assert readers.read(step, record) == pytest.approx(0.090)
    assert readers.read(kernel, record) == pytest.approx(0.030)
    assert readers.read(absent, record) is None
    assert readers.read(idle, record) == pytest.approx(100 * 170 / 350)
    assert readers.read(idle, {"trace": None}) is None


def test_window_falls_back_to_the_operations():
    raw = {"devices": HAND["devices"], "host": []}
    got = trace_reduce.reduce(raw)
    assert got["window_s"] == pytest.approx(850e-6)   # 100..950


def test_no_device_plane_gives_nothing():
    assert trace_reduce.reduce({"devices": {}, "host": HAND["host"]}) is None


def test_other_readers():
    record = {
        "saves": [
            {"stall_s": 2.0, "bytes": 4e9, "fill_s": 0.5},
            {"stall_s": 4.0, "bytes": 4e9, "fill_s": 0.7},
            {"stall_s": 5.0, "bytes": 4e9, "fill_s": 0.9},
        ],
        "memory_stats": {"peak_bytes_in_use": 14.5e9},
        "step_seconds": [0.140] * 30 + [0.150, 0.185],
        "tokens_per_s": 25000.0, "flops_per_token": 3.5e9,
        "peak_flops": 197e12, "chips": 1,
    }

    def read(**reader):
        return readers.read({"reader": reader}, record)

    assert read(name="stats_key", source="saves", key="fill_s") == 0.7
    assert read(name="stats_key", source="saves", key="bytes",
                per="stall_s", scale=1e-9) == pytest.approx(1.0)
    assert read(name="stats_key", source="saves", key="absent") is None
    assert read(name="stats_key", source="memory", key="peak_bytes_in_use",
                scale=1e-9) == pytest.approx(14.5)
    # 32 values: position 0.95 x 31 = 29.45, between 0.140 and 0.150
    assert read(name="step_quantile_ms", q=19) == pytest.approx(144.5)
    assert read(name="derived_mfu") == pytest.approx(
        100 * 3.5e9 * 25000 / 197e12
    )
    assert read(name="step_rate") == 25000.0
    with pytest.raises(ValueError):
        read(name="no_such_reader")


RECORDED = os.path.join(HERE, "recorded_trace.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_trace():
    with open(RECORDED) as f:
        fixture = json.load(f)
    got = trace_reduce.reduce(fixture["raw"])
    for key, want in fixture["by_hand"].items():
        assert got[key] == pytest.approx(want, rel=1e-6), key
    for name, want in fixture["ops_by_hand"].items():
        assert got["ops_s"][name] == pytest.approx(want, rel=1e-6), name
