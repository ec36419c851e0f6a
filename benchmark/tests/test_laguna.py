"""The ``laguna`` family: its FLOP count and its kernels' work counted
by hand from the published sizes, the reader of the routed experts'
roofline on recorded numbers, what the program lacks refused, and its
toy cell through ``run.py`` on the CPU. The reference against the
program's model (logits, loss, gradients, the shares, near ties, the
window's kernels) is tier-1's ``tests/test_laguna.py``."""

import families
import lookup
import pytest
from test_rehearsal import _not_printed, _run, _workload

FAMILY = lookup.module("family", "laguna")
CELL = "toy-laguna.steady"


def _sizes(name):
    return lookup.data("configs", name)


def test_flops_by_hand():
    sizes = _sizes("laguna-xs.2")
    share = sizes["held_pair_share"]
    # multiply-adds a token crosses: q, k, v, o and the gate a layer
    full = 2048 * (48 + 2 * 8) * 128 + 48 * 128 * 2048 + 2048 * 48
    window = 2048 * (64 + 2 * 8) * 128 + 64 * 128 * 2048 + 2048 * 64
    # QK^T and AV: a full layer over (8192 + 1) / 2 keys on average, a
    # window layer over 512 but for its first 511 rows
    full_keys = (8192 + 1) / 2
    window_keys = (512 * 513 / 2 + (8192 - 512) * 512) / 8192
    assert window_keys == pytest.approx(496.03, abs=0.01)
    dense = 3 * 2048 * 8192
    routed = 2048 * 256 + 3 * 2048 * 512 + share * 8 * 3 * 2048 * 512
    forward = 2 * (
        2 * full + window * 3 + 12544 * 2048 + dense + 4 * routed
    ) + 2 * 2 * 128 * (2 * 48 * full_keys + 3 * 64 * window_keys)
    assert FAMILY.flops_per_token(sizes, 8192) == pytest.approx(3 * forward)
    family = families.build(sizes)
    assert family.flops_per_token == pytest.approx(3 * forward)
    # what is held enters the count at the window's share
    more = dict(sizes, held_pair_share=share + 0.1)
    assert FAMILY.flops_per_token(more, 8192) - family.flops_per_token \
        == pytest.approx(3 * 2 * 4 * 0.1 * 8 * 3 * 2048 * 512)
    assert family.tolerances == {
        "logits_rel_rms": FAMILY.LOGITS_REL_RMS_TOL,
        "logits_rel_max": 8 * FAMILY.LOGITS_REL_RMS_TOL, "loss_abs": 0.02}


def test_kernels_work_by_hand():
    sizes = _sizes("laguna-xs.2")
    family = families.build(sizes)
    win, win_bytes = families.kernel_work(family, sizes, "flash_fwd_window")
    window_keys = (512 * 513 / 2 + (8192 - 512) * 512) / 8192
    # three layers, two rows of 8192, 64 heads of 128
    assert win == pytest.approx(3 * 2 * 8192 * 64 * 2 * 2 * 128 * window_keys)
    q, kv = 2 * 8192 * 64 * 128 * 2, 2 * 8192 * 8 * 128 * 2
    assert win_bytes == 3 * (2 * q + 2 * kv + 2 * 64 * 8192 * 4)
    every, _ = families.kernel_work(family, sizes, "flash_fwd")
    full = 2 * 2 * 8192 * 48 * 2 * 2 * 128 * (8192 + 1) / 2
    assert every == pytest.approx(win + full)
    backward, _ = families.kernel_work(family, sizes, "flash_bwd_window")
    assert backward == pytest.approx(2.5 * win)
    # the experts at the pairs the window held: the count follows them
    pairs = 123_456
    flops, hbm = families.kernel_work(
        family, dict(sizes, held_pairs=pairs), "moe_routed")
    assert flops == 3 * 2 * 3 * 2048 * 512 * pairs
    weights = 32 * 3 * 2048 * 512 * 2           # bf16
    assert hbm == 4 * 3 * weights + 5 * pairs * 2048 * 2
    half, _ = families.kernel_work(
        family, dict(sizes, held_pairs=pairs / 2), "moe_routed")
    assert half == flops / 2
    # without a reading, the configuration's share of the pairs routed
    default, _ = families.kernel_work(family, sizes, "moe_routed")
    assert default == pytest.approx(
        18 * 2048 * 512 * sizes["held_pair_share"] * 4 * 16384 * 8)


def _record(held, ms=40.0, steps=10, traced=(10.0, 20.0)):
    sizes = _sizes("laguna-xs.2")
    return {
        "trace": {"ops_s": {"ragged-dot-none.1": ms * steps / 1e3 * 0.9,
                            "ragged-dot-metadata.2": ms * steps / 1e3 * 0.1,
                            "fusion.3": 1.0}},
        "traced_steps": steps, "peak_flops": 197e12,
        "peak_hbm_bytes_per_s": 819e9, "chips": 1,
        "family": families.build(sizes), "sizes": sizes,
        "traced_wall": traced,
        "events": [{"kind": "step.counts", "t": t, "moe.tokens_held": h,
                    "moe.tokens_routed": 4 * 16384 * 8}
                   for t, h in held],
    }


PARAMS = {"kind": "step.counts", "held": "moe.tokens_held",
          "pattern": "^ragged-dot", "work": "moe_routed"}


def test_routed_roofline_follows_the_held_pairs():
    """The share is of the work the step's own held pairs require: the
    event inside the traced stretch when there is one (not the window's
    mean), and half the pairs in the same time read half the share."""
    reader = lookup.module("reader", "held_pair_roofline")
    inside = 262_144
    value = reader.read(_record([(5.0, 131_072), (15.0, inside),
                                 (25.0, 131_072)]), PARAMS)
    flops = 18 * 2048 * 512 * inside
    assert value == pytest.approx(100 * flops / 197e12 / 0.040)
    assert 0 < value < 100
    halved = reader.read(_record([(15.0, inside / 2)]), PARAMS)
    assert halved == pytest.approx(value / 2, rel=0.02)
    # no event in the stretch: the window's mean
    outside = reader.read(_record([(5.0, inside), (25.0, inside)]), PARAMS)
    assert outside == pytest.approx(value)


@pytest.mark.parametrize("record", [
    {},                                      # nothing traced
    {"events": []},                          # a program that counts nothing
    {"held": [(15.0, 0)]},                   # no pair held
    {"ms": 0.0},                             # the kernels did not run
])
def test_routed_roofline_reads_nothing_where_there_is_nothing(record):
    reader = lookup.module("reader", "held_pair_roofline")
    if not record:
        assert reader.read({}, PARAMS) is None
        return
    if "events" in record:
        rec = _record([])
    elif "held" in record:
        rec = _record(record["held"])
    else:
        rec = _record([(15.0, 1000)], ms=0.0)
    assert reader.read(rec, PARAMS) is None


@pytest.mark.parametrize("key,value,match", [
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("num_hidden_layers", 4, "layer_types"),
    ("mlp_layer_types", ["dense"] * 5, "sliding_attention"),
    ("num_experts", 17, "not among 16"),
])
def test_what_the_program_lacks_is_refused(key, value, match):
    with pytest.raises(ValueError, match=match):
        families.build(dict(_sizes("toy-laguna"), **{key: value}))


def test_toy_cell_reads_its_layers():
    """The cell's list is the manifest's 14, the three of the routed
    counters and kernels it shares with zaya's cell and its own four;
    on the CPU those that need no device plane find their numbers, the
    two that read the program's counters among them."""
    proc, result = _run(CELL, trace=1, seconds=5)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] and result["metrics"] == {}
    for name, pair in result["compared"].items():
        assert 0 <= pair["value"] <= pair["limit"], name
    assert "[laguna] near ties followed:" in proc.stderr
    read, listed = _not_printed(proc), _workload(CELL)["per_layer"]
    assert listed == lookup.data("workloads", "laguna-xs.2.steady")[
        "per_layer"]
    assert listed[:14] == lookup.data(
        "workloads", "granite-4.0-h-micro.steady")["per_layer"]
    assert "moe_expert_roofline" not in listed
    assert set(read) <= set(listed)
    # 4 of 16 experts held, 4 a token: a quarter of the pairs at first,
    # more as the toy trains
    assert 20 < read["moe_held_token_pct"]["value"] <= 100
    assert 1 <= read["moe_expert_imbalance"]["value"] < 4
