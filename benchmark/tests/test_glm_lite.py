"""The ``glm_lite`` family: its FLOP count and its kernels' work counted
by hand from the published sizes, the MTP depth's share of the rows a
step predicts read off recorded counts, what the program lacks refused,
and its toy cell through ``run.py`` on the CPU. The reference against
the program's model (both heads' logits, both losses, gradients, the
shares, the bias, near ties) is tier-1's ``tests/test_glm_lite.py``."""

import families
import lookup
import pytest
from test_rehearsal import _not_printed, _run, _workload

FAMILY = lookup.module("family", "glm_lite")
CELL = "toy-glm-lite.steady"


def _sizes(name):
    return lookup.data("configs", name)


def test_flops_by_hand():
    sizes = _sizes("glm-4.7-flash")
    share = sizes["held_pair_share"]
    # multiply-adds a token crosses in a layer's latent attention: W_qa,
    # W_qb (20 heads of 192 + 64), W_kva (512 + 64), W_kvb (20 heads of
    # 192 + 256) and W_o
    mla = 2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448 \
        + 20 * 256 * 2048
    assert mla == 21_757_952
    # QK^T and AV at 20 heads of 256 over (8192 + 1) / 2 keys on average
    attention = 2 * 2 * 20 * 256 * (8192 + 1) / 2
    dense = 3 * 2048 * 10240
    routed = 2048 * 64 + 3 * 2048 * 1536 + share * 4 * 3 * 2048 * 1536
    # six layers of attention (five and the MTP depth's), five routed
    # (four and the MTP depth's), W_eh, and two heads over the slice
    forward = 2 * (6 * mla + dense + 5 * routed + 2 * 2048 * 2048
                   + 2 * 19360 * 2048) + 6 * attention
    assert FAMILY.flops_per_token(sizes, 8192) == pytest.approx(3 * forward)
    family = families.build(sizes)
    assert family.flops_per_token == pytest.approx(3 * forward)
    # what is held enters the count at the window's share
    more = dict(sizes, held_pair_share=share + 0.1)
    assert FAMILY.flops_per_token(more, 8192) - family.flops_per_token \
        == pytest.approx(3 * 2 * 5 * 0.1 * 4 * 3 * 2048 * 1536)
    # the limits are on the logits alone (the module says why)
    assert family.tolerances == {
        "logits_rel_rms": FAMILY.LOGITS_REL_RMS_TOL,
        "logits_rel_max": 8 * FAMILY.LOGITS_REL_RMS_TOL}


def test_kernels_work_by_hand():
    sizes = _sizes("glm-4.7-flash")
    family = families.build(sizes)
    fwd, fwd_bytes = families.kernel_work(family, sizes, "flash_fwd")
    # six layers, two rows of 8192, 20 heads of 256 over 20
    assert fwd == pytest.approx(
        6 * 2 * 8192 * 2 * 2 * 20 * 256 * (8192 + 1) / 2)
    q = 2 * 8192 * 20 * 256 * 2
    assert fwd_bytes == 6 * (4 * q + 2 * 20 * 8192 * 4)
    bwd, bwd_bytes = families.kernel_work(family, sizes, "flash_bwd")
    assert bwd == pytest.approx(2.5 * fwd)
    assert bwd_bytes == 6 * (8 * q + 2 * 20 * 8192 * 4)
    # the experts at the pairs the window held: the count follows them
    pairs = 123_456
    flops, hbm = families.kernel_work(
        family, dict(sizes, held_pairs=pairs), "moe_routed")
    assert flops == 3 * 2 * 3 * 2048 * 1536 * pairs
    weights = 8 * 3 * 2048 * 1536 * 2           # bf16
    assert hbm == 5 * 3 * weights + 5 * pairs * 2048 * 2
    # without a reading, the configuration's share of the pairs routed
    default, _ = families.kernel_work(family, sizes, "moe_routed")
    assert default == pytest.approx(
        18 * 2048 * 1536 * sizes["held_pair_share"] * 4 * 16384 * 5)


def _record(events):
    return {"events": [dict(e, kind="step.counts") for e in events]}


PARAMS = lookup.data("layer_metrics", "mtp_token_pct")["reader"]


def test_mtp_share_reads_the_window_s_counts():
    """``mtp_token_pct``: the MTP depth's labelled positions over the
    main head's, summed over the window's read-backs; nothing where the
    program counts neither (a program without the depth)."""
    import readers

    record = _record([{"lm.tokens": 16384.0, "mtp.tokens": 16382.0},
                      {"lm.tokens": 16384.0, "mtp.tokens": 16382.0}])
    assert readers.read({"reader": PARAMS}, record) == pytest.approx(
        100 * 8191 / 8192)
    assert readers.read({"reader": PARAMS}, _record(
        [{"moe.tokens_held": 1.0, "moe.tokens_routed": 2.0}])) is None
    assert readers.read({"reader": PARAMS}, {}) is None


@pytest.mark.parametrize("key,value,match", [
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("topk_method", "greedy", "topk_method"),
    ("v_head_dim", 128, "one head width"),
    ("n_routed_experts", 17, "not among 16"),
])
def test_what_the_program_lacks_is_refused(key, value, match):
    with pytest.raises(ValueError, match=match):
        families.build(dict(_sizes("toy-glm-lite"), **{key: value}))


def test_toy_cell_reads_its_layers():
    """The cell's list is the manifest's 14, the four of the routed
    counters and kernels it shares with laguna's cell and the MTP
    depth's share; on the CPU those that need no device plane find
    their numbers, those that read the program's counters among
    them."""
    proc, result = _run(CELL, trace=1, seconds=5)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] and result["metrics"] == {}
    assert set(result["compared"]) == {"logits_rel_rms", "logits_rel_max"}
    for name, pair in result["compared"].items():
        assert 0 <= pair["value"] <= pair["limit"], name
    assert "[glm_lite] near ties followed:" in proc.stderr
    read, listed = _not_printed(proc), _workload(CELL)["per_layer"]
    assert listed == lookup.data("workloads", "glm-4.7-flash.steady")[
        "per_layer"]
    assert listed[:14] == lookup.data(
        "workloads", "granite-4.0-h-micro.steady")["per_layer"]
    assert not [name for name in listed if "window" in name]
    assert set(read) <= set(listed)
    # 4 of 16 experts held, 4 a token: a quarter of the pairs at first
    assert 10 < read["moe_held_token_pct"]["value"] <= 100
    assert 1 <= read["moe_expert_imbalance"]["value"] < 4
    # 127 of a row's 128 positions have a label two ahead
    assert read["mtp_token_pct"]["value"] == pytest.approx(100 * 127 / 128)
