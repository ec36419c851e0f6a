"""The ``zaya`` family: its reference pinned on seeded toy weights, its
FLOP count and its kernels' work counted by hand from the published
sizes, what the program lacks refused, and its toy cell through
``run.py`` on the CPU. The reference against the program's model
(logits, loss, gradients, the shares, planted faults, near ties) is
tier-1's ``tests/test_zaya.py``."""

import hashlib

import families
import jax
import jax.numpy as jnp
import lookup
import numpy as np
import pytest
from test_rehearsal import _not_printed, _run, _workload

FAMILY = lookup.module("family", "zaya")
CELL = "toy-zaya.steady"


def _sizes(name):
    return lookup.data("configs", name)


CORNER, DIGEST = "-0x1.6b4b140000000p-1", "02e10329de3ae2e0"


def _seeded(sizes):
    """The family's initial values moved by seeded noise: matrices at
    1/sqrt(fan-in), so that every branch counts; scales near 1 and
    biases near 0 by 0.05."""
    family = families.build(sizes)
    rs = np.random.RandomState(11)

    def leaf(path, x):
        noise = jnp.asarray(rs.standard_normal(x.shape), jnp.float32)
        if x.ndim >= 3 and path[-1].key not in ("alpha", "beta", "conv2_b"):
            return noise * x.shape[-2] ** -0.5
        return x + 0.05 * noise

    params = jax.tree_util.tree_map_with_path(
        leaf, family.init(jax.random.key(0)))
    tokens = jnp.asarray(
        np.random.RandomState(5).randint(0, sizes["vocab_size"], (129,)),
        jnp.int32)
    return params, tokens


def test_reference_is_pinned_on_toy_weights():
    """``logits`` on seeded weights gave these when the family was
    written (PR 34): the first 16 hex digits of the SHA-256 of the
    float32 array, and its last row's first entry. The reference is the
    yardstick of ``correct``: a change to it shows here."""
    sizes = _sizes("toy-zaya")
    params, tokens = _seeded(sizes)
    logits, followed = jax.jit(
        lambda p, t: FAMILY.logits(sizes, p, t))(params, tokens[:-1])
    logits = np.asarray(logits)
    assert int(followed) == 0
    assert logits.dtype == np.float32 and logits.shape == (128, 256)
    assert float(logits[-1, 0]).hex() == CORNER
    assert hashlib.sha256(logits.tobytes()).hexdigest()[:16] == DIGEST


def test_reference_follows_only_inside_eps():
    """Another forward pass's choices, here every token's second-best
    expert in every layer: with ``eps`` 0 none is taken and the logits
    are the reference's own; with ``eps`` 1 (no two probabilities lie
    further apart) every one is."""
    sizes = _sizes("toy-zaya")
    params, tokens = _seeded(sizes)
    tokens = tokens[:-1]
    own, _ = FAMILY.logits(sizes, params, tokens)
    follow = jnp.full((3, 128), 5, jnp.int32)       # an expert not held
    same, none = FAMILY.logits(sizes, params, tokens, follow, 0.0)
    assert int(none) == 0
    np.testing.assert_array_equal(same, own)
    other, all_ = FAMILY.logits(sizes, params, tokens, follow, 1.0)
    # all but the tokens whose own choice is expert 5
    assert 0.7 * 384 < int(all_) <= 384
    assert float(jnp.max(jnp.abs(other - own))) > 1e-3


def test_flops_by_hand():
    sizes = _sizes("zaya1-8b")
    assert sizes["num_hidden_layers"] == 6 and sizes["sequence"] == 8192
    assert sizes["num_experts"] == 8 and sizes["vocab_size"] == 32784
    assert sizes["published"]["num_experts"] == 16
    # multiply-adds a token crosses in a layer
    projections = 2048 * (1024 + 256 + 256) + 1024 * 2048   # q, k, v; o
    grouped_conv = 10 * 2 * 128 * 128
    causal = 2 * 8 * 128 * (8192 + 1) / 2
    cca = projections + grouped_conv + causal
    assert 2 * cca == pytest.approx(27.9e6, rel=2e-3)
    assert 2 * causal == pytest.approx(16.8e6, rel=2e-3)
    router = 2048 * 256 + 2 * 256 * 256 + 256 * 16
    assert 2 * router == pytest.approx(1.32e6, rel=2e-3)
    experts = 3 * 2048 * 2048           # one expert, for every token
    assert 2 * experts == pytest.approx(25.17e6, rel=1e-3)
    head = 2048 * 32784
    forward = 2 * (6 * (cca + router + experts) + head)
    assert forward == pytest.approx(460.8e6, rel=2e-3)
    assert FAMILY.flops_per_token(sizes, 8192) == pytest.approx(3 * forward)
    family = families.build(sizes)
    assert family.flops_per_token == pytest.approx(1.382e9, rel=1e-3)
    assert 2 * head / forward == pytest.approx(0.29, abs=0.005)
    # what is held does not enter the count: every token has one expert
    assert FAMILY.flops_per_token(
        dict(sizes, num_experts=16), 8192) == family.flops_per_token
    assert family.tolerances == {
        "logits_rel_rms": FAMILY.LOGITS_REL_RMS_TOL,
        "logits_rel_max": 8 * FAMILY.LOGITS_REL_RMS_TOL, "loss_abs": 0.02}


def test_kernels_work_by_hand():
    sizes = _sizes("zaya1-8b")
    family = families.build(sizes)
    forward, forward_bytes = families.kernel_work(family, sizes, "flash_fwd")
    # six layers, two rows of 8192, 8 heads of 128, (8192 + 1) / 2 keys
    assert forward == 6 * 2 * 8192 * 8 * (2 * 2 * 128 * 4096.5)
    q, kv = 2 * 8192 * 8 * 128 * 2, 2 * 8192 * 2 * 128 * 2
    assert forward_bytes == 6 * (2 * q + 2 * kv + 2 * 8 * 8192 * 4)
    backward, _ = families.kernel_work(family, sizes, "flash_bwd")
    assert backward == 2.5 * forward
    flops, hbm = families.kernel_work(family, sizes, "moe_experts")
    # all 16,384 tokens a layer (the window's regime); gate, up and
    # down; forward and twice that backward
    assert flops == 6 * 16384 * (3 * 2 * 2048 * 2048) * 3
    weights = 8 * 3 * 2048 * 2048 * 2           # bf16
    assert hbm == 6 * (3 * weights + 5 * 16384 * 2048 * 2)
    # compute-bound at the chip's peaks (197 TFLOP/s, 819 GB/s)
    assert flops / 197e12 > 5 * hbm / 819e9


@pytest.mark.parametrize("key,value,match", [
    ("num_experts_per_tok", 2, "num_experts_per_tok"),
    ("sliding_window", 4096, "sliding_window"),
    ("tie_word_embeddings", False, "tie_word_embeddings"),
    ("hidden_act", "gelu", "hidden_act"),
    ("num_hidden_layers", 5, "layer_types"),    # 3 layer_types, 5 layers
    ("layer_types", ["hybrid", "hybrid_sliding", "hybrid"], "hybrid"),
    ("num_experts", 9, "not among 8"),          # 9 held of 8 published
])
def test_what_the_program_lacks_is_refused(key, value, match):
    with pytest.raises(ValueError, match=match):
        families.build(dict(_sizes("toy-zaya"), **{key: value}))


def test_toy_cell_runs():
    proc, result = _run(CELL)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0 and result["metrics"] == {}
    assert set(_not_printed(proc)) == {"train_tokens_per_s", "setup_s"}
    for name, pair in result["compared"].items():
        assert 0 <= pair["value"] <= pair["limit"], name
    assert "[zaya] near ties followed:" in proc.stderr


def test_toy_cell_reads_its_layers():
    """The cell's list is the manifest's 14 and the family's four; on
    the CPU those that need no device plane find their numbers, the
    two that read the program's counters among them."""
    proc, result = _run(CELL, trace=1, seconds=5)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] and result["metrics"] == {}
    read, listed = _not_printed(proc), _workload(CELL)["per_layer"]
    assert listed == lookup.data("workloads", "zaya1-8b.steady")["per_layer"]
    assert listed[:14] == lookup.data(
        "workloads", "granite-4.0-h-micro.steady")["per_layer"]
    assert set(read) <= set(listed)
    assert 0 < read["step_p95_ms.program"]["value"] < 5000
    # experts 0-3 of 8 held: half the tokens at first, and more as the
    # toy trains (only a held expert's weight p[e*] gets a gradient)
    assert 25 < read["moe_held_token_pct"]["value"] <= 100
    assert 1 <= read["moe_expert_imbalance"]["value"] < 4


def test_counter_ratio_reads_the_windows_increments():
    """The ratio is of what the window's ``step.counts`` events carry,
    whatever the cumulative counters hold of the warm-up; the parent's
    program leaves no such event, so the metric is left out of the line
    and nothing is raised."""
    reader = lookup.module("reader", "counter_ratio")
    params = {"kind": "step.counts", "over": "moe.tokens_held",
              "under": "moe.tokens_routed", "scale": 100.0}
    assert reader.read({}, params) is None
    assert reader.read({"counters": {"train.steps": 9.0},
                        "events": [{"kind": "step.end", "dur": 0.1}]},
                       params) is None
    counts = {"kind": "step.counts", "moe.tokens_held": 30.0}
    assert reader.read(
        {"events": [dict(counts, **{"moe.tokens_routed": 0.0})]},
        params) is None
    assert reader.read({
        # the warm-up's share, which the window's events do not carry
        "counters": {"moe.tokens_held": 500.0, "moe.tokens_routed": 1000.0},
        "events": [dict(counts, **{"moe.tokens_routed": 120.0}),
                   {"kind": "step.end", "dur": 0.1},
                   dict(counts, **{"moe.tokens_routed": 120.0})],
    }, params) == 25.0
