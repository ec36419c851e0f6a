"""The ``granite_hybrid`` family: its reference pinned on seeded toy
weights, its FLOP count and its kernels' work counted by hand from the
published sizes, and its toy cell through ``run.py`` on the CPU. The
reference against the program's model (logits, loss, gradients, planted
faults) is tier-1's ``tests/test_granite_hybrid.py``."""

import hashlib

import families
import jax
import jax.numpy as jnp
import lookup
import numpy as np
import pytest
from test_rehearsal import _not_printed, _run, _workload

FAMILY = lookup.module("family", "granite_hybrid")
CELL = "toy-granite.steady"


def _sizes(name):
    return lookup.data("configs", name)


CORNER, DIGEST = "-0x1.1895240000000p-11", "b9a031c861605a5e"


def test_reference_is_pinned_on_toy_weights():
    """``family.reference_logits`` on seeded weights gave these logits
    when the family was written (PR 29): the first 16 hex digits of the
    SHA-256 of the float32 array, and its last row's first entry. The
    position-by-position recurrence and the blocked attention are the
    yardstick of ``correct``: a change to either shows here."""
    sizes = _sizes("toy-granite")
    family = families.build(sizes)
    shapes = jax.eval_shape(family.init, jax.random.key(0))
    leaves, treedef = jax.tree.flatten(shapes)
    rs = np.random.RandomState(11)
    params = jax.tree.unflatten(treedef, [
        jnp.asarray(rs.standard_normal(leaf.shape) * 0.05, jnp.float32)
        for leaf in leaves
    ])
    tokens = jnp.asarray(
        np.random.RandomState(5).randint(0, sizes["vocab_size"], (129,)),
        jnp.int32)
    logits = np.asarray(jax.jit(family.reference_logits)(params, tokens[:-1]))
    assert logits.dtype == np.float32 and logits.shape == (128, 256)
    assert float(logits[-1, 0]).hex() == CORNER
    assert hashlib.sha256(logits.tobytes()).hexdigest()[:16] == DIGEST


def test_blocked_attention_is_plain_attention():
    """Blocks of query rows give what all rows at once give
    (``reference.attention`` scaled by 1/sqrt(head) = 0.25 here)."""
    import reference

    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randn(512, 4, 16), jnp.float32)
    k = jnp.asarray(rs.randn(512, 2, 16), jnp.float32)
    v = jnp.asarray(rs.randn(512, 2, 16), jnp.float32)
    assert FAMILY.QUERY_BLOCK < 512
    np.testing.assert_allclose(
        FAMILY._attention(q, k, v, 0.25), reference.attention(q, k, v),
        atol=2e-6)


def test_flops_by_hand():
    sizes = _sizes("granite-4.0-h-micro")
    assert sizes["layer_types"].count("mamba") == 9
    assert sizes["layer_types"].count("attention") == 1
    assert sizes["sequence"] == 8192 and sizes["vocab_size"] == 12544
    # multiply-adds a token crosses
    mlp = 2048 * 16384 + 8192 * 2048
    in_proj = 2048 * (4096 + 4352 + 64)
    out_proj = 4096 * 2048
    scan = 2 * 64 * 64 * 128        # the state's update and its read-out
    mamba = in_proj + out_proj + scan + mlp
    assert 2 * mamba == 154_402_816               # 0.154 GFLOP forward
    projections = 2 * 2048 * 2048 + 2 * 2048 * 512      # q, o; k, v
    causal = 2 * 32 * 64 * (8192 + 1) / 2
    attention = projections + causal + mlp
    assert 2 * attention == pytest.approx(155.2e6, rel=1e-3)
    head = 2048 * 12544
    forward = 2 * (9 * mamba + attention + head)
    assert forward == pytest.approx(1.596e9, rel=1e-3)
    assert FAMILY.flops_per_token(sizes, 8192) == pytest.approx(3 * forward)
    family = families.build(sizes)
    assert family.flops_per_token == pytest.approx(4.79e9, rel=1e-3)
    assert family.tolerances == {
        "logits_rel_rms": 0.054, "logits_rel_max": 0.432, "loss_abs": 0.02}


def test_attention_kernel_work_counts_the_attention_layers_alone():
    sizes = _sizes("granite-4.0-h-micro")
    family = families.build(sizes)
    forward, forward_bytes = families.kernel_work(family, sizes, "flash_fwd")
    # one layer, one row of 8192, 32 heads of 64, (8192 + 1) / 2 keys
    assert forward == 8192 * 32 * (2 * 2 * 64 * 4096.5)
    q, kv = 8192 * 32 * 64 * 2, 8192 * 8 * 64 * 2
    assert forward_bytes == 2 * q + 2 * kv + 32 * 8192 * 4
    backward, backward_bytes = families.kernel_work(family, sizes, "flash_bwd")
    assert backward == 2.5 * forward
    assert backward_bytes == 4 * q + 4 * kv + 32 * 8192 * 4


@pytest.mark.parametrize("key,value", [
    ("num_local_experts", 8), ("position_embedding_type", "rope"),
    ("tie_word_embeddings", False), ("mamba_expand", 4),
    ("num_hidden_layers", 5),       # 4 layer_types for 5 layers
])
def test_what_the_program_lacks_is_refused(key, value):
    with pytest.raises(ValueError, match=key.split("_")[-1]):
        families.build(dict(_sizes("toy-granite"), **{key: value}))


def test_toy_cell_runs():
    proc, result = _run(CELL)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0 and result["metrics"] == {}
    assert set(_not_printed(proc)) == {"train_tokens_per_s", "setup_s"}
    for name, pair in result["compared"].items():
        assert 0 <= pair["value"] <= pair["limit"], name


def test_toy_cell_reads_its_layers():
    """The manifest's 14 per-layer metrics are the cell's list; on the
    CPU those that need no device plane find their numbers."""
    proc, result = _run(CELL, trace=1, seconds=5)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] and result["metrics"] == {}
    read, listed = _not_printed(proc), _workload(CELL)["per_layer"]
    assert listed == lookup.data(
        "workloads", "granite-4.0-h-micro.steady")["per_layer"]
    assert set(read) <= set(listed)
    assert 0 < read["step_p95_ms.program"]["value"] < 5000
