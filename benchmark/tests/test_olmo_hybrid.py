"""The ``olmo_hybrid`` family: its reference pinned on seeded toy
weights, its FLOP count and its kernels' work counted by hand from the
published sizes, what the program lacks refused, the 8-bit control, and
its toy cell through ``run.py`` on four virtual CPU devices (fsdp=4).
The reference against the program's model (logits, loss, gradients,
planted faults, the sharded step) is tier-1's
``tests/test_olmo_hybrid.py``."""

import hashlib
import os
import subprocess
import sys

import families
import jax
import jax.numpy as jnp
import lookup
import numpy as np
import pytest
from test_rehearsal import BENCH, ROOT, _not_printed, _run, _workload

FAMILY = lookup.module("family", "olmo_hybrid")
CELL = "toy-olmo-hybrid.steady"


def _sizes(name):
    return lookup.data("configs", name)


CORNER, DIGEST = "0x1.5725bc0000000p-9", "545ca0f6fc07b79a"


def test_reference_is_pinned_on_toy_weights():
    """``family.reference_logits`` on seeded weights gave these logits
    when the family was written (PR 37): the first 16 hex digits of the
    SHA-256 of the float32 array, and its last row's first entry. The
    position-by-position rule and the blocked attention are the
    yardstick of ``correct``: a change to either shows here."""
    sizes = _sizes("toy-olmo-hybrid")
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


def test_reference_rule_is_the_rule_written_the_other_way():
    """The reference steps ``S_t = exp(g_t) (I - beta_t k_t k_t^T)
    S_{t-1} + beta_t k_t v_t^T``; the other way to write it, ``S_t =
    exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T k_t)^T``, in
    numpy float64, one head."""
    rs = np.random.RandomState(3)
    seq, dk, dv = 24, 8, 16
    q, v = rs.randn(seq, 1, dk), rs.randn(seq, 1, dv)
    k = rs.randn(seq, 1, dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g, beta = -rs.uniform(0.01, 2.0, (seq, 1)), rs.uniform(0, 2, (seq, 1))
    state, want = np.zeros((dk, dv)), []
    for t in range(seq):
        decayed = np.exp(g[t, 0]) * state
        state = decayed + beta[t, 0] * np.outer(
            k[t, 0], v[t, 0] - decayed.T @ k[t, 0])
        want.append(state.T @ q[t, 0])
    got = FAMILY._rule(*(jnp.asarray(x, jnp.float32)
                         for x in (q, k, v, g, beta)))
    np.testing.assert_allclose(got[:, 0], np.stack(want), atol=1e-5)


def test_flops_by_hand():
    sizes = _sizes("olmo-hybrid-7b")
    assert sizes["layer_types"] == (
        ["linear_attention"] * 3 + ["full_attention"]) * 2
    assert sizes["sequence"] == 8192 and sizes["vocab_size"] == 100352
    # multiply-adds a token crosses
    mlp = 3 * 3840 * 11008
    in_proj = 3840 * (2 * 2880 + 2 * 5760 + 2 * 30)     # q k v gate a b
    out_proj = 5760 * 3840
    rule = 3 * 30 * 96 * 192        # read, decay-and-correct, update
    linear = in_proj + out_proj + rule + mlp
    assert 2 * linear == 434_350_080              # 0.434 GFLOP forward
    projections = 4 * 3840 * 3840
    causal = 2 * 30 * 128 * (8192 + 1) / 2
    full = projections + causal + mlp
    assert 2 * full == pytest.approx(434.5e6, rel=1e-3)
    head = 3840 * 100352
    forward = 2 * (6 * linear + 2 * full + head)
    assert forward == pytest.approx(4.246e9, rel=1e-3)
    assert FAMILY.flops_per_token(sizes, 8192) == pytest.approx(3 * forward)
    family = families.build(sizes)
    assert family.flops_per_token == pytest.approx(12.74e9, rel=1e-3)
    assert family.tolerances == {
        "logits_rel_rms": FAMILY.LOGITS_REL_RMS_TOL,
        "logits_rel_max": 8 * FAMILY.LOGITS_REL_RMS_TOL, "loss_abs": 0.02}


def test_kernels_work_by_hand():
    sizes = _sizes("olmo-hybrid-7b")
    family = families.build(sizes)
    forward, forward_bytes = families.kernel_work(family, sizes, "flash_fwd")
    # two layers, four rows of 8192, 30 heads of 128, (8192 + 1) / 2 keys
    assert forward == 2 * 4 * 8192 * 30 * (2 * 2 * 128 * 4096.5)
    q = 4 * 8192 * 30 * 128 * 2                     # = k = v = o, bf16
    assert forward_bytes == 2 * (4 * q + 4 * 30 * 8192 * 4)
    backward, backward_bytes = families.kernel_work(family, sizes, "flash_bwd")
    assert backward == 2.5 * forward
    assert backward_bytes == 2 * (8 * q + 4 * 30 * 8192 * 4)
    flops, hbm = families.kernel_work(family, sizes, "causal_conv")
    # six layers, 32,768 positions, 11,520 convolved channels in bf16:
    # two forward passes (a layer is recomputed from its input) read
    # and write them, the backward pass reads twice and writes once
    cells = 6 * 32768 * 11520
    assert hbm == (2 * 2 + 3) * cells * 2 == 31_708_938_240
    assert flops == 2 * 4 * cells * (2 + 2)
    # bound by the bytes at the chip's peaks (197 TFLOP/s, 819 GB/s)
    assert hbm / 819e9 > 50 * flops / 197e12
    kept = dict(sizes, program=dict(sizes["program"], remat=False))
    assert FAMILY.WORK["causal_conv"](kept)[1] == (2 + 3) * cells * 2


@pytest.mark.parametrize("key,value,match", [
    ("model_type", "olmo3", "model_type"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("attention_bias", True, "attention_bias"),
    ("rope_parameters", {"rope_theta": 500000}, "rope_parameters"),
    ("linear_num_value_heads", 8, "linear_num_value_heads"),
    ("num_hidden_layers", 5, "layer_types"),    # 4 layer_types, 5 layers
    ("layer_types", ["linear_attention", "sliding_attention",
                     "full_attention", "linear_attention"], "sliding"),
])
def test_what_the_program_lacks_is_refused(key, value, match):
    with pytest.raises(ValueError, match=match):
        families.build(dict(_sizes("toy-olmo-hybrid"), **{key: value}))


def _probe(*seeds):
    """``precision_probe_mesh.py`` on the toy configuration's own mesh,
    four virtual CPU devices, in a process of its own (this one has the
    devices it has)."""
    import json

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests",
                                      "precision_probe_mesh.py"),
         "toy-olmo-hybrid", *map(str, seeds)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return [json.loads(line) for line in proc.stdout.strip().splitlines()]


def test_lower_precision_fails_the_agreement():
    """The control at toy size, sharded fsdp=4, on three seeds: the
    configuration's bf16 passes and the program's own 8-bit matmuls do
    not (read 0.083-0.084 and 0.19-0.21 against a limit of 0.135)."""
    readings = _probe(0, 1, 2147483659)
    assert [r["compute"] for r in readings] == ["bfloat16", "int8"] * 3
    assert {r["devices"] for r in readings} == {4}
    for stated, int8 in zip(readings[::2], readings[1::2]):
        assert stated["ok"], stated
        assert not int8["ok"], int8
        assert int8["logits_rel_rms"] > stated["limits"]["logits_rel_rms"]


def test_toy_cell_runs_on_four_devices():
    proc, result = _run(CELL, devices=4)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0 and result["metrics"] == {}
    assert result["device"]["count"] == 4
    assert set(_not_printed(proc)) == {"train_tokens_per_s", "setup_s"}
    for name, pair in result["compared"].items():
        assert 0 <= pair["value"] <= pair["limit"], name


def test_toy_cell_reads_its_layers():
    """The cell's list is the manifest's 14 and the family's three; on
    the CPU those that need no device plane find their numbers, and
    those that read one return nothing and do not raise."""
    proc, result = _run(CELL, trace=1, devices=4, seconds=5)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] and result["metrics"] == {}
    read, listed = _not_printed(proc), _workload(CELL)["per_layer"]
    assert listed == lookup.data(
        "workloads", "olmo-hybrid-7b.steady")["per_layer"]
    assert listed[:14] == lookup.data(
        "workloads", "granite-4.0-h-micro.steady")["per_layer"]
    assert listed[14:] == ["collective_ms", "causal_conv_ms",
                           "causal_conv_roofline"]
    assert set(read) <= set(listed)
    assert 0 < read["step_p95_ms.program"]["value"] < 5000


def test_new_metrics_read_a_recorded_trace():
    """The three metric files through their readers on a record made by
    hand: the collectives' and the kernels' events by name, the
    roofline from the family's count over all chips."""
    import readers

    sizes = _sizes("olmo-hybrid-7b")
    record = {
        "trace": {"ops_s": {
            "all-gather.12": 0.020, "collective-permute-done.3": 0.010,
            "all-reduce-scatter.1 [x]": 0.005, "fusion.7": 1.0,
            "causal_conv_fwd.4 [tpu_custom_call]": 0.080,
            "causal_conv_bwd.2 [tpu_custom_call]": 0.060}},
        "traced_steps": 10, "chips": 4, "sizes": sizes,
        "family": families.build(sizes),
        "peak_flops": 197e12, "peak_hbm_bytes_per_s": 819e9,
    }
    metric = {name: lookup.data("layer_metrics", name) for name in
              ("collective_ms", "causal_conv_ms", "causal_conv_roofline")}
    assert readers.read(metric["collective_ms"], record) \
        == pytest.approx(3.5)
    assert readers.read(metric["causal_conv_ms"], record) \
        == pytest.approx(14.0)
    # 31.7 GB over 819 GB/s over four chips = 9.68 ms of 14
    assert readers.read(metric["causal_conv_roofline"], record) \
        == pytest.approx(100 * 31_708_938_240 / 819e9 / 4 / 0.014)
    # a program without the kernels or the collectives: nothing, no raise
    bare = dict(record, trace={"ops_s": {"fusion.7": 1.0}})
    assert all(readers.read(m, bare) is None for m in metric.values())
