"""The Olmo-Hybrid model (models/olmo_hybrid.py: gated-delta-rule
linear-attention layers beside full-attention layers) on the CPU at
tiny sizes.

The model is held to the benchmark's plain float32 reference
(``benchmark/family/olmo_hybrid.py``, which shares no code with the
program), in float32 and in the bf16 configuration, on logits, loss and
every parameter's gradient; each tolerance is written with the readings
it was set from, and the nearest precision below the configuration's
(the program's own int8 matmuls) fails it, as planted faults do. The
step under ``fsdp=4`` is held to the one-device step, and the stacked
state to a flash-checkpoint round trip. The rule itself is
``tests/test_gated_delta.py``'s.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import olmo_hybrid as oh
from dlrover_tpu.ops.fp8 import quant_autocast

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own modules (lookup, reference, families), as
    its harness imports them."""
    sys.path.insert(0, BENCH)
    try:
        import families
        import lookup
        import reference

        yield {"families": families, "lookup": lookup, "reference": reference}
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def family(bench):
    """The benchmark's toy configuration of the family (hidden 64, four
    layers: linear, linear, full, linear; 128 tokens a row), with the
    plain attention in place of the interpreted kernel."""
    sizes = bench["lookup"].data("configs", "toy-olmo-hybrid")
    sizes["program"] = dict(sizes["program"], attn_impl="reference")
    return bench["families"].build(sizes)


def _lively(params, seed):
    """Seeded weights at which every part of a layer counts: matrices
    at 1/sqrt(fan-in) (the initial 0.02 is a sixth of that at width 64),
    the embedding at 1 (the norms sit on the sub-blocks' outputs, so a
    first layer fed 0.02 puts out 0.01, its norm multiplies by a
    hundred, and so does its backward pass: gradients that explode
    towards the input compare nothing) and the norms' scales moved off
    their initial 1."""
    rs = np.random.RandomState(seed)

    def leaf(path, x):
        name = path[-1].key
        if name == "embed":
            return jnp.asarray(rs.randn(*x.shape), jnp.float32)
        if name == "lm_head" or (x.ndim == 3 and name != "conv_w"):
            return jnp.asarray(
                rs.randn(*x.shape) * x.shape[-2] ** -0.5, jnp.float32)
        if "norm" in name:
            return jnp.asarray(1 + 0.2 * rs.randn(*x.shape), jnp.float32)
        if name == "A_log":
            # decays of 0.98 to 0.9999 a step: with the initial A in
            # (0, 16) and projections this lively a state is forgotten
            # within a position or two and the rule is not exercised
            return jnp.asarray(
                np.log(rs.uniform(0.02, 0.2, x.shape)), jnp.float32)
        return x

    return jax.tree_util.tree_map_with_path(leaf, params)


def _tokens(rows=2, seed=1):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, 256, (rows, 129)), jnp.int32)


@pytest.fixture(scope="module")
def toy(bench, family):
    """The toy family, seeded lively weights, a batch, and the
    reference's loss, logits and gradient on them."""
    params = _lively(family.init(jax.random.key(0)), 0)
    tokens = _tokens()
    next_token_loss = bench["reference"].next_token_loss

    def ref_loss(params):
        rows = [family.reference_logits(params, row[:-1]) for row in tokens]
        loss = jnp.mean(jnp.stack([
            next_token_loss(logits, row) for logits, row in zip(rows, tokens)
        ]))
        return loss, jnp.stack(rows)

    (loss, logits), grads = jax.jit(
        jax.value_and_grad(ref_loss, has_aux=True))(params)
    return {"family": family, "params": params, "tokens": tokens,
            "loss": loss, "logits": logits, "grads": grads}


def _system(toy, dtype, mode=None, **config):
    """The program's loss, logits and gradients in ``dtype`` (params
    cast as auto_accelerate casts them), optionally with its own 8-bit
    matmuls."""
    cfg = dataclasses.replace(toy["family"].model_config, dtype=dtype,
                              **config)
    loss_fn = oh.olmo_hybrid_loss_fn(cfg)
    tokens = toy["tokens"]

    def loss(params):
        cast = jax.tree.map(lambda x: x.astype(dtype), params)
        logits = oh.olmo_hybrid_apply(cfg, cast, tokens[:, :-1])
        return loss_fn(cast, {"tokens": tokens}, None), logits

    def run():
        return jax.jit(jax.value_and_grad(loss, has_aux=True))(toy["params"])

    if mode is None:
        return run()
    with quant_autocast(mode):
        return run()


def _rel_rms(got, want):
    """Distance of two trees (or arrays) as a share of ``want``'s rms."""
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    diff = sum(float(jnp.sum((g.astype(jnp.float32) - w) ** 2))
               for g, w in zip(got, want))
    return (diff / sum(float(jnp.sum(w ** 2)) for w in want)) ** 0.5


# float32 against float32: two implementations of one function, the
# rule chunked on one side and position by position on the other. Read
# over three seeds of weights: logits 2.2e-6 to 2.3e-6 of their rms,
# loss 0 to 5e-7, a leaf's gradient 1.1e-5 to 3.1e-5.
F32_LOGITS, F32_LOSS, F32_GRAD_LEAF = 2e-5, 2e-5, 2e-4
# the bf16 configuration, over the same seeds: logits 0.0445 to 0.0465,
# whole gradient 0.123 to 0.147; the program's int8 matmuls read 0.105
# to 0.116 and 0.258 to 0.328. The limits are the geometric means of
# the nearest two. The norms sit on the sub-blocks' outputs, so a
# perturbation of x comes back from a sub-block as about twice itself
# beside an x of rms 1 to 3, and where the rule's output is small its
# norm multiplies by 1 / rms in both directions (``_lively`` keeps the
# decays slow, so the state is not forgotten and few outputs are): the
# distances are several times those of ``tests/test_granite_hybrid.py``
# at the same width, in every precision alike.
BF16_LOGITS, BF16_GRAD = 0.070, 0.195


@pytest.mark.parametrize("attn_impl", ["reference", "flash"])
def test_model_is_the_reference_in_float32(toy, attn_impl):
    (loss, logits), grads = _system(toy, "float32", attn_impl=attn_impl)
    assert _rel_rms(logits, toy["logits"]) < F32_LOGITS
    assert abs(float(loss - toy["loss"])) < F32_LOSS
    worst = jax.tree.map(_rel_rms, grads, toy["grads"])
    assert max(jax.tree.leaves(worst)) < F32_GRAD_LEAF, worst


def test_model_is_near_the_reference_in_bf16_and_int8_is_not(toy):
    (loss, logits), grads = _system(toy, "bfloat16")
    assert _rel_rms(logits, toy["logits"]) < BF16_LOGITS
    assert abs(float(loss - toy["loss"])) < 5e-3      # read 0.0006-0.0024
    assert _rel_rms(grads, toy["grads"]) < BF16_GRAD
    (_, logits8), grads8 = _system(toy, "bfloat16", mode="int8")
    assert _rel_rms(grads8, toy["grads"]) > BF16_GRAD
    assert _rel_rms(logits8, toy["logits"]) > BF16_LOGITS


def _gate_before_norm(out, gate, scale, eps):
    """The fault: RMSNorm(o * silu(gate)), Mamba-2's order."""
    gated = out.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    return oh._rms_norm(gated, scale.astype(jnp.float32), eps).astype(
        out.dtype)


def _rule_without_decay(q, k, v, g, beta, chunk):
    """The fault: the plain delta rule, ``exp(g_t) = 1``."""
    from dlrover_tpu.ops.gated_delta import gated_delta_rule

    return gated_delta_rule(q, k, v, jnp.zeros_like(g), beta, chunk)


@pytest.mark.parametrize("name,fault", [
    ("_gate_norm", _gate_before_norm),
    ("gated_delta_rule", _rule_without_decay),
])
def test_planted_fault_fails_the_comparison(toy, monkeypatch, name, fault):
    monkeypatch.setattr(oh, name, fault)
    (loss, logits), grads = _system(toy, "float32")
    # not by a hair: the bf16 limits fail too
    assert _rel_rms(logits, toy["logits"]) > 2 * BF16_LOGITS
    assert _rel_rms(grads, toy["grads"]) > 2 * BF16_GRAD


def _gauges(trace):
    """The gauges a trace of ``trace()`` leaves, {(name, labels): value}."""
    from dlrover_tpu.common import telemetry

    telemetry.enable("test")
    try:
        trace()
        return {(g["name"], tuple(sorted(g["labels"].items()))): g["value"]
                for g in telemetry.snapshot()["gauges"]}
    finally:
        telemetry.install_from_env()


def test_toy_model_runs_the_kernels_and_the_chunked_rule(toy):
    """128 positions, 128 convolved channels from channel 0 on, chunks
    of 32: the model tests above ran the convolution's kernel pair
    (interpret mode) over the projection's output where it lies and the
    chunked rule, and the gauges say so. A backward pass says what a
    layer keeps beside its input: a full-attention layer the kernel's
    output and row statistic, a linear layer nothing."""
    cfg = dataclasses.replace(toy["family"].model_config, attn_impl="flash")
    batch = {"tokens": toy["tokens"]}
    lowered = {}

    def trace():
        lowered["text"] = jax.jit(jax.grad(oh.olmo_hybrid_loss_fn(cfg))).lower(
            toy["params"], batch, None).as_text(debug_info=True)

    gauges = _gauges(trace)
    assert gauges["model.conv.impl", (("impl", "kernel"),)] == 1
    assert gauges["model.gdn.impl", (("impl", "chunked"),)] == 1
    assert ("model.conv.impl", (("impl", "plain"),)) not in gauges
    assert ("model.gdn.impl", (("impl", "plain"),)) not in gauges
    rows, seq = toy["tokens"].shape[0], toy["tokens"].shape[1] - 1
    kept = {k[1]: v for k, v in gauges.items() if k[0] == "model.remat.kept"}
    assert kept == {
        (("kind", "full_attention"), ("name", "attn_out")):
        rows * seq * cfg.dim * 2 + rows * cfg.n_heads * seq * 4}
    for scope in ("gdn_proj", "gdn_conv", "gdn_qk_norm", "gdn_rule",
                  "gdn_gate_norm", "gdn_out_proj", "attn", "mlp", "head",
                  "causal_conv_fwd", "causal_conv_bwd"):
        assert scope in lowered["text"], scope


def test_build_publishes_its_shape():
    gauges = _gauges(lambda: oh.olmo_hybrid_loss_fn(oh.OlmoHybridConfig()))
    by_kind = {(name, dict(labels).get("kind")): value
               for (name, labels), value in gauges.items()}
    assert by_kind["model.layers", "linear_attention"] == 24
    assert by_kind["model.layers", "full_attention"] == 8
    assert by_kind["model.params", "gdn_mixer"] == 24 * 88_750_332
    assert by_kind["model.params", "attention"] == 8 * 58_990_080
    assert by_kind["model.params", "mlp"] == 32 * 126_819_840 + 3840
    assert by_kind["model.params", "embedding"] == 100352 * 3840
    assert by_kind["model.params", "head"] == 100352 * 3840
    assert by_kind["model.gdn.chunk", None] == 64


# ---------------------------------------------------------- configuration


@pytest.mark.parametrize("which,parameters", [
    ("published", 7_430_870_688), ("cut", 2_435_748_072),
])
def test_configuration_builds_with_its_parameter_count(bench, which,
                                                       parameters):
    """The benchmark's configuration file, as cut for one four-chip
    host and with its ``published`` values put back, under
    ``jax.eval_shape``: nothing is allocated. By hand: a linear layer
    215,570,172, a full one 185,809,920, a period of three and one
    832,520,436; embedding and head 770,703,360; the final norm 3,840."""
    with open(os.path.join(BENCH, "configs", "olmo-hybrid-7b.json")) as f:
        sizes = json.load(f)
    if which == "published":
        sizes.update(sizes["published"])
        assert len(sizes["layer_types"]) == 32
    periods = len(sizes["layer_types"]) // 4
    assert parameters == periods * 832_520_436 + 770_703_360 + 3_840
    family = bench["families"].build(sizes)
    config = family.model_config
    shapes = jax.eval_shape(family.init, jax.random.key(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(shapes)) == parameters
    assert config.param_count() == parameters
    assert [count for _, _, count in config.runs()] == [3, 1] * periods
    axes = family.logical_axes
    assert jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple)
    ) == jax.tree.structure(shapes)
    for leaf, names in zip(
        jax.tree.leaves(shapes),
        jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple)),
    ):
        assert len(names) == leaf.ndim


def test_unknown_layer_kind_is_refused():
    with pytest.raises(ValueError, match="mamba"):
        oh.OlmoHybridConfig(layer_types=("linear_attention", "mamba"))


def test_pipeline_stages_of_unlike_layers_are_refused(family):
    from dlrover_tpu.parallel import mesh as mesh_lib

    config = family.model_config
    params = jax.eval_shape(family.init, jax.random.key(0))
    before = mesh_lib._global_mesh
    mesh_lib.set_mesh(mesh_lib.build_mesh(
        mesh_lib.MeshConfig(pipe=2, data=4)))
    try:
        with pytest.raises(NotImplementedError, match="unlike layers"):
            jax.eval_shape(
                lambda p: oh.olmo_hybrid_apply(
                    config, p, jnp.zeros((4, 128), jnp.int32)), params)
    finally:
        mesh_lib._global_mesh = before


# ------------------------------------------------- sharding and checkpoint


def _steps(config, devices, mesh, overlap="off", steps=4):
    """``steps`` steps of plain SGD through ``auto_accelerate`` on
    ``devices``: (losses, the gradient's norm a step), the norm read off
    what a step did to the parameters."""
    from dlrover_tpu.parallel import MeshConfig, Strategy, auto_accelerate
    from dlrover_tpu.parallel import mesh as mesh_lib

    rate = 0.01
    before = mesh_lib._global_mesh
    try:
        accel = auto_accelerate(
            oh.olmo_hybrid_loss_fn(config),
            lambda rng: _lively(oh.olmo_hybrid_init(config, rng), 3),
            optax.sgd(rate), oh.olmo_hybrid_logical_axes(config),
            strategy=Strategy(mesh=MeshConfig(**mesh), donate=False,
                              overlap_collectives=overlap),
            devices=devices, seed=3,
        )
        state, batch = accel.state, {"tokens": _tokens(rows=4, seed=4)}
        losses, norms = [], []
        for _ in range(steps):
            new, metrics = accel.train_step(state, batch, jax.random.key(0))
            losses.append(float(metrics["loss"]))
            moved = jax.tree.map(
                lambda a, b: np.asarray(a, np.float64) - np.asarray(b),
                new.params, state.params)
            norms.append(float(optax.global_norm(moved)) / rate)
            state = new
        return losses, norms, state
    finally:
        mesh_lib._global_mesh = before


@pytest.mark.parametrize("overlap", ["off", "xla"])
def test_fsdp_over_four_devices_is_the_one_device_step(family, overlap):
    """The cell's mesh on four CPU devices, one row a device, against
    one device holding all four rows: the loss and the gradient's norm
    of four steps (bf16 compute on both sides; what differs is the order
    of the sums the collectives make: the first step's loss reads 8e-8
    apart and its norm 4e-5; the steps after it, taken from parameters
    that already differ, up to 2e-4 and 1.2e-2).
    ``xla`` takes every run of like layers through the fsdp gather hook
    (``layer_axes``), a stack of unlike runs."""
    config = family.model_config
    one = _steps(config, jax.devices()[:1], {"data": 1})
    four = _steps(config, jax.devices()[:4], {"fsdp": 4}, overlap)
    assert one[0][-1] < one[0][0] - 0.02
    np.testing.assert_allclose(four[0], one[0], rtol=1e-3)
    np.testing.assert_allclose(four[1][0], one[1][0], rtol=1e-3)
    np.testing.assert_allclose(four[1], one[1], rtol=3e-2)
    in_proj = four[2].params["layers"]["00_linear_attention"]["in_proj"]
    assert "fsdp" in str(in_proj.sharding.spec)
    assert "fsdp" in str(four[2].params["lm_head"].sharding.spec)


@pytest.fixture
def _isolate(isolated_ckpt_env):
    yield


def test_flash_checkpoint_round_trip_of_the_stacked_state(tmp_path, _isolate):
    """A parameter tree keyed by run of like layers
    (``params["layers"]["00_linear_attention"]`` ...), with AdamW's
    moments beside it: shm save -> load is bit for bit."""
    from dlrover_tpu.trainer.flash_checkpoint.engine import (
        ReplicatedCheckpointEngine,
    )

    config = oh.OlmoHybridConfig(
        vocab_size=64, dim=32, n_heads=2, n_kv_heads=2, mlp_dim=64,
        layer_types=("linear_attention", "linear_attention",
                     "full_attention", "linear_attention"),
        linear_heads=2, linear_key_head_dim=8, linear_value_head_dim=16,
    )
    params = oh.olmo_hybrid_init(config, jax.random.key(5))
    assert sorted(params["layers"]) == [
        "00_linear_attention", "01_full_attention", "02_linear_attention"]
    state = {"step": jnp.asarray(7, jnp.int32), "params": params,
             "opt_state": optax.adamw(1e-3).init(params)}
    engine = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
    try:
        assert engine.save_to_memory(7, state)
        restored, step = engine.load(
            target=jax.tree.map(jnp.zeros_like, state))
    finally:
        engine.close()
    assert step == 7
    assert jax.tree.structure(restored) == jax.tree.structure(state)
    for got, want in zip(jax.tree.leaves(restored), jax.tree.leaves(state)):
        got, want = np.asarray(got), np.asarray(want)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
