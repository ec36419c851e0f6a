"""The hybrid state-space / attention model (models/granite_hybrid.py),
its chunked scan (ops/ssd.py) and the stack of unlike layers
(pipeline.stage_run_scan), on the CPU at tiny sizes.

The scan is held to the recurrence it stands for, position by
position; the model to the benchmark's plain float32 reference
(``benchmark/family/granite_hybrid.py``, which shares no code with the
program), in float32 and in the bf16 configuration, on logits, loss and
every parameter's gradient. Each tolerance is written with the readings
it was set from, and the nearest precision below the configuration's
(the program's own int8 matmuls) fails it, as two planted faults do.
"""

import dataclasses
import json
import logging
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import granite_hybrid as gh
from dlrover_tpu.ops import ssd
from dlrover_tpu.ops.fp8 import quant_autocast
from dlrover_tpu.parallel import accelerate, pipeline

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


# ------------------------------------------------------------------ scan


def _sequential(x, dt, a, b, c, d):
    """H_t = exp(dt_t a) H_{t-1} + dt_t x_t B_t^T; y_t = H_t C_t + d x_t."""
    heads, groups = x.shape[2], b.shape[2]
    b = jnp.repeat(b, heads // groups, axis=2)
    c = jnp.repeat(c, heads // groups, axis=2)

    def step(state, at):
        xt, dtt, bt, ct = at
        state = jnp.exp(dtt * a)[..., None, None] * state \
            + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct) + d[:, None] * xt

    state = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:])
    ys = jax.lax.scan(step, state, tuple(
        v.swapaxes(0, 1) for v in (x, dt, b, c)))[1]
    return ys.swapaxes(0, 1)


def _scan_operands(seq=64, groups=2, seed=0):
    """Operands whose per-step decays exp(dt a) spread over
    [e^-6, 0.999], log-uniformly in the exponent."""
    rs = np.random.RandomState(seed)
    batch, heads, head, state = 2, 4, 8, 16
    a = -jnp.arange(1, heads + 1, dtype=jnp.float32)
    exponent = -np.exp(rs.uniform(np.log(0.001), np.log(6.0),
                                  (batch, seq, heads)))
    return (
        jnp.asarray(rs.randn(batch, seq, heads, head), jnp.float32),
        jnp.asarray(exponent, jnp.float32) / a,
        a,
        jnp.asarray(rs.randn(batch, seq, groups, state), jnp.float32),
        jnp.asarray(rs.randn(batch, seq, groups, state), jnp.float32),
        jnp.asarray(rs.randn(heads), jnp.float32),
    )


@pytest.mark.parametrize("chunk,groups", [(32, 1), (16, 2), (64, 2)])
def test_chunked_scan_is_the_recurrence(chunk, groups):
    """Outputs and the gradient of every operand, at 2, 4 and 1 chunks.
    float32 on the CPU: both sides round differently and nothing else
    (readings: outputs 1e-6 of the largest, gradients 1e-6 to 1e-5)."""
    operands = _scan_operands(groups=groups)
    weight = jnp.asarray(
        np.random.RandomState(9).randn(*operands[0].shape), jnp.float32)

    def chunked(*ops):
        return ssd.ssd_scan(*ops, chunk)

    got, want = chunked(*operands), _sequential(*operands)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * scale

    def grads(fn):
        return jax.grad(
            lambda *ops: jnp.sum(fn(*ops) * weight), argnums=range(6)
        )(*operands)

    for name, g, w in zip(("x", "dt", "a", "b", "c", "d"),
                          grads(chunked), grads(_sequential)):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert float(jnp.max(jnp.abs(g - w))) \
            < 1e-4 * float(jnp.max(jnp.abs(w))), name


def test_scan_in_bf16_stays_near_the_recurrence():
    """bf16 operands, float32 decays and accumulation: the distance is
    bf16's rounding (2^-9 an operand; read 0.004-0.006 relative rms),
    not a loss of the state over chunks."""
    operands = _scan_operands(seq=128)
    want = _sequential(*operands)
    x, dt, a, b, c, d = operands
    got = ssd.ssd_scan(x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
                       c.astype(jnp.bfloat16), d, 32)
    assert got.dtype == jnp.bfloat16
    rel = float(jnp.sqrt(jnp.mean((got.astype(jnp.float32) - want) ** 2))
                / jnp.sqrt(jnp.mean(want ** 2)))
    assert rel < 0.012


def test_scan_refuses_a_ragged_sequence():
    operands = _scan_operands(seq=48)
    with pytest.raises(ValueError, match="48 is not a multiple of the chunk "
                                         "size 32"):
        ssd.ssd_scan(*operands, 32)


def test_causal_convolution_by_hand():
    rs = np.random.RandomState(2)
    x = rs.randn(2, 9, 3).astype(np.float32)
    weight = rs.randn(4, 3).astype(np.float32)
    bias = rs.randn(3).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(9):
        for k in range(4):
            if t - 3 + k >= 0:
                want[:, t] += weight[k] * x[:, t - 3 + k]
    want += bias
    got = ssd.causal_conv1d(jnp.asarray(x), jnp.asarray(weight),
                            jnp.asarray(bias))
    np.testing.assert_allclose(got, want, atol=1e-5)


# ------------------------------------------------- a stack of unlike layers


def test_layer_runs():
    pattern = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert pipeline.layer_runs(pattern) == [
        ("mamba", 5), ("attention", 1), ("mamba", 4)]
    assert pipeline.layer_runs(["a"]) == [("a", 1)]


def test_run_scan_chains_unlike_layers_in_order():
    """Two kinds of toy layer that do not commute: the scanned runs
    equal the plain loop over the declared pattern, and so do the
    gradients, with and without recomputation."""
    pattern = ("scale", "scale", "shift", "scale")
    fns = {
        "scale": lambda h, p: (h * p["w"] + jnp.sin(h), jnp.sum(p["w"])),
        "shift": lambda h, p: (jnp.tanh(h + p["b"]), jnp.zeros(())),
    }
    params = {
        "00_scale": {"w": jnp.asarray([[1.5, 0.5], [0.7, 2.0]])},
        "01_shift": {"b": jnp.asarray([[0.3, -0.2]])},
        "02_scale": {"w": jnp.asarray([[0.9, 1.1]])},
    }
    runs = [("00_scale", "scale"), ("01_shift", "shift"),
            ("02_scale", "scale")]
    h0 = jnp.asarray([0.4, -1.3])

    def plain(params, h):
        aux = 0.0
        flat = [(kind, jax.tree.map(lambda v: v[i], params[name]))
                for name, kind in runs
                for i in range(jax.tree.leaves(params[name])[0].shape[0])]
        assert tuple(kind for kind, _ in flat) == pattern
        for kind, p in flat:
            h, a = fns[kind](h, p)
            aux = aux + a
        return jnp.sum(h) + aux

    want = jax.value_and_grad(plain)(params, h0)
    for remat, policy in ((False, None), (True, None),
                          (True, {"scale": pipeline.LAYER_INPUT})):
        stage = pipeline.stage_run_scan(fns, runs, remat=remat, policy=policy)

        def scanned(params, h):
            h, aux = stage(params, h)
            return jnp.sum(h) + aux

        got = jax.value_and_grad(scanned)(params, h0)
        jax.tree.map(
            lambda g, w: np.testing.assert_allclose(g, w, rtol=1e-6),
            got, want)


def test_layer_input_keeps_nothing_of_a_layer_under_an_outer_checkpoint():
    """The whole-loss checkpoint's policy keeps every weight matmul's
    output of a jax.checkpoint-ed layer (it rules the first forward
    pass right through the inner checkpoint); a LAYER_INPUT layer keeps
    none of its own: no residual is as wide as the layer's inner
    matmul."""
    from jax._src.ad_checkpoint import saved_residuals

    width, inner, tokens, layers = 8, 48, 16, 3

    def layer(h, p):
        return h + jnp.tanh(h @ p["up"]) @ p["down"], jnp.zeros(())

    params = {"up": jnp.ones((layers, width, inner)) * 0.1,
              "down": jnp.ones((layers, inner, width)) * 0.1}
    h0 = jnp.ones((tokens, width))

    def inner_wide(policy):
        stage = pipeline.stage_layer_scan(layer, remat=True, policy=policy)
        loss = jax.checkpoint(
            lambda p, h: jnp.sum(stage(p, h)[0]),
            policy=pipeline.minimal_save_policy(),
        )
        return [aval.shape for aval, _ in saved_residuals(loss, params, h0)
                if aval.shape and aval.shape[-1] == inner
                and tokens in aval.shape]

    assert inner_wide(None)                      # [layers, tokens, inner]
    assert not inner_wide(pipeline.LAYER_INPUT)


@pytest.mark.parametrize("name,of_a_matmul", [
    ("attn_out", False), ("up_out", True), ("up_out", False),
], ids=["a_name_every_level_knows", "a_weight_matmuls_output",
        "a_name_of_the_layers_own"])
def test_layer_input_keeps_what_the_layer_names(name, of_a_matmul):
    """``layer_input(keep=names)``: under the whole-loss checkpoint the
    saved residuals hold the named value stacked over the layers, once,
    and still no other residual as wide as the layer's inner matmuls
    (the default policy keeps both of them); a whole-loss checkpoint
    that keeps nothing keeps none; the value and the gradients are the
    un-checkpointed scan's and the bare LAYER_INPUT's to the last bit;
    the gauge says how many bytes a layer keeps."""
    from jax._src.ad_checkpoint import saved_residuals
    from jax.ad_checkpoint import checkpoint_name

    from dlrover_tpu.common import telemetry

    width, inner, tokens, layers = 8, 48, 16, 3

    def layer(h, p):
        up = h @ p["up"]
        up = checkpoint_name(up if of_a_matmul else jnp.sin(up), name)
        return (h + (jnp.tanh(h @ p["gate"]) * up) @ p["down"],
                jnp.zeros(()))

    rs = np.random.RandomState(0)
    params = {
        "up": jnp.asarray(rs.randn(layers, width, inner) * 0.3),
        "gate": jnp.asarray(rs.randn(layers, width, inner) * 0.3),
        "down": jnp.asarray(rs.randn(layers, inner, width) * 0.1),
    }
    h0 = jnp.asarray(rs.randn(tokens, width))
    keeping = pipeline.layer_input(keep=(name,))

    def loss_of(policy, remat=True):
        stage = pipeline.stage_layer_scan(
            layer, remat=remat, policy=policy, kind="toy")
        return lambda p, h: jnp.sum(stage(p, h)[0] ** 2)

    def inner_wide(policy, outer=None):
        loss = jax.checkpoint(
            loss_of(policy), policy=outer or pipeline.minimal_save_policy())
        return [aval.shape for aval, _ in saved_residuals(loss, params, h0)
                if aval.shape and aval.shape[-1] == inner
                and tokens in aval.shape]

    telemetry.enable("test")
    try:
        assert len(inner_wide(None)) >= 2
        assert inner_wide(pipeline.LAYER_INPUT) == []
        assert inner_wide(keeping) == [(layers, tokens, inner)]
        assert inner_wide(
            keeping, jax.checkpoint_policies.nothing_saveable) == []
        gauges = {tuple(sorted(g["labels"].items())): g["value"]
                  for g in telemetry.snapshot()["gauges"]
                  if g["name"] == "model.remat.kept"}
    finally:
        telemetry.install_from_env()
    assert gauges == {(("kind", "toy"), ("name", name)): tokens * inner * 4}

    want = jax.value_and_grad(loss_of(None, remat=False), (0, 1))(params, h0)
    for outer in (None, "minimal", "full"):
        for policy in (pipeline.LAYER_INPUT, keeping):
            loss = loss_of(policy)
            if outer:
                loss = accelerate._remat_wrap(
                    lambda p, h, _rng, inner_loss=loss: inner_loss(p, h),
                    outer)
                got = jax.value_and_grad(loss, (0, 1))(params, h0, None)
            else:
                got = jax.value_and_grad(loss, (0, 1))(params, h0)
            jax.tree.map(np.testing.assert_array_equal, got, want)


# ----------------------------------------------------------------- model


@pytest.fixture(scope="module")
def family(bench):
    """The benchmark's toy configuration of the family (hidden 64, four
    layers: mamba, mamba, attention, mamba; 2 x 128 tokens), with the
    plain attention in place of the interpreted kernel."""
    sizes = bench["lookup"].data("configs", "toy-granite")
    sizes["program"] = dict(sizes["program"], attn_impl="reference")
    return bench["families"].build(sizes)


def _lively(params, seed):
    """Seeded weights at which every part of a layer counts: matrices
    at 1/sqrt(fan-in) (the initial 0.02 is a tenth of that at width 64
    and leaves the mixers' share of the logits in the rounding), norms'
    scales, the convolution's bias and D moved off their initial 1, 0
    and 1."""
    rs = np.random.RandomState(seed)

    def leaf(path, x):
        name = path[-1].key
        if x.ndim == 3 and name != "conv_w":
            return jnp.asarray(
                rs.randn(*x.shape) * x.shape[1] ** -0.5, jnp.float32)
        if "norm" in name:
            return jnp.asarray(1 + 0.2 * rs.randn(*x.shape), jnp.float32)
        if name in ("conv_b", "D"):
            return x + jnp.asarray(0.3 * rs.randn(*x.shape), jnp.float32)
        return x

    return jax.tree_util.tree_map_with_path(leaf, params)


def _tokens(seed=1):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, 256, (2, 129)), jnp.int32)


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
    loss_fn = gh.granite_hybrid_loss_fn(cfg)
    tokens = toy["tokens"]

    def loss(params):
        cast = jax.tree.map(lambda x: x.astype(dtype), params)
        logits = gh.granite_hybrid_apply(cfg, cast, tokens[:, :-1])
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


# float32 against float32: two implementations of one function. Read:
# logits 4e-7 of their rms, loss 1e-6, a leaf's gradient 3e-6.
F32_LOGITS, F32_LOSS, F32_GRAD_LEAF = 2e-5, 2e-5, 1e-4
# the bf16 configuration: read logits 0.0144, whole gradient 0.0215 to
# 0.0230 over three seeds; the program's int8 matmuls read 0.0486 to
# 0.0491 on the gradient. The limits are the geometric means.
BF16_LOGITS, BF16_GRAD = 0.025, 0.033


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
    assert abs(float(loss - toy["loss"])) < 1e-3
    assert _rel_rms(grads, toy["grads"]) < BF16_GRAD
    (_, logits8), grads8 = _system(toy, "bfloat16", mode="int8")
    assert _rel_rms(grads8, toy["grads"]) > BF16_GRAD
    assert _rel_rms(logits8, toy["logits"]) > BF16_LOGITS


def _shifted_conv(x, weight, bias, first=0):
    """The fault: every tap reads one position too early."""
    return ssd.causal_conv_silu(
        jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1], weight, bias, first)


def _gate_after_norm(y, z, scale, eps):
    """The fault: RMSNorm(y) * silu(z), the Mamba-1 order."""
    normed = gh._rms_norm(y.astype(jnp.float32), scale.astype(jnp.float32),
                          eps)
    return (normed * jax.nn.silu(z.astype(jnp.float32))).astype(y.dtype)


@pytest.mark.parametrize("name,fault", [
    ("causal_conv_silu", _shifted_conv), ("_gated_norm", _gate_after_norm),
])
def test_planted_fault_fails_the_comparison(toy, monkeypatch, name, fault):
    monkeypatch.setattr(gh, name, fault)
    (loss, logits), grads = _system(toy, "float32")
    # not by a hair: the bf16 limits fail too (read 0.2 to 0.6)
    assert _rel_rms(logits, toy["logits"]) > 4 * BF16_LOGITS
    assert _rel_rms(grads, toy["grads"]) > 4 * BF16_GRAD


def test_toy_model_runs_the_convolution_kernels(toy):
    """128 positions and 160 channels from channel 128 on: the model
    tests above ran the kernel pair (interpret mode), not the plain
    form, and the gauge says so."""
    from dlrover_tpu.common import telemetry

    cfg = toy["family"].model_config
    telemetry.enable("test")
    try:
        jaxpr = jax.make_jaxpr(
            lambda p: gh.granite_hybrid_apply(cfg, p, toy["tokens"][:, :-1])
        )(toy["params"])
        impls = {g["labels"]["impl"] for g in telemetry.snapshot()["gauges"]
                 if g["name"] == "model.conv.impl"}
    finally:
        telemetry.install_from_env()
    assert impls == {"kernel"}
    assert "causal_conv_fwd" in str(jaxpr)


# ---------------------------------------------------------- configuration


@pytest.mark.parametrize("which,parameters", [
    ("published", 3_191_396_096), ("cut", 772_160_448),
])
def test_configuration_builds_with_its_parameter_count(bench, which,
                                                       parameters):
    """The benchmark's configuration file, as cut for one chip and with
    its ``published`` values put back, under ``jax.eval_shape``: nothing
    is allocated."""
    with open(os.path.join(BENCH, "configs", "granite-4.0-h-micro.json")) as f:
        sizes = json.load(f)
    if which == "published":
        sizes.update(sizes["published"])
        assert len(sizes["layer_types"]) == 40
    family = bench["families"].build(sizes)
    config = family.model_config
    shapes = jax.eval_shape(family.init, jax.random.key(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(shapes)) == parameters
    assert config.param_count() == parameters
    # runs of like layers, in the declared order
    assert [count for _, _, count in config.runs()] == (
        [5, 1, 9, 1, 9, 1, 9, 1, 4] if which == "published" else [5, 1, 4])
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
    with pytest.raises(ValueError, match="linear"):
        gh.GraniteHybridConfig(layer_types=("mamba", "linear"))


def test_pipeline_stages_of_unlike_layers_are_refused(family):
    """The schedules shard one stacked tree over ``pipe``; a hybrid
    stack is several, and says so instead of failing inside GSPMD."""
    from dlrover_tpu.parallel import mesh as mesh_lib

    config = family.model_config
    params = jax.eval_shape(family.init, jax.random.key(0))
    before = mesh_lib._global_mesh
    mesh_lib.set_mesh(mesh_lib.build_mesh(
        mesh_lib.MeshConfig(pipe=2, data=4)))
    try:
        with pytest.raises(NotImplementedError, match="unlike layers"):
            jax.eval_shape(
                lambda p: gh.granite_hybrid_apply(
                    config, p, jnp.zeros((4, 128), jnp.int32)), params)
    finally:
        mesh_lib._global_mesh = before


def test_a_traced_backward_pass_publishes_what_a_layer_keeps(toy):
    """``model.remat.kept``: a Mamba layer keeps, beside its input, its
    first projection's output [B, S, 2 inner + 2 groups x state +
    heads]; the attention layer is under the default policy and
    publishes nothing."""
    from dlrover_tpu.common import telemetry

    cfg = toy["family"].model_config
    batch, seq = toy["tokens"].shape[0], toy["tokens"].shape[1] - 1
    telemetry.enable("test")
    try:
        jax.eval_shape(jax.grad(gh.granite_hybrid_loss_fn(cfg)),
                       toy["params"], {"tokens": toy["tokens"]}, None)
        gauges = {(g["labels"]["kind"], g["labels"]["name"]): g["value"]
                  for g in telemetry.snapshot()["gauges"]
                  if g["name"] == "model.remat.kept"}
    finally:
        telemetry.install_from_env()
    width = 2 * cfg.mamba_inner + 2 * cfg.mamba_groups * cfg.mamba_state \
        + cfg.mamba_heads
    assert gauges == {("mamba", "mamba_in_proj"):
                      batch * seq * width * jnp.dtype(cfg.dtype).itemsize}


def test_build_publishes_its_shape():
    from dlrover_tpu.common import telemetry

    telemetry.enable("test")
    try:
        config = gh.GraniteHybridConfig()
        gh.granite_hybrid_loss_fn(config)
        gauges = {(g["name"], g["labels"].get("kind")): g["value"]
                  for g in telemetry.snapshot()["gauges"]}
    finally:
        telemetry.install_from_env()
    assert gauges["model.layers", "mamba"] == 9
    assert gauges["model.layers", "attention"] == 1
    assert gauges["model.params", "mamba_mixer"] == 9 * 25_847_232
    assert gauges["model.params", "attention"] == 10_485_760
    assert gauges["model.params", "embedding"] == 100352 * 2048
    assert gauges["model.ssd.chunk", None] == 256


# ------------------------------------------------- trainer and checkpoint


@pytest.fixture
def _isolate(isolated_ckpt_env):
    yield


def _train(config, tmp_path, remat, steps=6, mesh=None):
    from dlrover_tpu.parallel import MeshConfig, Strategy
    from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

    config = dataclasses.replace(config, remat=remat)
    losses = []

    class Tap(logging.Handler):
        def emit(self, record):
            if str(record.msg).startswith("step %d epoch %d loss"):
                losses.append(float(record.args[2]))

    trainer = Trainer(
        gh.granite_hybrid_loss_fn(config),
        lambda rng: gh.granite_hybrid_init(config, rng),
        gh.granite_hybrid_logical_axes(config),
        TrainingArgs(
            output_dir=str(tmp_path / f"out{remat}"), seed=3, max_steps=steps,
            learning_rate=3e-3, log_steps=1, flash_checkpoint=False,
            # the Trainer takes every device: the 8 virtual ones
            strategy=Strategy(mesh=MeshConfig(**(mesh or {"data": 8}))),
        ),
        train_data=[{"tokens": np.random.RandomState(4).randint(
            0, 256, (8, 129)).astype(np.int32)}] * steps,
    )
    log = logging.getLogger("dlrover_tpu.trainer.trainer")
    tap = Tap()
    log.addHandler(tap)
    try:
        trainer.train()
        return losses, trainer.state
    finally:
        log.removeHandler(tap)
        trainer.close()


def test_trainer_steps_with_and_without_recomputation(family, tmp_path,
                                                      _isolate):
    """Through Trainer -> auto_accelerate (bf16 compute, the whole-loss
    checkpoint of Strategy.remat "minimal" round the model's own): the
    loss falls, and recomputing a layer from its input gives the loss
    of keeping everything, to bf16's rounding of a recomputed value."""
    with_remat, _ = _train(family.model_config, tmp_path, remat=True)
    without, _ = _train(family.model_config, tmp_path, remat=False)
    assert with_remat[-1] < with_remat[0] - 0.05
    np.testing.assert_allclose(with_remat, without, rtol=2e-3)


def test_trainer_steps_sharded_over_fsdp(family, tmp_path, _isolate):
    """The new leaves' logical axes shard: data=2 x fsdp=4 trains to
    the losses of plain data parallelism."""
    plain, _ = _train(family.model_config, tmp_path, remat=True, steps=3)
    sharded, state = _train(family.model_config, tmp_path, remat=True,
                            steps=3, mesh={"data": 2, "fsdp": 4})
    np.testing.assert_allclose(sharded, plain, rtol=5e-3)
    in_proj = state.params["layers"]["00_mamba"]["in_proj"]
    assert "fsdp" in str(in_proj.sharding.spec)


def test_flash_checkpoint_round_trip_of_the_hybrid_state(tmp_path, _isolate):
    """A parameter tree keyed by run of like layers, with AdamW's
    moments beside it: shm save -> load is bit for bit."""
    import optax

    from dlrover_tpu.trainer.flash_checkpoint.engine import (
        ReplicatedCheckpointEngine,
    )

    config = gh.GraniteHybridConfig(
        vocab_size=64, dim=32, layer_types=("mamba", "attention", "mamba"),
        n_heads=2, n_kv_heads=1, mlp_dim=64, mamba_heads=4,
        mamba_head_dim=16, mamba_state=8, mamba_chunk=8,
    )
    params = gh.granite_hybrid_init(config, jax.random.key(5))
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
