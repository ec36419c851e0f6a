"""The ZAYA1 model (models/zaya.py) on the CPU at tiny sizes: held to
the benchmark's plain float32 reference (``benchmark/family/zaya.py``,
which shares no code with the program) on logits, loss and every
parameter's gradient, in float32 and in the bf16 configuration; the
two chips' shares of an expert layer add up to the uncut layer; no
token is lost under imbalance; each planted fault fails the comparison,
as the program's own int8 matmuls do; a choice at a near tie is
followed and a mis-route is not; the carry ``(x, r, counts)`` through
the layer scan, the Trainer, the checkpoint and the configuration.
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

from dlrover_tpu.models import zaya
from dlrover_tpu.ops.fp8 import quant_autocast

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own modules, as its harness imports them."""
    sys.path.insert(0, BENCH)
    try:
        import families
        import lookup
        import reference

        yield {"families": families, "lookup": lookup, "reference": reference,
               "family": lookup.module("family", "zaya")}
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def sizes(bench):
    """The benchmark's toy configuration of the family (hidden 64, 3
    layers, 4 / 2 heads of 16, experts 0-3 of 8 held, 2 x 128 tokens),
    with the plain attention in place of the interpreted kernel."""
    sizes = bench["lookup"].data("configs", "toy-zaya")
    sizes["program"] = dict(sizes["program"], attn_impl="reference")
    return sizes


@pytest.fixture(scope="module")
def family(bench, sizes):
    return bench["families"].build(sizes)


def _lively(params, seed):
    """Seeded weights at which every part of a layer counts: matrices
    at 1/sqrt(fan-in), the norms' scales, ``tau``, ``gamma`` and every
    ``alpha`` moved off 1, every bias and ``beta`` off 0."""
    rs = np.random.RandomState(seed)

    def leaf(path, x):
        name = path[-1].key
        if name == "balance_bias" or name.startswith("conv") \
                and name.endswith("_w"):
            return x
        if name in ("w_in", "w_out") or x.ndim == 3 \
                and name not in ("alpha", "beta", "conv2_b"):
            return jnp.asarray(
                rs.randn(*x.shape) * x.shape[-2] ** -0.5, jnp.float32)
        if "norm" in name or name in ("alpha", "tau", "router_gamma"):
            return jnp.asarray(1 + 0.2 * rs.randn(*x.shape), jnp.float32)
        if name == "embed":
            return x
        return jnp.asarray(0.1 * rs.randn(*x.shape), jnp.float32)

    return jax.tree_util.tree_map_with_path(leaf, params)


def _tokens(seed=1):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, 256, (2, 129)), jnp.int32)


def _reference(bench, sizes, params, tokens, follow=None, eps=0.0):
    """(loss, logits [B, S, V], followed) of the plain reference."""
    logits = bench["family"].logits
    rows, followed = zip(*[
        logits(sizes, params, row[:-1],
               None if follow is None else follow[:, i], eps)
        for i, row in enumerate(tokens)
    ])
    loss = jnp.mean(jnp.stack([
        bench["reference"].next_token_loss(r, row)
        for r, row in zip(rows, tokens)
    ]))
    return loss, jnp.stack(rows), sum(followed)


@pytest.fixture(scope="module")
def toy(bench, sizes, family):
    """The toy family, seeded lively weights, a batch, and the
    reference's loss, logits and gradient on them."""
    params = _lively(family.init(jax.random.key(0)), 0)
    tokens = _tokens()

    def ref_loss(params):
        loss, logits, _ = _reference(bench, sizes, params, tokens)
        return loss, logits

    (loss, logits), grads = jax.jit(
        jax.value_and_grad(ref_loss, has_aux=True))(params)
    return {"family": family, "params": params, "tokens": tokens,
            "loss": loss, "logits": logits, "grads": grads}


def _system(toy, dtype, mode=None, params=None, **config):
    """The program's loss, logits and gradients in ``dtype`` (params
    cast as auto_accelerate casts them), optionally with its own 8-bit
    matmuls."""
    cfg = dataclasses.replace(toy["family"].model_config, dtype=dtype,
                              **config)
    loss_fn = zaya.zaya_loss_fn(cfg)
    tokens = toy["tokens"]

    def loss(params):
        cast = jax.tree.map(lambda x: x.astype(dtype), params)
        logits = zaya.zaya_apply(cfg, cast, tokens[:, :-1])
        return loss_fn(cast, {"tokens": tokens}, None), logits

    def run():
        return jax.jit(jax.value_and_grad(loss, has_aux=True))(
            toy["params"] if params is None else params)

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
# logits 3e-7 of their rms, loss 1e-7, a leaf's gradient 2e-6.
F32_LOGITS, F32_LOSS, F32_GRAD_LEAF = 2e-5, 2e-5, 1e-4
# the bf16 configuration against the reference that follows it at near
# ties (NEAR_TIE below): read logits 0.0084, 0.0101, 0.0087 over three
# seeds of weights, the program's int8 matmuls (the attention
# sub-block's: the grouped matmuls have no 8-bit form) 0.0151, 0.0191,
# 0.0167. The limit is the geometric mean of the nearest two. Without
# the following the two overlap: bf16 0.0109, 0.0192, 0.0120 and int8
# 0.0194, 0.0292, 0.0180, a handful of tokens routed otherwise in each.
BF16_LOGITS = 0.0123
# a near tie, at toy size: the reference's own p for the program's
# choice within this of its best (a tenth of the median margin there)
NEAR_TIE = 0.01


@pytest.mark.parametrize("attn_impl", ["reference", "flash"])
def test_model_is_the_reference_in_float32(toy, attn_impl):
    (loss, logits), grads = _system(toy, "float32", attn_impl=attn_impl)
    assert _rel_rms(logits, toy["logits"]) < F32_LOGITS
    assert abs(float(loss - toy["loss"])) < F32_LOSS
    for (path, got), want in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0],
        jax.tree.leaves(toy["grads"]),
    ):
        name = jax.tree_util.keystr(path)
        if not np.asarray(want).any():
            # no gradient reaches the balancing bias, here or there
            assert "balance_bias" in name and not np.asarray(got).any()
            continue
        assert _rel_rms(got, want) < F32_GRAD_LEAF, name


def _followed(bench, sizes, toy, cfg, params=None, eps=NEAR_TIE):
    """The reference's logits where it follows the program ``cfg``'s
    choices at near ties, and how many it followed."""
    params = toy["params"] if params is None else params
    dtype = jnp.dtype(cfg.dtype)
    cast = jax.tree.map(lambda x: x.astype(dtype), params)
    follow = jax.jit(
        lambda p, t: zaya.zaya_apply(cfg, p, t, choices=True)[1]
    )(cast, toy["tokens"][:, :-1])
    _, logits, followed = jax.jit(
        lambda p, f: _reference(bench, sizes, p, toy["tokens"], f, eps)
    )(params, follow)
    return logits, int(followed)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_model_is_near_the_reference_in_bf16_and_int8_is_not(
        bench, sizes, toy, seed):
    """The comparison the cell makes: the reference keeps its own
    choice of expert everywhere but at a near tie. bf16 passes; the
    nearest precision below it fails, although the reference follows
    it at as many near ties as it finds."""
    cfg = dataclasses.replace(toy["family"].model_config, dtype="bfloat16")
    params = _lively(toy["family"].init(jax.random.key(seed)), seed)
    (_, logits), _ = _system(toy, "bfloat16", params=params)
    want, followed = _followed(bench, sizes, toy, cfg, params)
    assert _rel_rms(logits, want) < BF16_LOGITS
    assert followed < 0.05 * 3 * 256
    with quant_autocast("int8"):
        want8, _ = _followed(bench, sizes, toy, cfg, params)
    (_, logits8), _ = _system(toy, "bfloat16", mode="int8", params=params)
    assert _rel_rms(logits8, want8) > BF16_LOGITS


def _flip(lo, hi):
    """The fault: the second-best expert for the tokens whose margin
    between the two best lies in [lo, hi)."""

    def choose(probs, p):
        score = probs + p["balance_bias"].astype(jnp.float32)
        best, index = jax.lax.top_k(score, 2)
        margin = best[..., 0] - best[..., 1]
        choice = jnp.where((margin >= lo) & (margin < hi),
                           index[..., 1], index[..., 0]).astype(jnp.int32)
        return choice, jnp.take_along_axis(
            probs, choice[..., None], -1)[..., 0]

    return choose


@pytest.mark.parametrize("lo,hi,passes", [
    (0.0, NEAR_TIE, True),          # ties inside eps: followed
    (4 * NEAR_TIE, 8 * NEAR_TIE, False),     # mis-routes outside it
])
def test_a_near_tie_is_followed_and_a_misroute_is_not(
        bench, sizes, toy, monkeypatch, lo, hi, passes):
    """A float32 program that takes the second-best expert: where the
    two best lie within ``eps`` the reference follows it and the two
    agree as two float32 implementations do; where they lie further
    apart the reference keeps its own choice and the comparison fails
    by more than bf16's limit."""
    monkeypatch.setattr(zaya, "_choose", _flip(lo, hi))
    cfg = dataclasses.replace(toy["family"].model_config, dtype="float32")
    (_, logits), _ = _system(toy, "float32")
    want, followed = _followed(bench, sizes, toy, cfg)
    # the program differs from the reference's own choices
    assert _rel_rms(logits, toy["logits"]) > BF16_LOGITS
    if passes:
        assert followed > 0
        assert _rel_rms(logits, want) < F32_LOGITS
    else:
        # (a token mis-routed in one layer reaches the next as another
        # token, so a few later choices differ too, some at near ties)
        assert _rel_rms(logits, want) > BF16_LOGITS


def test_the_choices_come_out_of_the_pass_whose_logits_are_compared(toy):
    """``zaya_apply(choices=True)`` is ``zaya_apply``'s own scan with a
    fourth part in its carry: the logits are the same numbers bit for
    bit, and every layer's row is that layer's choice (a program that
    ignores the layer below's router state chooses otherwise from the
    second layer on, and the rows show it)."""
    cfg = dataclasses.replace(toy["family"].model_config, dtype="bfloat16")
    cast = jax.tree.map(lambda x: x.astype(jnp.bfloat16), toy["params"])
    tokens = toy["tokens"][:, :-1]
    plain = jax.jit(lambda p, t: zaya.zaya_apply(cfg, p, t))(cast, tokens)
    logits, choices = jax.jit(
        lambda p, t: zaya.zaya_apply(cfg, p, t, choices=True))(cast, tokens)
    assert np.array_equal(np.asarray(plain), np.asarray(logits))
    assert choices.shape == (cfg.n_layers, *tokens.shape)
    assert choices.dtype == jnp.int32
    # every layer uses several experts, and no two layers' rows are one
    assert all(len(np.unique(row)) > 2 for row in np.asarray(choices))
    assert not np.array_equal(choices[0], choices[1])


def _reference_logits_report(bench, family, toy, params=None):
    """What the cell's agreement check reads: the family's own
    ``reference_logits`` (which asks the program for its choices)
    against the program's bf16 logits, first row, by the harness's
    ``compare`` and the family's limits."""
    params = toy["params"] if params is None else params
    row = toy["tokens"][0]
    cast = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    # (a new function each time: jit would hand a patched program the
    # trace of the sound one)
    got = jax.jit(lambda p, t: family.apply(p, t))(cast, row[None, :-1])[0]
    want = jax.jit(lambda p, t: family.reference_logits(p, t))(
        params, row[:-1])
    loss = bench["reference"].next_token_loss
    return bench["reference"].compare(
        got, loss(got, row), want, loss(want, row), family.tolerances,
        last=128)


def test_a_program_followed_at_too_many_choices_is_followed_nowhere(
        bench, family, toy, monkeypatch, capfd):
    """``reference_logits`` follows a sound program at a handful of near
    ties. One whose choices differ from the reference's at more than
    ``MAX_FOLLOWED_SHARE`` of them is compared with the reference's own
    choices throughout, even where every difference is a tie inside
    eps, and fails as a program that routes wrongly does."""
    module = bench["family"]
    # an eps under which every flipped choice below counts as a tie
    monkeypatch.setattr(module, "NEAR_TIE_EPS", 1.0)
    sound = _reference_logits_report(bench, family, toy)
    assert "none is" not in capfd.readouterr().err
    # the second-best expert for a token in five: followed at 20% of
    # the choices, where 1.4% is the most
    monkeypatch.setattr(zaya, "_choose", _flip(0.0, 0.03))
    flipped = _reference_logits_report(bench, family, toy)
    said = capfd.readouterr().err
    assert "none is" in said, said
    assert flipped["logits_rel_rms"] > 3 * sound["logits_rel_rms"]
    assert flipped["logits_rel_rms"] > bench["reference"].tolerances(
        BF16_LOGITS)["logits_rel_rms"]


def test_the_shares_add_up_to_the_uncut_layer(bench, sizes, toy):
    """Two chips share a layer, experts 0-3 here and 4-7 on the other.
    Each runs the router on its rows (counted once: the same ``p`` and
    choice on both) and computes its own experts' part of ``y``; the
    two parts add up to what the reference gives for the layer with
    all 8 experts, and each token's ``y`` comes from one chip."""
    ref = bench["family"]
    cfg = dataclasses.replace(toy["family"].model_config, dtype="float32")
    rs = np.random.RandomState(3)
    whole = {
        "w_in": jnp.asarray(rs.randn(8, 64, 64) * 0.125, jnp.float32),
        "w_out": jnp.asarray(rs.randn(8, 32, 64) * 0.18, jnp.float32),
    }
    h = jnp.asarray(rs.randn(2, 128, 64), jnp.float32)
    layer = jax.tree.map(lambda x: x[0], toy["params"]["layers"])
    r_prev = jnp.asarray(rs.randn(2, 128, 16), jnp.float32)
    _, probs = zaya._router(cfg, h, r_prev, layer)
    choice, weight = zaya._choose(probs, layer)
    assert len(np.unique(np.asarray(choice))) == 8
    parts = [
        zaya.routed_experts(
            h, choice, weight,
            {k: v[first:first + 4] for k, v in whole.items()}, (first, 4))
        for first in (0, 4)
    ]
    uncut = dict(sizes, num_experts=8)
    want = jnp.stack([
        ref._router(uncut, hb, rb, layer)[1][
            jnp.arange(128), cb][:, None]
        * ref._held_experts(uncut, hb, cb, whole)
        for hb, rb, cb in zip(h, r_prev, choice)
    ])
    np.testing.assert_allclose(parts[0] + parts[1], want, atol=2e-6)
    here = np.asarray(choice) < 4
    assert not np.asarray(parts[0])[~here].any()
    assert not np.asarray(parts[1])[here].any()


def test_no_token_is_lost_under_imbalance(bench, sizes, toy):
    """A balancing bias that sends nine tokens in ten to expert 1 in
    every layer: the model is still the reference (a capacity of 1.25
    x the mean would drop six in seven of them)."""
    params = jax.tree.map(lambda x: x, toy["params"])
    params["layers"]["balance_bias"] = \
        params["layers"]["balance_bias"].at[:, 1].set(
            jnp.asarray([0.11, 0.22, 0.14]))
    cfg = dataclasses.replace(toy["family"].model_config, dtype="float32")
    choices = np.asarray(zaya.zaya_apply(
        cfg, params, toy["tokens"][:, :-1], choices=True)[1])
    share = np.mean(choices == 1, axis=(1, 2))
    assert (share > 0.88).all() and (share < 0.94).all(), share
    (loss, logits), _ = _system(toy, "float32", params=params)
    want_loss, want, _ = jax.jit(
        lambda p: _reference(bench, sizes, p, toy["tokens"]))(params)
    assert _rel_rms(logits, want) < F32_LOGITS
    assert abs(float(loss - want_loss)) < F32_LOSS
    # and the bias moved the result
    assert _rel_rms(want, toy["logits"]) > 0.1


def _unshifted_values(config, proj):
    B, S, _ = proj.shape
    return proj.reshape(B, S, config.n_kv_heads, config.head_dim)


def _no_qk_mean(config, u):
    m_q, m_k = _QK_MEAN(config, u)
    return jnp.zeros_like(m_q), jnp.zeros_like(m_k)


def _first_convolution_alone(config, u, p):
    taps = dataclasses.replace(config, conv_taps=(config.conv_taps[0], 1))
    p = dict(p, conv2_b=jnp.zeros_like(p["conv2_b"]),
             conv2_w=jnp.broadcast_to(
                 jnp.eye(config.head_dim), p["conv2_w"][:, :1].shape))
    return _CONVOLUTIONS(taps, u, p)


def _router_without_state(config, y, r_prev, p):
    return _ROUTER(config, y, jnp.zeros_like(r_prev), p)


def _norm_without_temperature(x, temperature=None):
    return _L2_NORM(x)


def _weight_left_out(probs, p):
    choice, weight = _CHOOSE(probs, p)
    return choice, jnp.ones_like(weight)


_QK_MEAN, _CONVOLUTIONS, _ROUTER = \
    zaya._qk_mean, zaya._convolutions, zaya._router
_L2_NORM, _CHOOSE = zaya._l2_norm, zaya._choose


@pytest.mark.parametrize("name,fault,config", [
    ("_values", _unshifted_values, {}),
    ("_qk_mean", _no_qk_mean, {}),
    ("_convolutions", _first_convolution_alone, {}),
    ("_router", _router_without_state, {}),
    ("_l2_norm", _norm_without_temperature, {}),
    ("_choose", _weight_left_out, {}),
    (None, None, {"rotary_factor": 1.0}),       # rotary on the whole head
    (None, None, {"held_first": 1}),        # the held range off by one
], ids=["no_value_shift", "no_qk_mean", "second_conv_skipped",
        "r_prev_ignored", "tau_ignored", "weight_left_out",
        "rotary_on_whole_head", "held_off_by_one"])
def test_planted_fault_fails_the_comparison(toy, monkeypatch, name, fault,
                                            config):
    if name:
        monkeypatch.setattr(zaya, name, fault)
    (_, logits), _ = _system(toy, "float32", **config)
    # not by a hair: twice the bf16 limit and more (read 0.05 to 1.1)
    assert _rel_rms(logits, toy["logits"]) > 2 * BF16_LOGITS


def test_the_carry_through_the_scan_with_and_without_layer_input(toy):
    """``(x, r, counts)`` through ``stage_layer_scan``: recomputing a
    layer from its kept input (LAYER_INPUT) gives the loss, the counts
    and the gradients of the plain scan."""
    batch = {"tokens": toy["tokens"]}

    def run(remat):
        cfg = dataclasses.replace(toy["family"].model_config,
                                  dtype="float32", remat=remat)
        return jax.jit(jax.value_and_grad(
            zaya.zaya_loss_fn(cfg).with_aux, has_aux=True)
        )(toy["params"], batch, None)

    (loss, counts), grads = run(True)
    (want_loss, want_counts), want = run(False)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert counts == want_counts
    assert counts["moe.tokens_routed"] == 3 * 2 * 128
    assert 0 < counts["moe.tokens_held"] < counts["moe.tokens_routed"]
    assert counts["moe.expert_load_mean"] * 4 == counts["moe.tokens_held"]
    assert counts["moe.expert_load_max"] >= counts["moe.expert_load_mean"]
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=1e-7)


# ---------------------------------------------------------- configuration


@pytest.mark.parametrize("which,parameters", [
    ("published", 40 * 207_583_506 + 537_133_056 + 2_048),
    ("cut", 708_664_940),
])
def test_configuration_builds_with_its_parameter_count(bench, which,
                                                       parameters):
    """The benchmark's configuration file, as cut for one chip and with
    its ``published`` values put back, under ``jax.eval_shape``: nothing
    is allocated."""
    with open(os.path.join(BENCH, "configs", "zaya1-8b.json")) as f:
        sizes = json.load(f)
    if which == "published":
        sizes.update(sizes["published"])
        assert len(sizes["layer_types"]) == 40
    family = bench["families"].build(sizes)
    config = family.model_config
    assert config.n_experts == 16
    assert config.held == ((0, 16) if which == "published" else (0, 8))
    shapes = jax.eval_shape(family.init, jax.random.key(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(shapes)) == parameters
    assert config.param_count() == parameters
    layer = {k: v.size // config.n_layers
             for k, v in shapes["layers"].items()}
    assert sum(layer.values()) == (
        207_583_506 if which == "published" else 106_920_210)
    assert sum(v for k, v in layer.items() if k.startswith("router_")) \
        + layer["balance_bias"] == 660_752
    axes = family.logical_axes
    assert jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple)
    ) == jax.tree.structure(shapes)
    for leaf, names in zip(
        jax.tree.leaves(shapes),
        jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple)),
    ):
        assert len(names) == leaf.ndim


@pytest.mark.parametrize("change,match", [
    ({"held_first": 12, "held_experts": 8}, "not among 16"),
    ({"n_kv_heads": 3, "n_heads": 6}, "is odd"),
    ({"rotary_factor": 0.3}, "no even number"),
])
def test_what_cannot_be_built_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        zaya.ZayaConfig(**change)


def test_pipeline_stages_are_refused(family):
    """The stage boundary would have to carry the router's state: the
    model says so instead of failing inside the schedule."""
    from dlrover_tpu.parallel import mesh as mesh_lib

    config = family.model_config
    params = jax.eval_shape(family.init, jax.random.key(0))
    before = mesh_lib._global_mesh
    mesh_lib.set_mesh(mesh_lib.build_mesh(
        mesh_lib.MeshConfig(pipe=2, data=4)))
    try:
        with pytest.raises(NotImplementedError, match="router's state"):
            jax.eval_shape(
                lambda p: zaya.zaya_apply(
                    config, p, jnp.zeros((4, 128), jnp.int32)), params)
    finally:
        mesh_lib._global_mesh = before


def test_build_publishes_its_shape():
    from dlrover_tpu.common import telemetry

    telemetry.enable("test")
    try:
        zaya.zaya_loss_fn(zaya.ZayaConfig(held_experts=8, n_layers=6,
                                          vocab_size=32784))
        gauges = {(g["name"], *sorted(g["labels"].values())): g["value"]
                  for g in telemetry.snapshot()["gauges"]}
    finally:
        telemetry.install_from_env()
    assert gauges["model.layers", "hybrid"] == 6
    assert gauges["model.params", "cca"] == 6 * (5_242_880 + 3_840
                                                 + 328_960 + 2)
    assert gauges["model.params", "router"] == 6 * 660_752
    assert gauges["model.params", "experts"] == 6 * 100_663_296
    assert gauges["model.params", "embedding"] == 67_141_632
    assert gauges["model.moe.experts", "held"] == 8
    assert gauges["model.moe.experts", "published"] == 16


def test_a_traced_backward_pass_publishes_what_a_layer_keeps(toy):
    """``model.remat.kept``: the bytes a layer keeps beside its input,
    as the forward rule's trace finds them: the attention kernel's
    output [B, S, Hq, d] in the compute dtype and one float32 row
    statistic a query."""
    from dlrover_tpu.common import telemetry

    cfg = dataclasses.replace(toy["family"].model_config, attn_impl="flash")
    batch, seq = toy["tokens"].shape[0], toy["tokens"].shape[1] - 1
    telemetry.enable("test")
    try:
        jax.eval_shape(jax.grad(zaya.zaya_loss_fn(cfg)), toy["params"],
                       {"tokens": toy["tokens"]}, None)
        gauges = {(g["labels"]["kind"], g["labels"]["name"]): g["value"]
                  for g in telemetry.snapshot()["gauges"]
                  if g["name"] == "model.remat.kept"}
    finally:
        telemetry.install_from_env()
    rows = batch * seq * cfg.n_heads
    assert gauges == {("hybrid", "attn_out"):
                      rows * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize
                      + rows * 4}


# ------------------------------------------------- trainer and checkpoint


@pytest.fixture
def _isolate(isolated_ckpt_env):
    yield


def _train(config, tmp_path, remat, steps=6, mesh=None, loss_fn=None,
           strategy=None, rows=8):
    from dlrover_tpu.common import telemetry
    from dlrover_tpu.parallel import MeshConfig, Strategy
    from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

    config = dataclasses.replace(config, remat=remat)
    losses = []

    class Tap(logging.Handler):
        def emit(self, record):
            if str(record.msg).startswith("step %d epoch %d loss"):
                losses.append(float(record.args[2]))

    telemetry.enable("test")
    trainer = Trainer(
        loss_fn or zaya.zaya_loss_fn(config),
        lambda rng: zaya.zaya_init(config, rng),
        zaya.zaya_logical_axes(config),
        TrainingArgs(
            output_dir=str(tmp_path / f"out{remat}"), seed=3, max_steps=steps,
            learning_rate=3e-3, log_steps=1, flash_checkpoint=False,
            # the Trainer takes every device: the 8 virtual ones
            strategy=Strategy(mesh=MeshConfig(**(mesh or {"data": 8})),
                              **(strategy or {})),
        ),
        train_data=[{"tokens": np.random.RandomState(4).randint(
            0, 256, (rows, 129)).astype(np.int32)}] * steps,
    )
    log = logging.getLogger("dlrover_tpu.trainer.trainer")
    tap = Tap()
    log.addHandler(tap)
    try:
        trainer.train()
        snapshot = telemetry.snapshot()
        counters = {c["name"]: c["value"] for c in snapshot["counters"]}
        events = [e for e in snapshot["events"]
                  if e["kind"] == "step.counts"]
        return losses, trainer.state, counters, events
    finally:
        log.removeHandler(tap)
        trainer.close()
        telemetry.install_from_env()


def test_trainer_steps_with_and_without_recomputation(family, tmp_path,
                                                      _isolate):
    """Through Trainer -> auto_accelerate (bf16 compute, the whole-loss
    checkpoint of Strategy.remat "minimal" round the model's own): the
    loss falls, recomputing a layer from its input gives the loss of
    keeping everything, and the counts of every step whose loss was
    read back are in the program's counters."""
    with_remat, _, counters, events = _train(family.model_config, tmp_path,
                                             remat=True)
    without, *_ = _train(family.model_config, tmp_path, remat=False)
    assert with_remat[-1] < with_remat[0] - 0.05
    np.testing.assert_allclose(with_remat, without, rtol=2e-3)
    assert counters["moe.tokens_routed"] == 6 * 3 * 8 * 128
    assert 0 < counters["moe.tokens_held"] < counters["moe.tokens_routed"]
    assert counters["moe.expert_load_max"] \
        >= counters["moe.expert_load_mean"] > 0
    # and each read-back left its own increments as one event
    assert [e["step"] for e in events] == [1, 2, 3, 4, 5, 6]
    for name in zaya.COUNTS:
        assert sum(e[name] for e in events) == counters[name]


def test_trainer_counts_what_the_loss_declares_and_sums_micro_batches(
        family, tmp_path, _isolate):
    """Only the names a loss function declares as ``counters`` become
    counters (another aux, here a norm, is no count), and with gradient
    accumulation a step's count is the sum over its micro-batches, not
    the mean the step hands back."""
    loss_fn = zaya.zaya_loss_fn(family.model_config)
    with_aux = loss_fn.with_aux

    def more_aux(params, batch, rng):
        loss, aux = with_aux(params, batch, rng)
        return loss, dict(aux, **{"embed.norm": jnp.sqrt(
            jnp.sum(params["embed"].astype(jnp.float32) ** 2))})

    loss_fn.with_aux = more_aux
    _, _, counters, events = _train(
        family.model_config, tmp_path, remat=True, steps=2, loss_fn=loss_fn,
        strategy={"grad_accum": 2}, rows=16)
    assert "embed.norm" not in counters
    assert all("embed.norm" not in e for e in events)
    # 2 steps x 3 layers x 16 x 128 tokens, two micro-batches of 8 rows
    assert counters["moe.tokens_routed"] == 2 * 3 * 16 * 128


def test_trainer_steps_sharded_over_fsdp(family, tmp_path, _isolate):
    """The leaves' logical axes shard, and every device routes its own
    rows: data=2 x fsdp=4 trains to the losses of plain data
    parallelism."""
    plain, *_ = _train(family.model_config, tmp_path, remat=True, steps=3)
    sharded, state, *_ = _train(family.model_config, tmp_path, remat=True,
                                steps=3, mesh={"data": 2, "fsdp": 4})
    np.testing.assert_allclose(sharded, plain, rtol=5e-3)
    assert "fsdp" in str(state.params["layers"]["w_in"].sharding.spec)


def test_flash_checkpoint_round_trip_of_the_state(tmp_path, _isolate):
    """The parameter tree with AdamW's moments beside it, the balancing
    bias (which no gradient reaches) moved off zero: shm save -> load
    is bit for bit."""
    import optax

    from dlrover_tpu.trainer.flash_checkpoint.engine import (
        ReplicatedCheckpointEngine,
    )

    config = zaya.ZayaConfig(
        vocab_size=64, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
        head_dim=8, n_experts=4, held_first=2, held_experts=2,
        expert_dim=16, router_dim=8,
    )
    params = zaya.zaya_init(config, jax.random.key(5))
    params["layers"]["balance_bias"] = jnp.asarray(
        np.random.RandomState(6).randn(2, 4) * 0.01, jnp.float32)
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
    assert np.asarray(restored["params"]["layers"]["balance_bias"]).any()
