"""The Laguna model (models/laguna.py) on the CPU at tiny sizes: held to
the benchmark's plain float32 reference (``benchmark/family/laguna.py``,
which shares no code with the program) on logits, loss and every
parameter's gradient with a layer of every kind in the stack; bf16
passes the comparison the cell makes and the program's own int8
matmuls fail it; a near tie at the k-th choice is followed and a
mis-route is not; the eight chips' shares of a routed layer, with what
every chip computes alike counted once, add up to the uncut layer; the
window layers' kernels are the masked plain form past the window; the
carry through the runs, the Trainer's counts, the configuration.
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

from dlrover_tpu.models import laguna
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
               "family": lookup.module("family", "laguna")}
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def sizes(bench):
    """The benchmark's toy configuration of the family (hidden 64; a
    dense full layer, three window layers of 8 heads, a full layer of
    6, over 2 key/value heads of 16; 4 of 16 experts held, 4 a token;
    window 32 in 2 x 128 tokens), with the plain attention in place of
    the interpreted kernel."""
    sizes = bench["lookup"].data("configs", "toy-laguna")
    sizes["program"] = dict(sizes["program"], attn_impl="reference")
    return sizes


@pytest.fixture(scope="module")
def family(bench, sizes):
    return bench["families"].build(sizes)


def _lively(params, seed):
    """Seeded weights at which every part of a layer counts: matrices
    at 1/sqrt(fan-in), the norms' scales moved off 1."""
    rs = np.random.RandomState(seed)

    def leaf(path, x):
        name = path[-1].key
        if "norm" in name:
            return jnp.asarray(1 + 0.2 * rs.randn(*x.shape), jnp.float32)
        if name == "embed":
            return x
        return jnp.asarray(
            rs.randn(*x.shape) * x.shape[-2] ** -0.5, jnp.float32)

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
    loss_fn = laguna.laguna_loss_fn(cfg)
    tokens = toy["tokens"]

    def loss(params):
        cast = jax.tree.map(lambda x: x.astype(dtype), params)
        logits = laguna.laguna_apply(cfg, cast, tokens[:, :-1])
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
# logits 2e-6 of their rms, loss 1e-7, a leaf's gradient 1e-5.
F32_LOGITS, F32_LOSS, F32_GRAD_LEAF = 2e-5, 2e-5, 2e-4
# the bf16 configuration against the reference that follows it at near
# ties (NEAR_TIE below), relative rms of the logits over three seeds of
# weights: 0.0363, 0.0360, 0.0392; the program's int8 matmuls (every
# projection's and the dense and shared MLPs': the grouped matmuls have
# no 8-bit form) 0.0982, 0.0842, 0.1006. The limit is the geometric
# mean of the nearest two. At these widths and weights the k-th and
# k+1-th of 16 experts lie close: bf16 takes another set at 66 to 72 of
# the 1,024 token-layer choices (int8 at 136 to 143), and without the
# following reads 0.094-0.107 (int8 0.126-0.158).
BF16_LOGITS = 0.0575
# a near tie, at toy size: the program's set a top-k of the reference's
# router logits within this (bf16's sets all lie within 0.2)
NEAR_TIE = 0.1


@pytest.mark.parametrize("attn_impl", ["reference", "flash"])
def test_model_is_the_reference_in_float32(toy, attn_impl):
    (loss, logits), grads = _system(toy, "float32", attn_impl=attn_impl)
    assert _rel_rms(logits, toy["logits"]) < F32_LOGITS
    assert abs(float(loss - toy["loss"])) < F32_LOSS
    kinds = set()
    for (path, got), want in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0],
        jax.tree.leaves(toy["grads"]),
    ):
        name = jax.tree_util.keystr(path)
        kinds.update(k for k in ("dense_full", "moe_window", "moe_full")
                     if k in name)
        assert np.asarray(want).any(), name
        assert _rel_rms(got, want) < F32_GRAD_LEAF, name
    assert kinds == {"dense_full", "moe_window", "moe_full"}


def _followed(bench, sizes, toy, cfg, params=None, eps=NEAR_TIE):
    """The reference's logits where it follows the program ``cfg``'s
    choices at near ties, and how many it followed."""
    params = toy["params"] if params is None else params
    dtype = jnp.dtype(cfg.dtype)
    cast = jax.tree.map(lambda x: x.astype(dtype), params)
    follow = jax.jit(
        lambda p, t: laguna.laguna_apply(cfg, p, t, choices=True)[1]
    )(cast, toy["tokens"][:, :-1])
    _, logits, followed = jax.jit(
        lambda p, f: _reference(bench, sizes, p, toy["tokens"], f, eps)
    )(params, follow)
    return logits, int(followed)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_model_is_near_the_reference_in_bf16_and_int8_is_not(
        bench, sizes, toy, seed):
    """The comparison the cell makes: the reference keeps its own set
    of experts everywhere but at a near tie. bf16 passes; the nearest
    precision below it fails, although the reference follows it at as
    many near ties as it finds."""
    cfg = dataclasses.replace(toy["family"].model_config, dtype="bfloat16")
    params = _lively(toy["family"].init(jax.random.key(seed)), seed)
    (_, logits), _ = _system(toy, "bfloat16", params=params)
    want, followed = _followed(bench, sizes, toy, cfg, params)
    assert _rel_rms(logits, want) < BF16_LOGITS
    assert followed < 0.08 * 4 * 256
    with quant_autocast("int8"):
        want8, _ = _followed(bench, sizes, toy, cfg, params)
    (_, logits8), _ = _system(toy, "bfloat16", mode="int8", params=params)
    assert _rel_rms(logits8, want8) > BF16_LOGITS


def _swap(lo, hi):
    """The fault: the k+1-th expert in place of the k-th for the tokens
    whose router logits for the two lie [lo, hi) apart."""

    def route(config, y, router):
        choice, weight = ROUTE(config, y, router)
        k = config.top_k
        scores = jnp.matmul(y, router.astype(y.dtype),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
        best, index = jax.lax.top_k(scores, k + 1)
        margin = best[..., k - 1] - best[..., k]
        probs = jax.nn.softmax(scores, -1)
        swap = (margin >= lo) & (margin < hi)
        choice = choice.at[..., k - 1].set(
            jnp.where(swap, index[..., k], index[..., k - 1]))
        top = jnp.take_along_axis(probs, choice, -1)
        return choice, top / jnp.sum(top, -1, keepdims=True) \
            * config.routed_scale

    return route


ROUTE = laguna.route


@pytest.mark.parametrize("lo,hi,passes", [
    (0.0, NEAR_TIE, True),          # ties inside eps: followed
    (4 * NEAR_TIE, 100.0, False),   # mis-routes outside it
])
def test_a_near_tie_is_followed_and_a_misroute_is_not(
        bench, sizes, toy, monkeypatch, lo, hi, passes):
    """A float32 program that takes the k+1-th expert for the k-th:
    where the two lie within ``eps`` the reference follows its set and
    the two agree as two float32 implementations do; where they lie
    further apart the reference keeps its own and the comparison fails
    by more than bf16's limit."""
    monkeypatch.setattr(laguna, "route", _swap(lo, hi))
    cfg = dataclasses.replace(toy["family"].model_config, dtype="float32")
    (_, logits), _ = _system(toy, "float32")
    want, followed = _followed(bench, sizes, toy, cfg)
    # the program differs from the reference's own choices
    assert _rel_rms(logits, toy["logits"]) > BF16_LOGITS
    if passes:
        assert followed > 0
        assert _rel_rms(logits, want) < F32_LOGITS
    else:
        assert _rel_rms(logits, want) > BF16_LOGITS


def test_the_choices_come_out_of_the_pass_whose_logits_are_compared(toy):
    """``laguna_apply(choices=True)`` is ``laguna_apply``'s own scan
    with a third part in its carry: the logits are the same numbers bit
    for bit, every routed layer's row is that layer's set of k distinct
    experts, and the dense layer's row is left zero."""
    cfg = dataclasses.replace(toy["family"].model_config, dtype="bfloat16")
    cast = jax.tree.map(lambda x: x.astype(jnp.bfloat16), toy["params"])
    tokens = toy["tokens"][:, :-1]
    plain = jax.jit(lambda p, t: laguna.laguna_apply(cfg, p, t))(cast, tokens)
    logits, choices = jax.jit(
        lambda p, t: laguna.laguna_apply(cfg, p, t, choices=True))(
            cast, tokens)
    assert np.array_equal(np.asarray(plain), np.asarray(logits))
    choices = np.asarray(choices)
    assert choices.shape == (5, *tokens.shape, cfg.top_k)
    assert not choices[0].any()
    for row in choices[1:]:
        assert (np.sort(row, -1)[..., 1:] != np.sort(row, -1)[..., :-1]).all()
        assert len(np.unique(row)) == cfg.n_experts
    assert not np.array_equal(choices[1], choices[2])


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
    ties. One whose sets differ from the reference's at more than
    ``MAX_FOLLOWED_SHARE`` of them is compared with the reference's own
    sets throughout, even where every difference is a tie inside eps,
    and fails as a program that routes wrongly does."""
    module = bench["family"]
    # an eps under which every swapped choice below counts as a tie, and
    # a most over the toy's own 7% of choices at a tie
    monkeypatch.setattr(module, "NEAR_TIE_EPS", 1.0)
    monkeypatch.setattr(module, "MAX_FOLLOWED_SHARE", 0.15)
    sound = _reference_logits_report(bench, family, toy)
    assert "none is" not in capfd.readouterr().err
    # the k+1-th expert for every token whose two lie within 1
    monkeypatch.setattr(laguna, "route", _swap(0.0, 1.0))
    swapped = _reference_logits_report(bench, family, toy)
    said = capfd.readouterr().err
    assert "none is" in said, said
    assert swapped["logits_rel_rms"] > 3 * sound["logits_rel_rms"]
    assert swapped["logits_rel_rms"] > BF16_LOGITS


def test_the_eight_shares_add_up_to_the_uncut_layer(bench, sizes, toy):
    """Eight chips share a routed layer, two of the 16 experts each.
    Each runs attention, the norms, the router and the shared expert on
    its rows (counted once: the same on all eight) and computes its own
    experts' part of the routed sum; with the common part counted once
    the eight give what the uncut reference gives for the layer with
    all 16 experts held."""
    ref = bench["family"]
    cfg = dataclasses.replace(toy["family"].model_config, dtype="float32")
    rs = np.random.RandomState(3)
    whole = {
        "w_in": jnp.asarray(rs.randn(16, 64, 32) * 0.125, jnp.float32),
        "w_out": jnp.asarray(rs.randn(16, 16, 64) * 0.25, jnp.float32),
    }
    run = toy["params"]["layers"]["01_moe_window"]
    layer = {k: v[0] for k, v in run.items() if k not in whole}
    x = jnp.asarray(rs.randn(1, 128, 64), jnp.float32)
    full = laguna.rope_tables(cfg, 128)
    tables = (*full["full"], *full["window"])

    def chip(first):
        share = dict(layer, **{k: v[first:first + 2]
                               for k, v in whole.items()})
        c = dataclasses.replace(cfg, held_first=first, held_experts=2)
        (out, counts), _ = laguna._layer_fn(c, "moe_window")(
            (x, jnp.zeros((4,), jnp.float32)), share, *tables)
        return out, counts

    outs, counts = zip(*[chip(first) for first in range(0, 16, 2)])
    # what every chip computes alike (attention, norms, router, shared
    # expert): the layer with no expert's part
    zero = dict(layer, **{k: jnp.zeros_like(v[:2]) for k, v in whole.items()})
    (common, _), _ = laguna._layer_fn(
        dataclasses.replace(cfg, held_experts=2), "moe_window")(
            (x, jnp.zeros((4,), jnp.float32)), zero, *tables)
    total = common + sum(out - common for out in outs)
    uncut = dict(sizes, num_experts=16)
    want, _ = ref._layer(uncut, "moe_window", x[0], dict(layer, **whole),
                         None, 0.0)
    np.testing.assert_allclose(total[0], want, atol=2e-5)
    # every pair is held on exactly one chip
    assert sum(float(c[1]) for c in counts) == float(counts[0][0]) == 128 * 4


def test_window_kernels_are_the_masked_plain_form(toy):
    """The flash kernels with the window past which a 128-position
    sequence runs (32), at the toy's 8 query heads over 2, forward and
    every gradient, against the plain form with the same mask; the
    kernels carry the window's names."""
    from dlrover_tpu.ops.attention import flash_attention, mha_reference

    rs = np.random.RandomState(4)
    q = jnp.asarray(rs.randn(2, 8, 128, 16), jnp.float32)
    k = jnp.asarray(rs.randn(2, 2, 128, 16), jnp.float32)
    v = jnp.asarray(rs.randn(2, 2, 128, 16), jnp.float32)
    probe = jnp.asarray(rs.randn(2, 8, 128, 16), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * probe)

    kernel = lambda q, k, v: flash_attention(       # noqa: E731
        q, k, v, window=32, block_q=32, block_k=32)
    plain = lambda q, k, v: mha_reference(q, k, v, window=32)  # noqa: E731
    got = jax.value_and_grad(loss(kernel), (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(loss(plain), (0, 1, 2))(q, k, v)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)
    # the window reaches: the unwindowed form is another function
    causal = jax.value_and_grad(loss(
        lambda q, k, v: mha_reference(q, k, v)), (0, 1, 2))(q, k, v)
    assert abs(float(causal[0] - want[0])) > 1.0
    text = str(jax.make_jaxpr(jax.grad(loss(kernel), (0, 1, 2)))(q, k, v))
    for name in ("flash_fwd_window", "flash_bwd_window"):
        assert name in text
    assert "name=flash_fwd " not in text


def _yarn_closed_form():
    """transformers' ``_compute_yarn_parameters`` written out for the
    published full layers: 64 rotated channels, theta 5e5, factor 64,
    4096 original positions, beta_fast 64, beta_slow 1: the ramp runs
    over channel pairs 5 to 16."""
    inv = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 5) / (16 - 5), 0, 1)
    return inv / 64 * ramp + inv * (1 - ramp)


def test_yarn_frequencies_of_the_program_and_of_the_reference(bench):
    cfg = laguna.LagunaConfig()
    want = _yarn_closed_form()
    got = laguna.yarn_inv_freq(64, cfg.full_rope_theta, cfg.yarn_factor,
                               cfg.yarn_original, cfg.yarn_beta_fast,
                               cfg.yarn_beta_slow)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    sizes = bench["lookup"].data("configs", "laguna-xs.2")
    rope = sizes["rope_parameters"]["full_attention"]
    np.testing.assert_allclose(bench["family"]._yarn(64, rope), want,
                               rtol=1e-6)


def test_the_carry_through_the_runs_with_and_without_layer_input(toy):
    """``(x, counts)`` through ``stage_run_scan``'s three runs:
    recomputing a layer from its kept input gives the loss, the counts
    and the gradients of the plain scan, and the counts are in pairs."""
    batch = {"tokens": toy["tokens"]}

    def run(remat):
        cfg = dataclasses.replace(toy["family"].model_config,
                                  dtype="float32", remat=remat)
        return jax.jit(jax.value_and_grad(
            laguna.laguna_loss_fn(cfg).with_aux, has_aux=True)
        )(toy["params"], batch, None)

    (loss, counts), grads = run(True)
    (want_loss, want_counts), want = run(False)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert counts == want_counts
    # four routed layers, 2 x 128 tokens, 4 pairs a token
    assert counts["moe.tokens_routed"] == 4 * 2 * 128 * 4
    assert 0 < counts["moe.tokens_held"] < counts["moe.tokens_routed"]
    assert counts["moe.expert_load_mean"] * 4 == counts["moe.tokens_held"]
    assert counts["moe.expert_load_max"] >= counts["moe.expert_load_mean"]
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=1e-7)


# ---------------------------------------------------------- configuration


def test_configuration_builds_with_its_parameter_count(bench):
    """The benchmark's configuration file under ``jax.eval_shape``
    (nothing is allocated): the reckoning of one chip's share, a
    dense full layer 79.8M, three window layers of 142.2M, a full one
    of 133.8M and an eighth of the vocabulary twice, 691.6M."""
    with open(os.path.join(BENCH, "configs", "laguna-xs.2.json")) as f:
        sizes = json.load(f)
    family = bench["families"].build(sizes)
    config = family.model_config
    assert config.kinds() == ("dense_full",) + ("moe_window",) * 3 \
        + ("moe_full",)
    assert config.held == (0, 32) and config.n_experts == 256
    shapes = jax.eval_shape(family.init, jax.random.key(0))
    total = sum(leaf.size for leaf in jax.tree.leaves(shapes))
    assert total == config.param_count() == 691_623_936
    runs = {name: sum(leaf.size for leaf in jax.tree.leaves(run))
            for name, run in shapes["layers"].items()}
    assert runs == {"00_dense_full": 79_794_176,
                    "01_moe_window": 3 * 142_217_216,
                    "02_moe_full": 133_795_840}
    axes = family.logical_axes
    for leaf, names in zip(
        jax.tree.leaves(shapes),
        jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple)),
    ):
        assert len(names) == leaf.ndim


def test_the_weights_are_the_configuration_s_whatever_the_seed(bench):
    """Every seed does the same work: ``init`` draws from the file's
    ``weights_seed`` and ignores the Trainer's key."""
    sizes = bench["lookup"].data("configs", "toy-laguna")
    family = bench["families"].build(sizes)
    a = family.init(jax.random.key(1))
    b = family.init(jax.random.key(2))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    other = bench["families"].build(dict(sizes, weights_seed=4))
    assert not np.array_equal(np.asarray(a["embed"]),
                              np.asarray(other.init(None)["embed"]))


@pytest.mark.parametrize("change,match", [
    ({"held_first": 250, "held_experts": 32}, "not among 256"),
    ({"heads": (48, 63, 63, 63) * 10}, "do not divide"),
    ({"heads": (48, 64, 64, 56) * 10}, "one count a kind"),
    ({"full_rotary_factor": 0.3}, "no even number"),
    ({"mlp_layer_types": ("dense",) * 39}, "one of each a layer"),
])
def test_what_cannot_be_built_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        laguna.LagunaConfig(**change)


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", True), ("gating", False),
    ("moe_apply_router_weight_on_input", True),
])
def test_what_the_program_does_not_implement_is_refused(bench, key, value):
    sizes = dict(bench["lookup"].data("configs", "toy-laguna"),
                 **{key: value})
    with pytest.raises(ValueError, match=key):
        bench["families"].build(sizes)


def test_pipeline_stages_are_refused(family):
    from dlrover_tpu.parallel import mesh as mesh_lib

    config = family.model_config
    params = jax.eval_shape(family.init, jax.random.key(0))
    before = mesh_lib._global_mesh
    mesh_lib.set_mesh(mesh_lib.build_mesh(
        mesh_lib.MeshConfig(pipe=2, data=4)))
    try:
        with pytest.raises(NotImplementedError, match="unlike layers"):
            jax.eval_shape(
                lambda p: laguna.laguna_apply(
                    config, p, jnp.zeros((4, 128), jnp.int32)), params)
    finally:
        mesh_lib._global_mesh = before


def test_build_publishes_its_shape(bench):
    from dlrover_tpu.common import telemetry

    with open(os.path.join(BENCH, "configs", "laguna-xs.2.json")) as f:
        sizes = json.load(f)
    telemetry.enable("test")
    try:
        bench["families"].build(sizes)
        gauges = {(g["name"], *sorted(g["labels"].values())): g["value"]
                  for g in telemetry.snapshot()["gauges"]}
    finally:
        telemetry.install_from_env()
    assert gauges["model.layers", "dense_full"] == 1
    assert gauges["model.layers", "moe_window"] == 3
    assert gauges["model.layers", "moe_full"] == 1
    assert gauges["model.params", "experts"] == 4 * 32 * 3 * 2048 * 512
    assert gauges["model.params", "gate"] == 2048 * (2 * 48 + 3 * 64)
    assert gauges["model.params", "router"] == 4 * 2048 * 256
    assert gauges["model.params", "shared_expert"] == \
        4 * (3 * 2048 * 512 + 2048)
    assert gauges["model.params", "dense_mlp"] == 3 * 2048 * 8192 + 2048
    assert gauges["model.params", "embedding"] == 12544 * 2048
    assert gauges["model.params", "head"] == 12544 * 2048 + 2048
    assert gauges["model.moe.experts", "held"] == 32
    assert gauges["model.moe.experts", "published"] == 256
    assert gauges["model.moe.top_k", ] == 8
    assert gauges["model.attn.window", ] == 512


def test_window_layers_trace_the_tiles_they_compute(bench):
    """The cell's step, traced: its window layers take the window's
    512 tiles where the full layers keep the configured 1024, and
    publish the score entries those tiles compute for each one the
    window keeps, 2.00 at 8192 positions (3.87 at 1024 tiles)."""
    from dlrover_tpu.common import telemetry
    from dlrover_tpu.models.llama import flash_blocks

    sizes = bench["lookup"].data("configs", "laguna-xs.2")
    family = bench["families"].build(sizes)
    config = family.model_config
    assert flash_blocks(config) == dict(
        block_q=1024, block_k=1024, bwd_block_q=0, bwd_block_k=0)
    assert flash_blocks(config, config.window) == dict(
        block_q=512, block_k=512, bwd_block_q=512, bwd_block_k=512)
    params = jax.eval_shape(family.init, jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (sizes["batch"], sizes["sequence"] + 1), jnp.int32)}
    telemetry.enable("test")
    try:
        jax.eval_shape(family.loss_fn, params, batch, jax.random.key(0))
        gauges = {g["name"]: g["value"]
                  for g in telemetry.snapshot()["gauges"]}
    finally:
        telemetry.install_from_env()
    assert 1.99 <= gauges["model.attn.window_computed_per_kept"] <= 2.01


# ------------------------------------------------------------ trainer


def test_trainer_counts_pairs_and_the_loss_falls(family, tmp_path,
                                                 isolated_ckpt_env):
    """Through Trainer -> auto_accelerate (bf16 compute, the whole-loss
    checkpoint round the model's own): the loss falls, and the counts of
    every step whose loss was read back, in token-expert pairs, are in
    the program's counters and one event a read-back."""
    from dlrover_tpu.common import telemetry
    from dlrover_tpu.parallel import MeshConfig, Strategy
    from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

    config = family.model_config
    losses = []

    class Tap(logging.Handler):
        def emit(self, record):
            if str(record.msg).startswith("step %d epoch %d loss"):
                losses.append(float(record.args[2]))

    telemetry.enable("test")
    trainer = Trainer(
        laguna.laguna_loss_fn(config), family.init,
        laguna.laguna_logical_axes(config),
        TrainingArgs(
            output_dir=str(tmp_path / "out"), seed=3, max_steps=4,
            learning_rate=3e-3, log_steps=1, flash_checkpoint=False,
            strategy=Strategy(mesh=MeshConfig(data=8)),
        ),
        train_data=[{"tokens": np.random.RandomState(4).randint(
            0, 256, (8, 129)).astype(np.int32)}] * 4,
    )
    log = logging.getLogger("dlrover_tpu.trainer.trainer")
    tap = Tap()
    log.addHandler(tap)
    try:
        trainer.train()
        snapshot = telemetry.snapshot()
    finally:
        log.removeHandler(tap)
        trainer.close()
        telemetry.install_from_env()
    counters = {c["name"]: c["value"] for c in snapshot["counters"]}
    events = [e for e in snapshot["events"] if e["kind"] == "step.counts"]
    assert losses[-1] < losses[0] - 0.05
    assert counters["moe.tokens_routed"] == 4 * 4 * 8 * 128 * 4
    assert 0 < counters["moe.tokens_held"] < counters["moe.tokens_routed"]
    assert [e["step"] for e in events] == [1, 2, 3, 4]
    for name in laguna.COUNTS:
        assert sum(e[name] for e in events) == counters[name]
