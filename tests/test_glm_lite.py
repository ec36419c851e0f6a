"""The GLM-4.7-Flash model (models/glm_lite.py) on the CPU at tiny sizes:
held to the benchmark's plain float32 reference
(``benchmark/family/glm_lite.py``, which shares no code with the
program) on the main and the MTP depth's logits, both losses and every
parameter's gradient; bf16 passes the comparison the cell makes and the
program's own int8 matmuls fail it; the correction bias moves the
choice and not the weights; a near tie at the k-th choice is followed
and a mis-route is not; the eight chips' shares of a routed layer, with
what every chip computes alike counted once, add up to the uncut
layer; the MTP depth's labels; the carry through the runs, the
Trainer's counts, the configuration and what is refused.
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

from dlrover_tpu.models import glm_lite
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
               "family": lookup.module("family", "glm_lite")}
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def sizes(bench):
    """The benchmark's toy configuration of the family (hidden 64; a
    dense layer and four routed ones, 4 heads of 8 + 8 over a value of
    16, latent ranks 24 and 16; 4 of 16 experts held, 4 a token; one MTP
    depth; 2 x 128 tokens), with the plain attention in place of the
    interpreted kernel."""
    sizes = bench["lookup"].data("configs", "toy-glm-lite")
    sizes["program"] = dict(sizes["program"], attn_impl="reference")
    return sizes


@pytest.fixture(scope="module")
def family(bench, sizes):
    return bench["families"].build(sizes)


def _lively(params, seed, bias=0.0):
    """Seeded weights at which every part of a layer counts: matrices
    at 1/sqrt(fan-in), the norms' scales moved off 1; the correction
    bias ``bias`` times a normal draw (0: its published value)."""
    rs = np.random.RandomState(seed)

    def leaf(path, x):
        name = path[-1].key
        if "norm" in name:
            return jnp.asarray(1 + 0.2 * rs.randn(*x.shape), jnp.float32)
        if name == "embed":
            return x
        if name == "router_bias":
            return jnp.asarray(bias * rs.randn(*x.shape), jnp.float32)
        return jnp.asarray(
            rs.randn(*x.shape) * x.shape[-2] ** -0.5, jnp.float32)

    return jax.tree_util.tree_map_with_path(leaf, params)


def _tokens(seed=1):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, 256, (2, 129)), jnp.int32)


def _reference(bench, sizes, params, tokens, follow=None, eps=0.0):
    """(loss, main logits [B, S, V], MTP logits [B, S - 1, V], followed)
    of the plain reference."""
    module = bench["family"]
    rows = [module.logits(sizes, params, row[:-1],
                          None if follow is None else follow[:, i], eps)
            for i, row in enumerate(tokens)]
    loss = jnp.mean(jnp.stack([
        module.loss(sizes, main, ahead, row)
        for (main, ahead, _), row in zip(rows, tokens)
    ]))
    main, ahead, followed = zip(*rows)
    return loss, jnp.stack(main), jnp.stack(ahead), sum(followed)


def _reference_of(bench, sizes, params, tokens):
    """The reference's loss, logits and gradient."""

    def ref_loss(params):
        loss, main, ahead, _ = _reference(bench, sizes, params, tokens)
        return loss, (main, ahead)

    (loss, logits), grads = jax.jit(
        jax.value_and_grad(ref_loss, has_aux=True))(params)
    return {"loss": loss, "logits": logits, "grads": grads}


@pytest.fixture(scope="module")
def toy(bench, sizes, family):
    """The toy family, seeded lively weights, a batch, and the
    reference's loss, logits and gradient on them."""
    params = _lively(family.init(jax.random.key(0)), 0)
    tokens = _tokens()
    return {"family": family, "params": params, "tokens": tokens,
            **_reference_of(bench, sizes, params, tokens)}


def _system(toy, dtype, mode=None, params=None, **config):
    """The program's loss, (main, MTP) logits and gradients in ``dtype``
    (params cast as auto_accelerate casts them), optionally with its
    own 8-bit matmuls."""
    cfg = dataclasses.replace(toy["family"].model_config, dtype=dtype,
                              **config)
    loss_fn = glm_lite.glm_lite_loss_fn(cfg)
    tokens = toy["tokens"]

    def loss(params):
        cast = jax.tree.map(lambda x: x.astype(dtype), params)
        logits = glm_lite.glm_lite_apply(cfg, cast, tokens[:, :-1])
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
# logits 9e-7 of their rms (main and MTP alike), loss 5e-7, a leaf's
# gradient 3e-6 at most.
F32_LOGITS, F32_LOSS, F32_GRAD_LEAF = 2e-5, 2e-5, 2e-4
# the bf16 configuration against the reference that follows it at near
# ties (NEAR_TIE below), relative rms of the main and MTP logits
# together over three seeds of weights: 0.0206, 0.0222, 0.0236; the
# program's int8 matmuls (every latent projection's, W_o's, W_eh's and
# the dense and shared MLPs': the grouped matmuls have no 8-bit form)
# 0.0472, 0.0501, 0.0541. The limit is the geometric mean of the
# nearest two: each side has a factor of 1.41. bf16 takes another set at
# 51 to 64 of the 1,280 token-layer choices (int8 at 117 to 145), and
# without the following reads 0.041-0.077 (int8 0.099-0.115); at a
# margin of 0.02 bf16 reads 0.0206-0.0303.
BF16_LOGITS = 0.0334
# a near tie, at toy size, in the sigmoid's scores plus the bias: the
# program's set a top-k of the reference's selection scores within this
NEAR_TIE = 0.05


@pytest.mark.parametrize("attn_impl", ["reference", "flash"])
def test_model_is_the_reference_in_float32(toy, attn_impl):
    (loss, logits), grads = _system(toy, "float32", attn_impl=attn_impl)
    assert _rel_rms(logits[0], toy["logits"][0]) < F32_LOGITS
    assert _rel_rms(logits[1], toy["logits"][1]) < F32_LOGITS
    assert abs(float(loss - toy["loss"])) < F32_LOSS
    parts = set()
    for (path, got), want in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0],
        jax.tree.leaves(toy["grads"]),
    ):
        name = jax.tree_util.keystr(path)
        parts.update(p for p in ("00_dense", "01_moe", "mtp") if p in name)
        if name.endswith("['router_bias']"):
            # it takes part in the choice alone: no gradient reaches it
            assert not np.asarray(got).any() and not np.asarray(want).any()
            continue
        assert np.asarray(want).any(), name
        assert _rel_rms(got, want) < F32_GRAD_LEAF, name
    assert parts == {"00_dense", "01_moe", "mtp"}


def _followed(bench, sizes, toy, cfg, params=None, eps=NEAR_TIE):
    """The reference's (main, MTP) logits where it follows the program
    ``cfg``'s choices at near ties, and how many it followed."""
    params = toy["params"] if params is None else params
    dtype = jnp.dtype(cfg.dtype)
    cast = jax.tree.map(lambda x: x.astype(dtype), params)
    follow = jax.jit(
        lambda p, t: glm_lite.glm_lite_apply(cfg, p, t, choices=True)[2]
    )(cast, toy["tokens"][:, :-1])
    _, main, ahead, followed = jax.jit(
        lambda p, f: _reference(bench, sizes, p, toy["tokens"], f, eps)
    )(params, follow)
    return (main, ahead), int(followed)


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
    assert followed < 0.06 * 5 * 256
    with quant_autocast("int8"):
        want8, _ = _followed(bench, sizes, toy, cfg, params)
    (_, logits8), _ = _system(toy, "bfloat16", mode="int8", params=params)
    assert _rel_rms(logits8, want8) > BF16_LOGITS


def test_the_bias_moves_the_choice_and_not_the_weights(bench, sizes,
                                                       family):
    """``route`` on random tokens with a random nonzero correction bias:
    the sets are the top-k of ``s + b`` and differ from the unbiased
    ones, the weights are ``s`` alone over the set, normalised and
    scaled; and the whole model with such a bias in every routed layer
    is the reference with the same bias, whose gradient is zero."""
    cfg = dataclasses.replace(family.model_config, dtype="float32")
    rs = np.random.RandomState(5)
    y = jnp.asarray(rs.randn(2, 64, 64), jnp.float32)
    router = jnp.asarray(rs.randn(64, 16) * 0.125, jnp.float32)
    bias = jnp.asarray(rs.randn(16) * 0.3, jnp.float32)
    plain, _ = glm_lite.route(cfg, y, router, jnp.zeros(16))
    choice, weight = glm_lite.route(cfg, y, router, bias)
    s = jax.nn.sigmoid(y @ router)
    assert (np.sort(np.asarray(plain), -1)
            != np.sort(np.asarray(choice), -1)).any()
    want = np.asarray(jax.lax.top_k(s + bias, 4)[1])
    np.testing.assert_array_equal(np.sort(np.asarray(choice), -1),
                                  np.sort(want, -1))
    top = jnp.take_along_axis(s, choice, -1)
    np.testing.assert_allclose(weight, 1.8 * top / top.sum(-1, keepdims=True),
                               rtol=1e-6)
    # the model and the reference, both given the bias
    params = _lively(family.init(jax.random.key(7)), 7, bias=0.3)
    toy = {"family": family, "params": params, "tokens": _tokens(2),
           **_reference_of(bench, sizes, params, _tokens(2))}
    (loss, logits), grads = _system(toy, "float32")
    assert _rel_rms(logits, toy["logits"]) < F32_LOGITS
    assert abs(float(loss - toy["loss"])) < F32_LOSS
    assert not np.asarray(grads["layers"]["01_moe"]["router_bias"]).any()
    # and the bias reaches the result: without it the sets move
    unbiased = _lively(family.init(jax.random.key(7)), 7)
    (_, other), _ = _system(toy, "float32", params=unbiased)
    assert _rel_rms(other, logits) > 10 * F32_LOGITS


def _swap(lo, hi):
    """The fault: the k+1-th expert in place of the k-th for the tokens
    whose selection scores for the two lie [lo, hi) apart."""

    def route(config, y, router, bias):
        choice, _ = ROUTE(config, y, router, bias)
        k = config.top_k
        s = jax.nn.sigmoid(jnp.matmul(
            y, router.astype(y.dtype), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32))
        best, index = jax.lax.top_k(s + bias, k + 1)
        margin = best[..., k - 1] - best[..., k]
        swap = (margin >= lo) & (margin < hi)
        choice = choice.at[..., k - 1].set(
            jnp.where(swap, index[..., k], index[..., k - 1]))
        top = jnp.take_along_axis(s, choice, -1)
        return choice, top / jnp.sum(top, -1, keepdims=True) \
            * config.routed_scale

    return route


ROUTE = glm_lite.route


@pytest.mark.parametrize("lo,hi,passes", [
    (0.0, NEAR_TIE, True),          # ties inside eps: followed
    (2 * NEAR_TIE, 100.0, False),   # mis-routes outside it
])
def test_a_near_tie_is_followed_and_a_misroute_is_not(
        bench, sizes, toy, monkeypatch, lo, hi, passes):
    """A float32 program that takes the k+1-th expert for the k-th:
    where the two lie within ``eps`` the reference follows its set and
    the two agree as two float32 implementations do; where they lie
    further apart the reference keeps its own and the comparison fails
    by more than bf16's limit."""
    monkeypatch.setattr(glm_lite, "route", _swap(lo, hi))
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
    """``glm_lite_apply(choices=True)`` is ``glm_lite_apply``'s own pass
    with a third part in its carry: the logits are the same numbers bit
    for bit, every routed layer's row (the MTP depth's last) is that
    layer's set of k distinct experts, and the dense layer's row is
    left zero."""
    cfg = dataclasses.replace(toy["family"].model_config, dtype="bfloat16")
    cast = jax.tree.map(lambda x: x.astype(jnp.bfloat16), toy["params"])
    tokens = toy["tokens"][:, :-1]
    plain = jax.jit(lambda p, t: glm_lite.glm_lite_apply(cfg, p, t))(
        cast, tokens)
    *logits, choices = jax.jit(
        lambda p, t: glm_lite.glm_lite_apply(cfg, p, t, choices=True))(
            cast, tokens)
    for a, b in zip(plain, logits):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    choices = np.asarray(choices)
    assert choices.shape == (6, *tokens.shape, cfg.top_k)
    assert not choices[0].any()
    for row in choices[1:]:
        assert (np.sort(row, -1)[..., 1:] != np.sort(row, -1)[..., :-1]).all()
        assert len(np.unique(row)) == cfg.n_experts
    assert not np.array_equal(choices[4], choices[5])


def _reference_logits_report(bench, family, toy, params=None):
    """What the cell's agreement check reads: the family's own
    ``apply`` and ``reference_logits`` (main and MTP side by side, the
    reference asking the program for its choices), first row, by the
    harness's ``compare`` and the family's limits."""
    params = toy["params"] if params is None else params
    row = toy["tokens"][0]
    cast = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    # (a new function each time: jit would hand a patched program the
    # trace of the sound one)
    got = jax.jit(lambda p, t: family.apply(p, t))(cast, row[None, :-1])[0]
    want = jax.jit(lambda p, t: family.reference_logits(p, t))(
        params, row[:-1])
    return bench["reference"].compare(got, 0.0, want, 0.0,
                                      family.tolerances, last=128)


def test_the_cell_compares_both_heads_side_by_side(bench, family, toy,
                                                   monkeypatch):
    """``apply`` and ``reference_logits`` hand the harness ``[S, 2 V]``:
    the main logits, then the MTP depth's, zeros where a position has
    no MTP row; the family's limits are on those logits alone, and a
    fault in the MTP depth alone moves them by more than bf16 does."""
    # the toy's margin: the cell's is of the published widths
    monkeypatch.setattr(bench["family"], "NEAR_TIE_EPS", NEAR_TIE)
    sound = _reference_logits_report(bench, family, toy)
    assert sound["logits_rel_rms"] < BF16_LOGITS
    assert set(family.tolerances) == {"logits_rel_rms", "logits_rel_max"}
    row = toy["tokens"][0]
    got = family.apply(toy["params"], row[None, :-1])[0]
    assert got.shape == (128, 2 * 256)
    assert not np.asarray(got[-1, 256:]).any()
    assert np.asarray(got[-2, 256:]).any()
    # the program against a reference whose MTP depth alone differs
    broken = jax.tree.map(lambda x: x, toy["params"])
    broken["mtp"]["w_eh"] = broken["mtp"]["w_eh"] * 1.5
    cast = jax.tree.map(lambda x: x.astype(jnp.bfloat16), toy["params"])
    sound_program = jax.jit(lambda p, t: family.apply(p, t))(
        cast, row[None, :-1])[0]
    other = family.reference_logits(broken, row[:-1])
    assert np.array_equal(np.asarray(other[:, :256]),
                          np.asarray(family.reference_logits(
                              toy["params"], row[:-1])[:, :256]))
    assert bench["reference"].compare(
        sound_program, 0.0, other, 0.0, family.tolerances, last=128)[
        "logits_rel_rms"] > 3 * BF16_LOGITS


def test_a_program_followed_at_too_many_choices_is_followed_nowhere(
        bench, family, toy, monkeypatch, capfd):
    """``reference_logits`` follows a sound program at a handful of near
    ties. One whose sets differ from the reference's at more than
    ``MAX_FOLLOWED_SHARE`` of them is compared with the reference's own
    sets throughout, even where every difference is a tie inside eps,
    and fails as a program that routes wrongly does."""
    module = bench["family"]
    monkeypatch.setattr(module, "NEAR_TIE_EPS", 1.0)
    monkeypatch.setattr(module, "MAX_FOLLOWED_SHARE", 0.15)
    sound = _reference_logits_report(bench, family, toy)
    assert "none is" not in capfd.readouterr().err
    # the k+1-th expert for every token whose two lie within 1
    monkeypatch.setattr(glm_lite, "route", _swap(0.0, 1.0))
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
    run = toy["params"]["layers"]["01_moe"]
    layer = {k: v[0] for k, v in run.items() if k not in whole}
    layer["router_bias"] = jnp.asarray(rs.randn(16) * 0.1, jnp.float32)
    x = jnp.asarray(rs.randn(1, 128, 64), jnp.float32)
    tables = glm_lite.rope_tables(cfg, 128)

    def chip(first):
        share = dict(layer, **{k: v[first:first + 2]
                               for k, v in whole.items()})
        c = dataclasses.replace(cfg, held_first=first, held_experts=2)
        (out, counts), _ = glm_lite._layer_fn(c, "moe")(
            (x, jnp.zeros((4,), jnp.float32)), share, *tables)
        return out, counts

    outs, counts = zip(*[chip(first) for first in range(0, 16, 2)])
    # what every chip computes alike (attention, norms, router, shared
    # expert): the layer with no expert's part
    zero = dict(layer, **{k: jnp.zeros_like(v[:2]) for k, v in whole.items()})
    (common, _), _ = glm_lite._layer_fn(
        dataclasses.replace(cfg, held_experts=2), "moe")(
            (x, jnp.zeros((4,), jnp.float32)), zero, *tables)
    total = common + sum(out - common for out in outs)
    uncut = dict(sizes, n_routed_experts=16)
    want, _ = ref._layer(uncut, "moe", x[0], dict(layer, **whole), None, 0.0)
    np.testing.assert_allclose(total[0], want, atol=2e-5)
    # every pair is held on exactly one chip
    assert sum(float(c[1]) for c in counts) == float(counts[0][0]) == 128 * 4


def test_the_mtp_labels_are_two_ahead_and_the_last_is_ignored(toy):
    """The loss is the main head's next-token CE over all S positions
    plus 0.3 times the MTP depth's CE of ``t_{i+2}`` over the first S - 1
    (the last has no label), each from ``glm_lite_apply``'s logits of
    the same weights; the counts say S and S - 1 a row."""
    cfg = dataclasses.replace(toy["family"].model_config, dtype="float32")
    tokens = toy["tokens"]
    loss, aux = jax.jit(glm_lite.glm_lite_loss_fn(cfg).with_aux)(
        toy["params"], {"tokens": tokens}, None)
    main, ahead = glm_lite.glm_lite_apply(cfg, toy["params"], tokens[:, :-1])

    def ce(logits, labels):
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))

    want = ce(main, tokens[:, 1:]) + 0.3 * ce(ahead, tokens[:, 2:])
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    # one ahead is another loss
    assert abs(float(ce(ahead, tokens[:, 1:-1])
                     - ce(ahead, tokens[:, 2:]))) > 1e-3
    assert aux["lm.tokens"] == 2 * 128
    assert aux["mtp.tokens"] == 2 * 127
    # the last token is the label of the last MTP position alone: the
    # loss moves with it only through the main head's last label
    moved = tokens.at[:, -1].set((tokens[:, -1] + 1) % 256)
    loss2 = jax.jit(glm_lite.glm_lite_loss_fn(cfg))(
        toy["params"], {"tokens": moved}, None)
    main_only = ce(main, moved[:, 1:]) + 0.3 * ce(ahead, moved[:, 2:])
    assert float(loss2) == pytest.approx(float(main_only), rel=1e-5)


def test_the_carry_through_the_runs_with_and_without_layer_input(toy):
    """``(x, counts)`` through ``stage_run_scan``'s runs and the MTP
    depth's: recomputing a layer from its kept input gives the loss,
    the counts and the gradients of the plain scan, and the counts are
    in pairs, the MTP depth's among them."""
    batch = {"tokens": toy["tokens"]}

    def run(remat):
        cfg = dataclasses.replace(toy["family"].model_config,
                                  dtype="float32", remat=remat)
        return jax.jit(jax.value_and_grad(
            glm_lite.glm_lite_loss_fn(cfg).with_aux, has_aux=True)
        )(toy["params"], batch, None)

    (loss, counts), grads = run(True)
    (want_loss, want_counts), want = run(False)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert counts == want_counts
    # four routed layers and the MTP depth's, 2 x 128 tokens, 4 pairs
    assert counts["moe.tokens_routed"] == 5 * 2 * 128 * 4
    assert 0 < counts["moe.tokens_held"] < counts["moe.tokens_routed"]
    assert counts["moe.expert_load_mean"] * 4 == counts["moe.tokens_held"]
    assert counts["moe.expert_load_max"] >= counts["moe.expert_load_mean"]
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=1e-7)


# ---------------------------------------------------------- configuration


def test_configuration_builds_with_its_parameter_count(bench):
    """The benchmark's configuration file under ``jax.eval_shape``
    (nothing is allocated): one chip's share, a dense layer 84.7M, four
    routed layers of 106.8M (31.3M outside the 8 experts held), the MTP
    depth 115.2M and an eighth of the vocabulary twice, 706.5M."""
    with open(os.path.join(BENCH, "configs", "glm-4.7-flash.json")) as f:
        sizes = json.load(f)
    family = bench["families"].build(sizes)
    config = family.model_config
    assert config.kinds() == ("dense",) + ("moe",) * 4
    assert config.held == (0, 8) and config.n_experts == 64
    shapes = jax.eval_shape(family.init, jax.random.key(0))
    total = sum(leaf.size for leaf in jax.tree.leaves(shapes))
    assert total == config.param_count() == 706_518_848

    def count(tree):
        return sum(leaf.size for leaf in jax.tree.leaves(tree))

    mla = 1_572_864 + 768 + 3_932_160 + 1_179_648 + 512 + 4_587_520 \
        + 10_485_760
    assert mla == 21_759_232
    assert count(shapes["layers"]["00_dense"]) == 84_677_888 \
        == mla + 2 * 2048 + 62_914_560
    routed = mla + 2 * 2048 + 131_072 + 64 + 9_437_184 + 8 * 9_437_184
    assert count(shapes["layers"]["01_moe"]) == 4 * routed \
        == 4 * 106_829_120
    assert count(shapes["mtp"]) == 115_223_872 \
        == 8_388_608 + 3 * 2048 + routed
    assert count(shapes["embed"]) + count(shapes["lm_head"]) == 79_298_560
    axes = family.logical_axes
    for leaf, names in zip(
        jax.tree.leaves(shapes),
        jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple)),
    ):
        assert len(names) == leaf.ndim


def test_the_configuration_keeps_every_published_width(bench):
    """The file's widths are the published ones, which are the model's
    defaults; depth, the experts held and the vocabulary are the cut,
    each with its published value beside it."""
    sizes = bench["lookup"].data("configs", "glm-4.7-flash")
    config = bench["families"].build(sizes).model_config
    published = glm_lite.GlmLiteConfig()
    for name in ("dim", "heads", "q_rank", "kv_rank", "nope_dim",
                 "rope_dim", "v_dim", "rope_theta", "mlp_dim", "n_experts",
                 "top_k", "expert_dim", "shared_dim", "routed_scale",
                 "norm_eps", "dense_layers"):
        assert getattr(config, name) == getattr(published, name), name
    assert sizes["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert sizes["published"] == {"num_hidden_layers": 47,
                                  "n_routed_experts": 64,
                                  "vocab_size": 154880}
    assert (config.n_layers, config.held_experts, config.vocab_size) == \
        (5, 8, 19360)
    assert published.n_layers == 47 and published.vocab_size == 154880


def test_the_weights_are_the_configuration_s_whatever_the_seed(bench):
    """Every seed does the same work: ``init`` draws from the file's
    ``weights_seed`` and ignores the Trainer's key."""
    sizes = bench["lookup"].data("configs", "toy-glm-lite")
    family = bench["families"].build(sizes)
    a = family.init(jax.random.key(1))
    b = family.init(jax.random.key(2))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    other = bench["families"].build(dict(sizes, weights_seed=4))
    assert not np.array_equal(np.asarray(a["embed"]),
                              np.asarray(other.init(None)["embed"]))


@pytest.mark.parametrize("change,match", [
    ({"v_dim": 128}, "one head width"),
    ({"held_first": 60, "held_experts": 8}, "not among 64"),
    ({"top_k": 65}, "top_k 65 of 64"),
    ({"rope_dim": 63, "nope_dim": 193}, "rotated slice"),
    ({"dense_layers": 48}, "48 dense layers of 47"),
])
def test_what_cannot_be_built_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        glm_lite.GlmLiteConfig(**change)


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", True), ("topk_method", "greedy"),
    ("n_group", 8), ("norm_topk_prob", False),
    ("rope_scaling", {"type": "yarn"}), ("num_nextn_predict_layers", 2),
    ("num_key_value_heads", 2),
])
def test_what_the_program_does_not_implement_is_refused(bench, key, value):
    sizes = dict(bench["lookup"].data("configs", "toy-glm-lite"),
                 **{key: value})
    with pytest.raises(ValueError, match=key):
        bench["families"].build(sizes)


@pytest.mark.parametrize("axes,error,match", [
    ({"pipe": 2, "data": 4}, NotImplementedError, "unlike layers"),
    ({"expert": 2, "data": 4}, NotImplementedError, "expert mesh axis"),
    ({"seq": 2, "data": 4}, NotImplementedError, "latent attention"),
])
def test_meshes_it_cannot_run_on_are_refused(family, axes, error, match):
    from dlrover_tpu.parallel import mesh as mesh_lib

    config = family.model_config
    params = jax.eval_shape(family.init, jax.random.key(0))
    before = mesh_lib._global_mesh
    mesh_lib.set_mesh(mesh_lib.build_mesh(mesh_lib.MeshConfig(**axes)))
    try:
        with pytest.raises(error, match=match):
            jax.eval_shape(
                lambda p: glm_lite.glm_lite_apply(
                    config, p, jnp.zeros((4, 128), jnp.int32)), params)
    finally:
        mesh_lib._global_mesh = before


def test_decode_is_refused(family):
    from dlrover_tpu.serving.engine import init_slot_cache

    with pytest.raises(NotImplementedError, match="GlmLiteConfig"):
        init_slot_cache(family.model_config, slots=2, capacity=16)


def test_build_publishes_its_shape(bench):
    from dlrover_tpu.common import telemetry

    with open(os.path.join(BENCH, "configs", "glm-4.7-flash.json")) as f:
        sizes = json.load(f)
    telemetry.enable("test")
    try:
        bench["families"].build(sizes)
        gauges = {(g["name"], *sorted(g["labels"].values())): g["value"]
                  for g in telemetry.snapshot()["gauges"]}
    finally:
        telemetry.install_from_env()
    assert gauges["model.layers", "dense"] == 1
    assert gauges["model.layers", "moe"] == 4
    assert gauges["model.layers", "mtp"] == 1
    assert gauges["model.params", "attention"] == 6 * (21_759_232 + 2048)
    assert gauges["model.params", "experts"] == 5 * 8 * 3 * 2048 * 1536
    assert gauges["model.params", "router"] == 5 * (2048 * 64 + 64)
    assert gauges["model.params", "shared_expert"] == \
        5 * (3 * 2048 * 1536 + 2048)
    assert gauges["model.params", "dense_mlp"] == 3 * 2048 * 10240 + 2048
    assert gauges["model.params", "mtp"] == 2 * 2048 * 2048 + 3 * 2048
    assert gauges["model.params", "embedding"] == 19360 * 2048
    assert gauges["model.params", "head"] == 19360 * 2048 + 2048
    assert gauges["model.moe.experts", "held"] == 8
    assert gauges["model.moe.experts", "published"] == 64
    assert gauges["model.moe.top_k", ] == 4


# ------------------------------------------------------------ trainer


def test_trainer_counts_pairs_and_tokens_and_the_loss_falls(
        family, tmp_path, isolated_ckpt_env):
    """Through Trainer -> auto_accelerate (bf16 compute): the loss
    falls, and the counts of every step whose loss was read back, the
    routed pairs and each loss's labelled positions, are in the
    program's counters and one event a read-back."""
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
        glm_lite.glm_lite_loss_fn(config), family.init,
        glm_lite.glm_lite_logical_axes(config),
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
    assert counters["moe.tokens_routed"] == 4 * 5 * 8 * 128 * 4
    assert 0 < counters["moe.tokens_held"] < counters["moe.tokens_routed"]
    assert counters["lm.tokens"] == 4 * 8 * 128
    assert counters["mtp.tokens"] == 4 * 8 * 127
    assert [e["step"] for e in events] == [1, 2, 3, 4]
    for name in glm_lite.COUNTS + glm_lite.TOKEN_COUNTS:
        assert sum(e[name] for e in events) == counters[name]
