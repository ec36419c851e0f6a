"""The ``laguna`` family (poolside Laguna, ``model_type`` ``laguna``),
run through ``dlrover_tpu/models/laguna.py``.

Sizes: ``D`` hidden, ``H`` query heads of a layer
(``num_attention_heads_per_layer``) over ``KV`` key/value heads of
``d``, ``W`` the sliding window, ``E`` experts of ``m`` of which ``k``
a token, a shared expert of ``s``, dense layers of ``M``. With ``x^ =
RMSNorm(x)`` and no bias anywhere, a layer is:

1.  ``q = x^ W_q``, ``k = x^ W_k``, ``v = x^ W_v`` (``H``, ``KV``, ``KV``
    heads of ``d``)
2.  rotary embedding, rotate-half: a ``sliding_attention`` layer on all
    ``d`` channels at ``rope_theta`` 10^4; a ``full_attention`` layer on
    the first ``d x partial_rotary_factor`` at YaRN's frequencies
    (``rope_theta``, ``factor``, ``original_max_position_embeddings``,
    ``beta_fast``, ``beta_slow``), its cos and sin times
    ``attention_factor``
3.  ``a_h = softmax(q_h k_g^T / sqrt(d)) v_g``, ``g = h // (H / KV)``,
    causal; in a ``sliding_attention`` layer position ``t`` sees ``s``
    with ``0 <= t - s < W``
4.  ``x = x + W_o concat_h(sigmoid(x^ W_gate)_h a_h)``: the ``gating``,
    one scalar a head
5.  ``y = RMSNorm(x)``; a ``dense`` layer: ``x = x + FFN(y)``,
    ``FFN(z) = (silu(z W_g) * z W_u) W_d``
6.  a ``sparse`` layer: ``p = softmax(y W_r)`` over all ``E`` experts,
    ``T`` = the ``k`` largest, ``w_e = c p_e / sum_T p`` (``c`` =
    ``moe_routed_scaling_factor``), ``x = x + sum_{e in T} w_e
    FFN_e(y) + FFN_shared(y)``; a pair whose expert is not held here
    adds 0

``x_0 = E[tokens]``, ``logits = RMSNorm(x_L) W_head``, head untied.

The reference below is float32 throughout and shares nothing with the
program: no sort and no grouped matmul (every held expert is applied to
all rows, times its weight in each token's choice, zero where it was
not chosen), YaRN's frequencies computed here again, attention over
blocks of query rows with the window as a mask. It is given the same
share: the file's ``num_experts`` experts from ``first_expert`` on are
held, the router scores ``published.num_experts``.

**A choice of the k best is not continuous.** Where the reference's
``k``-th and ``k+1``-th experts lie closer than the rounding of the
program's bf16 hidden state, the two choose other sets, and a token
differs by the weight of one expert's whole output. ``NEAR_TIE_EPS``
says what the comparison does about that, with its readings.

Weights arrive as the Trainer holds them: ``params["layers"]`` maps
``<index>_<kind>`` to one run of like layers stacked on axis 0 (a kind
is ``dense_full``, ``moe_window`` or ``moe_full``); the experts' gate
and up matrices side by side in ``w_in [L, held, D, 2 m]``, the dense
and the shared ones likewise.
"""

from __future__ import annotations

import math
import sys

import flops
import jax
import jax.numpy as jnp
import reference
from families import Family

# Where the program's bf16 hidden state and the reference's float32
# one put the k-th and the k+1-th expert in another order, the token is
# routed otherwise and differs by one expert's whole output (times its
# weight). So at a near tie the reference follows the program: a token
# and layer where the program chose another set of experts than the
# reference would AND that set is a top-k of the reference's own router
# logits to within NEAR_TIE_EPS (its least logit at or above the largest
# it left out, less eps: a ratio of p of exp(-eps)). Everywhere else the
# reference keeps its own set, so a program that routes wrongly still
# fails (tests/test_laguna.py: a mis-route outside eps fails, a tie
# inside it passes). The rounding of the hidden state moves a router
# logit by about 0.02 in the later layers, so the margin is in logits:
# in p the top-8 of 256 lie between about 0.01 and 0.05, and one eps in
# p is a tie for one token and a mis-route for another.
#
# Readings, relative rms / largest of the logits at the last 256
# positions, on one v5e chip at the cell's widths, weights and 8,192
# positions, the first sequence of four seeds' batches (2147483659 and
# the next three 7,919 apart; benchmark/tests/laguna_precision_probe.py,
# PERF.md, section 6):
# bf16 chooses another set at 14% of the 32,768 token-layer choices
# (4,507-4,641 followed at eps 0.05, 4,525-4,661 at 0.1); left alone it
# reads 0.0228-0.0246 / 0.335-0.448, its largest logit a token routed
# otherwise (over the largest's limit on every seed); at eps 0.02 it
# still reads 0.306 and 0.399 on two seeds; followed at eps 0.05
# 0.0151-0.0153 / 0.080-0.100. The program's int8 matmuls move 25% of
# the sets (8,102-8,198 at 0.05) and read 0.0611-0.0643 / 0.439-0.531
# left alone, 0.0565-0.0582 / 0.372-0.472 followed at 0.05, 0.0542-0.0550
# at 0.1.
NEAR_TIE_EPS = 0.05

# A run that would be followed at more than this share of its
# token-layer choices is followed nowhere: it is compared with the
# reference's own sets throughout. bf16 is followed at 14% and the
# int8 control at 25%: both under it, and the limit below separates
# them; a program that moves more than a third of the sets by as
# little as eps is no rounding.
MAX_FOLLOWED_SHARE = 0.35

# The geometric mean of bf16's largest 0.0153 and int8's least 0.0565
# followed at eps: bf16 passes with a factor of 1.92 to spare and int8
# fails by as much, and by its largest logit (8 x the limit = 0.235;
# bf16 reads 0.100 at most, int8 0.372 at least) too.
LOGITS_REL_RMS_TOL = 0.0294

QUERY_BLOCK = 256       # rows of queries scored at a time

KINDS = {("full_attention", "dense"): "dense_full",
         ("sliding_attention", "sparse"): "moe_window",
         ("full_attention", "sparse"): "moe_full"}


def _yarn(dim, rope):
    """YaRN's inverse frequencies of ``dim`` rotated channels
    (arXiv:2309.00071): channel pairs that turn more than ``beta_fast``
    times over the original context keep theirs, those that turn fewer
    than ``beta_slow`` times are divided by ``factor``, a linear ramp
    between (its ends rounded outwards)."""
    theta, original = float(rope["rope_theta"]), \
        rope["original_max_position_embeddings"]

    def pair(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(pair(rope["beta_fast"])), 0)
    hi = min(math.ceil(pair(rope["beta_slow"])), dim - 1)
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    keep = 1 - jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - lo) / max(hi - lo, 1e-3),
        0, 1)
    return inv / rope["factor"] * (1 - keep) + inv * keep


def _rotary(x, kind, sizes):
    """Step 2 on ``x [S, heads, d]``."""
    rope = sizes["rope_parameters"][
        "full_attention" if kind.endswith("full") else "sliding_attention"]
    d = x.shape[-1]
    n = int(d * rope["partial_rotary_factor"])
    if rope["rope_type"] == "yarn":
        inv, scale = _yarn(n, rope), float(rope["attention_factor"])
    else:
        inv = float(rope["rope_theta"]) ** (
            -jnp.arange(0, n, 2, dtype=jnp.float32) / n)
        scale = 1.0
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos = (jnp.cos(angle) * scale)[:, None]
    sin = (jnp.sin(angle) * scale)[:, None]
    x1, x2 = x[..., :n // 2], x[..., n // 2:n]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., n:]], -1)


def _attention(q, k, v, window):
    """Step 3: q [S, H, d], k/v [S, KV, d] -> [S, H, d], ``QUERY_BLOCK``
    rows of queries at a time."""
    seq, heads, d = q.shape
    group = heads // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    block = min(QUERY_BLOCK, seq)
    if seq % block:
        raise ValueError(f"{seq} positions are no multiple of {block}")
    keys = jnp.arange(seq)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
        at = (start + jnp.arange(block))[:, None]
        visible = keys[None, :] <= at
        if window:
            visible = visible & (keys[None, :] > at - window)
        probs = jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    return jax.lax.map(rows, jnp.arange(0, seq, block)).reshape(seq, heads, d)


def _ffn(z, w_in, w_out):
    mid = w_out.shape[0]
    return (jax.nn.silu(z @ w_in[:, :mid]) * (z @ w_in[:, mid:])) @ w_out


def _choose(sizes, scores, theirs, eps):
    """Step 6's set: the k largest of the router's ``scores [S, E]``
    (the logits of ``p``), or ``theirs [S, k]`` where it differs and is
    a top-k of the scores to within ``eps``; and how many tokens took
    theirs."""
    k = sizes["num_experts_per_tok"]
    own = jax.lax.top_k(scores, k)[1]
    if theirs is None:
        return own, jnp.zeros((), jnp.int32)
    member = jnp.any(
        theirs[:, :, None] == jnp.arange(scores.shape[1])[None, None],
        axis=1)
    differs = ~jnp.all(jnp.take_along_axis(member, own, 1), axis=1)
    least_in = jnp.min(jnp.take_along_axis(scores, theirs, 1), axis=1)
    most_out = jnp.max(jnp.where(member, -jnp.inf, scores), axis=1)
    near = differs & (least_in >= most_out - eps)
    return jnp.where(near[:, None], theirs, own), jnp.sum(near)


def _routed(sizes, h, p, choice, w):
    """Step 6's sum over the held experts: each over all rows, times its
    weight in a token's set (0 where it is not in it)."""
    first = sizes.get("first_expert", 0)
    weight = jnp.take_along_axis(p, choice, 1)
    weight = sizes["moe_routed_scaling_factor"] * weight \
        / jnp.sum(weight, -1, keepdims=True)

    def one(y, expert):
        index, w_in, w_out = expert
        coef = jnp.sum(jnp.where(choice == first + index, weight, 0), -1)
        return y + coef[:, None] * _ffn(h, w_in, w_out), None

    held = w["w_in"].shape[0]
    return jax.lax.scan(
        one, jnp.zeros_like(h), (jnp.arange(held), w["w_in"], w["w_out"]))[0]


def _layer(sizes, kind, x, w, theirs, eps):
    """Steps 1 to 6; (x, tokens whose set was the program's)."""
    norm_eps = sizes["rms_norm_eps"]
    d, seq = sizes["head_dim"], x.shape[0]
    heads = w["wq"].shape[1] // d
    h = reference.rms_norm(x, w["attn_norm"], norm_eps)
    q = _rotary((h @ w["wq"]).reshape(seq, heads, d), kind, sizes)
    k = _rotary((h @ w["wk"]).reshape(seq, -1, d), kind, sizes)
    v = (h @ w["wv"]).reshape(seq, -1, d)
    window = sizes["sliding_window"] if kind.endswith("window") else None
    gate = jax.nn.sigmoid(h @ w["w_gate"])[..., None]
    a = _attention(q, k, v, window) * gate
    x = x + a.reshape(seq, heads * d) @ w["wo"]
    y = reference.rms_norm(x, w["mlp_norm"], norm_eps)
    if kind.startswith("dense"):
        return x + _ffn(y, w["w_in"], w["w_out"]), jnp.zeros((), jnp.int32)
    scores = y @ w["router"]
    choice, followed = _choose(sizes, scores, theirs, eps)
    p = jax.nn.softmax(scores, axis=-1)
    routed = _routed(sizes, y, p, choice, w)
    return x + routed + _ffn(y, w["shared_in"], w["shared_out"]), followed


def logits(sizes: dict, params: dict, tokens, follow=None,
           eps: float = 0.0):
    """The plain reference: tokens [S] int32 -> (logits [S, vocab]
    float32, the number of token-layer choices at which ``follow``'s
    set was taken). ``follow [layers, S, k]`` is another forward pass's
    choice of experts a token and layer (a dense layer's row is not
    read)."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        followed, at = jnp.zeros((), jnp.int32), 0
        for name in sorted(params["layers"]):
            kind = name.split("_", 1)[1]
            run = params["layers"][name]
            count = jax.tree.leaves(run)[0].shape[0]
            theirs = None if follow is None else follow[at:at + count]

            def layer(carry, layer_at, kind=kind, theirs=theirs):
                x, followed = carry
                w, index = layer_at
                x, more = _layer(
                    sizes, kind, x, w,
                    None if theirs is None else theirs[index], eps)
                return (x, followed + more), None

            (x, followed), _ = jax.lax.scan(
                layer, (x, followed), (run, jnp.arange(count)))
            at += count
        x = reference.rms_norm(x, params["final_norm"], sizes["rms_norm_eps"])
        return x @ params["lm_head"], followed


def _published_experts(sizes: dict) -> int:
    return sizes.get("published", {}).get("num_experts", sizes["num_experts"])


def _layer_kinds(sizes: dict) -> list:
    return [KINDS[pair] for pair in
            zip(sizes["layer_types"], sizes["mlp_layer_types"])]


def _rows_routed(sizes: dict) -> float:
    """Token-expert pairs a step routes, over all routed layers."""
    return sizes["batch"] * sizes["sequence"] \
        * sizes["num_experts_per_tok"] \
        * sum(kind.startswith("moe") for kind in _layer_kinds(sizes))


def flops_per_token(sizes: dict, seq: int) -> float:
    """FLOPs a training step requires per token (``flops.py`` says what
    counts). The routed experts count at the share of a step's pairs
    whose expert is held here, ``held_pair_share`` of the configuration
    (the window's own reading, PERF.md, section 7, 2i): what a chip
    does without the exchange. Each layer's attention at its own head
    count, a window layer's over the keys its window holds."""
    d, hd, kv = sizes["hidden_size"], sizes["head_dim"], \
        sizes["num_key_value_heads"]
    m, k = sizes["moe_intermediate_size"], sizes["num_experts_per_tok"]
    forward = 2 * d * sizes["vocab_size"]
    for kind, heads in zip(_layer_kinds(sizes),
                           sizes["num_attention_heads_per_layer"]):
        window = sizes["sliding_window"] if kind.endswith("window") else None
        forward += 2 * 2 * d * (heads + kv) * hd + 2 * d * heads \
            + flops.attention_flops(heads, hd, seq, window)
        if kind.startswith("dense"):
            forward += 2 * 3 * d * sizes["intermediate_size"]
        else:
            forward += 2 * d * _published_experts(sizes) \
                + 2 * 3 * d * sizes["shared_expert_intermediate_size"] \
                + sizes["held_pair_share"] * k * 2 * 3 * d * m
    return 3 * forward


def _attention_work(sizes: dict, which: str) -> dict:
    """The attention kernels' work over the layers ``which`` names
    ("window", "full" or "" for both), each kind at its head count."""
    total = {"forward": (0.0, 0.0), "backward": (0.0, 0.0)}
    for kind in ("moe_window", "moe_full", "dense_full"):
        if not kind.endswith(which):
            continue
        layers = _layer_kinds(sizes).count(kind)
        if not layers:
            continue
        heads = sizes["num_attention_heads_per_layer"][
            _layer_kinds(sizes).index(kind)]
        work = flops.attention_kernel_work(
            sizes["batch"], sizes["sequence"], layers, heads,
            sizes["num_key_value_heads"], sizes["head_dim"],
            window=sizes["sliding_window"] if kind.endswith("window")
            else None)
        total = {p: tuple(a + b for a, b in zip(total[p], work[p]))
                 for p in total}
    return total


def _routed_work(sizes: dict):
    """(FLOPs, least HBM bytes) a train step requires of the experts'
    grouped matmuls for ``held_pairs`` token-expert pairs whose expert
    is held (over all routed layers; a reader sets it from the step's
    own counts, else ``held_pair_share`` of the pairs routed): three
    matmuls of D x m a pair forward and twice that backward; in bf16
    the held experts' weights read once forward and once backward and
    their gradient written once a layer, a pair's row read and its
    result written forward, and backward the row and the result's
    cotangent read and the row's cotangent written. A recomputed
    forward pass is in the time and not here."""
    d, m = sizes["hidden_size"], sizes["moe_intermediate_size"]
    pairs = sizes.get("held_pairs")
    if pairs is None:
        pairs = sizes["held_pair_share"] * _rows_routed(sizes)
    layers = sum(k.startswith("moe") for k in _layer_kinds(sizes))
    weights = sizes["num_experts"] * 3 * d * m * 2
    return (3 * 2 * 3 * d * m * pairs,
            layers * 3 * weights + 5 * pairs * d * 2)


# what a train step requires of each kernel it runs, by the kernel's
# name in the device trace: sizes -> (FLOPs, least HBM bytes).
# ``flash_*`` is every attention layer's, ``flash_*_window`` the window
# layers' alone; ``moe_routed`` the experts' grouped matmuls
# (``ragged-dot-*``) for the pairs held
WORK = {
    "flash_fwd": lambda sizes: _attention_work(sizes, "")["forward"],
    "flash_bwd": lambda sizes: _attention_work(sizes, "")["backward"],
    "flash_fwd_window":
        lambda sizes: _attention_work(sizes, "window")["forward"],
    "flash_bwd_window":
        lambda sizes: _attention_work(sizes, "window")["backward"],
    "moe_routed": _routed_work,
}

# what the program does not implement of the family: a file that asks
# for it is refused, not run as something else
_REQUIRED = {
    "attention_bias": False, "tie_word_embeddings": False, "gating": True,
    "moe_apply_router_weight_on_input": False,
}


def build(sizes: dict) -> Family:
    from dlrover_tpu.models import laguna as model

    for key, value in _REQUIRED.items():
        if sizes[key] != value:
            raise ValueError(
                f"models/laguna.py implements {key}={value!r} only; the "
                f"configuration says {sizes[key]!r}"
            )
    layers = sizes["num_hidden_layers"]
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        if len(sizes[key]) != layers:
            raise ValueError(f"{len(sizes[key])} {key} for {layers} layers")
    pairs = set(zip(sizes["layer_types"], sizes["mlp_layer_types"]))
    if pairs - set(KINDS):
        raise ValueError(
            f"models/laguna.py implements the layers {sorted(KINDS)} only; "
            f"the configuration has {sorted(pairs - set(KINDS))}"
        )
    full = sizes["rope_parameters"]["full_attention"]
    window = sizes["rope_parameters"]["sliding_attention"]
    if full["rope_type"] != "yarn" or window["rope_type"] != "default":
        raise ValueError(
            "models/laguna.py implements YaRN on full_attention and the "
            f"default rotary embedding on sliding_attention only; the "
            f"configuration says {full['rope_type']} and "
            f"{window['rope_type']}"
        )
    config = model.LagunaConfig(
        vocab_size=sizes["vocab_size"], dim=sizes["hidden_size"],
        layer_types=tuple(sizes["layer_types"]),
        mlp_layer_types=tuple(sizes["mlp_layer_types"]),
        heads=tuple(sizes["num_attention_heads_per_layer"]),
        n_kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        window=sizes["sliding_window"],
        mlp_dim=sizes["intermediate_size"],
        n_experts=_published_experts(sizes),
        held_first=sizes.get("first_expert", 0),
        held_experts=sizes["num_experts"],
        top_k=sizes["num_experts_per_tok"],
        expert_dim=sizes["moe_intermediate_size"],
        shared_dim=sizes["shared_expert_intermediate_size"],
        routed_scale=sizes["moe_routed_scaling_factor"],
        full_rope_theta=float(full["rope_theta"]),
        full_rotary_factor=full["partial_rotary_factor"],
        yarn_factor=full["factor"],
        yarn_original=full["original_max_position_embeddings"],
        yarn_beta_fast=full["beta_fast"], yarn_beta_slow=full["beta_slow"],
        yarn_attention_factor=full["attention_factor"],
        window_rope_theta=float(window["rope_theta"]),
        window_rotary_factor=window["partial_rotary_factor"],
        norm_eps=sizes["rms_norm_eps"], **sizes.get("program", {}),
    )
    dtype = jnp.dtype(config.dtype)
    # the weights are the configuration's, whatever the run's seed: a
    # routed cell's work follows its weights (which pairs the router
    # sends to the experts held here), so every seed does the same work
    # and the seed draws the batch alone
    weights = jax.random.key(sizes["weights_seed"])

    def reference_logits(params, tokens):
        """The reference, following at near ties the choices of the
        program's own forward pass (``apply``'s scan, the parameters
        cast as the step casts them); followed nowhere where it would
        be at more than MAX_FOLLOWED_SHARE of the choices."""
        cast = jax.tree.map(lambda x: x.astype(dtype), params)
        _, theirs = model.laguna_apply(config, cast, tokens[None],
                                       choices=True)
        out, followed = logits(sizes, params, tokens, theirs[:, 0],
                               NEAR_TIE_EPS)
        choices = tokens.shape[0] * sum(
            k.startswith("moe") for k in _layer_kinds(sizes))
        most = int(MAX_FOLLOWED_SHARE * choices)
        jax.debug.callback(_say_followed, followed, most, choices)
        return jax.lax.cond(
            followed > most,
            lambda: logits(sizes, params, tokens)[0], lambda: out)

    return Family(
        model_config=config,
        init=lambda rng: model.laguna_init(config, weights),
        loss_fn=model.laguna_loss_fn(config),
        logical_axes=model.laguna_logical_axes(config),
        apply=lambda p, t: model.laguna_apply(config, p, t),
        reference_logits=reference_logits,
        tolerances=reference.tolerances(LOGITS_REL_RMS_TOL),
        flops_per_token=flops_per_token(sizes, sizes["sequence"]),
        work=WORK,
    )


def _say_followed(followed, most, choices):
    print(f"[laguna] near ties followed: {int(followed)} of {choices} "
          f"token-layer choices (eps {NEAR_TIE_EPS})"
          + (f": over {int(most)}, so none is" if followed > most else ""),
          file=sys.stderr, flush=True)
