"""The ``zaya`` family (Zyphra ZAYA1, ``model_type`` ``zaya``), run
through ``dlrover_tpu/models/zaya.py``: compressed convolutional
attention (arXiv:2510.04476), a router MLP whose state is carried
through depth and top-1 routed experts (the ZAYA1 technical report,
arXiv:2511.17127), learned residual scaling.

Sizes: ``D`` hidden, ``Hq`` query and ``Hk`` key/value heads of ``d``,
``G = Hq / Hk``, ``T0`` / ``T1`` the convolutions' taps (``cca_time0``,
``cca_time1``), ``R`` = ``router_hidden_size``, ``E`` experts of ``M``.
A layer takes ``x [S, D]`` and the router state of the layer below,
``r_prev [S, R]`` (zeros into the first), and hands both on; linear
maps have no bias unless one is written:

1.  ``h = RMSNorm(x; g_a)``
2.  ``q~ = h W_q``, ``k~ = h W_k``, ``u = [q~ | k~]`` (``Hq + Hk``
    heads of ``d``)
3.  ``c1_t = b1 + sum_j w1[j] * u_{t-T0+1+j}`` (depthwise), ``c2_t[g] =
    b2[g] + sum_j c1_{t-T1+1+j}[g] W2[g, j]`` a head ``g``; zero history
    on the left; ``[q_c | k_c] = c2``
4.  ``m_q[i] = (q~[i] + k~[i // G]) / 2``, ``m_k[j]`` the mean of
    ``m_q`` over KV head ``j``'s ``G`` query heads; ``q = q_c + m_q``,
    ``k = k_c + m_k``
5.  ``v_t = [h_t W_v1 | h_{t-1} W_v2]``: the first half of the KV heads
    holds the current token's values, the second the previous token's
6.  ``q^ = sqrt(d) q / ||q||``, ``k^ = tau_j sqrt(d) k / ||k||`` a head
7.  rotary embedding on the first ``d x partial_rotary_factor`` dims of
    every head of ``q^`` and ``k^``, rotate-half pairing inside the
    slice; the other dims pass
8.  ``a = softmax_causal(q^ k^T / sqrt(d)) v W_o``, query head ``i``
    reading KV head ``i // G``
9.  ``x = (x + beta_1) * alpha_1 + (a + beta_2) * alpha_2``
10. ``h = RMSNorm(x; g_m)``
11. ``s = h W_d + b_d``, ``r = s + gamma * r_prev`` (handed on), ``p =
    softmax(W_3 gelu(W_2 gelu(W_1 RMSNorm(r; g_r) + b_1) + b_2))`` over
    all ``E`` experts, ``gelu`` exact
12. ``e* = argmax(p + b)``, ``b`` a balancing bias (zero here)
13. ``y = p[e*] FFN_e*(h)``, ``FFN_e(h) = (silu(h Wg_e) * (h Wu_e))
    Wd_e``; ``y = 0`` for a token whose ``e*`` is not held here
14. ``x = (x + beta_3) * alpha_3 + (y + beta_4) * alpha_4``

``x_0 = E[tokens]``, ``logits = RMSNorm(x; g_f) E^T``.

The reference below is float32 throughout and shares nothing with the
program: no sort and no grouped matmul (every held expert is applied to
all rows and selected by a mask), the convolutions as shifted sums,
attention over blocks of query rows (the blocks of
``family/granite_hybrid.py``). It is given the same share: the file's
``num_experts`` experts from ``first_expert`` on are held, the router
scores ``published.num_experts``.

**A choice of one expert is not continuous.** Where the reference's two
best experts lie closer than the rounding of the program's bf16 hidden
state, the two choose differently, and a token routed otherwise differs
by an expert's whole output. ``NEAR_TIE_EPS`` below says what the
comparison does about that, with the readings it was set from.

Weights arrive as the Trainer holds them: every layer's leaf stacked on
axis 0 under ``params["layers"]``; the experts' gate and up matrices
side by side in ``w_in [L, held, D, 2 M]``.
"""

from __future__ import annotations

import sys

import flops
import jax
import jax.numpy as jnp
import lookup
import reference
from families import Family

# Where the program's bf16 hidden state and the reference's float32 one
# put two experts in another order, the token is routed otherwise and
# differs by an expert's whole output. On the chip at the real widths
# (the sweep of eps over seeds 0 and 1 is in PERF.md, Findings, PR 34)
# 376 and 381 of a run's 49,152 token-layer choices differ in bf16
# (0.77%), every one of them at a margin under 3e-5 in the reference's
# own ``p`` (190 under 3e-6, 345 under 1e-5; the median margin between
# the two best experts is 2e-4); the program's int8 matmuls move 1,096
# and 1,103, under 1e-4. Left alone that reads, relative rms / largest
# of the logits at the last 256 of 8192 positions: bf16 0.0122, 0.0129
# / 0.304, 0.320 and int8 0.0294, 0.0305 / 0.362, 0.311: the largest
# single logit is a token routed otherwise, in both, and no limit on it
# that bf16 passes fails int8.
#
# So at a near tie the reference follows the program: a token and layer
# where the program chose another expert than the reference would AND
# the reference's own ``p + b`` for that expert lies within
# NEAR_TIE_EPS of its best. Everywhere else it keeps its own choice, so
# a program that routes wrongly still fails (tests/test_zaya.py: a
# mis-route outside eps fails, a tie inside it passes). The eps lies
# between bf16's widest margin (under 3e-5) and int8's (under 1e-4).
# The program's choices are those of ``zaya_apply``'s own pass, the one
# scan whose logits are compared. On the chip
# (``tests/zaya_routing_probe.py``, seeds 2 and 3, relative rms /
# largest): the second-best expert for the tokens whose two best lie
# between 2 and 2.04 eps apart, 474 choices moved, as few as bf16 moves
# by itself, reads 0.00966, 0.00879 / 0.159, 0.175 and fails by the
# largest logit; the router's state of the layer below ignored moves
# 61% and 66% of the choices and reads 0.0645, 0.0715 / 0.404, 0.457.
# What it does NOT see: the router's matmuls rounded to bf16 move 301
# to 357 choices (0.6-0.7%), every one inside eps, and read as the
# sound program does (0.00829, 0.00723 / 0.0461, 0.0425): the cell
# holds the router to choices that differ at near ties alone, not to
# float32.
NEAR_TIE_EPS = 5e-5

# A sound program is followed at few places: 332 to 439 of the 49,152
# in the cell's own 21 runs over 14 seeds (0.68% to 0.89%; printed to
# standard error beside ``compared``), the int8 one at 1,092 to 1,116
# (2.2%). A run that would be followed at more than this share of its
# choices (the geometric mean of the two: 688 of 49,152) is followed
# nowhere: it is compared with the reference's own choices throughout,
# where every token routed otherwise shows as a whole expert's output.
MAX_FOLLOWED_SHARE = 0.014

# With the following, same runs: bf16 0.00846, 0.00748 / 0.0471, 0.0467
# at eps 3e-5 and the same at 1e-4 (every choice of its 0.77% followed);
# int8 0.02555, 0.02613 / 0.1637, 0.1613 at 3e-5 and 0.02540, 0.02610 /
# 0.1339, 0.1249 at 1e-4; at the eps kept, seeds 2 and 3: bf16 0.00833,
# 0.00720 / 0.0463, 0.0410 and int8 0.03013, 0.02740 / 0.1469, 0.1342.
# The limit on the relative rms is the geometric mean of bf16's largest
# and int8's smallest: bf16 passes with a factor of 1.7 to spare and
# int8 fails by as much; its largest logit fails too (8 x the limit =
# 0.1176), by 6% at the least. Under MAX_FOLLOWED_SHARE int8 is not
# followed at all and reads what it does left alone: 0.0294, 0.0305,
# 0.0330, 0.0323 / 0.362, 0.311, 0.336, 0.339 (seeds 0 to 3), outside
# both limits by a factor of two and more. The cell's own runs read
# 0.00717 to 0.00862 / 0.0371 to 0.0446, the loss within 3e-4 (limit
# 0.02).
LOGITS_REL_RMS_TOL = 0.0147

_attention = lookup.module("family", "granite_hybrid")._attention


def _shift(x):
    """x_{t-1} at t, zeros at t = 0: x [S, ...]."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]])


def _cca(sizes, h, w):
    """Steps 2 to 8 on the normed ``h [S, D]``."""
    hq, hk = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d, group, seq = sizes["head_dim"], hq // hk, h.shape[0]
    q_raw = (h @ w["wq"]).reshape(seq, hq, d)
    k_raw = (h @ w["wk"]).reshape(seq, hk, d)
    u = jnp.concatenate([q_raw, k_raw], axis=1)             # [S, 10, d]
    if w["conv1_w"].shape[0] != sizes["cca_time0"] \
            or w["conv2_w"].shape[1] != sizes["cca_time1"]:
        raise ValueError("the convolutions' taps are not cca_time0/1")
    # tap j reads position t - T + 1 + j: the last tap reads t itself
    c1, tap = 0.0, u.reshape(seq, -1)
    for j in reversed(range(sizes["cca_time0"])):
        c1 = c1 + w["conv1_w"][j] * tap
        tap = _shift(tap)
    c1 = (c1 + w["conv1_b"]).reshape(seq, hq + hk, d)
    c2, tap = 0.0, c1
    for j in reversed(range(sizes["cca_time1"])):
        c2 = c2 + jnp.einsum("sgc,gcd->sgd", tap, w["conv2_w"][:, j])
        tap = _shift(tap)
    c2 = c2 + w["conv2_b"]
    m_q = (q_raw + jnp.repeat(k_raw, group, axis=1)) / 2
    m_k = jnp.mean(m_q.reshape(seq, hk, group, d), axis=2)
    q, k = c2[:, :hq] + m_q, c2[:, hq:] + m_k
    v = jnp.concatenate([h @ w["wv1"], _shift(h) @ w["wv2"]], -1)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True)) * d ** 0.5

    def rotary(x):
        n = int(d * sizes["partial_rotary_factor"])
        theta = float(sizes["rope_parameters"]["hybrid"]["rope_theta"])
        return jnp.concatenate(
            [reference.rotate_half(x[..., :n], theta), x[..., n:]], -1)

    q, k = rotary(unit(q)), rotary(unit(k) * w["tau"][:, None])
    out = _attention(q, k, v.reshape(seq, hk, d), d ** -0.5)
    return out @ w["wo"]


def _router(sizes, h, r_prev, w):
    """Step 11: (r, p [S, E])."""
    eps = sizes["rms_norm_eps"]
    r = h @ w["router_down"] + w["router_down_b"] \
        + w["router_gamma"] * r_prev
    z = reference.rms_norm(r, w["router_norm"], eps)
    z = jax.nn.gelu(z @ w["router_w1"] + w["router_b1"], approximate=False)
    z = jax.nn.gelu(z @ w["router_w2"] + w["router_b2"], approximate=False)
    return r, jax.nn.softmax(z @ w["router_w3"], axis=-1)


def _held_experts(sizes, h, choice, w):
    """Step 13 without its weight: ``FFN_choice(h)`` for the experts
    held here, 0 elsewhere. Every held expert runs over all rows and a
    mask selects."""
    first, mid = sizes.get("first_expert", 0), sizes["moe_intermediate_size"]

    def one(y, expert):
        index, w_in, w_out = expert
        out = (jax.nn.silu(h @ w_in[:, :mid]) * (h @ w_in[:, mid:])) @ w_out
        return y + jnp.where((choice == first + index)[:, None], out, 0), None

    held = w["w_in"].shape[0]
    return jax.lax.scan(
        one, jnp.zeros_like(h), (jnp.arange(held), w["w_in"], w["w_out"]))[0]


def _scaled(x, branch, w, first):
    return (x + w["beta"][first]) * w["alpha"][first] \
        + (branch + w["beta"][first + 1]) * w["alpha"][first + 1]


def logits(sizes: dict, params: dict, tokens, follow=None,
           eps: float = 0.0):
    """The plain reference: tokens [S] int32 -> (logits [S, vocab]
    float32, the number of near ties at which a choice of ``follow``
    was taken). ``follow [layers, S]`` is another forward pass's choice
    of expert a token and layer; it is taken where it differs from the
    reference's own and the reference's own ``p + b`` for it lies
    within ``eps`` of its best."""
    norm_eps = sizes["rms_norm_eps"]
    layers = sizes["num_hidden_layers"]
    if follow is None:
        follow = jnp.full((layers, tokens.shape[0]), -1, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        r = jnp.zeros((tokens.shape[0], sizes["router_hidden_size"]))

        def layer(carry, at):
            x, r, followed = carry
            w, theirs = at
            h = reference.rms_norm(x, w["attn_norm"], norm_eps)
            x = _scaled(x, _cca(sizes, h, w), w, 0)
            h = reference.rms_norm(x, w["moe_norm"], norm_eps)
            r, p = _router(sizes, h, r, w)
            score = p + w["balance_bias"]
            choice = jnp.argmax(score, axis=-1)
            at_theirs = jnp.take_along_axis(
                score, jnp.maximum(theirs, 0)[:, None], 1)[:, 0]
            near = (theirs >= 0) & (theirs != choice) \
                & (jnp.max(score, axis=-1) - at_theirs <= eps)
            choice = jnp.where(near, theirs, choice)
            weight = jnp.take_along_axis(p, choice[:, None], 1)
            y = weight * _held_experts(sizes, h, choice, w)
            x = _scaled(x, y, w, 2)
            return (x, r, followed + jnp.sum(near)), None

        (x, _r, followed), _ = jax.lax.scan(
            layer, (x, r, jnp.zeros((), jnp.int32)),
            (params["layers"], follow))
        x = reference.rms_norm(x, params["final_norm"], norm_eps)
        return x @ params["embed"].T, followed


def _router_outputs(sizes: dict) -> int:
    return sizes.get("published", {}).get("num_experts", sizes["num_experts"])


def flops_per_token(sizes: dict, seq: int) -> float:
    """FLOPs a training step requires per token (``flops.py`` says what
    counts). The experts count as the active parameters: one expert a
    token, for EVERY token. That is the regime a training run is in
    whatever share of the model's experts is held: where only some are
    (8 of 16) and nothing exchanges tokens, only the held experts hand
    anything back, the router learns that within its first twenty
    steps (97.0% of the tokens at step 10, 99.75% at step 20), and from
    then on every token goes to a held expert (the cell's
    ``moe_held_token_pct`` reads 99.9 in the window: PERF.md, Findings,
    PR 34), which is also the number of rows a chip's experts see in
    the deployment, their own and the other chip's. The depthwise
    convolution is no matmul a token crosses and is not counted, the
    grouped one is."""
    d, m = sizes["hidden_size"], sizes["moe_intermediate_size"]
    hq, hk = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    head, r = sizes["head_dim"], sizes["router_hidden_size"]
    cca = (
        2 * d * (hq + 2 * hk) * head            # W_q; W_k; W_v1 and W_v2
        + 2 * (hq + hk) * sizes["cca_time1"] * head * head    # conv 2
        + 2 * hq * head * d                                   # W_o
        + flops.attention_flops(hq, head, seq)
    )
    router = 2 * (d * r + 2 * r * r + r * _router_outputs(sizes))
    experts = 2 * 3 * d * m
    forward = sizes["num_hidden_layers"] * (cca + router + experts) \
        + 2 * d * sizes["vocab_size"]
    return 3 * forward


def _attention_work(sizes: dict) -> dict:
    return flops.attention_kernel_work(
        sizes["batch"], sizes["sequence"], sizes["num_hidden_layers"],
        sizes["num_attention_heads"], sizes["num_key_value_heads"],
        sizes["head_dim"],
    )


def _expert_work(sizes: dict):
    """(FLOPs, least HBM bytes) a train step requires of the experts'
    grouped matmuls, whatever implements them, with every token of the
    step routed to a held expert (the regime of the window, as
    ``flops_per_token`` says): three matmuls of D x M a token forward
    and twice that backward; in bf16 the held experts' weights read
    once forward and once backward and their gradient written once, the
    rows read and written once forward, and backward the rows and the
    result's cotangent read and the rows' cotangent written. The
    intermediates (gate, up, their product) are no operand and no
    result. A recomputed forward pass is in the time and not here."""
    d, m = sizes["hidden_size"], sizes["moe_intermediate_size"]
    rows = sizes["batch"] * sizes["sequence"]
    weights = sizes["num_experts"] * 3 * d * m * 2
    per_layer = (3 * 2 * 3 * d * m * rows, 3 * weights + 5 * rows * d * 2)
    return tuple(sizes["num_hidden_layers"] * part for part in per_layer)


# what a train step requires of each kernel it runs, by the kernel's
# name in the device trace (``moe_experts``: the grouped matmuls,
# ``ragged-dot-*`` there): sizes -> (FLOPs, least HBM bytes)
WORK = {
    "flash_fwd": lambda sizes: _attention_work(sizes)["forward"],
    "flash_bwd": lambda sizes: _attention_work(sizes)["backward"],
    "moe_experts": _expert_work,
}

# what the program does not implement of the family: a file that asks
# for it is refused, not run as something else
_REQUIRED = {
    "num_experts_per_tok": 1, "hidden_act": "silu",
    "tie_word_embeddings": True, "attention_bias": False,
    "lm_head_bias": False, "sliding_window": None,
}


def build(sizes: dict) -> Family:
    from dlrover_tpu.models import zaya as model

    for key, value in _REQUIRED.items():
        if sizes[key] != value:
            raise ValueError(
                f"models/zaya.py implements {key}={value!r} only; the "
                f"configuration says {sizes[key]!r}"
            )
    kinds = sizes["layer_types"]
    if len(kinds) != sizes["num_hidden_layers"] or set(kinds) != {"hybrid"}:
        raise ValueError(
            f"models/zaya.py implements {sizes['num_hidden_layers']} "
            f"layer_types of 'hybrid' only; the configuration says {kinds}"
        )
    rope = sizes["rope_parameters"]["hybrid"]
    if rope["rope_type"] != "default" or \
            rope["partial_rotary_factor"] != sizes["partial_rotary_factor"]:
        raise ValueError(
            f"models/zaya.py implements the default rope_type at the "
            f"model's partial_rotary_factor only; the configuration "
            f"says {rope}"
        )
    config = model.ZayaConfig(
        vocab_size=sizes["vocab_size"], dim=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        conv_taps=(sizes["cca_time0"], sizes["cca_time1"]),
        rotary_factor=sizes["partial_rotary_factor"],
        rope_theta=float(rope["rope_theta"]),
        n_experts=_router_outputs(sizes),
        held_first=sizes.get("first_expert", 0),
        held_experts=sizes["num_experts"],
        expert_dim=sizes["moe_intermediate_size"],
        router_dim=sizes["router_hidden_size"],
        norm_eps=sizes["rms_norm_eps"], **sizes.get("program", {}),
    )
    dtype = jnp.dtype(config.dtype)

    def reference_logits(params, tokens):
        """The reference, following at near ties the choices of the
        program's own forward pass (``apply``'s scan, the parameters
        cast as the step casts them); followed nowhere where it would
        be at more than MAX_FOLLOWED_SHARE of the choices."""
        cast = jax.tree.map(lambda x: x.astype(dtype), params)
        _, theirs = model.zaya_apply(config, cast, tokens[None], choices=True)
        out, followed = logits(sizes, params, tokens, theirs[:, 0],
                               NEAR_TIE_EPS)
        most = int(MAX_FOLLOWED_SHARE * theirs.size)
        jax.debug.callback(_say_followed, followed, most, theirs.size)
        return jax.lax.cond(
            followed > most,
            lambda: logits(sizes, params, tokens)[0], lambda: out)

    return Family(
        model_config=config,
        init=lambda rng: model.zaya_init(config, rng),
        loss_fn=model.zaya_loss_fn(config),
        logical_axes=model.zaya_logical_axes(config),
        apply=lambda p, t: model.zaya_apply(config, p, t),
        reference_logits=reference_logits,
        tolerances=reference.tolerances(LOGITS_REL_RMS_TOL),
        flops_per_token=flops_per_token(sizes, sizes["sequence"]),
        work=WORK,
    )


def _say_followed(followed, most, choices):
    print(f"[zaya] near ties followed: {int(followed)} of {choices} "
          f"token-layer choices (eps {NEAR_TIE_EPS})"
          + (f": over {int(most)}, so none is" if followed > most else ""),
          file=sys.stderr, flush=True)
