"""The ``glm_lite`` family (zai-org GLM-4.7-Flash, ``model_type``
``glm4_moe_lite``), run through ``dlrover_tpu/models/glm_lite.py``.

Sizes: ``D`` hidden, ``H`` heads, ``r_q`` / ``r_kv`` the latent ranks,
``n`` / ``p`` / ``d_v`` a head's unrotated and rotated q.k widths and
its value width, ``E`` experts of ``m`` of which ``k`` a token, a
shared expert of ``m``, a dense layer of ``M``. With ``x^ =
RMSNorm(x)`` (``rms_norm_eps``) and no bias anywhere, a layer is:

1.  ``c_q = RMSNorm_q(x^ W_qa)``, ``[q_nope | q_pe]_h = c_q W_qb`` (``H``
    heads of ``n + p``)
2.  ``[c_kv | k_pe] = x^ W_kva``, ``[k_nope | v]_h = RMSNorm_kv(c_kv)
    W_kvb`` (``H`` heads of ``n + d_v``); the latent norms at eps 1e-6
3.  ``q_pe`` and ``k_pe`` rotated, rotate-half, at ``rope_theta``,
    positions 0..S-1; ``k_pe`` is one head, every head's
4.  ``a_h = softmax(q_h k_h^T / sqrt(n + p) + causal) v_h``, ``q_h =
    [q_nope | q_pe]_h``, ``k_h = [k_nope | k_pe]_h``; ``x = x + W_o
    concat_h(a_h)``
5.  ``y = RMSNorm(x)``; the first ``first_k_dense_replace`` layers: ``x
    = x + FFN(y)``, ``FFN(z) = (silu(z W_g) * z W_u) W_d``
6.  the others: ``s = sigmoid(y W_r)`` over all ``E`` experts, ``T`` =
    the ``k`` largest of ``s + b`` (``e_score_correction_bias``,
    ``noaux_tc`` with one group), ``w_e = c s_e / sum_T s`` (``c`` =
    ``routed_scaling_factor``), ``x = x + sum_{e in T} w_e FFN_e(y) +
    FFN_shared(y)``; a pair whose expert is not held here adds 0

``x_0 = E[t]``, ``logits = RMSNorm(x_L) W_head`` (head untied). The
multi-token-prediction depth (``num_nextn_predict_layers`` 1, DeepSeek-
V3's layout, arXiv:2412.19437 section 2.2): ``g = [RMSNorm_e(E[t_{i+1}])
| RMSNorm_h(x_L)] W_eh``, one routed layer (steps 1 to 6), then
``RMSNorm_head(g) W_head`` predicts ``t_{i+2}``; the embedding and the
head are the main model's. The loss is ``CE_main + 0.3 CE_mtp`` (the
weight assumed: DeepSeek-V3's first stage's).

The reference below is float32 throughout and shares nothing with the
program: no sort and no grouped matmul (every held expert is applied to
all rows, times its weight in each token's choice, zero where it was
not chosen), no kernel, attention over blocks of query rows with k and
v at their own widths. Its MTP depth runs over the positions whose next
token is in the sequence. It is given the same share: the file's
``n_routed_experts`` experts from ``first_expert`` on are held, the
router scores ``published.n_routed_experts``.

**What is compared.** The harness compares one array of logits and
takes the next-token loss of it. This family hands it the main and the
MTP logits side by side, ``[S, 2 V]``: at position ``i`` the main
head's prediction of ``t_{i+1}`` and the MTP depth's of ``t_{i+2}``;
the last position has no MTP row (its next token is not in the
sequence) and holds zeros in both. The next-token loss of that array is
no loss the program trains on, so the family's limits are on the
logits alone: both losses and every gradient are held to the reference
in the CPU tests (``tests/test_glm_lite.py``).

**A choice of the k best is not continuous.** Where the reference's
``k``-th and ``k+1``-th experts lie closer than the rounding of the
program's bf16 hidden state, the two choose other sets, and a token
differs by the weight of one expert's whole output. ``NEAR_TIE_EPS``
says what the comparison does about that, with its readings.

Weights arrive as the Trainer holds them: ``params["layers"]`` maps
``<index>_<kind>`` to one run of like layers stacked on axis 0 (a kind
is ``dense`` or ``moe``), ``params["mtp"]["layer"]`` a ``moe`` run of
one; the experts' gate and up matrices side by side in ``w_in [L, held,
D, 2 m]``, the dense and the shared ones likewise.
"""

from __future__ import annotations

import math
import sys

import flops
import jax
import jax.numpy as jnp
import reference
from families import Family

LATENT_EPS = 1e-6       # the latent norms' (DeepSeek-V3's RMSNorm default)

# Where the program's bf16 hidden state and the reference's float32
# one put the k-th and the k+1-th expert in another order, the token is
# routed otherwise and differs by one expert's whole output. So at a
# near tie the reference follows the program: a token and layer where
# the program chose another set than the reference would AND that set
# is a top-k of the reference's own selection scores ``s + b`` to within
# NEAR_TIE_EPS (its least score at or above the largest it left out,
# less eps). Everywhere else the reference keeps its own set, so a
# program that routes wrongly still fails (tests/test_glm_lite.py: a
# mis-route outside eps fails, a tie inside it passes). The margin is
# in the sigmoid's scores, which lie near 0.85 at the 4th of 64.
#
# Readings, relative rms / largest of the main and MTP logits side by
# side at the last 256 positions, on one v5e chip at the cell's widths,
# weights and 8,192 positions, the first sequence of three seeds'
# batches (2147483659, 2147491578, 2147499497;
# benchmark/tests/glm_lite_precision_probe.py, PERF.md, section 6):
# bf16 chooses another set at 4.1-4.3% of the 40,960 token-layer
# choices (1,656-1,757 followed at eps 0.005, 1,677-1,773 at 0.01 and
# at 0.02: every bf16 tie lies within 0.01); left alone it reads
# 0.0247-0.0326 / 0.81-1.24, its largest logit a token routed otherwise;
# at eps 0.002 0.0158-0.0190 / 0.62-0.83; followed at 0.01
# 0.0112-0.0113 / 0.063-0.081. The program's int8 matmuls read
# 0.0703-0.0788 / 0.88-1.48 left alone, 0.0500-0.0579 / 0.72-1.48
# followed at 0.01 (5,688-5,849 followed), 0.0466-0.0478 / 0.28-0.57 at
# 0.02.
NEAR_TIE_EPS = 0.01

# A run that would be followed at more than this share of its
# token-layer choices is followed nowhere: it is compared with the
# reference's own sets throughout. bf16 is followed at 4.3% at most and
# the int8 control at 14.3% (eps 0.01); a program that moves more than
# that by as little as eps is no rounding.
MAX_FOLLOWED_SHARE = 0.15

# The geometric mean of bf16's largest 0.0113 and int8's least 0.0500
# followed at eps: each side has a factor of 2.1; and by the largest
# logit (8 x the limit = 0.190; bf16 reads 0.081 at most, int8 0.72 at
# least) too.
LOGITS_REL_RMS_TOL = 0.0238

QUERY_BLOCK = 256       # rows of queries scored at a time


def _attention(q, k, v):
    """Step 4's softmax: q/k [S, H, n + p], v [S, H, d_v] -> [S, H, d_v],
    causal, ``QUERY_BLOCK`` rows of queries at a time (the last block
    padded)."""
    seq, heads, width = q.shape
    block = min(QUERY_BLOCK, seq)
    pad = (-seq) % block
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    keys = jnp.arange(seq)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(width)
        visible = keys[None, :] <= (start + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(rows, jnp.arange(0, seq + pad, block))
    return out.reshape(seq + pad, heads, v.shape[-1])[:seq]


def _mla(sizes, x, w):
    """Steps 1 to 4 on the normed ``x [S, D]``."""
    seq, heads = x.shape[0], sizes["num_attention_heads"]
    n, p = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    rank, theta = sizes["kv_lora_rank"], float(sizes["rope_theta"])
    c_q = reference.rms_norm(x @ w["wq_a"], w["q_norm"], LATENT_EPS)
    q = (c_q @ w["wq_b"]).reshape(seq, heads, n + p)
    kv_a = x @ w["wkv_a"]
    c_kv = reference.rms_norm(kv_a[:, :rank], w["kv_norm"], LATENT_EPS)
    kv = (c_kv @ w["wkv_b"]).reshape(seq, heads, -1)
    q = jnp.concatenate(
        [q[..., :n], reference.rotate_half(q[..., n:], theta)], -1)
    k_pe = reference.rotate_half(kv_a[:, None, rank:], theta)
    k = jnp.concatenate(
        [kv[..., :n], jnp.broadcast_to(k_pe, (seq, heads, p))], -1)
    a = _attention(q, k, kv[..., n:])
    return a.reshape(seq, -1) @ w["wo"]


def _ffn(z, w_in, w_out):
    mid = w_out.shape[0]
    return (jax.nn.silu(z @ w_in[:, :mid]) * (z @ w_in[:, mid:])) @ w_out


def _choose(sizes, select, theirs, eps):
    """Step 6's set: the k largest of the selection scores ``select [S,
    E]`` (``s + b``), or ``theirs [S, k]`` where it differs and is a
    top-k of them to within ``eps``; and how many tokens took theirs."""
    k = sizes["num_experts_per_tok"]
    own = jax.lax.top_k(select, k)[1]
    if theirs is None:
        return own, jnp.zeros((), jnp.int32)
    member = jnp.any(
        theirs[:, :, None] == jnp.arange(select.shape[1])[None, None],
        axis=1)
    differs = ~jnp.all(jnp.take_along_axis(member, own, 1), axis=1)
    least_in = jnp.min(jnp.take_along_axis(select, theirs, 1), axis=1)
    most_out = jnp.max(jnp.where(member, -jnp.inf, select), axis=1)
    near = differs & (least_in >= most_out - eps)
    return jnp.where(near[:, None], theirs, own), jnp.sum(near)


def _routed(sizes, h, s, choice, w):
    """Step 6's sum over the held experts: each over all rows, times its
    weight in a token's set (0 where it is not in it)."""
    first = sizes.get("first_expert", 0)
    weight = jnp.take_along_axis(s, choice, 1)
    weight = sizes["routed_scaling_factor"] * weight \
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
    x = x + _mla(sizes, reference.rms_norm(x, w["attn_norm"], norm_eps), w)
    y = reference.rms_norm(x, w["mlp_norm"], norm_eps)
    if kind == "dense":
        return x + _ffn(y, w["w_in"], w["w_out"]), jnp.zeros((), jnp.int32)
    s = jax.nn.sigmoid(y @ w["router"])
    choice, followed = _choose(sizes, s + w["router_bias"], theirs, eps)
    routed = _routed(sizes, y, s, choice, w)
    return x + routed + _ffn(y, w["shared_in"], w["shared_out"]), followed


def logits(sizes: dict, params: dict, tokens, follow=None,
           eps: float = 0.0):
    """The plain reference: tokens [S] int32 -> (logits [S, vocab], the
    MTP depth's logits [S - 1, vocab] at the positions whose next token
    is in ``tokens``, float32; the number of token-layer choices at
    which ``follow``'s set was taken). ``follow [layers + 1, S, k]`` is
    another forward pass's choice of experts a token and layer, the MTP
    depth's row last (a dense layer's row is not read)."""
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
        eps_norm = sizes["rms_norm_eps"]
        mtp = params["mtp"]
        h = x[:-1]
        g = jnp.concatenate(
            [reference.rms_norm(params["embed"][tokens[1:]], mtp["e_norm"],
                                eps_norm),
             reference.rms_norm(h, mtp["h_norm"], eps_norm)], -1) \
            @ mtp["w_eh"]
        g, more = _layer(
            sizes, "moe", g, jax.tree.map(lambda a: a[0], mtp["layer"]),
            None if follow is None else follow[-1, :-1], eps)
        main = reference.rms_norm(x, params["final_norm"], eps_norm) \
            @ params["lm_head"]
        ahead = reference.rms_norm(g, mtp["head_norm"], eps_norm) \
            @ params["lm_head"]
        return main, ahead, followed + more


def loss(sizes: dict, main, ahead, tokens):
    """``CE_main + mtp_weight * CE_mtp`` of one sequence: ``main`` and
    ``ahead`` are ``logits``'s of ``tokens[:-1]``, their labels
    ``tokens[1:]`` and ``tokens[2:]``."""
    ce = reference.next_token_loss
    return ce(main, tokens) \
        + sizes["mtp_loss_weight"] * ce(ahead, tokens[1:])


def side_by_side(main, ahead):
    """[S, 2 V]: each position's main logits and its MTP logits, zeros
    where it has none (the last position)."""
    ahead = jnp.concatenate([ahead, jnp.zeros_like(ahead[-1:])], 0)
    return jnp.concatenate([main, ahead], -1)


def _published_experts(sizes: dict) -> int:
    return sizes.get("published", {}).get("n_routed_experts",
                                          sizes["n_routed_experts"])


def _routed_layers(sizes: dict) -> int:
    """Routed layers: the main stack's after the dense ones, and the
    MTP depth's."""
    return sizes["num_hidden_layers"] - sizes["first_k_dense_replace"] \
        + sizes["num_nextn_predict_layers"]


def _attention_layers(sizes: dict) -> int:
    return sizes["num_hidden_layers"] + sizes["num_nextn_predict_layers"]


def _head_dim(sizes: dict) -> int:
    return sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]


def flops_per_token(sizes: dict, seq: int) -> float:
    """FLOPs a training step requires per token (``flops.py`` says what
    counts): every layer's latent projections and causal attention at
    20 heads of 256, the dense MLP, the routed layers' router, shared
    expert and experts, the MTP depth's ``W_eh`` and layer, and two
    heads. The routed experts count at the share of a step's pairs
    whose expert is held here, ``held_pair_share`` of the configuration
    (the window's own reading): what a chip does without the
    exchange."""
    d, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    rq, rkv = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    n, p, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], \
        sizes["v_head_dim"]
    m, k = sizes["moe_intermediate_size"], sizes["num_experts_per_tok"]
    shared = sizes["n_shared_experts"] * m
    mla = 2 * (d * rq + rq * heads * (n + p) + d * (rkv + p)
               + rkv * heads * (n + dv) + heads * dv * d) \
        + flops.attention_flops(heads, n + p, seq)
    routed = 2 * d * _published_experts(sizes) + 2 * 3 * d * shared \
        + sizes["held_pair_share"] * k * 2 * 3 * d * m
    heads_ = 1 + sizes["num_nextn_predict_layers"]
    forward = _attention_layers(sizes) * mla \
        + sizes["first_k_dense_replace"] * 2 * 3 * d \
        * sizes["intermediate_size"] \
        + _routed_layers(sizes) * routed \
        + sizes["num_nextn_predict_layers"] * 2 * 2 * d * d \
        + heads_ * 2 * d * sizes["vocab_size"]
    return 3 * forward


def _attention_work(sizes: dict) -> dict:
    """The flash kernels' work over every layer: ``H`` heads of ``n +
    p`` over as many key/value heads (k and v expanded from the
    latent), causal."""
    return flops.attention_kernel_work(
        sizes["batch"], sizes["sequence"], _attention_layers(sizes),
        sizes["num_attention_heads"], sizes["num_attention_heads"],
        _head_dim(sizes))


def _routed_work(sizes: dict):
    """(FLOPs, least HBM bytes) a train step requires of the experts'
    grouped matmuls for ``held_pairs`` token-expert pairs whose expert
    is held (over all routed layers, the MTP depth's among them; a
    reader sets it from the step's own counts, else ``held_pair_share``
    of the pairs routed): three matmuls of D x m a pair forward and
    twice that backward; in bf16 the held experts' weights read once
    forward and once backward and their gradient written once a layer,
    a pair's row read and its result written forward, and backward the
    row and the result's cotangent read and the row's cotangent
    written. A recomputed forward pass is in the time and not here."""
    d, m = sizes["hidden_size"], sizes["moe_intermediate_size"]
    pairs = sizes.get("held_pairs")
    if pairs is None:
        pairs = sizes["held_pair_share"] * sizes["batch"] \
            * sizes["sequence"] * sizes["num_experts_per_tok"] \
            * _routed_layers(sizes)
    weights = sizes["n_routed_experts"] * 3 * d * m * 2
    return (3 * 2 * 3 * d * m * pairs,
            _routed_layers(sizes) * 3 * weights + 5 * pairs * d * 2)


# what a train step requires of each kernel it runs, by the kernel's
# name in the device trace: sizes -> (FLOPs, least HBM bytes).
# ``flash_*`` is every layer's latent attention, ``moe_routed`` the
# experts' grouped matmuls (``ragged-dot-*``) for the pairs held
WORK = {
    "flash_fwd": lambda sizes: _attention_work(sizes)["forward"],
    "flash_bwd": lambda sizes: _attention_work(sizes)["backward"],
    "moe_routed": _routed_work,
}

# what the program does not implement of the family: a file that asks
# for it is refused, not run as something else
_REQUIRED = {
    "attention_bias": False, "tie_word_embeddings": False,
    "hidden_act": "silu", "topk_method": "noaux_tc", "norm_topk_prob": True,
    "n_group": 1, "topk_group": 1, "rope_scaling": None,
    "partial_rotary_factor": 1, "num_nextn_predict_layers": 1,
}


def build(sizes: dict) -> Family:
    from dlrover_tpu.models import glm_lite as model

    for key, value in _REQUIRED.items():
        if sizes.get(key) != value:
            raise ValueError(
                f"models/glm_lite.py implements {key}={value!r} only; the "
                f"configuration says {sizes.get(key)!r}"
            )
    if sizes["num_key_value_heads"] != sizes["num_attention_heads"]:
        raise ValueError(
            "latent attention expands k and v to every head: "
            f"num_key_value_heads {sizes['num_key_value_heads']} for "
            f"{sizes['num_attention_heads']} heads")
    config = model.GlmLiteConfig(
        vocab_size=sizes["vocab_size"], dim=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"],
        dense_layers=sizes["first_k_dense_replace"],
        heads=sizes["num_attention_heads"], q_rank=sizes["q_lora_rank"],
        kv_rank=sizes["kv_lora_rank"], nope_dim=sizes["qk_nope_head_dim"],
        rope_dim=sizes["qk_rope_head_dim"], v_dim=sizes["v_head_dim"],
        rope_theta=float(sizes["rope_theta"]),
        mlp_dim=sizes["intermediate_size"],
        n_experts=_published_experts(sizes),
        held_first=sizes.get("first_expert", 0),
        held_experts=sizes["n_routed_experts"],
        top_k=sizes["num_experts_per_tok"],
        expert_dim=sizes["moe_intermediate_size"],
        shared_dim=sizes["n_shared_experts"] * sizes["moe_intermediate_size"],
        routed_scale=sizes["routed_scaling_factor"],
        mtp_weight=sizes["mtp_loss_weight"],
        norm_eps=sizes["rms_norm_eps"],
        **sizes.get("program", {}),
    )
    dtype = jnp.dtype(config.dtype)
    # the weights are the configuration's, whatever the run's seed: a
    # routed cell's work follows its weights (which pairs the router
    # sends to the experts held here), so every seed does the same work
    # and the seed draws the batch alone
    weights = jax.random.key(sizes["weights_seed"])

    def forward(params, tokens):
        """The program's pass that the check compares and whose choices
        the reference follows, one function for both, its inputs and
        outputs behind barriers, so that nothing the harness computes
        around it (the loss of the same row, beside the compared
        logits) is compiled into it. Without them, in ``run.py``'s
        check the two passes chose other sets at a near tie in two
        runs of two (``logits_rel_max`` 0.58 and 1.37); with them in
        none of seven (PERF.md, section 6)."""
        params, tokens = jax.lax.optimization_barrier((params, tokens))
        return jax.lax.optimization_barrier(
            model.glm_lite_apply(config, params, tokens, choices=True))

    def apply(params, tokens):
        main, ahead, _ = forward(params, tokens)
        return jax.vmap(side_by_side)(main, ahead)

    def reference_logits(params, tokens):
        """The reference's main and MTP logits side by side, following
        at near ties the choices of the program's own forward pass
        (``apply``'s, the parameters cast as the step casts them);
        followed nowhere where it would be at more than
        MAX_FOLLOWED_SHARE of the choices."""
        cast = jax.tree.map(lambda x: x.astype(dtype), params)
        _, _, theirs = forward(cast, tokens[None])
        main, ahead, followed = logits(sizes, params, tokens, theirs[:, 0],
                                       NEAR_TIE_EPS)
        choices = tokens.shape[0] * _routed_layers(sizes)
        most = int(MAX_FOLLOWED_SHARE * choices)
        jax.debug.callback(_say_followed, followed, most, choices)
        return jax.lax.cond(
            followed > most,
            lambda: side_by_side(*logits(sizes, params, tokens)[:2]),
            lambda: side_by_side(main, ahead))

    return Family(
        model_config=config,
        init=lambda rng: model.glm_lite_init(config, weights),
        loss_fn=model.glm_lite_loss_fn(config),
        logical_axes=model.glm_lite_logical_axes(config),
        apply=apply,
        reference_logits=reference_logits,
        tolerances={
            key: value for key, value in
            reference.tolerances(LOGITS_REL_RMS_TOL).items()
            if key.startswith("logits")
        },
        flops_per_token=flops_per_token(sizes, sizes["sequence"]),
        work=WORK,
    )


def _say_followed(followed, most, choices):
    print(f"[glm_lite] near ties followed: {int(followed)} of {choices} "
          f"token-layer choices (eps {NEAR_TIE_EPS})"
          + (f": over {int(most)}, so none is" if followed > most else ""),
          file=sys.stderr, flush=True)
