"""The ``granite_hybrid`` family (IBM Granite 4.0-H, ``model_type``
``granitemoehybrid`` with ``num_local_experts`` 0, as ``transformers``'
``GraniteMoeHybridForCausalLM`` computes it; the mixer is Mamba-2, Dao &
Gu 2024), run through ``dlrover_tpu/models/granite_hybrid.py``.

``layer_types`` declares each layer as ``mamba`` or ``attention``. With
``h`` the hidden state and every linear map without bias unless said:

- ``x0 = E[tokens] * embedding_multiplier``; per layer ``x = x +
  residual_multiplier * Mixer(RMSNorm(x))``, then ``x = x +
  residual_multiplier * MLP(RMSNorm(x))``; ``MLP(h) = W_out (silu(g) *
  u)`` with ``[g | u] = W_in h`` (``shared_intermediate_size`` wide);
  ``logits = RMSNorm(x) E^T / logits_scaling``, head tied.
- ``attention``: grouped-query causal attention without any position
  embedding (``position_embedding_type`` "nope"), ``softmax(q k^T *
  attention_multiplier) v``, then ``W_o``.
- ``mamba``: ``[z | xBC | dt] = in_proj(h)``; ``xBC =
  silu(conv1d(xBC))`` depthwise, causal, ``mamba_d_conv`` taps, with
  bias; ``xBC = [x | B | C]`` (``mamba_n_heads`` heads of
  ``mamba_d_head``; ``mamba_n_groups`` groups of ``mamba_d_state``);
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head ``H_t
  = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T``, ``y_t = H_t C_t + D x_t``;
  ``y = RMSNorm(y * silu(z)) * scale`` over all channels; ``out_proj``.

The reference below runs the recurrence **position by position** (a
``lax.scan`` over the sequence), so it shares nothing with the
program's chunked form, and computes attention over blocks of query
rows: at 8192 positions and 32 heads the ``S x S`` scores of
``reference.attention`` would be 8.6 GB.

Weights arrive as the Trainer holds them: ``params["layers"]`` maps
``<index>_<kind>`` to one run of like layers, stacked on axis 0.
``sizes`` holds the published keys of the model's ``config.json``.
"""

from __future__ import annotations

import itertools

import flops
import jax
import jax.numpy as jnp
import reference
from families import Family

# The system multiplies in bf16 with float32 accumulation (and its
# A_log, D and dt_bias reach the step rounded to bf16); the reference is
# float32 throughout. Relative rms distance of the logits at the last
# 256 of 8192 positions, on the chip at the configuration's real widths
# (tests/precision_probe.py granite-4.0-h-micro; PERF.md, Findings,
# PR 29): bf16 0.0240 (the cell's own runs 0.0237-0.0239), the
# program's int8 matmuls 0.1219, fp8 0.2053. The limit is the geometric
# mean of the first two: bf16 passes with a factor of 2.2 to spare, and
# the nearest precision below it fails by as much (its largest single
# logit reads 0.678 against 8 x the limit = 0.432, so it fails by both).
LOGITS_REL_RMS_TOL = 0.054

QUERY_BLOCK = 256       # rows of queries scored at a time


def _runs(sizes):
    """[(parameter key, kind, layers)] of the runs of like layers."""
    kinds = [(kind, len(list(group)))
             for kind, group in itertools.groupby(sizes["layer_types"])]
    return [(f"{i:02d}_{kind}", kind, count)
            for i, (kind, count) in enumerate(kinds)]


def _attention(q, k, v, scale):
    """q [S, H, Dh], k/v [S, KVH, Dh] -> [S, H * Dh]: causal softmax(q
    k^T * scale) v in float32, ``QUERY_BLOCK`` rows of queries at a
    time."""
    seq, heads, head = q.shape
    group = heads // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    block = min(QUERY_BLOCK, seq)
    if seq % block:
        raise ValueError(f"{seq} positions are no multiple of {block}")
    keys = jnp.arange(seq)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        visible = keys[None, :] <= (start + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(rows, jnp.arange(0, seq, block))
    return out.reshape(seq, heads * head)


def _conv(x, weight, bias):
    """x [S, C], weight [K, C]: y_t = bias + sum_k weight[k] x_{t-K+1+k}."""
    taps = weight.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x])
    return bias + sum(
        weight[k] * padded[k:k + x.shape[0]] for k in range(taps)
    )


def _recurrence(x, dt, a, b, c, d):
    """x [S, H, P], dt [S, H], a [H], b/c [S, H, N], d [H] -> y [S, H,
    P], one position at a time."""

    def step(state, at):
        xt, dtt, bt, ct = at
        state = jnp.exp(dtt * a)[:, None, None] * state \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, ct) + d[:, None] * xt

    state = jnp.zeros(x.shape[1:] + (b.shape[-1],), jnp.float32)
    return jax.lax.scan(step, state, (x, dt, b, c))[1]


def _mamba(sizes, y, w):
    heads, head = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    groups, state = sizes["mamba_n_groups"], sizes["mamba_d_state"]
    inner, seq = heads * head, y.shape[0]
    zxbcdt = y @ w["in_proj"]
    z, xbc, dt = jnp.split(
        zxbcdt, [inner, 2 * inner + 2 * groups * state], axis=-1)
    xbc = jax.nn.silu(_conv(xbc, w["conv_w"], w["conv_b"]))
    x, b, c = jnp.split(xbc, [inner, inner + groups * state], axis=-1)
    b = jnp.repeat(b.reshape(seq, groups, state), heads // groups, axis=1)
    c = jnp.repeat(c.reshape(seq, groups, state), heads // groups, axis=1)
    out = _recurrence(
        x.reshape(seq, heads, head), jax.nn.softplus(dt + w["dt_bias"]),
        -jnp.exp(w["A_log"]), b, c, w["D"],
    ).reshape(seq, inner)
    out = reference.rms_norm(
        out * jax.nn.silu(z), w["gate_norm"], sizes["rms_norm_eps"])
    return out @ w["out_proj"]


def _self_attention(sizes, y, w):
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    seq, head = y.shape[0], sizes["hidden_size"] // heads
    q = (y @ w["wq"]).reshape(seq, heads, head)
    k = (y @ w["wk"]).reshape(seq, kv, head)
    v = (y @ w["wv"]).reshape(seq, kv, head)
    return _attention(q, k, v, sizes["attention_multiplier"]) @ w["wo"]


_MIXER = {"mamba": _mamba, "attention": _self_attention}


def logits(sizes: dict, params: dict, tokens):
    """The plain reference: tokens [S] int32 -> logits [S, vocab]
    float32."""
    eps, mid = sizes["rms_norm_eps"], sizes["shared_intermediate_size"]
    residual = sizes["residual_multiplier"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens] * sizes["embedding_multiplier"]
        for name, kind, _ in _runs(sizes):

            def layer(x, w, mixer=_MIXER[kind]):
                y = reference.rms_norm(x, w["norm"], eps)
                x = x + residual * mixer(sizes, y, w)
                y = reference.rms_norm(x, w["mlp_norm"], eps)
                gu = y @ w["w_in"]
                out = (jax.nn.silu(gu[:, :mid]) * gu[:, mid:]) @ w["w_out"]
                return x + residual * out, None

            x, _ = jax.lax.scan(layer, x, params["layers"][name])
        x = reference.rms_norm(x, params["final_norm"], eps)
        return x @ params["embed"].T / sizes["logits_scaling"]


def flops_per_token(sizes: dict, seq: int) -> float:
    """FLOPs a training step requires per token (``flops.py`` says what
    counts). The scan counts as the recurrence itself: the state's
    update ``(dt x) B^T`` and its read-out ``H C``, 2 FLOPs a
    multiply-add each over ``heads x head x state``; the chunked form's
    extra products, the decays, the convolution's 4 taps and the gate
    are not matmuls a token has to cross."""
    d, m = sizes["hidden_size"], sizes["shared_intermediate_size"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    head = d // heads
    m_heads, m_head = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    state, groups = sizes["mamba_d_state"], sizes["mamba_n_groups"]
    inner = m_heads * m_head
    mlp = 2 * d * 2 * m + 2 * m * d                 # w_in, w_out
    mamba = (
        2 * d * (2 * inner + 2 * groups * state + m_heads)    # in_proj
        + 2 * inner * d                                       # out_proj
        + 2 * 2 * m_heads * m_head * state                    # the scan
        + mlp
    )
    attention = (
        2 * 2 * d * heads * head          # wq, wo
        + 2 * 2 * d * kv * head           # wk, wv
        + flops.attention_flops(heads, head, seq)
        + mlp
    )
    kinds = sizes["layer_types"]
    n_mamba = kinds.count("mamba")
    forward = n_mamba * mamba + (len(kinds) - n_mamba) * attention \
        + 2 * d * sizes["vocab_size"]
    return 3 * forward


def _attention_work(sizes: dict) -> dict:
    heads = sizes["num_attention_heads"]
    return flops.attention_kernel_work(
        sizes["batch"], sizes["sequence"],
        sizes["layer_types"].count("attention"), heads,
        sizes["num_key_value_heads"], sizes["hidden_size"] // heads,
    )


# what a train step requires of each kernel it runs, by the kernel's
# name in the device trace: sizes -> (FLOPs, least HBM bytes); the
# attention layers held here are the only ones that run the kernels
WORK = {
    "flash_fwd": lambda sizes: _attention_work(sizes)["forward"],
    "flash_bwd": lambda sizes: _attention_work(sizes)["backward"],
}

# what the program does not implement of the family: a file that asks
# for it is refused, not run as something else
_REQUIRED = {
    "num_local_experts": 0, "position_embedding_type": "nope",
    "tie_word_embeddings": True, "hidden_act": "silu",
    "normalization_function": "rmsnorm", "attention_bias": False,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
}


def build(sizes: dict) -> Family:
    from dlrover_tpu.models import granite_hybrid as model

    for key, value in _REQUIRED.items():
        if sizes[key] != value:
            raise ValueError(
                f"models/granite_hybrid.py implements {key}={value!r} "
                f"only; the configuration says {sizes[key]!r}"
            )
    if len(sizes["layer_types"]) != sizes["num_hidden_layers"]:
        raise ValueError(
            f"{len(sizes['layer_types'])} layer_types for "
            f"{sizes['num_hidden_layers']} layers"
        )
    inner = sizes["mamba_n_heads"] * sizes["mamba_d_head"]
    if inner != sizes["mamba_expand"] * sizes["hidden_size"]:
        raise ValueError(
            f"mamba_n_heads x mamba_d_head = {inner} is not mamba_expand "
            f"x hidden_size"
        )
    config = model.GraniteHybridConfig(
        vocab_size=sizes["vocab_size"], dim=sizes["hidden_size"],
        layer_types=tuple(sizes["layer_types"]),
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"],
        mlp_dim=sizes["shared_intermediate_size"],
        mamba_heads=sizes["mamba_n_heads"],
        mamba_head_dim=sizes["mamba_d_head"],
        mamba_state=sizes["mamba_d_state"],
        mamba_groups=sizes["mamba_n_groups"],
        mamba_conv=sizes["mamba_d_conv"],
        mamba_chunk=sizes["mamba_chunk_size"],
        embedding_multiplier=sizes["embedding_multiplier"],
        residual_multiplier=sizes["residual_multiplier"],
        attention_multiplier=sizes["attention_multiplier"],
        logits_scaling=sizes["logits_scaling"],
        norm_eps=sizes["rms_norm_eps"], **sizes.get("program", {}),
    )
    return Family(
        model_config=config,
        init=lambda rng: model.granite_hybrid_init(config, rng),
        loss_fn=model.granite_hybrid_loss_fn(config),
        logical_axes=model.granite_hybrid_logical_axes(config),
        apply=lambda p, t: model.granite_hybrid_apply(config, p, t),
        reference_logits=lambda p, t: logits(sizes, p, t),
        tolerances=reference.tolerances(LOGITS_REL_RMS_TOL),
        flops_per_token=flops_per_token(sizes, sizes["sequence"]),
        work=WORK,
    )
