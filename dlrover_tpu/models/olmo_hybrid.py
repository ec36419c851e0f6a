"""Olmo-Hybrid decoder: gated-delta-rule linear-attention layers and
full-attention layers in one declared stack (``model_type``
``olmo_hybrid``; the recurrent mixer is Gated DeltaNet, Yang, Kautz &
Hatamizadeh 2024, arXiv:2412.06464).

Equations (``h`` a block's input; no linear map has a bias)::

    x0     = E[tokens]
    x      = x + RMSNorm(Mixer(x))        the Olmo family's reordered
    x      = x + RMSNorm(MLP(x))          norm: on the output, none on
    MLP(h) = W_down (silu(W_gate h) * (W_up h))             the input
    logits = RMSNorm(x_L) W_head^T        head untied from E

``Mixer`` of a ``full_attention`` layer: causal softmax attention at
``1 / sqrt(head)`` with **no position embedding** (the published
``rope_theta`` is null), ``q = RMSNorm(W_q h)`` and ``k = RMSNorm(W_k
h)`` over all of a position's channels before the heads are split, then
``W_o``.

``Mixer`` of a ``linear_attention`` layer (H heads; keys ``dk`` wide,
values ``dv``)::

    q = W_q h, k = W_k h, v = W_v h          widths H dk, H dk, H dv
    q, k, v = silu(conv1d(.)) each           depthwise, causal, 4 taps
    q = q / ||q||_2 / sqrt(dk),  k = k / ||k||_2      per head, float32
    beta = 2 sigmoid(W_b h)                  the 2: linear_allow_neg_eigval
    g    = -exp(A_log) softplus(W_a h + dt_bias)      log-decay, float32
    S_t  = exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T k_t)^T
    o_t  = S_t^T q_t                         ops/gated_delta.py
    o    = RMSNorm_dv(o) * w * silu(W_g h)   per head, gate after norm
    W_o o

``W_q``, ``W_k``, ``W_v``, ``W_g``, ``W_a`` and ``W_b`` lie side by side
in one matrix, ``in_proj`` (columns ``[q | k | v | gate | a | b]``):
one matmul, and the three convolutions are one call of the kernel pair
over the first ``2 H dk + H dv`` columns of its output, read where they
lie (``first=0``). The published files hold six matrices; nothing in
the mathematics knows.

Layers of one kind that follow one another form a *run*, stacked on
axis 0 and scanned by ``pipeline.stage_run_scan`` as in
``models/granite_hybrid.py``; the full-attention mixer is that file's
own (the flash kernels' dispatch, shared) with the q/k norm hooked in.

Under the Trainer's bf16 compute ``A_log`` and ``dt_bias`` reach the
step rounded to bf16 like every parameter (``accelerate._compute_cast``);
the float32 masters are what the optimizer updates.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from dlrover_tpu.common import telemetry
from dlrover_tpu.models.granite_hybrid import _attention_mixer
from dlrover_tpu.models.llama import _rms_norm
from dlrover_tpu.ops.cross_entropy import fused_linear_cross_entropy
from dlrover_tpu.ops.fp8 import qdot
from dlrover_tpu.ops.gated_delta import gated_delta_rule
from dlrover_tpu.ops.ssd import causal_conv_silu
from dlrover_tpu.parallel.sharding import shard_logical

KINDS = ("linear_attention", "full_attention")
L2_EPS = 1e-6       # under the root of q's and k's length


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    dim: int = 3840
    # one entry a layer, "linear_attention" or "full_attention"
    layer_types: Tuple[str, ...] = (
        ("linear_attention",) * 3 + ("full_attention",)) * 8
    n_heads: int = 30
    n_kv_heads: int = 30
    mlp_dim: int = 11008
    linear_heads: int = 30               # key heads = value heads
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv: int = 4
    linear_allow_neg_eigval: bool = True
    linear_chunk: int = 64
    norm_eps: float = 1e-6
    init_range: float = 0.02
    dtype: str = "bfloat16"
    remat: bool = True                   # a layer keeps its input (and o)
    ce_chunks: int = 8                   # the head, a chunk of rows a time
    # attention dispatch shared with the llama family
    attn_impl: str = "flash"
    attn_block_q: int = 1024
    attn_block_k: int = 1024
    # what else that dispatcher and the shared mixer read off a config;
    # no field: nothing sets another value
    attn_bwd_block_q = 0
    attn_bwd_block_k = 0
    attention_multiplier = None          # the kernels' 1 / sqrt(head)

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unknown = sorted(set(self.layer_types) - set(KINDS))
        if unknown or not self.layer_types:
            raise ValueError(
                f"layer_types must be a non-empty list of {KINDS}, "
                f"got {unknown or 'nothing'}"
            )
        if self.dim % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"hidden {self.dim}, {self.n_heads} heads and "
                f"{self.n_kv_heads} key/value heads do not divide"
            )

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def linear_key_dim(self) -> int:
        return self.linear_heads * self.linear_key_head_dim

    @property
    def linear_value_dim(self) -> int:
        return self.linear_heads * self.linear_value_head_dim

    @property
    def linear_conv_dim(self) -> int:
        """Channels the convolutions see: q, k and v side by side."""
        return 2 * self.linear_key_dim + self.linear_value_dim

    @property
    def linear_proj_dim(self) -> int:
        """Columns of ``in_proj``: [q | k | v | gate | a | b]."""
        return self.linear_conv_dim + self.linear_value_dim \
            + 2 * self.linear_heads

    def runs(self):
        """[(name, kind, layers)] of the runs of like layers, in order;
        the name keys the run's stacked parameters and sorts as the
        stack does."""
        from dlrover_tpu.parallel.pipeline import layer_runs

        return [
            (f"{i:02d}_{kind}", kind, count)
            for i, (kind, count) in enumerate(layer_runs(self.layer_types))
        ]

    def param_counts(self) -> dict:
        """Parameters by part, the labels of the ``model.params`` gauge."""
        d = self.dim
        mixer = (
            d * self.linear_proj_dim                    # in_proj
            + self.linear_conv * self.linear_conv_dim   # three convolutions
            + 2 * self.linear_heads                     # A_log, dt_bias
            + self.linear_value_head_dim                # gated norm
            + self.linear_value_dim * d                 # out_proj
        )
        attention = 2 * d * d + 2 * d * self.n_kv_heads * self.head_dim \
            + d + self.n_kv_heads * self.head_dim       # q and k norms
        n_linear = self.layer_types.count("linear_attention")
        return {
            "gdn_mixer": n_linear * mixer,
            "attention": (len(self.layer_types) - n_linear) * attention,
            # with the two block norms of every layer and the final norm
            "mlp": len(self.layer_types) * (3 * d * self.mlp_dim + 2 * d) + d,
            "embedding": self.vocab_size * d,
            "head": self.vocab_size * d,
        }

    def param_count(self) -> int:
        return sum(self.param_counts().values())


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def _mlp_init(config, keys, layers):
    d, m = config.dim, config.mlp_dim
    std = config.init_range
    return {
        "mixer_norm": jnp.ones((layers, d)),
        "mlp_norm": jnp.ones((layers, d)),
        # [gate | up]
        "w_in": jax.random.normal(keys[0], (layers, d, 2 * m)) * std,
        "w_out": jax.random.normal(keys[1], (layers, m, d)) * std,
    }


def _linear_init(config, rng, layers):
    d, heads, std = config.dim, config.linear_heads, config.init_range
    keys = jax.random.split(rng, 7)
    # step sizes log-uniform in [0.001, 0.1], stored as the inverse of
    # the softplus the layer applies; A uniform in (0, 16): the cited
    # paper's reference implementation
    dt = jnp.exp(
        jax.random.uniform(keys[3], (layers, heads))
        * (math.log(0.1) - math.log(0.001)) + math.log(0.001)
    )
    return {
        "in_proj": jax.random.normal(
            keys[0], (layers, d, config.linear_proj_dim)) * std,
        # uniform in +-1/sqrt(taps), a depthwise convolution's default
        # there: normal(0, init_range) would scale q, k and v by 0.03
        "conv_w": jax.random.uniform(
            keys[1], (layers, config.linear_conv, config.linear_conv_dim),
            minval=-1.0, maxval=1.0) * config.linear_conv ** -0.5,
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(
            keys[4], (layers, heads), minval=1e-4, maxval=16.0)),
        "gate_norm": jnp.ones((layers, config.linear_value_head_dim)),
        "out_proj": jax.random.normal(
            keys[2], (layers, config.linear_value_dim, d)) * std,
        **_mlp_init(config, keys[5:7], layers),
    }


def _attention_init(config, rng, layers):
    d, hd, std = config.dim, config.head_dim, config.init_range
    h, kvh = config.n_heads, config.n_kv_heads
    keys = jax.random.split(rng, 6)
    return {
        "wq": jax.random.normal(keys[0], (layers, d, h * hd)) * std,
        "wk": jax.random.normal(keys[1], (layers, d, kvh * hd)) * std,
        "wv": jax.random.normal(keys[2], (layers, d, kvh * hd)) * std,
        "wo": jax.random.normal(keys[3], (layers, h * hd, d)) * std,
        "q_norm": jnp.ones((layers, h * hd)),
        "k_norm": jnp.ones((layers, kvh * hd)),
        **_mlp_init(config, keys[4:6], layers),
    }


_RUN_INIT = {"linear_attention": _linear_init,
             "full_attention": _attention_init}


def olmo_hybrid_init(config: OlmoHybridConfig, rng) -> dict:
    """Initialise params (fp32 masters): one stacked tree a run of like
    layers under ``layers``, keyed ``<index>_<kind>``."""
    runs = config.runs()
    keys = jax.random.split(rng, len(runs) + 2)
    return {
        "embed": jax.random.normal(
            keys[0], (config.vocab_size, config.dim)) * config.init_range,
        "layers": {
            name: _RUN_INIT[kind](config, key, count)
            for (name, kind, count), key in zip(runs, keys[2:])
        },
        "final_norm": jnp.ones((config.dim,)),
        "lm_head": jax.random.normal(
            keys[1], (config.dim, config.vocab_size)) * config.init_range,
    }


_MLP_AXES = {
    "mixer_norm": ("layer", "embed"),
    "mlp_norm": ("layer", "embed"),
    "w_in": ("layer", "embed", "mlp"),
    "w_out": ("layer", "mlp", "embed"),
}
# the linear mixer's inner width is not split over the tensor axis: q,
# k, v, the gate and the two per-head scalars lie side by side in one
# projection; fsdp shards the hidden dim of both projections
_RUN_AXES = {
    "linear_attention": {
        "in_proj": ("layer", "embed", None),
        "conv_w": ("layer", None, None),
        "dt_bias": ("layer", None),
        "A_log": ("layer", None),
        "gate_norm": ("layer", None),
        "out_proj": ("layer", None, "embed"),
        **_MLP_AXES,
    },
    "full_attention": {
        "wq": ("layer", "embed", "heads"),
        "wk": ("layer", "embed", "kv_heads"),
        "wv": ("layer", "embed", "kv_heads"),
        "wo": ("layer", "heads", "embed"),
        "q_norm": ("layer", "heads"),
        "k_norm": ("layer", "kv_heads"),
        **_MLP_AXES,
    },
}


def olmo_hybrid_logical_axes(config: OlmoHybridConfig) -> dict:
    """Logical sharding names matching the ``olmo_hybrid_init`` tree."""
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            name: dict(_RUN_AXES[kind]) for name, kind, _ in config.runs()
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _mlp(config, x, p):
    """``x + RMSNorm(MLP(x))``: SwiGLU on the second residual branch."""
    dtype = x.dtype
    with jax.named_scope("mlp"):
        gu = qdot(x, p["w_in"].astype(dtype), site="mlp")
        mid = jax.nn.silu(gu[..., :config.mlp_dim]) * gu[..., config.mlp_dim:]
        mid = shard_logical(mid, ("batch", "seq", "mlp"))
        out = qdot(mid, p["w_out"].astype(dtype), site="mlp")
        x = x + _rms_norm(out, p["mlp_norm"], config.norm_eps)
    return shard_logical(x, ("batch", "seq", "embed"))


def _unit_length(x, scale=1.0):
    """``x / ||x||_2 * scale`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * (jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS) * scale)


def _gate_norm(out, gate, scale, eps):
    """``RMSNorm(o) * w * silu(gate)`` a head: the gate goes in after
    the norm, in float32."""
    with jax.named_scope("gdn_gate_norm"):
        normed = _rms_norm(
            out.astype(jnp.float32), scale.astype(jnp.float32), eps)
        return (normed * jax.nn.silu(gate.astype(jnp.float32))).astype(
            out.dtype)


def _linear_mixer(config, x, p):
    """x [B, S, D] -> the gated-delta-rule mixer's output [B, S, D]."""
    dtype = x.dtype
    B, S, _ = x.shape
    heads = config.linear_heads
    dk, dv = config.linear_key_head_dim, config.linear_value_head_dim
    keys, values = config.linear_key_dim, config.linear_value_dim
    conv_dim = config.linear_conv_dim
    with jax.named_scope("gdn_proj"):
        proj = qdot(x, p["in_proj"].astype(dtype), site="gdn_proj")
        gate = proj[..., conv_dim:conv_dim + values]
        a = proj[..., conv_dim + values:conv_dim + values + heads]
        b = proj[..., conv_dim + values + heads:]
    with jax.named_scope("gdn_conv"):
        # q, k and v, the projection's first columns: the kernel reads
        # them where they lie; the published convolutions have no bias
        qkv = causal_conv_silu(
            proj, p["conv_w"], jnp.zeros((conv_dim,), dtype))
    with jax.named_scope("gdn_qk_norm"):
        q = _unit_length(
            qkv[..., :keys].reshape(B, S, heads, dk), dk ** -0.5)
        k = _unit_length(qkv[..., keys:2 * keys].reshape(B, S, heads, dk))
        beta = jax.nn.sigmoid(b.astype(jnp.float32))
        if config.linear_allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            a.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    v = qkv[..., 2 * keys:].reshape(B, S, heads, dv)
    out = gated_delta_rule(
        q.astype(dtype), k.astype(dtype), v, g, beta, config.linear_chunk)
    gated = _gate_norm(out, gate.reshape(B, S, heads, dv), p["gate_norm"],
                       config.norm_eps)
    with jax.named_scope("gdn_out_proj"):
        return qdot(gated.reshape(B, S, values), p["out_proj"].astype(dtype),
                    site="gdn_proj")


def _qk_norm(config, p):
    """``RMSNorm`` over all of a position's channels, the heads
    together, on arrays whose heads lie on axis ``heads`` and whose
    last axis is a head's channels."""

    def norm(x, scale, heads):
        var = jnp.mean(jnp.square(x.astype(jnp.float32)),
                       axis=(heads, 3), keepdims=True)
        shape = [1, 1, 1, x.shape[3]]
        shape[heads] = x.shape[heads]
        return x * jax.lax.rsqrt(var + config.norm_eps).astype(x.dtype) \
            * scale.astype(x.dtype).reshape(shape)

    return lambda q, k, heads: (
        norm(q, p["q_norm"], heads), norm(k, p["k_norm"], heads))


def _full_mixer(config, x, p):
    return _attention_mixer(config, x, p, qk_norm=_qk_norm(config, p))


_MIXER = {"linear_attention": _linear_mixer, "full_attention": _full_mixer}


def _layer_fn(config, kind):
    mixer = _MIXER[kind]

    def layer(x, p):
        x = x + _rms_norm(mixer(config, x, p), p["mixer_norm"],
                          config.norm_eps)
        x = shard_logical(x, ("batch", "seq", "embed"))
        return _mlp(config, x, p), jnp.zeros((), jnp.float32)

    return layer


def _stage_fn(config: OlmoHybridConfig):
    """The whole stack: each run through the shared layer scan, the
    runs chained in their declared order."""
    from dlrover_tpu.parallel.pipeline import layer_input, stage_run_scan

    return stage_run_scan(
        {kind: _layer_fn(config, kind) for kind in KINDS},
        [(name, kind) for name, kind, _ in config.runs()],
        remat=config.remat,
        # every layer keeps its input and is recomputed from it in the
        # backward pass: what the default policy keeps, every weight
        # matmul's output, is 0.9 GiB a layer at 8192 tokens. A
        # full-attention layer also keeps the kernel's output and row
        # statistic (63 MB), so the forward kernel runs once
        policy={"linear_attention": layer_input(),
                "full_attention": layer_input(keep=("attn_out",))},
        # one layer's logical axes a kind (sans the leading "layer"
        # dim): opts each run into the fsdp-gather overlap
        layer_axes={
            kind: {k: tuple(v[1:]) for k, v in axes.items()}
            for kind, axes in _RUN_AXES.items()
        },
    )


def _hidden(config: OlmoHybridConfig, params, tokens):
    """tokens [B, S] -> the last layer's x [B, S, D]."""
    from dlrover_tpu.parallel.pipeline import pipe_size

    if pipe_size() > 1:
        raise NotImplementedError(
            "pipeline stages of unlike layers: the schedules shard one "
            "stacked tree over the pipe axis, and a hybrid stack is "
            "several (docs/DESIGN.md); use a mesh with pipe=1"
        )
    dtype = jnp.dtype(config.dtype)
    x = params["embed"].astype(dtype)[tokens]
    x = shard_logical(x, ("batch", "seq", "embed"))
    return _stage_fn(config)(params["layers"], x)[0]


def olmo_hybrid_apply(config: OlmoHybridConfig, params, tokens):
    """tokens [B, S] int32 -> logits [B, S, vocab] float32."""
    dtype = jnp.dtype(config.dtype)
    x = _hidden(config, params, tokens)
    with jax.named_scope("head"):
        x = _rms_norm(x, params["final_norm"], config.norm_eps)
        logits = x @ params["lm_head"].astype(dtype)
        logits = shard_logical(logits, ("batch", "seq", "vocab"))
        return logits.astype(jnp.float32)


def _publish_shape(config: OlmoHybridConfig):
    """What was built, as gauges: layers by kind, parameters by part,
    the rule's chunk."""
    for kind in KINDS:
        telemetry.gauge_set(
            "model.layers", config.layer_types.count(kind), kind=kind)
    for part, count in config.param_counts().items():
        telemetry.gauge_set("model.params", count, kind=part)
    telemetry.gauge_set("model.gdn.chunk", config.linear_chunk)


def olmo_hybrid_loss_fn(config: OlmoHybridConfig):
    """Next-token CE loss closure for auto_accelerate: the head a chunk
    of rows at a time (``fused_linear_cross_entropy``), so the float32
    logits of a whole row are never there."""
    _publish_shape(config)

    def loss_fn(params, batch, rng):
        tokens = batch["tokens"]
        x = _hidden(config, params, tokens[:, :-1])
        with jax.named_scope("head"):
            loss_sum, valid = fused_linear_cross_entropy(
                x, params["lm_head"].astype(x.dtype), tokens[:, 1:],
                n_chunks=config.ce_chunks,
                norm_scale=params["final_norm"], norm_eps=config.norm_eps,
            )
        return loss_sum / jnp.maximum(valid, 1)

    return loss_fn
