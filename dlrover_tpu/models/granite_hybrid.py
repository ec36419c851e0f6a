"""Granite-4.0-H hybrid decoder: Mamba-2 state-space layers and
attention layers in one declared stack (``model_type``
``granitemoehybrid`` without routed experts, as ``transformers``'
``GraniteMoeHybridForCausalLM`` computes it).

Equations (``h`` the hidden state; every linear map without bias
unless said)::

    x0     = E[tokens] * embedding_multiplier
    x      = x + residual_multiplier * Mixer(RMSNorm(x))      per layer,
    x      = x + residual_multiplier * MLP(RMSNorm(x))        in this order
    MLP(h) = W_out (silu(g) * u),   [g | u] = W_in h
    logits = RMSNorm(x) E^T / logits_scaling                  head tied to E

``Mixer`` of an ``attention`` layer: grouped-query causal attention
with **no position embedding** (``position_embedding_type`` "nope"),
``softmax(q k^T * attention_multiplier) v`` (the published multiplier,
not ``1 / sqrt(head)``, handed to the flash kernels as ``sm_scale``),
then ``W_o``.

``Mixer`` of a ``mamba`` layer (Mamba-2)::

    [z | xBC | dt] = in_proj(h)             widths d_inner, d_inner + 2 G N, H
    xBC = silu(conv1d(xBC))                 depthwise, causal, kernel 4, bias
    [x | B | C] = xBC                       x: H heads of P; B, C: G groups of N
    dt = softplus(dt + dt_bias),  A = -exp(A_log)          one scalar a head
    H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T,   y_t = H_t C_t + D x_t
    y = RMSNorm(y * silu(z)) * scale        gate before norm, one group
    out_proj(y)

The recurrence runs in its chunked form (``ops/ssd.py``) at the
published ``mamba_chunk_size``.

Layers of one kind that follow one another form a *run*; a run's
parameters are stacked on axis 0 and scanned by
``pipeline.stage_run_scan`` -> ``stage_layer_scan``, the scan (save
policy, ``remat``, fsdp overlap hook) that the homogeneous models use,
and the runs are chained in the order ``layer_types`` declares.

Departures from the published code: none in the mathematics. Initial
values follow it where that matters for the recurrence (``A_log =
log(1..H)``, ``D = 1``, ``dt_bias`` the inverse softplus of step sizes
log-uniform in [0.001, 0.1], the convolution uniform in +-1/sqrt(taps)
as in the Mamba-2 authors' code); every matrix is normal(0,
``init_range``). Under the Trainer's bf16 compute ``A_log``, ``D`` and
``dt_bias`` reach the step rounded to bf16 like every parameter
(``accelerate._compute_cast``), where the published code keeps them
float32; the float32 masters are what the optimizer updates.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.common import telemetry
from dlrover_tpu.models.llama import (
    _attention,
    _rms_norm,
    bhsd_flash_attention,
    flash_einsum_path,
)
from dlrover_tpu.ops.cross_entropy import softmax_cross_entropy
from dlrover_tpu.ops.fp8 import qdot, qeinsum
from dlrover_tpu.ops.ssd import causal_conv_silu, ssd_scan
from dlrover_tpu.parallel.sharding import shard_logical

KINDS = ("mamba", "attention")


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    dim: int = 2048
    # one entry a layer, "mamba" or "attention"
    layer_types: Tuple[str, ...] = ("mamba",) * 5 + ("attention",) \
        + ("mamba",) * 4
    n_heads: int = 32
    n_kv_heads: int = 8
    mlp_dim: int = 8192                  # shared_intermediate_size
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    mamba_state: int = 128
    mamba_groups: int = 1
    mamba_conv: int = 4
    mamba_chunk: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    norm_eps: float = 1e-5
    init_range: float = 0.02
    dtype: str = "bfloat16"
    remat: bool = True                   # checkpoint each scanned layer
    # attention dispatch shared with the llama family
    attn_impl: str = "flash"
    attn_block_q: int = 1024
    attn_block_k: int = 1024
    attn_bwd_block_q: int = 0
    attn_bwd_block_k: int = 0

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unknown = sorted(set(self.layer_types) - set(KINDS))
        if unknown or not self.layer_types:
            raise ValueError(
                f"layer_types must be a non-empty list of {KINDS}, "
                f"got {unknown or 'nothing'}"
            )
        if self.mamba_heads % self.mamba_groups:
            raise ValueError(
                f"{self.mamba_heads} Mamba heads do not divide into "
                f"{self.mamba_groups} groups"
            )

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        """Channels the convolution sees: x, B and C side by side."""
        return self.mamba_inner + 2 * self.mamba_groups * self.mamba_state

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
        d, m = self.dim, self.mlp_dim
        inner, heads = self.mamba_inner, self.mamba_heads
        mixer = (
            d * (inner + self.mamba_conv_dim + heads)       # in_proj
            + self.mamba_conv_dim * (self.mamba_conv + 1)   # conv + bias
            + 3 * heads                                     # A_log, D, dt_bias
            + inner                                         # gated norm
            + inner * d                                     # out_proj
        )
        attention = 2 * d * self.n_heads * self.head_dim \
            + 2 * d * self.n_kv_heads * self.head_dim
        n_mamba = self.layer_types.count("mamba")
        n_attn = len(self.layer_types) - n_mamba
        return {
            "mamba_mixer": n_mamba * mixer,
            "attention": n_attn * attention,
            # with the two norms of every layer and the final norm
            "mlp": len(self.layer_types) * (3 * d * m + 2 * d) + d,
            "embedding": self.vocab_size * d,
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
        "mlp_norm": jnp.ones((layers, d)),
        "w_in": jax.random.normal(keys[0], (layers, d, 2 * m)) * std,
        "w_out": jax.random.normal(keys[1], (layers, m, d)) * std,
    }


def _mamba_init(config, rng, layers):
    d, inner, heads = config.dim, config.mamba_inner, config.mamba_heads
    conv_dim, std = config.mamba_conv_dim, config.init_range
    keys = jax.random.split(rng, 6)
    # step sizes log-uniform in [0.001, 0.1], stored as the inverse of
    # the softplus the layer applies
    dt = jnp.exp(
        jax.random.uniform(keys[3], (layers, heads))
        * (math.log(0.1) - math.log(0.001)) + math.log(0.001)
    )
    return {
        "norm": jnp.ones((layers, d)),
        "in_proj": jax.random.normal(
            keys[0], (layers, d, inner + conv_dim + heads)) * std,
        # uniform in +-1/sqrt(taps), the convolution's default in the
        # Mamba-2 authors' code: normal(0, init_range) would scale x, B
        # and C by 0.03 and leave the state path numerically dead
        "conv_w": jax.random.uniform(
            keys[1], (layers, config.mamba_conv, conv_dim),
            minval=-1.0, maxval=1.0) * config.mamba_conv ** -0.5,
        "conv_b": jnp.zeros((layers, conv_dim)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)),
            (layers, heads)),
        "D": jnp.ones((layers, heads)),
        "gate_norm": jnp.ones((layers, inner)),
        "out_proj": jax.random.normal(keys[2], (layers, inner, d)) * std,
        **_mlp_init(config, keys[4:6], layers),
    }


def _attention_init(config, rng, layers):
    d, hd, std = config.dim, config.head_dim, config.init_range
    h, kvh = config.n_heads, config.n_kv_heads
    keys = jax.random.split(rng, 6)
    return {
        "norm": jnp.ones((layers, d)),
        "wq": jax.random.normal(keys[0], (layers, d, h * hd)) * std,
        "wk": jax.random.normal(keys[1], (layers, d, kvh * hd)) * std,
        "wv": jax.random.normal(keys[2], (layers, d, kvh * hd)) * std,
        "wo": jax.random.normal(keys[3], (layers, h * hd, d)) * std,
        **_mlp_init(config, keys[4:6], layers),
    }


_RUN_INIT = {"mamba": _mamba_init, "attention": _attention_init}


def granite_hybrid_init(config: GraniteHybridConfig, rng) -> dict:
    """Initialise params (fp32 masters): one stacked tree a run of like
    layers under ``layers``, keyed ``<index>_<kind>``."""
    runs = config.runs()
    keys = jax.random.split(rng, len(runs) + 1)
    return {
        "embed": jax.random.normal(
            keys[0], (config.vocab_size, config.dim)) * config.init_range,
        "layers": {
            name: _RUN_INIT[kind](config, key, count)
            for (name, kind, count), key in zip(runs, keys[1:])
        },
        "final_norm": jnp.ones((config.dim,)),
    }


_MLP_AXES = {
    "mlp_norm": ("layer", "embed"),
    "w_in": ("layer", "embed", "mlp"),
    "w_out": ("layer", "mlp", "embed"),
}
# the mixer's inner width is not split over the tensor axis: z, x, B, C
# and dt lie side by side in one projection, and B and C are shared by
# all heads; fsdp shards the hidden dim of both projections
_RUN_AXES = {
    "mamba": {
        "norm": ("layer", "embed"),
        "in_proj": ("layer", "embed", None),
        "conv_w": ("layer", None, None),
        "conv_b": ("layer", None),
        "dt_bias": ("layer", None),
        "A_log": ("layer", None),
        "D": ("layer", None),
        "gate_norm": ("layer", None),
        "out_proj": ("layer", None, "embed"),
        **_MLP_AXES,
    },
    "attention": {
        "norm": ("layer", "embed"),
        "wq": ("layer", "embed", "heads"),
        "wk": ("layer", "embed", "kv_heads"),
        "wv": ("layer", "embed", "kv_heads"),
        "wo": ("layer", "heads", "embed"),
        **_MLP_AXES,
    },
}


def granite_hybrid_logical_axes(config: GraniteHybridConfig) -> dict:
    """Logical sharding names matching the ``granite_hybrid_init`` tree."""
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            name: dict(_RUN_AXES[kind]) for name, kind, _ in config.runs()
        },
        "final_norm": ("embed",),
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _mlp(config, x, p):
    """The shared SwiGLU block on the second residual branch."""
    dtype = x.dtype
    with jax.named_scope("mlp"):
        y = _rms_norm(x, p["mlp_norm"], config.norm_eps)
        gu = qdot(y, p["w_in"].astype(dtype), site="mlp")
        mid = jax.nn.silu(gu[..., :config.mlp_dim]) * gu[..., config.mlp_dim:]
        mid = shard_logical(mid, ("batch", "seq", "mlp"))
        out = qdot(mid, p["w_out"].astype(dtype), site="mlp")
    x = x + out * jnp.asarray(config.residual_multiplier, dtype)
    return shard_logical(x, ("batch", "seq", "embed"))


def _gated_norm(y, z, scale, eps):
    """``RMSNorm(y * silu(z)) * scale`` over all channels: the gate
    goes in before the norm (one group), in float32."""
    with jax.named_scope("mamba_gate_norm"):
        gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        return _rms_norm(gated, scale.astype(jnp.float32), eps).astype(y.dtype)


def _mamba_mixer(config, y, p):
    """y [B, S, D] (normed) -> the Mamba-2 mixer's output [B, S, D]."""
    dtype = y.dtype
    B, S, _ = y.shape
    inner, heads = config.mamba_inner, config.mamba_heads
    groups, state = config.mamba_groups, config.mamba_state
    with jax.named_scope("mamba_in_proj"):
        # named for the layer's own checkpoint (_stage_fn's policy)
        zxbcdt = checkpoint_name(
            qdot(y, p["in_proj"].astype(dtype), site="mamba_proj"),
            "mamba_in_proj")
        z = zxbcdt[..., :inner]
        dt = zxbcdt[..., inner + config.mamba_conv_dim:]
    with jax.named_scope("mamba_conv"):
        # xBC, channels inner.. of the projection's output: the kernel
        # reads them where they lie
        xbc = causal_conv_silu(zxbcdt, p["conv_w"], p["conv_b"], first=inner)
    x = xbc[..., :inner].reshape(B, S, heads, config.mamba_head_dim)
    b = xbc[..., inner:inner + groups * state].reshape(B, S, groups, state)
    c = xbc[..., inner + groups * state:].reshape(B, S, groups, state)
    dt = jax.nn.softplus(
        dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    out = ssd_scan(x, dt, a, b, c, p["D"], config.mamba_chunk)
    gated = _gated_norm(out.reshape(B, S, inner), z, p["gate_norm"],
                        config.norm_eps)
    with jax.named_scope("mamba_out_proj"):
        return qdot(gated, p["out_proj"].astype(dtype), site="mamba_proj")


def _attention_mixer(config, y, p, qk_norm=None):
    """y [B, S, D] (normed) -> attention output [B, S, D]; no position
    embedding, the published multiplier as the softmax scale (None: the
    kernels' ``1 / sqrt(head_dim)``). ``qk_norm(q, k, heads)`` stands
    between the projections and the kernel where a model norms its
    queries and keys: both arrive with their heads on axis ``heads``
    and a head's channels last."""
    dtype = y.dtype
    B, S, D = y.shape
    h, kvh, hd = config.n_heads, config.n_kv_heads, config.head_dim
    scale = config.attention_multiplier
    with jax.named_scope("attn"):
        if flash_einsum_path(config):
            # projections write the kernel's [B,H,S,Dh] layout directly
            # (llama's einsum form, without the rotary tables)
            w_qkv = jnp.concatenate(
                [p["wq"].astype(dtype).reshape(D, h, hd),
                 p["wk"].astype(dtype).reshape(D, kvh, hd),
                 p["wv"].astype(dtype).reshape(D, kvh, hd)], axis=1)
            qkv = qeinsum("bsd,dhk->bhsk", y, w_qkv, site="attn_qkv")
            q, k = qkv[:, :h], qkv[:, h:h + kvh]
            if qk_norm is not None:
                q, k = qk_norm(q, k, 1)
            out = bhsd_flash_attention(
                config, q, k, qkv[:, h + kvh:], sm_scale=scale)
            return qeinsum("bhsk,hkd->bsd", out,
                           p["wo"].astype(dtype).reshape(h, hd, D),
                           site="attn_out")
        q = qdot(y, p["wq"].astype(dtype), site="attn_qkv")
        k = qdot(y, p["wk"].astype(dtype), site="attn_qkv")
        v = qdot(y, p["wv"].astype(dtype), site="attn_qkv")
        q, k = q.reshape(B, S, h, hd), k.reshape(B, S, kvh, hd)
        if qk_norm is not None:
            q, k = qk_norm(q, k, 2)
        out = _attention(
            config, q, k, v.reshape(B, S, kvh, hd), sm_scale=scale)
        return qdot(out.reshape(B, S, h * hd), p["wo"].astype(dtype),
                    site="attn_out")


_MIXER = {"mamba": _mamba_mixer, "attention": _attention_mixer}


def _layer_fn(config, kind):
    mixer = _MIXER[kind]

    def layer(x, p):
        y = _rms_norm(x, p["norm"], config.norm_eps)
        x = x + mixer(config, y, p) * jnp.asarray(
            config.residual_multiplier, x.dtype)
        x = shard_logical(x, ("batch", "seq", "embed"))
        return _mlp(config, x, p), jnp.zeros((), jnp.float32)

    return layer


def _stage_fn(config: GraniteHybridConfig):
    """The whole stack: each run through the shared layer scan, the
    runs chained in their declared order."""
    from dlrover_tpu.parallel.pipeline import layer_input, stage_run_scan

    return stage_run_scan(
        {kind: _layer_fn(config, kind) for kind in KINDS},
        [(name, kind) for name, kind, _ in config.runs()],
        remat=config.remat,
        # a Mamba layer keeps its input and its first projection's
        # output (0.13 GiB a layer at 8192 tokens: the one matmul in
        # five its recomputation then leaves out): what the default
        # policy keeps of it, every projection's output, is 0.44 GiB a
        # layer there, and nine of them do not fit beside the train
        # state. The attention layers keep the default (dots and the
        # kernel's output), so the forward kernel runs once
        policy={"mamba": layer_input(keep=("mamba_in_proj",))},
        # one layer's logical axes a kind (sans the leading "layer"
        # dim): opts each run into the fsdp-gather overlap
        layer_axes={
            kind: {k: tuple(v[1:]) for k, v in axes.items()}
            for kind, axes in _RUN_AXES.items()
        },
    )


def granite_hybrid_apply(config: GraniteHybridConfig, params, tokens):
    """tokens [B, S] int32 -> logits [B, S, vocab] float32."""
    from dlrover_tpu.parallel.pipeline import pipe_size

    if pipe_size() > 1:
        raise NotImplementedError(
            "pipeline stages of unlike layers: the schedules shard one "
            "stacked tree over the pipe axis, and a hybrid stack is "
            "several (docs/DESIGN.md); use a mesh with pipe=1"
        )
    dtype = jnp.dtype(config.dtype)
    x = params["embed"].astype(dtype)[tokens] * jnp.asarray(
        config.embedding_multiplier, dtype)
    x = shard_logical(x, ("batch", "seq", "embed"))
    x, _aux = _stage_fn(config)(params["layers"], x)
    with jax.named_scope("head"):
        x = _rms_norm(x, params["final_norm"], config.norm_eps)
        logits = x @ params["embed"].astype(dtype).T
        logits = shard_logical(logits, ("batch", "seq", "vocab"))
        return logits.astype(jnp.float32) / config.logits_scaling


def _publish_shape(config: GraniteHybridConfig):
    """What was built, as gauges: layers by kind, parameters by part,
    the scan's chunk."""
    for kind in KINDS:
        telemetry.gauge_set(
            "model.layers", config.layer_types.count(kind), kind=kind)
    for part, count in config.param_counts().items():
        telemetry.gauge_set("model.params", count, kind=part)
    telemetry.gauge_set("model.ssd.chunk", config.mamba_chunk)


def granite_hybrid_loss_fn(config: GraniteHybridConfig):
    """Next-token CE loss closure for auto_accelerate."""
    _publish_shape(config)

    def loss_fn(params, batch, rng):
        tokens = batch["tokens"]
        logits = granite_hybrid_apply(config, params, tokens[:, :-1])
        loss, valid = softmax_cross_entropy(logits, tokens[:, 1:])
        return loss.sum() / jnp.maximum(valid.sum(), 1)

    return loss_fn
