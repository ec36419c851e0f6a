"""LLaMA-family decoder, TPU-first.

Pure functional JAX (params are a plain pytree): RMSNorm, RoPE, GQA,
SwiGLU, untied LM head. Layers are *stacked* along a leading axis and the
forward is a ``lax.scan`` over them — one compiled layer body regardless
of depth (fast compiles, XLA-friendly), with ``jax.checkpoint`` applied to
the scanned body for rematerialisation.

Attention is the Pallas flash kernel (dlrover_tpu/ops/attention.py) on
TPU; set ``attn_impl="reference"`` for tiny CPU test shapes where the
plain einsum is faster than interpret mode.

Sharding: every param carries logical axis names (see
``llama_logical_axes``); the parallel layer maps them onto the mesh
(fsdp/tensor/seq/...). Reference parity: this is the flagship-model role
played by atorch's Llama-2 examples (atorch/examples/llama2/) and the HF
attention swaps (atorch/atorch/modules/transformer/layers.py:1354
LlamaAttentionFA).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.ops.attention import (
    flash_attention,
    flash_attention_bshd,
    mha_reference,
    window_block,
)
from dlrover_tpu.ops.cross_entropy import softmax_cross_entropy
from dlrover_tpu.ops.fp8 import qdot, qeinsum, quant_mode
from dlrover_tpu.parallel.sharding import shard_logical


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    mlp_dim: int = 11008
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"          # activation/compute dtype
    # "flash" (Pallas, [B,H,S,Dh]) | "bshd" (Pallas, model-native
    # zero-transpose layout) | "ulysses" | "reference"
    attn_impl: str = "flash"
    remat: bool = True               # checkpoint each scanned layer
    # checkpoint policy when remat=True: "dots_attn" saves weight
    # matmuls AND the flash-attention output (the Pallas kernel is the
    # costliest op to recompute); "dots_attn_offload" sends the dot
    # saves to pinned host memory instead of HBM (pair with
    # auto_accelerate(infer_out_shardings=True)); "dots_no_batch"
    # saves weight matmuls only; "dots" additionally saves batched dots
    remat_policy: str = "dots_attn"
    # measured on v5e (nano-350m, seq 2048): 1024x1024 beats 512x512 by
    # ~15% tokens/s; 2048-wide K blocks fail to fit VMEM. A bwd-block
    # sweep (1024/512/256 combinations) found the fwd blocks also
    # optimal for the bwd kernels at these shapes; 0 = use fwd blocks
    attn_block_q: int = 1024
    attn_block_k: int = 1024
    attn_bwd_block_q: int = 0
    attn_bwd_block_k: int = 0
    # pipeline microbatches when the ``pipe`` mesh axis is active
    # (0 = default 2 * n_stages)
    pipe_microbatches: int = 0
    # "gpipe" (activation-returning schedule, AD-derived backward) or
    # "1f1b" (loss-in-pipeline fused schedule, in-flight activations
    # bounded by pipeline depth — reference default Interleaved1F1B,
    # pipeline_parallel_optimization.py:98). "1f1b" affects the
    # training loss path only; plain forwards always use gpipe.
    pipe_schedule: str = "gpipe"
    # virtual chunks per device for the interleaved 1F1B schedule
    # (1 = plain; V>1 needs pipe_schedule="1f1b", layers divisible by
    # pipe*V, and microbatches divisible by pipe). The pipe-sharded
    # layer stack is applied in interleaved_layer_order.
    pipe_virtual_stages: int = 1
    # sequence chunks for the fused linear CE (1 = materialise full
    # logits). n>1 bounds peak logits memory to [B, S/n, V] by
    # recomputing each chunk's logits in the backward — the lever that
    # makes large per-device batches fit HBM at 32k vocab.
    ce_chunks: int = 1
    # MoE (mixtral-style FFN swap): 0/1 experts = dense
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    moe_z_weight: float = 1e-3

    def __post_init__(self):
        if self.pipe_schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"pipe_schedule must be 'gpipe' or '1f1b', got "
                f"{self.pipe_schedule!r}"
            )

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 1

    def moe_config(self):
        from dlrover_tpu.parallel.moe import MoEConfig

        return MoEConfig(
            n_experts=self.n_experts,
            top_k=self.moe_top_k,
            capacity_factor=self.moe_capacity_factor,
        )

    def param_count(self) -> int:
        d, v, h = self.dim, self.vocab_size, self.head_dim
        if self.is_moe:
            ffn = d * self.n_experts + 3 * d * self.mlp_dim * self.n_experts
        else:
            ffn = 3 * d * self.mlp_dim      # gate, up, down
        per_layer = (
            d * self.n_heads * h            # wq
            + 2 * d * self.n_kv_heads * h   # wk, wv
            + self.n_heads * h * d          # wo
            + ffn
            + 2 * d                         # norms
        )
        return v * d * 2 + d + self.n_layers * per_layer


PRESETS = {
    "tiny": LlamaConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        mlp_dim=128, max_seq_len=128, attn_impl="reference", remat=False,
        dtype="float32",
    ),
    # head_dim 128 (llama-standard): K=64 contractions cap the MXU at
    # half utilisation, measured 2x slower attention kernels on v5e
    "nano-350m": LlamaConfig(
        vocab_size=32000, dim=1024, n_layers=16, n_heads=8, n_kv_heads=8,
        mlp_dim=2816, max_seq_len=2048,
    ),
    "llama2-1b": LlamaConfig(
        vocab_size=32000, dim=2048, n_layers=16, n_heads=16, n_kv_heads=16,
        mlp_dim=5504, max_seq_len=2048,
    ),
    "llama2-7b": LlamaConfig(
        vocab_size=32000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=32,
        mlp_dim=11008, max_seq_len=4096,
    ),
    "llama3-8b": LlamaConfig(
        vocab_size=128256, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        mlp_dim=14336, max_seq_len=8192, rope_theta=500000.0,
    ),
}


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def llama_init(config: LlamaConfig, rng) -> dict:
    """Initialise params (fp32 masters); layer params stacked on axis 0."""
    d, h, hd = config.dim, config.n_heads, config.head_dim
    kvh, m, L = config.n_kv_heads, config.mlp_dim, config.n_layers
    keys = jax.random.split(rng, 10)

    def norm_init(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5))

    if config.is_moe:
        E = config.n_experts
        ffn_params = {
            "router": norm_init(keys[9], (L, d, E), d),
            "w_gate": norm_init(keys[5], (L, E, d, m), d),
            "w_up": norm_init(keys[6], (L, E, d, m), d),
            "w_down": norm_init(keys[7], (L, E, m, d), m),
        }
    else:
        ffn_params = {
            "w_gate": norm_init(keys[5], (L, d, m), d),
            "w_up": norm_init(keys[6], (L, d, m), d),
            "w_down": norm_init(keys[7], (L, m, d), m),
        }
    return {
        "embed": jax.random.normal(keys[0], (config.vocab_size, d)) * 0.02,
        "layers": {
            "attn_norm": jnp.ones((L, d)),
            "wq": norm_init(keys[1], (L, d, h * hd), d),
            "wk": norm_init(keys[2], (L, d, kvh * hd), d),
            "wv": norm_init(keys[3], (L, d, kvh * hd), d),
            "wo": norm_init(keys[4], (L, h * hd, d), h * hd),
            "mlp_norm": jnp.ones((L, d)),
            **ffn_params,
        },
        "final_norm": jnp.ones((d,)),
        "lm_head": jax.random.normal(keys[8], (d, config.vocab_size)) * 0.02,
    }


def llama_logical_axes(config: LlamaConfig) -> dict:
    """Logical sharding names matching the ``llama_init`` tree."""
    if config.is_moe:
        ffn_axes = {
            "router": ("layer", "embed", None),
            "w_gate": ("layer", "expert", "embed", "mlp"),
            "w_up": ("layer", "expert", "embed", "mlp"),
            "w_down": ("layer", "expert", "mlp", "embed"),
        }
    else:
        ffn_axes = {
            "w_gate": ("layer", "embed", "mlp"),
            "w_up": ("layer", "embed", "mlp"),
            "w_down": ("layer", "mlp", "embed"),
        }
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layer", "embed"),
            "wq": ("layer", "embed", "heads"),
            "wk": ("layer", "embed", "kv_heads"),
            "wv": ("layer", "embed", "kv_heads"),
            "wo": ("layer", "heads", "embed"),
            "mlp_norm": ("layer", "embed"),
            **ffn_axes,
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    normed = x * jax.lax.rsqrt(var + eps).astype(x.dtype)
    return normed * scale.astype(x.dtype)


def _rope_tables(positions, half, theta, dtype):
    """cos/sin tables [B, S, half] — computed ONCE per step and passed
    into the layer scan (the trig is identical for every layer; leaving
    it inside the scanned body recomputes it depth times)."""
    freqs = jnp.exp(
        -jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    angles = positions[:, :, None].astype(jnp.float32) * freqs  # [B,S,half]
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def _rope_apply(x, cos, sin):
    """x: [B, S, H, Dh]; rotate pairs (first half, second half)."""
    half = x.shape[-1] // 2
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _rope_apply_bhsd(x, cos, sin):
    """x: [B, H, S, Dh]; rope tables [B, S, Dh/2]."""
    half = x.shape[-1] // 2
    c = cos[:, None, :, :].astype(x.dtype)
    s = sin[:, None, :, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _rope(x, positions, theta):
    """x: [B, S, H, Dh]; rotate pairs (single-call convenience)."""
    cos, sin = _rope_tables(positions, x.shape[-1] // 2, theta, x.dtype)
    return _rope_apply(x, cos, sin)


def _maybe_full_rope(config, cos, sin):
    """Duplicate the half-width tables to [B, S, Dh] when the einsum
    flash path is active: rope is then applied INSIDE the Pallas kernels
    (ops/attention.py _rope_tile), which removes the XLA-side rope
    read-modify-write and pad/concat relayout passes (~16 ms/step on the
    nano-350m profile). Done once outside the layer scan."""
    if flash_einsum_path(config):
        return (jnp.concatenate([cos, cos], -1),
                jnp.concatenate([sin, sin], -1))
    return cos, sin


def flash_blocks(config, window=None) -> dict:
    """The kernels' tiles: the configured blocks, or where a sliding
    ``window`` is set the window's own (``ops.attention.window_block``);
    a backward block of 0 is the forward's."""
    if window is None:
        return dict(block_q=config.attn_block_q,
                    block_k=config.attn_block_k,
                    bwd_block_q=config.attn_bwd_block_q,
                    bwd_block_k=config.attn_bwd_block_k)
    return dict(
        block_q=window_block(config.attn_block_q, window),
        block_k=window_block(config.attn_block_k, window),
        bwd_block_q=window_block(
            config.attn_bwd_block_q or config.attn_block_q, window),
        bwd_block_k=window_block(
            config.attn_bwd_block_k or config.attn_block_k, window),
    )


def _sharded_flash(config: LlamaConfig, qt, kt, vt, layout: str = "bhsd",
                   rope_cos=None, rope_sin=None, sm_scale=None, window=None):
    """pallas_call does not auto-partition under GSPMD: without an explicit
    shard_map, jit would all-gather q/k/v to run the kernel replicated.
    Map the kernel over the mesh's batch/head axes (seq stays local here —
    the seq axis is the ring-attention path, parallel/ring_attention.py).

    layout "bhsd": operands [B, H, S, Dh]; "bshd": model-native
    [B, S, H, Dh] (no transposes anywhere — the kernel reads heads as
    tile-aligned column blocks).
    """
    from dlrover_tpu.parallel.mesh import get_mesh
    from dlrover_tpu.parallel.sharding import logical_to_mesh_axes

    fa = flash_attention if layout == "bhsd" else flash_attention_bshd
    rope = rope_cos is not None

    def kernel(q, k, v, *tables):
        extra = (
            {"rope_cos": tables[0], "rope_sin": tables[1]} if rope else {}
        )
        return fa(
            q, k, v, causal=True, sm_scale=sm_scale, window=window,
            **flash_blocks(config, window), **extra,
        )

    tables = (rope_cos, rope_sin) if rope else ()
    try:
        mesh = get_mesh()
    except RuntimeError:
        mesh = None
    if mesh is None or all(
        mesh.shape[a] == 1 for a in ("data", "fsdp", "tensor")
    ):
        return kernel(qt, kt, vt, *tables)

    rules = (
        ("batch", ("data", "fsdp")),
        ("heads", "tensor"),
        ("kv_heads", "tensor"),
    )
    if layout == "bhsd":
        q_axes = ("batch", "heads", None, None)
        kv_axes = ("batch", "kv_heads", None, None)
    else:
        q_axes = ("batch", None, "heads", None)
        kv_axes = ("batch", None, "kv_heads", None)
    q_spec = logical_to_mesh_axes(q_axes, rules)
    kv_spec = logical_to_mesh_axes(kv_axes, rules)
    in_specs = (q_spec, kv_spec, kv_spec)
    if rope:
        table_spec = logical_to_mesh_axes(("batch", None, None), rules)
        in_specs = in_specs + (table_spec, table_spec)
    return jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=q_spec,
        check_vma=False,
    )(qt, kt, vt, *tables)


def flash_einsum_path(config) -> bool:
    """Whether the einsum-form flash branch applies: projections write
    the kernel's [B,H,S,Dh] layout directly (layout rides the matmuls).
    Shared by the llama and gpt2 blocks so gating never diverges.

    int8 mode KEEPS this path (the projections run as quantized einsums
    via qeinsum — int8 x int8 -> int32 on the MXU's 2x int8 path);
    only the emulated fp8 mode falls back to the qdot branch."""
    return (
        config.attn_impl == "flash"
        and not _seq_axis_active()
        and quant_mode() != "fp8"
    )


def bhsd_flash_attention(config, qt, kt, vt, rope_cos=None, rope_sin=None,
                         sm_scale=None, window=None):
    """Shard + run the Pallas flash kernel on [B,H,S,Dh] operands.
    ``sm_scale`` None is the kernels' ``1 / sqrt(head_dim)``; ``window``
    a sliding window (``flash_attention``'s).

    With ``rope_cos``/``rope_sin`` (full-width [B,S,Dh] tables), rope is
    fused into the kernels (q/k passed raw, dq/dk un-roped on the way
    out)."""
    qt = shard_logical(qt, ("batch", "heads", "seq", "head_dim"))
    kt = shard_logical(kt, ("batch", "kv_heads", "seq", "head_dim"))
    vt = shard_logical(vt, ("batch", "kv_heads", "seq", "head_dim"))
    return _sharded_flash(config, qt, kt, vt, rope_cos=rope_cos,
                          rope_sin=rope_sin, sm_scale=sm_scale, window=window)


def _seq_axis_active() -> bool:
    from dlrover_tpu.parallel.mesh import get_mesh

    try:
        return get_mesh().shape.get("seq", 1) > 1
    except RuntimeError:
        return False


def _attention(config: LlamaConfig, q, k, v, sm_scale=None):
    """q: [B,S,H,Dh], k/v: [B,S,KVH,Dh] -> [B,S,H,Dh]. ``sm_scale`` None
    is every implementation's ``1 / sqrt(head_dim)``."""
    if config.attn_impl == "bshd" and not _seq_axis_active():
        # model-native layout end to end: no q/k/v/o transposes
        q = shard_logical(q, ("batch", "seq", "heads", "head_dim"))
        k = shard_logical(k, ("batch", "seq", "kv_heads", "head_dim"))
        v = shard_logical(v, ("batch", "seq", "kv_heads", "head_dim"))
        return _sharded_flash(config, q, k, v, layout="bshd",
                              sm_scale=sm_scale)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    qt = shard_logical(qt, ("batch", "heads", "seq", "head_dim"))
    kt = shard_logical(kt, ("batch", "kv_heads", "seq", "head_dim"))
    vt = shard_logical(vt, ("batch", "kv_heads", "seq", "head_dim"))
    if _seq_axis_active():
        # sequence sharded on the mesh: ring (default) or Ulysses schedule
        from dlrover_tpu.parallel.sequence import sequence_sharded_attention

        impl = "ulysses" if config.attn_impl == "ulysses" else "ring"
        out = sequence_sharded_attention(qt, kt, vt, impl=impl, causal=True,
                                         sm_scale=sm_scale)
    elif config.attn_impl in ("flash", "bshd"):
        out = _sharded_flash(config, qt, kt, vt, sm_scale=sm_scale)
    else:
        out = mha_reference(qt, kt, vt, causal=True, sm_scale=sm_scale)
    return out.transpose(0, 2, 1, 3)


def _layer(config: LlamaConfig, x, layer_params, rope_cos, rope_sin):
    """One transformer block. x: [B,S,D].

    Rope tables are [B,S,Dh] FULL-width when ``flash_einsum_path``
    holds (rope fuses into the kernels via _maybe_full_rope) and
    [B,S,Dh/2] half-width otherwise (external _rope_apply*)."""
    p = layer_params
    dtype = x.dtype
    B, S, D = x.shape
    h, kvh, hd = config.n_heads, config.n_kv_heads, config.head_dim

    y = _rms_norm(x, p["attn_norm"], config.norm_eps)
    if flash_einsum_path(config):
        # einsum-form projections: q/k/v are produced directly in the
        # kernel's [B,H,S,Dh] layout and the output projection contracts
        # (h, k) straight back to [B,S,D] — the layout permutation rides
        # the matmuls instead of materialising transpose copies.
        # q/k/v as ONE stacked einsum: a single larger MXU contraction,
        # and one residual copy of y instead of three (the per-call
        # custom_vjp residuals of the quantized path would otherwise
        # stack 3x under the layer scan — the difference between
        # fitting HBM and not in int8 mode)
        w_qkv = jnp.concatenate(
            [p["wq"].astype(dtype).reshape(D, h, hd),
             p["wk"].astype(dtype).reshape(D, kvh, hd),
             p["wv"].astype(dtype).reshape(D, kvh, hd)], axis=1)
        qkv = qeinsum("bsd,dhk->bhsk", y, w_qkv, site="attn_qkv")
        qt = qkv[:, :h]
        kt = qkv[:, h:h + kvh]
        vt = qkv[:, h + kvh:]
        # rope_cos/rope_sin are FULL-width here (_maybe_full_rope):
        # rope applies inside the kernels, q/k stay raw
        out = bhsd_flash_attention(
            config, qt, kt, vt, rope_cos=rope_cos, rope_sin=rope_sin)
        x = x + qeinsum("bhsk,hkd->bsd", out,
                        p["wo"].astype(dtype).reshape(h, hd, D),
                        site="attn_out")
    else:
        q = qdot(y, p["wq"].astype(dtype), site="attn_qkv") \
            .reshape(B, S, h, hd)
        k = qdot(y, p["wk"].astype(dtype), site="attn_qkv") \
            .reshape(B, S, kvh, hd)
        v = qdot(y, p["wv"].astype(dtype), site="attn_qkv") \
            .reshape(B, S, kvh, hd)
        q = _rope_apply(q, rope_cos, rope_sin)
        k = _rope_apply(k, rope_cos, rope_sin)
        attn = _attention(config, q, k, v).reshape(B, S, h * hd)
        x = x + qdot(attn, p["wo"].astype(dtype), site="attn_out")
    x = shard_logical(x, ("batch", "seq", "embed"))

    y = _rms_norm(x, p["mlp_norm"], config.norm_eps)
    if config.is_moe:
        from dlrover_tpu.parallel.moe import moe_ffn

        moe_params = {
            k: p[k] for k in ("router", "w_gate", "w_up", "w_down")
        }
        moe_out, metrics = moe_ffn(y, moe_params, config.moe_config())
        x = x + moe_out
        aux = (config.moe_aux_weight * metrics["aux_loss"]
               + config.moe_z_weight * metrics["z_loss"])
    else:
        if quant_mode() == "fp8":
            # fp8_dot scales per TENSOR: stacking gate/up would share
            # one e4m3 scale and crush whichever operand is smaller —
            # keep independent matmuls there (int8 scales per output
            # channel, unaffected by the concat)
            gate = jax.nn.silu(qdot(y, p["w_gate"].astype(dtype),
                                    site="mlp"))
            up = qdot(y, p["w_up"].astype(dtype), site="mlp")
            mlp = gate * up
        else:
            # gate/up as one stacked matmul (same residual-dedup
            # argument as the qkv stack; one MXU dispatch instead of two)
            m = p["w_gate"].shape[-1]
            w_gu = jnp.concatenate(
                [p["w_gate"].astype(dtype), p["w_up"].astype(dtype)],
                axis=-1)
            gu = qdot(y, w_gu, site="mlp")
            mlp = jax.nn.silu(gu[..., :m]) * gu[..., m:]
        mlp = shard_logical(mlp, ("batch", "seq", "mlp"))
        x = x + qdot(mlp, p["w_down"].astype(dtype), site="mlp")
        aux = jnp.zeros((), jnp.float32)
    return shard_logical(x, ("batch", "seq", "embed")), aux


def _stage_fn(config: LlamaConfig):
    """Per-stage layer-scan closure shared by the pipeline schedules."""
    from dlrover_tpu.parallel.pipeline import (
        minimal_save_policy,
        stage_layer_scan,
    )

    policy = {
        "dots_attn": minimal_save_policy(),
        # selective offload: the dot saves go to pinned host memory,
        # attn_out (the costliest recompute) stays in HBM
        "dots_attn_offload": minimal_save_policy(offload=True),
        "dots_no_batch":
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "dots": jax.checkpoint_policies.dots_saveable,
    }[config.remat_policy]
    # one layer's logical axes (stacked tree minus the leading "layer"
    # dim): lets the scan double-buffer the per-layer fsdp gathers when
    # Strategy.overlap_collectives is active (parallel/overlap.py)
    layer_axes = {
        k: tuple(v[1:])
        for k, v in llama_logical_axes(config)["layers"].items()
    }
    return stage_layer_scan(
        lambda h, lp, cos, sin: _layer(config, h, lp, cos, sin),
        remat=config.remat,
        policy=policy,
        layer_axes=layer_axes,
    )


def llama_apply(config: LlamaConfig, params, tokens, positions=None,
                return_aux: bool = False, return_hidden: bool = False):
    """tokens [B, S] int32 -> logits [B, S, vocab] float32.

    With ``return_aux=True`` also returns the summed auxiliary loss
    (MoE load-balancing + router z-loss; zero for dense models).
    ``return_hidden=True`` returns the PRE-final-norm hidden states
    instead of logits (the chunked-CE loss applies norm + head itself,
    chunk by chunk)."""
    dtype = jnp.dtype(config.dtype)
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    x = params["embed"].astype(dtype)[tokens]
    x = shard_logical(x, ("batch", "seq", "embed"))
    cos, sin = _rope_tables(
        positions, config.head_dim // 2, config.rope_theta, dtype)
    cos, sin = _maybe_full_rope(config, cos, sin)

    from dlrover_tpu.parallel.pipeline import pipe_size, pipeline_apply

    stage_fn = _stage_fn(config)
    if pipe_size() > 1:
        # layer stack sharded over the ``pipe`` axis: GPipe microbatch
        # schedule inside the step (parallel/pipeline.py), embed/head
        # replicated across stages.
        x, aux_total = pipeline_apply(
            stage_fn, params["layers"], x, cos, sin,
            n_microbatches=config.pipe_microbatches,
        )
    else:
        x, aux_total = stage_fn(params["layers"], x, cos, sin)

    if return_hidden:
        if return_aux:
            return x, aux_total
        return x
    x = _rms_norm(x, params["final_norm"], config.norm_eps)
    logits = x @ params["lm_head"].astype(dtype)
    logits = shard_logical(logits, ("batch", "seq", "vocab"))
    logits = logits.astype(jnp.float32)
    if return_aux:
        return logits, aux_total
    return logits


def _llama_1f1b_loss(config: LlamaConfig, params, tokens):
    """Training loss through the 1F1B schedule: the final norm + head +
    CE run as the pipeline's last stage (loss-in-pipeline), bounding
    in-flight microbatch activations by the pipeline depth."""
    from dlrover_tpu.parallel.pipeline import (
        pipe_size,
        pipeline_loss_1f1b,
    )

    dtype = jnp.dtype(config.dtype)
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    B, S = inputs.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = params["embed"].astype(dtype)[inputs]
    x = shard_logical(x, ("batch", "seq", "embed"))
    cos, sin = _rope_tables(
        positions, config.head_dim // 2, config.rope_theta, dtype)
    cos, sin = _maybe_full_rope(config, cos, sin)

    # Global valid-token normalizer, computed from the labels BEFORE the
    # schedule: per-microbatch normalization would weight tokens in
    # sparsely-valid microbatches more than the dense/gpipe objective.
    # Each last_fn returns loss_sum * M / total_valid so the schedule's
    # /M yields exactly sum(loss) / total_valid.
    M = config.pipe_microbatches or 2 * pipe_size()
    valid_total = jnp.maximum((labels != -100).sum(), 1)

    def last_fn(lp, h, labels_mb):
        h = _rms_norm(h, lp["final_norm"], config.norm_eps)
        logits = (h @ lp["lm_head"].astype(dtype)).astype(jnp.float32)
        loss, _valid = softmax_cross_entropy(logits, labels_mb)
        return loss.sum() * (M / valid_total)

    last_params = {
        "final_norm": params["final_norm"],
        "lm_head": params["lm_head"],
    }
    if config.pipe_virtual_stages > 1:
        from dlrover_tpu.parallel.pipeline import (
            pipeline_loss_1f1b_interleaved,
        )

        return pipeline_loss_1f1b_interleaved(
            _stage_fn(config), last_fn, params["layers"], last_params, x,
            stage_extras=(cos, sin), last_extras=(labels,),
            n_microbatches=config.pipe_microbatches,
            virtual_stages=config.pipe_virtual_stages,
        )
    return pipeline_loss_1f1b(
        _stage_fn(config), last_fn, params["layers"], last_params, x,
        stage_extras=(cos, sin), last_extras=(labels,),
        n_microbatches=config.pipe_microbatches,
    )


def llama_loss_fn(config: LlamaConfig):
    """Next-token CE loss closure for auto_accelerate."""
    from dlrover_tpu.parallel.pipeline import pipe_size

    def loss_fn(params, batch, rng):
        tokens = batch["tokens"]
        if config.pipe_schedule == "1f1b" and pipe_size() > 1:
            return _llama_1f1b_loss(config, params, tokens)
        labels = tokens[:, 1:]
        if config.ce_chunks > 1:
            from dlrover_tpu.ops.cross_entropy import (
                fused_linear_cross_entropy,
            )

            h, aux = llama_apply(
                config, params, tokens[:, :-1], return_aux=True,
                return_hidden=True,
            )
            dtype = jnp.dtype(config.dtype)
            # norm_scale path: the final RMSNorm fuses into the chunked
            # custom-VJP CE — no jax.checkpoint, so a remat="none" step
            # carries no checkpoint custom-call (the old norm_fn closure
            # form kept one, ~25.7 ms/step in a pre-PR-1 chip run)
            loss_sum, valid_sum = fused_linear_cross_entropy(
                h, params["lm_head"].astype(dtype), labels,
                n_chunks=config.ce_chunks,
                norm_scale=params["final_norm"],
                norm_eps=config.norm_eps,
            )
            return loss_sum / jnp.maximum(valid_sum, 1) + aux
        logits, aux = llama_apply(
            config, params, tokens[:, :-1], return_aux=True
        )
        loss, valid = softmax_cross_entropy(logits, labels)
        return loss.sum() / jnp.maximum(valid.sum(), 1) + aux

    return loss_fn
