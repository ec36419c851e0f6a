"""Int8 block quantization kernels (optimizer-state compression).

Equivalent capability: the reference's CUDA quantization kernels
(atorch/atorch/ops/csrc/quantization/{quantize,dequantize,quant_reduce}.cu
and the 8-bit Adam quantization_optimizer.cu) consumed by
atorch/atorch/optimizers/low_bit/. TPU redesign: Pallas VPU kernels doing
blockwise absmax int8 quantization with stochastic rounding (the unbiased
rounding the reference gets from its CUDA kernel's RNG); used by the
8-bit optimizer in dlrover_tpu/optimizers/low_bit.py. Interpret mode
covers CPU tests (``JAX_PLATFORMS=cpu`` only — common/backend.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.common.backend import use_interpret
from dlrover_tpu.ops.named import named_pallas_call

BLOCK = 256  # quantization group size (elements)
# rows per grid step: 512 x 256 x 4B = 512 KB per f32 operand. One
# whole-array VMEM block is refused by the TPU compiler from a ~32M
# element leaf on (a 4096x11008 f32 leaf wants 172 MB of VMEM), so every
# kernel over [rows, BLOCK] buffers walks a grid of row tiles.
TILE_ROWS = 512


def row_spec(tile):
    return pl.BlockSpec((tile, BLOCK), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)


def scale_spec(tile):
    return pl.BlockSpec((tile, 1), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)


def _symmetric_scale(absmax):
    """absmax -> int8 scale with the zero-block guard (shared by the
    optimizer-state kernel and the int8 matmul path)."""
    return jnp.where(absmax == 0.0, 1.0, absmax / 127.0)


def _quant_kernel(x_ref, u_ref, q_ref, scale_ref, *, stochastic):
    x = x_ref[:].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = _symmetric_scale(absmax)
    scaled = x / scale
    if stochastic:
        # floor(x + u), u ~ U[0,1): unbiased rounding for any real x.
        rounded = jnp.floor(scaled + u_ref[:])
    else:
        rounded = jnp.round(scaled)
    q_ref[:] = jnp.clip(rounded, -127, 127).astype(jnp.int8)
    scale_ref[:] = scale


def _dequant_kernel(q_ref, scale_ref, out_ref):
    out_ref[:] = q_ref[:].astype(jnp.float32) * scale_ref[:]


def _pad_to_blocks(flat):
    n = flat.shape[0]
    rows = pl.cdiv(n, BLOCK)
    pad = rows * BLOCK - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(rows, BLOCK), n


def quantize_int8(x, seed: int = 0, stochastic: bool = True,
                  interpret: bool | None = None):
    """Blockwise absmax int8 quantization.

    Returns (q int8 [rows, BLOCK], scales f32 [rows, 1], orig_shape).
    """
    if interpret is None:
        interpret = use_interpret()
    orig_shape = x.shape
    blocks, _n = _pad_to_blocks(x.reshape(-1))
    rows = blocks.shape[0]
    if stochastic:
        u = jax.random.uniform(jax.random.key(seed), blocks.shape)
    else:
        u = jnp.zeros(blocks.shape, jnp.float32)
    # rows are independent (blockwise absmax), so a ragged last tile
    # only computes garbage rows whose writes are dropped
    tile = min(TILE_ROWS, rows)
    q, scales = named_pallas_call(
        "quantize_int8",
        functools.partial(_quant_kernel, stochastic=stochastic),
        grid=(pl.cdiv(rows, tile),),
        in_specs=[row_spec(tile), row_spec(tile)],
        out_specs=(row_spec(tile), scale_spec(tile)),
        out_shape=(
            jax.ShapeDtypeStruct((rows, BLOCK), jnp.int8),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ),
        interpret=interpret,
    )(blocks, u)
    return q, scales, orig_shape


# Non-negative tensors with huge dynamic range (Adam's second moment) use
# a log-spaced codebook instead of linear absmax — the TPU analogue of the
# reference's *dynamic* 8-bit code: a nonlinear codebook is required
# because linear absmax zeroes small entries and the Adam denominator
# then collapses to eps.
#
# log-spaced codebook for non-negative values: index 0 is exact zero;
# indices 1..255 span [LOG_FLOOR, 1] * blockwise absmax geometrically.
LOG_FLOOR = 1e-12
_LOG_LEVELS = 255


def _log_codebook():
    import numpy as np

    code = np.geomspace(LOG_FLOOR, 1.0, _LOG_LEVELS)
    return jnp.asarray(np.concatenate([[0.0], code]), jnp.float32)


def quantize_pos_log(x):
    """Blockwise log-codebook quantization for non-negative tensors.

    Returns (q uint8 [rows, BLOCK], scales f32 [rows, 1]). Relative error
    is ~|log step| (~11%) for every magnitude down to LOG_FLOOR x absmax;
    only exact zeros map to zero, so a requantized Adam denominator can
    never collapse for a live coordinate.
    """
    blocks, _n = _pad_to_blocks(x.reshape(-1))
    absmax = jnp.max(blocks, axis=-1, keepdims=True)
    scale = jnp.where(absmax == 0.0, 1.0, absmax)
    rel = blocks / scale
    # nearest codebook index in log space; zeros stay at index 0
    log_rel = jnp.log(jnp.maximum(rel, LOG_FLOOR))
    log_lo = jnp.log(LOG_FLOOR)
    step = -log_lo / (_LOG_LEVELS - 1)
    idx = jnp.clip(
        jnp.round((log_rel - log_lo) / step) + 1, 1, _LOG_LEVELS
    ).astype(jnp.uint8)
    q = jnp.where(rel > 0.0, idx, jnp.uint8(0))
    return q, scale.astype(jnp.float32)


def dequantize_pos_log(q, scales, orig_shape, dtype=jnp.float32):
    code = _log_codebook()
    out = code[q.astype(jnp.int32)] * scales
    n = 1
    for d in orig_shape:
        n *= d
    return out.reshape(-1)[:n].reshape(orig_shape).astype(dtype)


def dequantize_int8(q, scales, orig_shape, dtype=jnp.float32,
                    interpret: bool | None = None):
    if interpret is None:
        interpret = use_interpret()
    tile = min(TILE_ROWS, q.shape[0])
    out = named_pallas_call(
        "dequantize_int8",
        _dequant_kernel,
        grid=(pl.cdiv(q.shape[0], tile),),
        in_specs=[row_spec(tile), scale_spec(tile)],
        out_specs=row_spec(tile),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        interpret=interpret,
    )(q, scales)
    n = 1
    for d in orig_shape:
        n *= d
    return out.reshape(-1)[:n].reshape(orig_shape).astype(dtype)


# ---------------------------------------------------------------------------
# int8 quantized matmul (AQT-style) — the low-precision COMPUTE path
# ---------------------------------------------------------------------------
#
# Per-channel symmetric scales, int8 x int8 -> int32 accumulation,
# dequantize in the epilogue; gradients stay bf16 (full-precision
# update dynamics — only forward GEMMs quantize). Reference
# capability: amp_optimization.py:197 Fp8Optimization (the CUDA
# analogue picks fp8 because Hopper has fp8 units).
#
# Measured on v5e (DESIGN.md "Low-precision compute"): int8
# dot_general with int32 accumulation DOES hit the MXU's 2x int8
# throughput — at the ``llama2-1b`` preset's GEMM shapes the full
# quantized dot
# (on-the-fly per-channel quantization included) runs 1.4-2.7x faster
# than the bf16 dot. The earlier "int8 is slower" conclusion measured
# a training step that lost the einsum-form flash path (transposes +
# unfused rope ate the GEMM win); :func:`int8_einsum` keeps that path
# quantized so the step-level win survives.


def _per_channel_q(x, axis):
    """Symmetric int8 quantization along ``axis`` (the contraction
    dim(s) — an int or tuple of ints).

    Returns (q int8, scale f32 with ``axis`` kept as size 1)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis,
                   keepdims=True)
    scale = _symmetric_scale(amax)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale), -127, 127
    ).astype(jnp.int8)
    return q, scale


def _int8_dot_impl(a, b):
    """Quantize both operands, dot in int8 -> int32, dequantize.

    Returns (out, (qa, sa, qb, sb)) so the custom_vjp fwd and the
    primal share ONE body (the primal just drops the residuals)."""
    out_dtype = jnp.promote_types(a.dtype, b.dtype)
    qa, sa = _per_channel_q(a, axis=-1)        # [..., M, 1]
    qb, sb = _per_channel_q(b, axis=0)         # [1, N]
    acc = jax.lax.dot_general(
        qa, qb, (((qa.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out = _name_qdot_out(
        (acc.astype(jnp.float32) * sa * sb).astype(out_dtype))
    return out, (qa, sa, qb, sb)


def _name_qdot_out(out):
    """Tag a quantized-matmul result for remat save policies.

    The useful bf16 output is elementwise-scaled from the (never-saved)
    int32 accumulator, so no dots_* policy would save it; the
    "qdot_out" name lets parallel/pipeline.py's quant_aware_policy keep
    it — without which the backward re-runs every projection's
    quantize+matmul chain under per-layer remat."""
    from jax.ad_checkpoint import checkpoint_name

    from dlrover_tpu.ops.fp8 import remat_disabled

    if remat_disabled():
        # remat="none": no checkpoint wraps the trace, so the tag would
        # only leave a stray name custom-call in the compiled step
        return out
    return checkpoint_name(out, "qdot_out")


def _name_qdot_res(qa, sa, qb, sb):
    """Tag the quantized residuals for remat save policies.

    Under per-layer remat, custom_vjp residuals are re-derived in the
    backward unless the policy saves them — re-running every amax/
    round/clip quantization chain per layer. The int8 copies are half
    the bf16 activation bytes, so saving them is exactly the memory
    deal the quantized residual design was chosen for."""
    from jax.ad_checkpoint import checkpoint_name

    from dlrover_tpu.ops.fp8 import remat_disabled

    if remat_disabled():
        # no-remat trace: custom_vjp residuals are stored directly, a
        # save-policy tag has nothing to gate and must not lower
        return qa, sa, qb, sb
    return (checkpoint_name(qa, "qdot_res"), checkpoint_name(sa, "qdot_res"),
            checkpoint_name(qb, "qdot_res"), checkpoint_name(sb, "qdot_res"))


@jax.custom_vjp
def int8_dot(a, b):
    """``a @ b`` with int8 per-channel forward operands (int32 MXU
    accumulation).

    The VJP residuals are the QUANTIZED operands, not the bf16 inputs:
    half the saved bytes (the difference between fitting HBM and not
    at a 16-layer scan's stacked residuals), and the backward matmuls
    run against dequantize(q) — the gradient of the function the
    forward actually computed (AQT's straight-through convention),
    rather than of the unquantized matmul."""
    out, _res = _int8_dot_impl(a, b)
    return out


def _int8_dot_fwd(a, b):
    out, (qa, sa, qb, sb) = _int8_dot_impl(a, b)
    qa, sa, qb, sb = _name_qdot_res(qa, sa, qb, sb)
    # dtype carriers: residuals must be jax types, so the operand
    # dtypes ride along as zero-size arrays
    return out, (qa, sa, qb, sb, jnp.zeros((0,), a.dtype),
                 jnp.zeros((0,), b.dtype))


def _int8_dot_bwd(res, g):
    qa, sa, qb, sb, a_dt, b_dt = res
    bd = (qb.astype(g.dtype) * sb.astype(g.dtype))
    da = jnp.matmul(g, bd.swapaxes(-1, -2))
    ad = (qa.astype(g.dtype) * sa.astype(g.dtype))
    if qa.ndim > 2:
        db = jnp.matmul(
            ad.reshape(-1, ad.shape[-1]).T, g.reshape(-1, g.shape[-1])
        )
    else:
        db = jnp.matmul(ad.swapaxes(-1, -2), g)
    return da.astype(a_dt.dtype), db.astype(b_dt.dtype)


int8_dot.defvjp(_int8_dot_fwd, _int8_dot_bwd)


# ---------------------------------------------------------------------------
# int8 quantized einsum — the einsum-form projection path
# ---------------------------------------------------------------------------
#
# The models' flash path writes q/k/v in the kernel's [B,H,S,Dh] layout
# straight out of the projection einsums ("bsd,dhk->bhsk" etc.) so the
# layout permutation rides the matmul. Quantizing those projections
# therefore needs a quantized EINSUM, not a quantized 2-D dot — routing
# them through int8_dot would resurrect the transpose copies the einsum
# form exists to remove. Per-channel scales are taken over each
# operand's contracted dims; the scale outer-product is recovered with
# the same einsum spec applied to the (keepdims) scale tensors.


@functools.lru_cache(maxsize=None)
def _einsum_parts(spec: str, a_ndim: int, b_ndim: int):
    """Parse a two-operand einsum spec -> (a_sub, b_sub, out_sub,
    a_contract_dims, b_contract_dims). Validates the spec is explicit
    and matmul-like (every input dim appears in the output or the other
    operand, so the transposed backward specs below are well-formed)."""
    if "->" not in spec or "." in spec:
        raise ValueError(
            f"int8_einsum needs an explicit two-operand spec, got {spec!r}")
    lhs, out_sub = spec.split("->")
    a_sub, b_sub = lhs.split(",")
    if len(a_sub) != a_ndim or len(b_sub) != b_ndim:
        raise ValueError(f"spec {spec!r} does not match operand ranks "
                         f"({a_ndim}, {b_ndim})")
    a_c = tuple(i for i, ch in enumerate(a_sub) if ch not in out_sub)
    b_c = tuple(i for i, ch in enumerate(b_sub) if ch not in out_sub)
    for sub, other in ((a_sub, b_sub), (b_sub, a_sub)):
        for ch in sub:
            if ch not in out_sub and ch not in other:
                raise ValueError(
                    f"spec {spec!r}: dim {ch!r} is summed within one "
                    "operand — not a matmul-like einsum")
    return a_sub, b_sub, out_sub, a_c, b_c


def _scale_to_out(s, sub, out_sub):
    """Reshape a keepdims per-channel scale (shape of ``sub`` with
    contracted dims = 1) for broadcasting against the ``out_sub``-shaped
    einsum output. Pure squeeze/transpose/reshape — an einsum here would
    be a dot_general over the size-1 contracted axes, which remat
    policies then dutifully SAVE as a full [out]-shaped f32 buffer per
    scan iteration (measured: 3 GB of stacked broadcast scale products
    at the ``llama2-1b`` preset)."""
    keep = [(ch, d) for ch, d in zip(sub, s.shape) if ch in out_sub]
    s = s.reshape([d for _ch, d in keep])
    order = sorted(range(len(keep)), key=lambda i: out_sub.index(keep[i][0]))
    s = jnp.transpose(s, order)
    dims = {ch: d for ch, d in keep}
    return s.reshape([dims.get(ch, 1) for ch in out_sub])


def _int8_einsum_impl(spec, a, b):
    """Quantize, einsum in int8 -> int32, dequantize.

    Returns (out, (qa, sa, qb, sb)); the primal drops the residuals so
    the custom_vjp fwd and the no-grad path share one body."""
    out_dtype = jnp.promote_types(a.dtype, b.dtype)
    a_sub, b_sub, out_sub, a_c, b_c = _einsum_parts(spec, a.ndim, b.ndim)
    qa, sa = _per_channel_q(a, axis=a_c)
    qb, sb = _per_channel_q(b, axis=b_c)
    acc = jnp.einsum(spec, qa, qb, preferred_element_type=jnp.int32)
    scale = (_scale_to_out(sa, a_sub, out_sub)
             * _scale_to_out(sb, b_sub, out_sub))
    out = _name_qdot_out(
        (acc.astype(jnp.float32) * scale).astype(out_dtype))
    return out, (qa, sa, qb, sb)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def int8_einsum(spec, a, b):
    """``jnp.einsum(spec, a, b)`` with int8 per-channel forward operands
    (int32 MXU accumulation) and straight-through gradients.

    Like :func:`int8_dot`, the residuals are the quantized operands:
    half the stacked-residual bytes under a layer scan, and the
    backward einsums see dequantize(q) — the gradient of the quantized
    forward (AQT convention)."""
    out, _res = _int8_einsum_impl(spec, a, b)
    return out


def _int8_einsum_fwd(spec, a, b):
    out, (qa, sa, qb, sb) = _int8_einsum_impl(spec, a, b)
    qa, sa, qb, sb = _name_qdot_res(qa, sa, qb, sb)
    return out, (qa, sa, qb, sb, jnp.zeros((0,), a.dtype),
                 jnp.zeros((0,), b.dtype))


def _int8_einsum_bwd(spec, res, g):
    qa, sa, qb, sb, a_dt, b_dt = res
    a_sub, b_sub, out_sub, _a_c, _b_c = _einsum_parts(
        spec, qa.ndim, qb.ndim)
    ad = qa.astype(g.dtype) * sa.astype(g.dtype)
    bd = qb.astype(g.dtype) * sb.astype(g.dtype)
    da = jnp.einsum(f"{out_sub},{b_sub}->{a_sub}", g, bd)
    db = jnp.einsum(f"{a_sub},{out_sub}->{b_sub}", ad, g)
    return da.astype(a_dt.dtype), db.astype(b_dt.dtype)


int8_einsum.defvjp(_int8_einsum_fwd, _int8_einsum_bwd)
