"""Flash attention as Pallas TPU kernels (FlashAttention-2 schedule).

Equivalent capability: the reference wraps the flash-attn CUDA package
(atorch/atorch/modules/transformer/layers.py:1168 flash_attn_with_mask_bias,
:1279 FlashAttnModule). TPU redesign — two ideas beyond the usual FA2
tiling:

1. **Packed (scalar-prefetch) grids.** Causal attention only touches the
   lower-triangular tiles, but a rectangular Pallas grid still *schedules*
   the dead j>i tiles and DMAs their blocks even when a predicate skips
   the compute. Instead, the set of live (q-block, kv-block) pairs is
   enumerated at trace time into a small int32 table that rides the
   scalar-prefetch channel (`pltpu.PrefetchScalarGridSpec`); the grid's
   last dimension walks that table, so dead tiles are never scheduled and
   never fetched — ~2x fewer tile steps for causal at no numeric cost.
   The same table carries first/last flags that replace the static
   ``j == 0`` / ``j == nk-1`` init/finalise conditions.

2. **BSHD-native layout.** The transformer's residual stream produces
   q/k/v as [B, S, H*Dh] (one matmul output, heads folded in the minor
   dim). The classic [B, H, S, Dh] kernel layout forces a transpose of
   every q/k/v/o at every layer — and their mirror copies in the
   backward. With Dh a multiple of the 128-lane tile, head ``h`` of a
   [B, S, H*Dh] array is a *tile-aligned column block*: BlockSpec
   ``(1, block_q, Dh)`` indexed ``(b, i, h)`` reads it directly. The
   ``layout="bshd"`` kernels (used via :func:`flash_attention_bshd`) run
   on that layout with zero data movement on either side; the legacy
   [B, H, S, Dh] entry :func:`flash_attention` shares the same kernel
   bodies with 4-D BlockSpecs.

Numerics: grid over (batch, head, packed-tile); VMEM scratch carries the
running softmax statistics (m, l) and the fp32 output accumulator across
a row's kv tiles; the MXU does the two matmuls per tile in the input
dtype with fp32 accumulation. Backward recomputes scores blockwise from
the saved logsumexp (no S x S materialisation) — the standard FA2 dq/dkv
split, each with its own packed grid (dq walks q-major, dkv kv-major).

GQA: the kv-head index is derived from the q-head grid index inside the
BlockSpec index maps — grouped kv is never materialised in the forward;
the backward produces per-q-head dk/dv and group-sums outside.

Under ``JAX_PLATFORMS=cpu`` the same kernels run in Pallas interpret
mode, so the unit-test suite exercises the real kernel code paths on the
CPU mesh. A process that finds no accelerator without that pin fails
(common/backend.py): interpret mode never stands in for a missing chip.
"""

from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.common.backend import use_interpret
from dlrover_tpu.ops.named import named_pallas_call

NEG_INF = -1e30

# Row-stats (lse/delta) lane width. 8 was the minimum legal block, but
# an 8-wide trailing dim is physically padded to 128 lanes anyway
# (T(8,128) tiling): the stacked remat saves and the delta broadcast
# paid 16x the logical bytes and sub-lane write masking. Full 128-wide
# stats make every stats tensor dense: half the physical bytes, full-
# bandwidth DUS/slice/broadcast.
STATS_W = 128


class _MaskCtxMeta(type):
    """Class-attribute syntax over thread-local storage: JAX permits
    concurrent tracing from multiple threads, and a process-global
    window/prefix would cross-contaminate unrelated kernel builds."""

    @property
    def window(cls):
        return getattr(cls._tls, "window", None)

    @window.setter
    def window(cls, v):
        cls._tls.window = v

    @property
    def prefix(cls):
        return getattr(cls._tls, "prefix", None)

    @prefix.setter
    def prefix(cls, v):
        cls._tls.prefix = v


class _MaskCtx(metaclass=_MaskCtxMeta):
    """Trace-time extras for the causal mask family (sliding window,
    prefix-LM). Set by the public entries via :func:`_mask_extras` and
    read by every mask helper, so the packed-grid machinery and all
    seven kernels pick them up without threading two more parameters
    through each signature. The custom_vjp boundary re-establishes the
    context in ``_anchor_bwd`` (the backward is traced outside the
    entry's dynamic extent). Storage is per-thread (see _MaskCtxMeta).

    Reference parity: Mistral-style sliding windows and GLM-style
    prefix-LM masks, which the reference reaches through its CUDA
    flash-attn wrappers (atorch/atorch/modules/transformer/layers.py:
    1168 flash_attn_with_mask_bias, :1256 fa2_with_glm_mask)."""

    _tls = threading.local()
    # window: visible iff 0 <= q_pos - k_pos < window
    # prefix: cols < prefix visible to every row


@contextlib.contextmanager
def _mask_extras(window, prefix):
    prev = (_MaskCtx.window, _MaskCtx.prefix)
    _MaskCtx.window, _MaskCtx.prefix = window, prefix
    try:
        yield
    finally:
        _MaskCtx.window, _MaskCtx.prefix = prev


def _block_mask(shape, i, j, *, block_q, block_k, causal, q_len, kv_len):
    """Validity mask for a (block_q, block_k) score tile.

    Causality is end-aligned (offset = kv_len - q_len), matching
    mha_reference's tril(k_len - q_len); rows/cols beyond the true
    lengths are masked so non-block-multiple shapes stay exact.
    Visibility under extras: ``(causal & in-window) | in-prefix``.
    ``i``/``j`` may be traced scalars (read from the packed-tile table).
    Returns None when every position is trivially valid."""
    window, prefix = _MaskCtx.window, _MaskCtx.prefix
    pad_rows = q_len % block_q != 0
    pad_cols = kv_len % block_k != 0
    if not (causal or pad_rows or pad_cols):
        return None
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + i * block_q
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + j * block_k
    mask = None

    def conj(m, new):
        return new if m is None else m & new

    if causal:
        offset = kv_len - q_len
        vis = offset + rows >= cols
        if window is not None:
            vis &= cols > offset + rows - window
        if prefix is not None:
            vis |= cols < prefix
        mask = conj(mask, vis)
    if pad_rows:
        mask = conj(mask, rows < q_len)
    if pad_cols:
        mask = conj(mask, cols < kv_len)
    return mask


def _zero_pad_rows(x, block_idx, block_size, true_len):
    """Zero rows of a [block, d] tile that lie beyond ``true_len``.

    Out-of-bounds block padding is undefined (NaN in interpret mode) and
    0*NaN == NaN, so masked probabilities alone cannot keep garbage out
    of the MXU contractions — the operand tails must be zeroed."""
    if true_len % block_size == 0:
        return x
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(rows + block_idx * block_size < true_len, x, 0)


# ---------------------------------------------------------------------------
# fused rope (rotary embedding applied inside the kernels)
# ---------------------------------------------------------------------------
#
# rope(x) = x * C + (x @ P) * S, where C/S are the cos/sin tables
# duplicated to full head width ([c, c] / [s, s]) and P is the
# rotate-half permutation-with-sign matrix (x @ P == [-x2, x1]).
# The matrix form avoids 64-lane slicing/concat — which Mosaic cannot
# lower and XLA fuses badly (pad+maximum relayouts) — at the cost of a
# tiny (block, Dh) @ (Dh, Dh) matmul that rides the MXU under the
# kernel's VPU softmax chain. The transposed map for gradients is
# unrope(g) = g * C - (g * S) @ P  (P^T == -P).


def _rope_rot_mat(dh, dtype):
    half = dh // 2
    r = jax.lax.broadcasted_iota(jnp.int32, (dh, dh), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (dh, dh), 1)
    p = jnp.where(r == c - half, 1.0, 0.0) - jnp.where(
        r == c + half, 1.0, 0.0)
    return p.astype(dtype)


def _rope_tile(x, cos_ref, sin_ref):
    """Apply rope to a [rows, Dh] tile (tables full-width)."""
    c = _t2(cos_ref).astype(x.dtype)
    s = _t2(sin_ref).astype(x.dtype)
    # f32 accumulation (Mosaic requires 32-bit acc); the result is an
    # exact signed permutation of x, so the cast back is lossless
    rot = jax.lax.dot_general(
        x, _rope_rot_mat(x.shape[-1], x.dtype),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    return x * c + rot * s


def _unrope_tile(g, cos_ref, sin_ref):
    """Transpose-of-rope on a [rows, Dh] fp32 gradient tile."""
    c = _t2(cos_ref).astype(g.dtype)
    s = _t2(sin_ref).astype(g.dtype)
    rot = jax.lax.dot_general(
        g * s, _rope_rot_mat(g.shape[-1], g.dtype),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    ).astype(g.dtype)
    return g * c - rot


def _compiler_params(dims):
    return pltpu.CompilerParams(dimension_semantics=dims)


def _col(ref):
    """Load a row-stats block ([..., bq, STATS_W]) as a (bq, 1) column.

    Row statistics (lse, delta) are stored STATS_W (=128) lanes wide: a
    trailing dim of 1 forces a 1-of-128-lane physical tiling whose
    XLA-side layout copies cost ~milliseconds per step, and any width
    below 128 is physically lane-padded to 128 anyway — so full width
    costs no extra HBM and keeps every stats DUS/slice/broadcast dense
    and full-bandwidth."""
    x = ref[...]
    return x.reshape(x.shape[-2], x.shape[-1])[:, :1]


def _t2(ref):
    """Load a block and squeeze the leading unit dims to [rows, cols]."""
    x = ref[...]
    return x.reshape(x.shape[-2], x.shape[-1])


def _wr(ref, val):
    ref[...] = val.reshape(ref.shape).astype(ref.dtype)


# ---------------------------------------------------------------------------
# packed tile enumeration
# ---------------------------------------------------------------------------


def _tile_meta(nq, nk, block_q, block_k, q_len, kv_len, causal, kv_major):
    """int32 [4, T] table of live tiles — see :func:`_tile_meta_impl`.

    Thin reader of the mask-extras context so the lru_cache key always
    includes the active window/prefix."""
    return _tile_meta_impl(nq, nk, block_q, block_k, q_len, kv_len,
                           causal, kv_major, _MaskCtx.window,
                           _MaskCtx.prefix)


@functools.lru_cache(maxsize=None)
def _tile_meta_impl(nq, nk, block_q, block_k, q_len, kv_len, causal,
                    kv_major, window, prefix):
    """int32 [4, T] table of live tiles: rows (i, j, first, last).

    ``first``/``last`` mark the boundaries of each accumulation group
    (a q-block row for q-major order, a kv-block column for kv-major).
    A group with no live tile keeps one fully-masked placeholder so its
    output block is still initialised and written.

    A sliding window drops tiles entirely below the window band (the
    long-context payoff: tile count goes from O(S^2) to O(S*window));
    a prefix keeps tiles above the diagonal whose columns intersect the
    always-visible prefix region."""
    offset = kv_len - q_len

    def live(i, j):
        if not causal:
            return True
        c_live = j * block_k < offset + (i + 1) * block_q
        if window is not None:
            # dead when every col is older than every row's window edge
            c_live = c_live and (
                j * block_k + block_k - 1 > offset + i * block_q - window)
        if prefix is not None:
            c_live = c_live or j * block_k < prefix
        return c_live

    rows = []
    if not kv_major:
        for i in range(nq):
            js = [j for j in range(nk) if live(i, j)] or [0]
            for n, j in enumerate(js):
                rows.append((i, j, n == 0, n == len(js) - 1))
    else:
        for j in range(nk):
            iis = [i for i in range(nq) if live(i, j)] or [nq - 1]
            for n, i in enumerate(iis):
                rows.append((i, j, n == 0, n == len(iis) - 1))
    return np.asarray(
        [
            [r[0] for r in rows],
            [r[1] for r in rows],
            [int(r[2]) for r in rows],
            [int(r[3]) for r in rows],
        ],
        dtype=np.int32,
    )


def _needs_p_zero(causal, block_q, block_k, q_len, kv_len):
    """Whether exp(s_masked) can be nonzero garbage, requiring an explicit
    p-zeroing select.

    In the aligned causal self-attention case (no padded edge tiles,
    kv_len >= q_len) every row of every live tile has at least one valid
    column, so the running max / lse is finite and
    ``exp(NEG_INF - finite) == 0`` exactly — the select is a wasted VPU
    pass per masked tile. Padded tiles (or q-longer-than-kv) contain
    fully-masked rows whose stats are +/-inf or NaN, where 0*NaN would
    otherwise leak into the contractions.

    A sliding window re-introduces the hazard: a window-edge tile is
    live for its in-window rows while its out-of-window rows see NO
    valid column in that tile — and it can be those rows' FIRST visited
    tile (earlier tiles are window-dead), where m_prev == m_new ==
    NEG_INF makes exp(s - m_new) == 1 garbage."""
    return (q_len % block_q != 0 or kv_len % block_k != 0
            or (causal and kv_len < q_len)
            or (causal and _MaskCtx.window is not None))


def _needs_mask_static(causal, block_q, block_k, q_len, kv_len):
    """Whether ANY tile can need masking (padding is static)."""
    return causal or q_len % block_q != 0 or kv_len % block_k != 0


def _mask_needed(i, j, *, causal, block_q, block_k, q_len, kv_len):
    """Dynamic predicate: this tile contains masked positions — it
    crosses the causal diagonal, the window's lower edge, the prefix
    boundary, or is a padded edge block. Interior tiles skip all mask
    VPU work."""
    window, prefix = _MaskCtx.window, _MaskCtx.prefix
    need = jnp.bool_(False)
    if causal:
        offset = kv_len - q_len
        need = need | (j * block_k + (block_k - 1) > offset + i * block_q)
        if window is not None:
            # some col is at or below some row's window edge
            need = need | (
                j * block_k <= offset + (i + 1) * block_q - 1 - window)
        if prefix is not None:
            # tiles wholly above the diagonal live only via the prefix;
            # they carry masked positions when they cross its edge
            above = j * block_k > offset + (i + 1) * block_q - 1
            need = need | (above & (j * block_k + block_k > prefix))
    if q_len % block_q != 0:
        need = need | (i == pl.cdiv(q_len, block_q) - 1)
    if kv_len % block_k != 0:
        need = need | (j == pl.cdiv(kv_len, block_k) - 1)
    return need


def _dispatch_tile(tile, i, j, *, causal, block_q, block_k, q_len, kv_len):
    """Invoke ``tile(masked)``, selecting the mask-free variant for tiles
    that cannot contain masked positions. Every scheduled tile is live
    (the packed grid already excluded dead ones)."""
    if _needs_mask_static(causal, block_q, block_k, q_len, kv_len):
        need = _mask_needed(i, j, causal=causal, block_q=block_q,
                            block_k=block_k, q_len=q_len, kv_len=kv_len)
        pl.when(need)(lambda: tile(True))
        pl.when(jnp.logical_not(need))(lambda: tile(False))
    else:
        tile(False)


# ---------------------------------------------------------------------------
# layout plumbing
# ---------------------------------------------------------------------------
#
# "bhsd": q [B, H, S, Dh], kv [B, KVH, S, Dh]     (legacy / Ulysses path)
# "bshd": q [B, S, H*Dh],  kv [B, S, KVH*Dh]      (model-native, rank 3)
#
# lse/delta are [B, H, S, 1] in both layouts.


def _fa_dims(layout, q, k, heads, kv_heads):
    if layout == "bhsd":
        batch, H, q_len, head_dim = q.shape
        KVH, kv_len = k.shape[1], k.shape[2]
    else:
        batch, q_len, qd = q.shape
        H, KVH = heads, kv_heads
        head_dim = qd // H
        kv_len = k.shape[1]
    return batch, H, KVH, q_len, kv_len, head_dim


def _io_specs(layout, *, block_q, block_k, head_dim, group):
    """(q_spec, kv_spec, row_spec): block geometries for the packed grid.

    Index maps receive (b, h, t, meta); meta[0, t] is the q-block index,
    meta[1, t] the kv-block index of packed tile ``t``."""
    if layout == "bhsd":
        q_spec = pl.BlockSpec(
            (1, 1, block_q, head_dim),
            lambda b, h, t, m: (b, h, m[0, t], 0),
        )
        kv_spec = pl.BlockSpec(
            (1, 1, block_k, head_dim),
            lambda b, h, t, m: (b, h // group, m[1, t], 0),
        )
    else:
        q_spec = pl.BlockSpec(
            (1, block_q, head_dim),
            lambda b, h, t, m: (b, m[0, t], h),
        )
        kv_spec = pl.BlockSpec(
            (1, block_k, head_dim),
            lambda b, h, t, m: (b, m[1, t], h // group),
        )
    row_spec = pl.BlockSpec(
        (1, 1, block_q, STATS_W), lambda b, h, t, m: (b, h, m[0, t], 0)
    )
    return q_spec, kv_spec, row_spec


def _kv_out(layout, *, block_k, head_dim):
    """Per-q-head dk/dv output spec (kv geometry, indexed by q head)."""
    if layout == "bhsd":
        return pl.BlockSpec(
            (1, 1, block_k, head_dim), lambda b, h, t, m: (b, h, m[1, t], 0)
        )
    return pl.BlockSpec(
        (1, block_k, head_dim), lambda b, h, t, m: (b, m[1, t], h)
    )


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _dyn_mask(shape, i, j, off_ref, *, block_q, block_k, q_len, kv_len):
    """Global-position causal mask from DYNAMIC offsets (ring attention:
    row r of this block is global position off[0] + i*bq + r; visibility
    is q_global >= k_global). Fully-masked tiles (a later chunk
    visiting) fall out as all-False -> zero contribution. Pad rows/cols
    beyond the true shard lengths are conjoined out exactly like
    _block_mask's bounds terms (their zero-padded scores would
    otherwise inflate l / NaN the backward)."""
    local_r = i * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    local_c = j * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = (off_ref[0] + local_r) >= (off_ref[1] + local_c)
    if q_len % block_q != 0:
        mask = mask & (local_r < q_len)
    if kv_len % block_k != 0:
        mask = mask & (local_c < kv_len)
    return mask


def _fwd_kernel(
    meta_ref, q_ref, k_ref, v_ref, *rest,
    sm_scale, causal, block_q, block_k, q_len, kv_len, p_zero,
    rope=False, dyn_mask=False,
):
    rest = list(rest)
    if rope:
        (cq_ref, sq_ref, ck_ref, sk_ref,
         o_ref, lse_ref, m_scr, l_scr, acc_scr, qr_scr) = rest
    elif dyn_mask:
        (off_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr) = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    t = pl.program_id(2)
    i = meta_ref[0, t]
    j = meta_ref[1, t]

    @pl.when(meta_ref[2, t] == 1)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)
        if rope:
            # rope the q tile ONCE per row (it stays resident across
            # the row's kv visits); k ropes per visit (fresh tile)
            qr_scr[:] = _rope_tile(_t2(q_ref), cq_ref, sq_ref) * (
                jnp.asarray(sm_scale, q_ref.dtype))

    def _tile(masked):
        # sm_scale folded into the q tile: one [bq, d] multiply instead
        # of a [bq, bk] multiply on the score matrix
        if rope:
            q = qr_scr[:]
            k = _rope_tile(_t2(k_ref), ck_ref, sk_ref)
        else:
            q = _t2(q_ref) * jnp.asarray(sm_scale, q_ref.dtype)
            k = _t2(k_ref)
        k = _zero_pad_rows(k, j, block_k, kv_len)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        mask = None
        if dyn_mask:
            mask = _dyn_mask(s.shape, i, j, off_ref,
                             block_q=block_q, block_k=block_k,
                             q_len=q_len, kv_len=kv_len)
        elif masked:
            mask = _block_mask(
                s.shape, i, j, block_q=block_q, block_k=block_k,
                causal=causal, q_len=q_len, kv_len=kv_len,
            )
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if mask is not None and p_zero:
            # explicit zeroing: a fully-masked row has m_new == NEG_INF
            # and exp(s - m_new) == 1 would pollute l
            p = jnp.where(mask, p, 0.0)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        v = _zero_pad_rows(_t2(v_ref), j, block_k, kv_len)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if dyn_mask:
        _tile(True)  # every tile needs the dynamic global-position mask
    else:
        _dispatch_tile(_tile, i, j, causal=causal, block_q=block_q,
                       block_k=block_k, q_len=q_len, kv_len=kv_len)

    @pl.when(meta_ref[3, t] == 1)
    def _final():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        _wr(o_ref, acc_scr[:] / l_safe)
        lse = m_scr[:, :1] + jnp.log(jnp.maximum(l_safe, 1e-30))
        _wr(lse_ref, jnp.broadcast_to(lse, (lse.shape[0], STATS_W)))


def _rope_specs(block_q, block_k, head_dim):
    """Table blocks for [B, S, Dh] cos/sin: one slice per q tile, one
    per kv tile (same arrays passed twice with different index maps)."""
    rq = pl.BlockSpec(
        (1, block_q, head_dim), lambda b, h, t, m: (b, m[0, t], 0))
    rk = pl.BlockSpec(
        (1, block_k, head_dim), lambda b, h, t, m: (b, m[1, t], 0))
    return [rq, rq, rk, rk]


def _fwd(q, k, v, layout, heads, kv_heads, sm_scale, causal, block_q,
         block_k, interpret, rope_cos=None, rope_sin=None):
    if layout == "bshdf":
        if rope_cos is not None:
            raise ValueError("fused rope is not supported on the fused-"
                             "heads (bshdf) layout")
        return _fwd_fused(q, k, v, heads, kv_heads, sm_scale, causal,
                          block_q, block_k, interpret)
    batch, H, KVH, q_len, kv_len, head_dim = _fa_dims(
        layout, q, k, heads, kv_heads)
    group = H // KVH
    block_q = min(block_q, q_len)
    block_k = min(block_k, kv_len)
    nq = pl.cdiv(q_len, block_q)
    nk = pl.cdiv(kv_len, block_k)
    meta = jnp.asarray(_tile_meta(
        nq, nk, block_q, block_k, q_len, kv_len, causal, False))

    rope = rope_cos is not None
    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, q_len=q_len, kv_len=kv_len,
        p_zero=_needs_p_zero(causal, block_q, block_k, q_len, kv_len),
        rope=rope,
    )
    q_spec, kv_spec, row_spec = _io_specs(
        layout, block_q=block_q, block_k=block_k, head_dim=head_dim,
        group=group)
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [q, k, v]
    scratch_shapes = [
        pltpu.VMEM((block_q, 128), jnp.float32),
        pltpu.VMEM((block_q, 128), jnp.float32),
        pltpu.VMEM((block_q, head_dim), jnp.float32),
    ]
    if rope:
        in_specs += _rope_specs(block_q, block_k, head_dim)
        operands += [rope_cos, rope_sin, rope_cos, rope_sin]
        scratch_shapes.append(pltpu.VMEM((block_q, head_dim), q.dtype))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch, H, meta.shape[1]),
        in_specs=in_specs,
        out_specs=(q_spec, row_spec),
        scratch_shapes=scratch_shapes,
    )
    o, lse = named_pallas_call(
        "flash_fwd",
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((batch, H, q_len, STATS_W), jnp.float32),
        ),
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(meta, *operands)
    return o, lse


# ---------------------------------------------------------------------------
# fused-heads kernels (layout "bshdf")
# ---------------------------------------------------------------------------
#
# Grid (batch, packed-tile) with the head loop UNROLLED inside the kernel:
# every block spans the full H*Dh minor dimension, so all HBM traffic is
# fully contiguous (no per-head striding, no layout copies), each kv block
# is fetched once and consumed by every q head, and the causal mask is
# built once per tile instead of once per head. Per-head softmax stats
# live in columns of a shared (block_q, 128) scratch. GQA accumulates
# dk/dv straight into the kv-head columns — no group-sum pass after.


def _fwdf_kernel(
    meta_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
    m_scr, l_scr, acc_scr,
    *, sm_scale, causal, block_q, block_k, q_len, kv_len, heads,
    kv_heads, p_zero,
):
    t = pl.program_id(1)
    i = meta_ref[0, t]
    j = meta_ref[1, t]
    hd = q_ref.shape[-1] // heads
    group = heads // kv_heads

    @pl.when(meta_ref[2, t] == 1)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _tile(masked):
        qb = _t2(q_ref) * jnp.asarray(sm_scale, q_ref.dtype)
        kb = _zero_pad_rows(_t2(k_ref), j, block_k, kv_len)
        vb = _zero_pad_rows(_t2(v_ref), j, block_k, kv_len)
        mask = None
        if masked:
            mask = _block_mask(
                (qb.shape[0], kb.shape[0]), i, j, block_q=block_q,
                block_k=block_k, causal=causal, q_len=q_len, kv_len=kv_len,
            )
        for h in range(heads):
            kvh = h // group
            q = qb[:, h * hd:(h + 1) * hd]
            k = kb[:, kvh * hd:(kvh + 1) * hd]
            v = vb[:, kvh * hd:(kvh + 1) * hd]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[:, h:h + 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            if mask is not None and p_zero:
                p = jnp.where(mask, p, 0.0)
            l_new = alpha * l_scr[:, h:h + 1] + jnp.sum(
                p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_scr[:, h * hd:(h + 1) * hd] = (
                acc_scr[:, h * hd:(h + 1) * hd] * alpha + pv)
            m_scr[:, h:h + 1] = m_new
            l_scr[:, h:h + 1] = l_new

    _dispatch_tile(_tile, i, j, causal=causal, block_q=block_q,
                   block_k=block_k, q_len=q_len, kv_len=kv_len)

    @pl.when(meta_ref[3, t] == 1)
    def _final():
        l = l_scr[:, :heads]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        lse = m_scr[:, :heads] + jnp.log(jnp.maximum(l_safe, 1e-30))
        # lse block is [1, H, bq, STATS_W]
        lse_ref[...] = jnp.broadcast_to(
            lse.T[:, :, None], lse_ref.shape[1:]
        ).reshape(lse_ref.shape).astype(lse_ref.dtype)
        parts = [
            acc_scr[:, h * hd:(h + 1) * hd] / l_safe[:, h:h + 1]
            for h in range(heads)
        ]
        _wr(o_ref, jnp.concatenate(parts, axis=1))


def _bwdf_dq_kernel(
    meta_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
    dq_scr,
    *, sm_scale, causal, block_q, block_k, q_len, kv_len, heads,
    kv_heads, p_zero,
):
    t = pl.program_id(1)
    i = meta_ref[0, t]
    j = meta_ref[1, t]
    hd = q_ref.shape[-1] // heads
    group = heads // kv_heads

    @pl.when(meta_ref[2, t] == 1)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _tile(masked):
        qb = _t2(q_ref) * jnp.asarray(sm_scale, q_ref.dtype)
        kb = _zero_pad_rows(_t2(k_ref), j, block_k, kv_len)
        vb = _zero_pad_rows(_t2(v_ref), j, block_k, kv_len)
        dob = _t2(do_ref)
        lse_all = lse_ref[...].reshape(heads, block_q, STATS_W)[..., 0].T  # [bq,H]
        delta_all = delta_ref[...].reshape(heads, block_q, STATS_W)[..., 0].T
        mask = None
        if masked:
            mask = _block_mask(
                (qb.shape[0], kb.shape[0]), i, j, block_q=block_q,
                block_k=block_k, causal=causal, q_len=q_len, kv_len=kv_len,
            )
        for h in range(heads):
            kvh = h // group
            q = qb[:, h * hd:(h + 1) * hd]
            k = kb[:, kvh * hd:(kvh + 1) * hd]
            v = vb[:, kvh * hd:(kvh + 1) * hd]
            do = dob[:, h * hd:(h + 1) * hd]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
            p = jnp.exp(s - lse_all[:, h:h + 1])
            if mask is not None and p_zero:
                p = jnp.where(mask, p, 0.0)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta_all[:, h:h + 1])
            dq_scr[:, h * hd:(h + 1) * hd] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    _dispatch_tile(_tile, i, j, causal=causal, block_q=block_q,
                   block_k=block_k, q_len=q_len, kv_len=kv_len)

    @pl.when(meta_ref[3, t] == 1)
    def _final():
        _wr(dq_ref, dq_scr[:] * sm_scale)


def _bwdf_dkv_kernel(
    meta_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, sm_scale, causal, block_q, block_k, q_len, kv_len, heads,
    kv_heads, p_zero,
):
    t = pl.program_id(1)
    i = meta_ref[0, t]
    j = meta_ref[1, t]
    hd = q_ref.shape[-1] // heads
    group = heads // kv_heads

    @pl.when(meta_ref[2, t] == 1)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _tile(masked):
        qb = _zero_pad_rows(_t2(q_ref), i, block_q, q_len)
        qb = qb * jnp.asarray(sm_scale, qb.dtype)
        kb = _t2(k_ref)
        vb = _t2(v_ref)
        dob = _zero_pad_rows(_t2(do_ref), i, block_q, q_len)
        lse_all = lse_ref[...].reshape(heads, block_q, STATS_W)[..., 0].T  # [bq,H]
        delta_all = delta_ref[...].reshape(heads, block_q, STATS_W)[..., 0].T
        delta_all = _zero_pad_rows(delta_all, i, block_q, q_len)
        mask = None
        if masked:
            mask = _block_mask(
                (qb.shape[0], kb.shape[0]), i, j, block_q=block_q,
                block_k=block_k, causal=causal, q_len=q_len, kv_len=kv_len,
            )
        for h in range(heads):
            kvh = h // group
            q = qb[:, h * hd:(h + 1) * hd]
            k = kb[:, kvh * hd:(kvh + 1) * hd]
            v = vb[:, kvh * hd:(kvh + 1) * hd]
            do = dob[:, h * hd:(h + 1) * hd]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
            p = jnp.exp(s - lse_all[:, h:h + 1])
            if mask is not None and p_zero:
                p = jnp.where(mask, p, 0.0)
            dv_scr[:, kvh * hd:(kvh + 1) * hd] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta_all[:, h:h + 1])
            dk_scr[:, kvh * hd:(kvh + 1) * hd] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    _dispatch_tile(_tile, i, j, causal=causal, block_q=block_q,
                   block_k=block_k, q_len=q_len, kv_len=kv_len)

    @pl.when(meta_ref[3, t] == 1)
    def _final():
        _wr(dk_ref, dk_scr[:])
        _wr(dv_ref, dv_scr[:])


def _fwd_fused(q, k, v, heads, kv_heads, sm_scale, causal, block_q,
               block_k, interpret):
    batch, q_len, qd = q.shape
    kv_len = k.shape[1]
    block_q = min(block_q, q_len)
    block_k = min(block_k, kv_len)
    nq = pl.cdiv(q_len, block_q)
    nk = pl.cdiv(kv_len, block_k)
    meta = jnp.asarray(_tile_meta(
        nq, nk, block_q, block_k, q_len, kv_len, causal, False))

    q_spec = pl.BlockSpec((1, block_q, qd), lambda b, t, m: (b, m[0, t], 0))
    kv_spec = pl.BlockSpec(
        (1, block_k, k.shape[2]), lambda b, t, m: (b, m[1, t], 0))
    lse_spec = pl.BlockSpec(
        (1, heads, block_q, STATS_W), lambda b, t, m: (b, 0, m[0, t], 0))
    o, lse = named_pallas_call(
        "flash_fwd",
        functools.partial(
            _fwdf_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, q_len=q_len, kv_len=kv_len,
            heads=heads, kv_heads=kv_heads,
            p_zero=_needs_p_zero(causal, block_q, block_k, q_len, kv_len),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, meta.shape[1]),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=(q_spec, lse_spec),
            scratch_shapes=[
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, qd), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((batch, heads, q_len, STATS_W), jnp.float32),
        ),
        compiler_params=_compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
    )(meta, q, k, v)
    return o, lse


def _bwd_fused(heads, kv_heads, sm_scale, causal, block_q, block_k,
               interpret, res, do):
    q, k, v, o, lse = res
    batch, q_len, qd = q.shape
    kv_len, kvd = k.shape[1], k.shape[2]
    head_dim = qd // heads
    block_q = min(block_q, q_len)
    block_k = min(block_k, kv_len)
    nq = pl.cdiv(q_len, block_q)
    nk = pl.cdiv(kv_len, block_k)

    dof = do.astype(jnp.float32) * o.astype(jnp.float32)
    delta = dof.reshape(batch, q_len, heads, head_dim).sum(-1)
    delta = jnp.broadcast_to(
        delta.transpose(0, 2, 1)[..., None],
        (batch, heads, q_len, STATS_W))

    q_spec = pl.BlockSpec((1, block_q, qd), lambda b, t, m: (b, m[0, t], 0))
    kv_spec = pl.BlockSpec((1, block_k, kvd), lambda b, t, m: (b, m[1, t], 0))
    row_spec = pl.BlockSpec(
        (1, heads, block_q, STATS_W), lambda b, t, m: (b, 0, m[0, t], 0))

    meta_q = jnp.asarray(_tile_meta(
        nq, nk, block_q, block_k, q_len, kv_len, causal, False))
    dq = named_pallas_call(
        "flash_bwd_dq",
        functools.partial(
            _bwdf_dq_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, q_len=q_len, kv_len=kv_len,
            heads=heads, kv_heads=kv_heads,
            p_zero=_needs_p_zero(causal, block_q, block_k, q_len, kv_len),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, meta_q.shape[1]),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((block_q, qd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
    )(meta_q, q, k, v, do, lse, delta)

    meta_kv = jnp.asarray(_tile_meta(
        nq, nk, block_q, block_k, q_len, kv_len, causal, True))
    dk, dv = named_pallas_call(
        "flash_bwd_dkv",
        functools.partial(
            _bwdf_dkv_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, q_len=q_len, kv_len=kv_len,
            heads=heads, kv_heads=kv_heads,
            p_zero=_needs_p_zero(causal, block_q, block_k, q_len, kv_len),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, meta_kv.shape[1]),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=(kv_spec, kv_spec),
            scratch_shapes=[
                pltpu.VMEM((block_k, kvd), jnp.float32),
                pltpu.VMEM((block_k, kvd), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ),
        compiler_params=_compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
    )(meta_kv, q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    meta_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    sm_scale, causal, block_q, block_k, q_len, kv_len, p_zero,
    rope=False, dyn_mask=False,
):
    if rope:
        cq_ref, sq_ref, ck_ref, sk_ref, dq_ref, dq_scr, qr_scr = rest
    elif dyn_mask:
        off_ref, dq_ref, dq_scr = rest
    else:
        dq_ref, dq_scr = rest
    t = pl.program_id(2)
    i = meta_ref[0, t]
    j = meta_ref[1, t]

    @pl.when(meta_ref[2, t] == 1)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        if rope:
            qr_scr[:] = _rope_tile(_t2(q_ref), cq_ref, sq_ref) * (
                jnp.asarray(sm_scale, q_ref.dtype))

    def _tile(masked):
        # scaled-q trick: s uses q*sm_scale; ds stays unscaled and the
        # final dq is scaled once (dq = scale * ds @ k)
        if rope:
            q = qr_scr[:]
            k = _rope_tile(_t2(k_ref), ck_ref, sk_ref)
        else:
            q = _t2(q_ref) * jnp.asarray(sm_scale, q_ref.dtype)
            k = _t2(k_ref)
        k = _zero_pad_rows(k, j, block_k, kv_len)
        v = _zero_pad_rows(_t2(v_ref), j, block_k, kv_len)
        do = _t2(do_ref)
        lse = _col(lse_ref)
        delta = _col(delta_ref)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        mask = None
        if dyn_mask:
            mask = _dyn_mask(s.shape, i, j, off_ref,
                             block_q=block_q, block_k=block_k,
                             q_len=q_len, kv_len=kv_len)
        elif masked:
            mask = _block_mask(
                s.shape, i, j, block_q=block_q, block_k=block_k,
                causal=causal, q_len=q_len, kv_len=kv_len,
            )
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        if mask is not None and p_zero:
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if dyn_mask:
        _tile(True)  # every tile needs the dynamic global-position mask
    else:
        _dispatch_tile(_tile, i, j, causal=causal, block_q=block_q,
                       block_k=block_k, q_len=q_len, kv_len=kv_len)

    @pl.when(meta_ref[3, t] == 1)
    def _final():
        dq = dq_scr[:] * sm_scale
        if rope:
            dq = _unrope_tile(dq, cq_ref, sq_ref)
        _wr(dq_ref, dq)


def _bwd_dkv_kernel(
    meta_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    sm_scale, causal, block_q, block_k, q_len, kv_len, p_zero,
    rope=False, dyn_mask=False,
):
    if rope:
        (cq_ref, sq_ref, ck_ref, sk_ref,
         dk_ref, dv_ref, dk_scr, dv_scr, kr_scr) = rest
    elif dyn_mask:
        off_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = rest
    t = pl.program_id(2)
    i = meta_ref[0, t]
    j = meta_ref[1, t]

    @pl.when(meta_ref[2, t] == 1)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)
        if rope:
            # kv-major: the k tile stays resident across the column's
            # q visits — rope it once; q ropes per visit
            kr_scr[:] = _rope_tile(_t2(k_ref), ck_ref, sk_ref)

    def _tile(masked):
        # scaled-q trick: the scaled q tile serves both s = (q*scale)@k
        # and dk += ds^T (q*scale), so ds itself never needs scaling
        q = _t2(q_ref)
        if rope:
            q = _rope_tile(q, cq_ref, sq_ref)
            k = kr_scr[:]
        else:
            k = _t2(k_ref)
        q = _zero_pad_rows(q, i, block_q, q_len)
        q = q * jnp.asarray(sm_scale, q.dtype)
        v = _t2(v_ref)
        do = _zero_pad_rows(_t2(do_ref), i, block_q, q_len)
        lse = _col(lse_ref)
        delta = _zero_pad_rows(_col(delta_ref), i, block_q, q_len)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        mask = None
        if dyn_mask:
            mask = _dyn_mask(s.shape, i, j, off_ref,
                             block_q=block_q, block_k=block_k,
                             q_len=q_len, kv_len=kv_len)
        elif masked:
            mask = _block_mask(
                s.shape, i, j, block_q=block_q, block_k=block_k,
                causal=causal, q_len=q_len, kv_len=kv_len,
            )
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        if mask is not None and p_zero:
            p = jnp.where(mask, p, 0.0)
        # dv += p^T do
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)
        # dk += ds^T (q*scale)
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if dyn_mask:
        _tile(True)  # every tile needs the dynamic global-position mask
    else:
        _dispatch_tile(_tile, i, j, causal=causal, block_q=block_q,
                       block_k=block_k, q_len=q_len, kv_len=kv_len)

    @pl.when(meta_ref[3, t] == 1)
    def _final():
        dk = dk_scr[:]
        if rope:
            dk = _unrope_tile(dk, ck_ref, sk_ref)
        _wr(dk_ref, dk)
        _wr(dv_ref, dv_scr[:])


def _delta_kernel(do_ref, o_ref, out_ref):
    dof = _t2(do_ref).astype(jnp.float32) * _t2(o_ref).astype(jnp.float32)
    d = jnp.sum(dof, axis=-1, keepdims=True)
    _wr(out_ref, jnp.broadcast_to(d, (d.shape[0], STATS_W)))


def _delta_bhsd(do, o, block_q, interpret):
    """delta = rowsum(do * o), emitted dense [B, H, S, STATS_W].

    A dedicated mini-kernel: XLA lowers the same reduce+broadcast as a
    [B,H,S] reduce followed by a sub-lane-masked broadcast write that
    runs ~20x under bandwidth; the kernel writes the wide layout the
    bwd kernels consume directly."""
    batch, H, q_len, head_dim = do.shape
    block_q = min(block_q, q_len)
    spec = pl.BlockSpec(
        (1, 1, block_q, head_dim), lambda b, h, i: (b, h, i, 0))
    out_spec = pl.BlockSpec(
        (1, 1, block_q, STATS_W), lambda b, h, i: (b, h, i, 0))
    return named_pallas_call(
        "flash_bwd_delta",
        _delta_kernel,
        grid=(batch, H, pl.cdiv(q_len, block_q)),
        in_specs=[spec, spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(
            (batch, H, q_len, STATS_W), jnp.float32),
        compiler_params=_compiler_params(
            ("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(do, o)


def _group_kv(dk_full, dv_full, batch, KVH, group, kv_len,
              head_dim, k_dtype, v_dtype):
    """GQA tail shared by the backward paths: per-q-head dk/dv are
    group-summed down to kv-head shapes."""
    if group == 1:
        return dk_full, dv_full
    dk = dk_full.reshape(
        batch, KVH, group, kv_len, head_dim).sum(axis=2).astype(k_dtype)
    dv = dv_full.reshape(
        batch, KVH, group, kv_len, head_dim).sum(axis=2).astype(v_dtype)
    return dk, dv


def _bwd_onepass_kernel(
    meta_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, *rest,
    sm_scale, causal, block_q, block_k, q_len, kv_len, p_zero,
    n_tiles, rope=False,
):
    """Fused dq+dk+dv backward (kv-major packed grid).

    The split dq/dkv kernels each recompute s, p and dp per tile — 7
    large matmuls and two softmax chains where 5 and one suffice. TPU
    grids execute SEQUENTIALLY, so dq can accumulate across the whole
    (b, h) walk in a full-length VMEM scratch ([q_len, Dh] f32 — 1 MB at
    2048x128) written out once at the final tile; dk/dv accumulate per
    kv column exactly like the split kernel. ~29% of backward MXU work
    and one of the two exp(s - lse) chains disappear.
    """
    if rope:
        (cq_ref, sq_ref, ck_ref, sk_ref,
         dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, kr_scr) = rest
    else:
        (dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr) = rest
    t = pl.program_id(2)
    i = meta_ref[0, t]
    j = meta_ref[1, t]

    @pl.when(t == 0)
    def _zero_dq():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(meta_ref[2, t] == 1)
    def _col_init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)
        if rope:
            # kv-major: the k column stays resident across its q visits
            kr_scr[:] = _rope_tile(_t2(k_ref), ck_ref, sk_ref)

    def _tile(masked):
        # scaled-q trick: the scaled q serves s = (q*scale)@k and
        # dk += ds^T (q*scale); dq takes one final *scale instead
        q = _t2(q_ref)
        if rope:
            q = _rope_tile(q, cq_ref, sq_ref)
            k = kr_scr[:]
        else:
            k = _t2(k_ref)
        q = _zero_pad_rows(q, i, block_q, q_len)
        q = q * jnp.asarray(sm_scale, q.dtype)
        v = _t2(v_ref)
        do = _zero_pad_rows(_t2(do_ref), i, block_q, q_len)
        lse = _col(lse_ref)
        # delta = rowsum(do * o) computed in place of a separate
        # mini-kernel: the per-visit (bq, Dh) mult+reduce is trivial
        # VPU work, and the delta tensor (plus its launch and wide-
        # stats traffic) disappears from the backward entirely
        o_t = _t2(o_ref).astype(jnp.float32)
        delta = jnp.sum(
            do.astype(jnp.float32) * o_t, axis=-1, keepdims=True)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        mask = None
        if masked:
            mask = _block_mask(
                s.shape, i, j, block_q=block_q, block_k=block_k,
                causal=causal, q_len=q_len, kv_len=kv_len,
            )
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        if mask is not None and p_zero:
            p = jnp.where(mask, p, 0.0)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dsk = jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        row = pl.dslice(i * block_q, block_q)
        dq_scr[row, :] = dq_scr[row, :] + dsk

    _dispatch_tile(_tile, i, j, causal=causal, block_q=block_q,
                   block_k=block_k, q_len=q_len, kv_len=kv_len)

    @pl.when(meta_ref[3, t] == 1)
    def _col_final():
        dk = dk_scr[:]
        if rope:
            dk = _unrope_tile(dk, ck_ref, sk_ref)
        _wr(dk_ref, dk)
        _wr(dv_ref, dv_scr[:])

    @pl.when(t == n_tiles - 1)
    def _dq_final():
        # rope: dq leaves ROPED; the caller un-ropes in XLA (a full
        # [q_len, Dh] cos/sin block pair here pushed the kernel ~1 MB
        # past the 16 MB scoped-vmem limit at 1024 blocks)
        _wr(dq_ref, dq_scr[:] * sm_scale)


def _bwd_onepass(layout, H, KVH, q_len, kv_len, head_dim, sm_scale,
                 causal, block_q, block_k, interpret, q, k, v, do, lse,
                 o, rope_cos, rope_sin):
    """Fused-backward pallas call (bhsd layout, kv-major packed grid)."""
    batch = q.shape[0]
    group = H // KVH
    nq = pl.cdiv(q_len, block_q)
    nk = pl.cdiv(kv_len, block_k)
    rope = rope_cos is not None
    meta = jnp.asarray(_tile_meta(
        nq, nk, block_q, block_k, q_len, kv_len, causal, True))
    q_spec, kv_spec, row_spec = _io_specs(
        layout, block_q=block_q, block_k=block_k, head_dim=head_dim,
        group=group)
    kv_out_spec = _kv_out(layout, block_k=block_k, head_dim=head_dim)
    dq_spec = pl.BlockSpec(
        (1, 1, q_len, head_dim), lambda b, h, t, m: (b, h, 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, q_spec]
    operands = [q, k, v, do, lse, o]
    scratch = [
        pltpu.VMEM((q_len, head_dim), jnp.float32),
        pltpu.VMEM((block_k, head_dim), jnp.float32),
        pltpu.VMEM((block_k, head_dim), jnp.float32),
    ]
    if rope:
        in_specs += _rope_specs(block_q, block_k, head_dim)
        operands += [rope_cos, rope_sin, rope_cos, rope_sin]
        scratch.append(pltpu.VMEM((block_k, head_dim), k.dtype))
    dq, dk_full, dv_full = named_pallas_call(
        "flash_bwd_fused",
        functools.partial(
            _bwd_onepass_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, q_len=q_len,
            kv_len=kv_len,
            p_zero=_needs_p_zero(causal, block_q, block_k, q_len,
                                 kv_len),
            n_tiles=int(meta.shape[1]), rope=rope,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, H, meta.shape[1]),
            in_specs=in_specs,
            out_specs=(dq_spec, kv_out_spec, kv_out_spec),
            scratch_shapes=scratch,
        ),
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((batch, H, kv_len, head_dim), q.dtype),
            jax.ShapeDtypeStruct((batch, H, kv_len, head_dim), q.dtype),
        ),
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(meta, *operands)
    if rope:
        # transpose-of-rope in XLA (see _unrope_tile): g*C - (g*S)@P
        c = rope_cos[:, None].astype(jnp.float32)
        s = rope_sin[:, None].astype(jnp.float32)
        rot_p = _rope_rot_mat(head_dim, jnp.float32)
        dqf = dq.astype(jnp.float32)
        dq = (dqf * c - jnp.einsum(
            "bhsd,de->bhse", dqf * s, rot_p)).astype(dq.dtype)
    return dq, dk_full, dv_full


def _bwd(layout, heads, kv_heads, sm_scale, causal, block_q, block_k,
         interpret, res, do, rope_cos=None, rope_sin=None):
    if layout == "bshdf":
        if rope_cos is not None:
            raise ValueError("fused rope is not supported on the fused-"
                             "heads (bshdf) layout")
        return _bwd_fused(heads, kv_heads, sm_scale, causal, block_q,
                          block_k, interpret, res, do)
    q, k, v, o, lse = res
    batch, H, KVH, q_len, kv_len, head_dim = _fa_dims(
        layout, q, k, heads, kv_heads)
    group = H // KVH
    block_q = min(block_q, q_len)
    block_k = min(block_k, kv_len)
    nq = pl.cdiv(q_len, block_q)
    nk = pl.cdiv(kv_len, block_k)

    # fused one-pass backward: dq accumulates in a full-length VMEM
    # scratch — gated on the scratch fitting comfortably and on
    # block-aligned lengths (a padded final tile's row slice would run
    # past the exact-length scratch). Conservative: 2048x128 at 1024
    # blocks measured ~1 MB under the 16 MB scoped-vmem cap; larger dq
    # scratches / output blocks would tip Mosaic over with no fallback,
    # so only shapes at or below the proven footprint take this path.
    # delta is computed per visit INSIDE the kernel (from o), so the
    # separate delta tensor never exists on this path.
    if (layout == "bhsd" and q_len * head_dim <= 2048 * 128
            and q_len % block_q == 0 and kv_len % block_k == 0):
        dq, dk_full, dv_full = _bwd_onepass(
            layout, H, KVH, q_len, kv_len, head_dim, sm_scale, causal,
            block_q, block_k, interpret, q, k, v, do, lse, o,
            rope_cos, rope_sin,
        )
        dk, dv = _group_kv(dk_full, dv_full, batch, KVH, group,
                           kv_len, head_dim, k.dtype, v.dtype)
        return dq, dk, dv

    # delta = rowsum(do * o) per head, dense [B, H, S, STATS_W]
    if layout == "bhsd":
        delta = _delta_bhsd(do, o, block_q, interpret)
    else:
        dof = do.astype(jnp.float32) * o.astype(jnp.float32)
        delta = dof.reshape(batch, q_len, H, head_dim).sum(-1)
        delta = delta.transpose(0, 2, 1)[..., None]
        delta = jnp.broadcast_to(delta, delta.shape[:-1] + (STATS_W,))

    q_spec, kv_spec, row_spec = _io_specs(
        layout, block_q=block_q, block_k=block_k, head_dim=head_dim,
        group=group)
    rope = rope_cos is not None
    rope_in_specs = (
        _rope_specs(block_q, block_k, head_dim) if rope else [])
    rope_operands = (
        [rope_cos, rope_sin, rope_cos, rope_sin] if rope else [])

    meta_q = jnp.asarray(_tile_meta(
        nq, nk, block_q, block_k, q_len, kv_len, causal, False))
    dq = named_pallas_call(
        "flash_bwd_dq",
        functools.partial(
            _bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, q_len=q_len, kv_len=kv_len,
            p_zero=_needs_p_zero(causal, block_q, block_k, q_len, kv_len),
            rope=rope,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, H, meta_q.shape[1]),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec,
                      row_spec] + rope_in_specs,
            out_specs=q_spec,
            scratch_shapes=(
                [pltpu.VMEM((block_q, head_dim), jnp.float32)]
                + ([pltpu.VMEM((block_q, head_dim), q.dtype)]
                   if rope else [])
            ),
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(meta_q, q, k, v, do, lse, delta, *rope_operands)

    # dk/dv are produced per q-head (packed kv-major), then group-summed
    # for GQA.
    meta_kv = jnp.asarray(_tile_meta(
        nq, nk, block_q, block_k, q_len, kv_len, causal, True))
    if layout == "bhsd":
        kv_out_shape = (batch, H, kv_len, head_dim)
    else:
        kv_out_shape = (batch, kv_len, H * head_dim)
    kv_out_spec = _kv_out(layout, block_k=block_k, head_dim=head_dim)
    dk_full, dv_full = named_pallas_call(
        "flash_bwd_dkv",
        functools.partial(
            _bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, q_len=q_len, kv_len=kv_len,
            p_zero=_needs_p_zero(causal, block_q, block_k, q_len, kv_len),
            rope=rope,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, H, meta_kv.shape[1]),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec,
                      row_spec] + rope_in_specs,
            out_specs=(kv_out_spec, kv_out_spec),
            scratch_shapes=(
                [
                    pltpu.VMEM((block_k, head_dim), jnp.float32),
                    pltpu.VMEM((block_k, head_dim), jnp.float32),
                ]
                + ([pltpu.VMEM((block_k, head_dim), k.dtype)]
                   if rope else [])
            ),
        ),
        out_shape=(
            jax.ShapeDtypeStruct(kv_out_shape, q.dtype),
            jax.ShapeDtypeStruct(kv_out_shape, q.dtype),
        ),
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(meta_kv, q, k, v, do, lse, delta, *rope_operands)

    if group > 1 and layout != "bhsd":
        dk = dk_full.reshape(
            batch, kv_len, KVH, group, head_dim
        ).sum(axis=3).reshape(batch, kv_len, KVH * head_dim).astype(
            k.dtype)
        dv = dv_full.reshape(
            batch, kv_len, KVH, group, head_dim
        ).sum(axis=3).reshape(batch, kv_len, KVH * head_dim).astype(
            v.dtype)
    else:
        dk, dv = _group_kv(dk_full, dv_full, batch, KVH, group, kv_len,
                           head_dim, k.dtype, v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# ring-attention block calls (dynamic global-position masking)
# ---------------------------------------------------------------------------
#
# parallel/sequence.py's ring schedule visits one (q_shard, kv_shard)
# block per tick with kv rotating over ppermute. These raw kernel
# entries run ONE such block with causality decided by dynamic global
# offsets (q_start, k_start) carried in SMEM — the visiting chunk's
# relation (before/on/after the diagonal) is data-dependent under SPMD,
# so it cannot be a static causal flag. No custom_vjp here: the ring
# schedule owns its VJP (it must merge lse across visits and rotate
# cotangents), calling these primitives in both passes.


class _RingSetup:
    """Shared geometry for one ring block call: clamped blocks, the
    all-tiles meta (no static causal skipping — visibility is dynamic),
    SMEM offsets and the bhsd block specs."""

    def __init__(self, q, k, q_start, k_start, block_q, block_k,
                 kv_major):
        self.batch, self.H, self.q_len, self.head_dim = q.shape
        self.KVH, self.kv_len = k.shape[1], k.shape[2]
        self.group = self.H // self.KVH
        self.block_q = min(block_q, self.q_len)
        self.block_k = min(block_k, self.kv_len)
        nq = pl.cdiv(self.q_len, self.block_q)
        nk = pl.cdiv(self.kv_len, self.block_k)
        self.meta = jnp.asarray(_tile_meta(
            nq, nk, self.block_q, self.block_k, self.q_len, self.kv_len,
            False, kv_major))
        self.off = jnp.stack([jnp.asarray(q_start, jnp.int32),
                              jnp.asarray(k_start, jnp.int32)])
        self.q_spec, self.kv_spec, self.row_spec = _io_specs(
            "bhsd", block_q=self.block_q, block_k=self.block_k,
            head_dim=self.head_dim, group=self.group)
        self.off_spec = pl.BlockSpec(memory_space=pltpu.SMEM)

    def kernel_args(self):
        return dict(
            block_q=self.block_q, block_k=self.block_k,
            q_len=self.q_len, kv_len=self.kv_len, p_zero=True,
            dyn_mask=True, causal=False,
        )


def ring_fwd_block(q, k, v, q_start, k_start, sm_scale,
                   block_q=512, block_k=512, interpret=None):
    """One ring block: (o_normalized, lse) with global causal masking.

    q: [B, H, Sq, D]; k/v: [B, KVH, Sk, D]; q_start/k_start: traced s32
    global offsets of this q/kv shard. Returns (o [q.shape],
    lse [B, H, Sq, STATS_W] f32).
    """
    if interpret is None:
        interpret = use_interpret()
    g = _RingSetup(q, k, q_start, k_start, block_q, block_k, False)
    return named_pallas_call(
        "flash_fwd",
        functools.partial(
            _fwd_kernel, sm_scale=sm_scale, **g.kernel_args()),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(g.batch, g.H, g.meta.shape[1]),
            in_specs=[g.q_spec, g.kv_spec, g.kv_spec, g.off_spec],
            out_specs=(g.q_spec, g.row_spec),
            scratch_shapes=[
                pltpu.VMEM((g.block_q, 128), jnp.float32),
                pltpu.VMEM((g.block_q, 128), jnp.float32),
                pltpu.VMEM((g.block_q, g.head_dim), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((g.batch, g.H, g.q_len, STATS_W),
                                 jnp.float32),
        ),
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(g.meta, q, k, v, g.off)


def ring_dq_block(q, k, v, do, lse, delta, q_start, k_start, sm_scale,
                  block_q=512, block_k=512, interpret=None):
    """dq contribution of one visiting kv block (global lse/delta).

    Emitted in f32: the ring accumulates n per-block contributions, and
    rounding each to the model dtype first would quantize the gradient
    once per tick (the monolithic kernel rounds exactly once)."""
    if interpret is None:
        interpret = use_interpret()
    g = _RingSetup(q, k, q_start, k_start, block_q, block_k, False)
    return named_pallas_call(
        "flash_bwd_dq",
        functools.partial(
            _bwd_dq_kernel, sm_scale=sm_scale, **g.kernel_args()),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(g.batch, g.H, g.meta.shape[1]),
            in_specs=[g.q_spec, g.kv_spec, g.kv_spec, g.q_spec,
                      g.row_spec, g.row_spec, g.off_spec],
            out_specs=g.q_spec,
            scratch_shapes=[
                pltpu.VMEM((g.block_q, g.head_dim), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(g.meta, q, k, v, do, lse, delta, g.off)


def ring_dkv_block(q, k, v, do, lse, delta, q_start, k_start, sm_scale,
                   block_q=512, block_k=512, interpret=None):
    """(dk, dv) contribution of one visiting q block, group-summed for
    GQA (kv shapes), emitted in f32 (see ring_dq_block)."""
    if interpret is None:
        interpret = use_interpret()
    g = _RingSetup(q, k, q_start, k_start, block_q, block_k, True)
    dk_full, dv_full = named_pallas_call(
        "flash_bwd_dkv",
        functools.partial(
            _bwd_dkv_kernel, sm_scale=sm_scale, **g.kernel_args()),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(g.batch, g.H, g.meta.shape[1]),
            in_specs=[g.q_spec, g.kv_spec, g.kv_spec, g.q_spec,
                      g.row_spec, g.row_spec, g.off_spec],
            out_specs=(
                _kv_out("bhsd", block_k=g.block_k,
                        head_dim=g.head_dim),
            ) * 2,
            scratch_shapes=[
                pltpu.VMEM((g.block_k, g.head_dim), jnp.float32),
                pltpu.VMEM((g.block_k, g.head_dim), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct(
                (g.batch, g.H, g.kv_len, g.head_dim), jnp.float32),
            jax.ShapeDtypeStruct(
                (g.batch, g.H, g.kv_len, g.head_dim), jnp.float32),
        ),
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(g.meta, q, k, v, do, lse, delta, g.off)
    if g.group > 1:
        dk_full = dk_full.reshape(
            g.batch, g.KVH, g.group, g.kv_len, g.head_dim).sum(axis=2)
        dv_full = dv_full.reshape(
            g.batch, g.KVH, g.group, g.kv_len, g.head_dim).sum(axis=2)
    return dk_full, dv_full


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


# The VJP is attached to an *identity* function whose inputs include the
# kernel outputs (o, lse). The pallas forward call then lives in the
# primal graph where ``checkpoint_name`` can tag it: under jax.checkpoint
# with a policy saving "attn_out" (pipeline.minimal_save_policy), the
# backward pass reuses the saved (o, lse) instead of re-running the
# forward kernel — a custom_vjp's own fwd residuals are invisible to
# checkpoint policies, so tagging must happen at the primal level.


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(7, 19)))
def _anchor(q, k, v, rope_cos, rope_sin, o, lse, layout, heads, kv_heads,
            sm_scale, causal, block_q, block_k, bwd_block_q, bwd_block_k,
            interpret, window, prefix):
    return o


def _anchor_fwd(q, k, v, rope_cos, rope_sin, o, lse, layout, heads,
                kv_heads, sm_scale, causal, block_q, block_k, bwd_block_q,
                bwd_block_k, interpret, window, prefix):
    return o, (q, k, v, o, lse, rope_cos, rope_sin)


def _anchor_bwd(layout, heads, kv_heads, sm_scale, causal, block_q, block_k,
                bwd_block_q, bwd_block_k, interpret, window, prefix, res,
                do):
    q, k, v, o, lse, rope_cos, rope_sin = res
    # where _flash kept one column, rebuild the STATS_W-lane operand the
    # kernels read (they take column 0, so this is bit for bit the
    # forward kernel's own output)
    lse_wide = lse if lse.ndim == 4 else jnp.broadcast_to(
        lse[..., None], (*lse.shape, STATS_W))
    # the backward is traced outside the public entry's dynamic extent —
    # re-establish the mask extras around the kernel construction
    with _mask_extras(window, prefix):
        dq, dk, dv = _bwd(
            layout, heads, kv_heads, sm_scale, causal, bwd_block_q,
            bwd_block_k, interpret, (q, k, v, o, lse_wide), do,
            rope_cos=rope_cos, rope_sin=rope_sin,
        )
    zc = None if rope_cos is None else jnp.zeros_like(rope_cos)
    zs = None if rope_sin is None else jnp.zeros_like(rope_sin)
    return dq, dk, dv, zc, zs, jnp.zeros_like(o), jnp.zeros_like(lse)


_anchor.defvjp(_anchor_fwd, _anchor_bwd)


def _flash(q, k, v, layout, heads, kv_heads, sm_scale, causal, block_q,
           block_k, bwd_block_q, bwd_block_k, interpret,
           rope_cos=None, rope_sin=None, window=None, prefix=None):
    from jax.ad_checkpoint import checkpoint_name

    from dlrover_tpu.ops.fp8 import remat_disabled

    # stop_gradient on the *inputs* keeps AD tracing out of the pallas
    # call entirely (it has no JVP rule); gradients flow only through
    # the anchor's q/k/v arguments.
    if rope_cos is not None:
        rope_cos = jax.lax.stop_gradient(rope_cos)
        rope_sin = jax.lax.stop_gradient(rope_sin)
    with _mask_extras(window, prefix):
        o, lse = _fwd(
            jax.lax.stop_gradient(q), jax.lax.stop_gradient(k),
            jax.lax.stop_gradient(v), layout, heads, kv_heads, sm_scale,
            causal, block_q, block_k, interpret,
            rope_cos=rope_cos, rope_sin=rope_sin,
        )
    if not remat_disabled():
        o = checkpoint_name(o, "attn_out")
        # the kernel writes the row statistic STATS_W identical lanes
        # wide; what a checkpoint policy saving "attn_out" holds from
        # forward to backward, stacked per layer, is one column of it,
        # 1/STATS_W of the bytes: at gpt2-xl's shapes 0.4 MB a layer in
        # place of 52 MB, twice the (lane-padded) output itself. A step
        # traced for Strategy.remat="none" has no checkpoint to save
        # anything: there the kernel's own output goes to the backward
        # kernels as it is, with no narrowing and no rebuilding.
        lse = checkpoint_name(lse[..., 0], "attn_out")
    return _anchor(q, k, v, rope_cos, rope_sin, o, lse, layout, heads,
                   kv_heads, sm_scale, causal, block_q, block_k,
                   bwd_block_q, bwd_block_k, interpret, window, prefix)


def _check_mask_extras(causal, window, prefix_len):
    if window is None and prefix_len is None:
        return
    if not causal:
        raise ValueError("window/prefix_len require causal=True")
    if window is not None and int(window) < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if prefix_len is not None and int(prefix_len) < 0:
        raise ValueError(f"prefix_len must be >= 0, got {prefix_len}")


def flash_attention(
    q, k, v,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    bwd_block_q: int | None = None,
    bwd_block_k: int | None = None,
    interpret: bool | None = None,
    rope_cos=None,
    rope_sin=None,
    window: int | None = None,
    prefix_len: int | None = None,
):
    """Multi-head attention, O(S) memory, MXU-tiled ([B,H,S,Dh] layout).

    Args:
      q: [batch, heads, q_len, head_dim]
      k, v: [batch, kv_heads, kv_len, head_dim]; heads % kv_heads == 0.
      bwd_block_q/k: backward-kernel tile sizes; default to the forward
        blocks. The dq/dkv kernels hold more live buffers per tile than
        the forward, so their VMEM-optimal blocks are often smaller.
      rope_cos/rope_sin: optional [batch, q_len, head_dim] FULL-WIDTH
        rotary tables (first-half values duplicated into the second
        half). When given, rope is applied to q and k INSIDE the
        kernels — q/k are passed raw, and dq/dk come back un-roped —
        which removes the XLA-side rope read-modify-write passes
        entirely (they run at sub-peak bandwidth as pad/concat
        relayouts). Self-attention only (q_len == kv_len).
      window: Mistral-style sliding window — position i attends to
        [i-window+1, i] (global positions, end-aligned). The packed
        grid drops out-of-window tiles, so cost scales O(S*window).
      prefix_len: GLM-style prefix-LM — the first ``prefix_len`` kv
        positions are visible to EVERY query row (bidirectional prefix,
        causal beyond). Both require causal=True and compose
        (visibility = (causal & in-window) | in-prefix).
    Returns [batch, heads, q_len, head_dim] in q.dtype.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if q.shape[1] % k.shape[1] != 0:
        raise ValueError(
            f"q heads {q.shape[1]} not divisible by kv {k.shape[1]}")
    _check_mask_extras(causal, window, prefix_len)
    if rope_cos is not None:
        if q.shape[2] != k.shape[2]:
            raise ValueError(
                "fused rope requires self-attention (q_len == kv_len)")
        want = (q.shape[0], q.shape[2], q.shape[3])
        if tuple(rope_cos.shape) != want or tuple(rope_sin.shape) != want:
            raise ValueError(
                f"rope tables must be [B, S, head_dim] {want}, got "
                f"{tuple(rope_cos.shape)} / {tuple(rope_sin.shape)}")
    if interpret is None:
        interpret = use_interpret()
    return _flash(q, k, v, "bhsd", int(q.shape[1]), int(k.shape[1]),
                  float(sm_scale), bool(causal),
                  int(block_q), int(block_k),
                  int(bwd_block_q or block_q), int(bwd_block_k or block_k),
                  bool(interpret), rope_cos=rope_cos, rope_sin=rope_sin,
                  window=None if window is None else int(window),
                  prefix=None if prefix_len is None else int(prefix_len))


def flash_attention_bshd(
    q, k, v,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    bwd_block_q: int | None = None,
    bwd_block_k: int | None = None,
    interpret: bool | None = None,
    fused: bool = True,
    window: int | None = None,
    prefix_len: int | None = None,
):
    """Flash attention on the model-native [B, S, H, Dh] layout.

    No transposes on either side: internally the heads fold into the
    minor dimension ([B, S, H*Dh], a free bitcast of the projection
    output). Two kernel families:

    - ``fused=True`` (default): blocks span the full H*Dh minor dim and
      the head loop is unrolled inside the kernel — all HBM traffic is
      contiguous, each kv block feeds every q head, mask built once per
      tile. VMEM scales with H*Dh, so block sizes are clamped to a
      width-dependent budget (512-row forward / 256-row backward at a
      1024-wide minor dim, halving as the width doubles) — a warning
      logs when user knobs are reduced.
    - ``fused=False``: per-head grid; each head is a tile-aligned
      128-lane column block (strided HBM reads — mainly an ablation
      reference).

    Requires head_dim % 128 == 0 on hardware (lane-tile alignment);
    other head dims transparently fall back to the transposing
    [B,H,S,Dh] path.

    Args:
      q: [batch, q_len, heads, head_dim]
      k, v: [batch, kv_len, kv_heads, head_dim]; heads % kv_heads == 0.
    Returns [batch, q_len, heads, head_dim] in q.dtype.
    """
    B, S, H, hd = q.shape
    KVH, Skv = k.shape[2], k.shape[1]
    if H % KVH != 0:
        raise ValueError(f"q heads {H} not divisible by kv {KVH}")
    if H > 128 or H * hd > 3072:
        # the fused kernels keep per-head softmax stats in columns of a
        # (block_q, 128) scratch, and their VMEM footprint grows with
        # the H*Dh width while blocks cannot shrink below 128 rows: at
        # 4096 wide the v5e compiler refuses the fused backward (17.3 MB
        # of scoped VMEM against its 16 MB limit; 3072 wide compiles).
        # Wider models use the per-head grid.
        fused = False
    if sm_scale is None:
        sm_scale = hd ** -0.5
    _check_mask_extras(causal, window, prefix_len)
    if interpret is None:
        interpret = use_interpret()
    if not interpret and hd % 128 != 0:
        o = flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, bwd_block_q=bwd_block_q,
            bwd_block_k=bwd_block_k, interpret=interpret,
            window=window, prefix_len=prefix_len,
        )
        return o.transpose(0, 2, 1, 3)
    if fused:
        # The fused kernels' VMEM footprint scales with the full H*Dh
        # minor width (double-buffered q/k/v/do blocks + f32
        # accumulator slabs + per-head [bq, bk] temporaries that Mosaic
        # keeps live across the unrolled head loop). Measured ceiling
        # on v5e at width 1024: the forward fits at 512-row blocks and
        # the backward at 256 (block knobs tuned for the per-head
        # kernels — where 1024x1024 is optimal — OOM the fused family,
        # verified on-chip). Clamp to the budget, tile-aligned.
        width = H * hd
        cap = max(128, ((512 * 1024) // max(width, 1024)) // 128 * 128)
        bcap = max(128, cap // 2)
        clamped = (
            min(block_q, cap), min(block_k, cap),
            min(bwd_block_q or block_q, bcap),
            min(bwd_block_k or block_k, bcap),
        )
        requested = (block_q, block_k, bwd_block_q or block_q,
                     bwd_block_k or block_k)
        if clamped != requested:
            from dlrover_tpu.common.log import get_logger

            get_logger(__name__).warning(
                "fused bshd kernels: blocks %s clamped to %s for the "
                "%d-wide minor dim (VMEM budget)", requested, clamped,
                width,
            )
        block_q, block_k, bwd_block_q, bwd_block_k = clamped
    o3 = _flash(
        q.reshape(B, S, H * hd), k.reshape(B, Skv, KVH * hd),
        v.reshape(B, Skv, KVH * hd), "bshdf" if fused else "bshd",
        int(H), int(KVH),
        float(sm_scale), bool(causal), int(block_q), int(block_k),
        int(bwd_block_q or block_q), int(bwd_block_k or block_k),
        bool(interpret),
        window=None if window is None else int(window),
        prefix=None if prefix_len is None else int(prefix_len))
    return o3.reshape(B, S, H, hd)


def mha_reference(q, k, v, causal: bool = True, sm_scale: float | None = None):
    """Plain-XLA reference attention (testing + tiny shapes)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * sm_scale
    if causal:
        q_len, k_len = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((q_len, k_len), bool), k_len - q_len)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
