"""The Mamba-2 mixer's causal convolution with its activation, as one
Pallas kernel pair.

``y_t = silu(bias + sum_k weight[k] * x_{t - (K - 1) + k})`` over
``x`` [B, S, C], depthwise in the channels, positions before the
sequence's start reading zero: what ``jax.nn.silu(ssd.causal_conv1d(x,
weight, bias)).astype(x.dtype)`` computes, in the same precision (taps
and bias accumulated in float32 in the plain form's order, ``silu`` in
float32, one rounding to ``x``'s dtype), in one pass over the bytes.
The plain form pads, reads four shifted float32 slices and writes
float32 for the activation; its transpose is pad-and-add of four
float32 gradients (PERF.md, PR 30, has both on the chip).

The kernels see ``x`` as [B, C, S]: channels on the sublanes, the
sequence on the lanes. That is the layout the compiler gives the whole
mixer where the heads are 64 wide (the projection before and the scan
after want the sequence minor), so the two ``swapaxes`` round the call
fold into its neighbours; a kernel with the channels on the lanes ran
as fast alone and put 20 ms a step of transposes round the scan
(PERF.md, PR 30). A block is ``(block_c, block_s)`` of one batch row.
The K - 1 positions before it (forward) and after it (backward) come
from a second ``BlockSpec`` over the same array, the neighbouring lane
tile. ``x`` may be wider than the convolution (the projection's whole
output): ``first`` is the convolution's first channel, an offset in
the block index and no copy. The backward kernel recomputes the
pre-activation from ``x``, so the ``custom_vjp`` keeps ``x``,
``weight`` and ``bias`` alone.

``ops/ssd.py:causal_conv_silu`` is the entry and decides which shapes
come here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.common.backend import use_interpret
from dlrover_tpu.ops.named import named_pallas_call

# positions of a halo block, and the unit of ``block_s``: one lane tile
LANES = 128
# the unit of ``block_c`` and of ``first``: one bf16 sublane tile (two
# float32 tiles)
SUBLANES = 16
# the backward kernel writes the taps' gradients and the bias's side by
# side in one lane tile
MAX_TAPS = LANES - 1
_F32 = jnp.float32


def kernel_takes(seq: int, channels: int, first: int, taps: int) -> bool:
    """Whether the kernels' blocks tile ``channels`` channels from
    ``first`` on over ``seq`` positions."""
    return (
        seq % LANES == 0 and channels % SUBLANES == 0
        and first % SUBLANES == 0 and taps <= MAX_TAPS
    )


def _largest_block(size: int, unit: int, cap: int, also: int = 0) -> int:
    """The largest multiple of ``unit`` up to ``cap`` that divides
    ``size`` (and ``also``); ``unit`` divides both."""
    block = min(cap, size) // unit * unit
    while size % block or also % block:
        block -= unit
    return block


def _columns(ext, start: int, cols: int):
    """``ext[:, start:start + cols]``, a slice that need not start on a
    lane tile: the lanes are rotated to the nearer tile and a whole
    tile's slice is taken (on the chip a fifth faster than the plain
    slice's relayout: PERF.md, PR 30). The ``cols`` positions from
    ``start`` on lie inside ``ext``, so what the rotation wraps round
    is never read."""
    inside = start % LANES
    if inside == 0:
        return ext[:, start:start + cols]
    right = LANES - inside if inside > LANES // 2 else -inside
    start += right
    return pltpu.roll(ext, right % ext.shape[1], 1)[:, start:start + cols]


def _pre_activation(ext, weight, bias, first: int, cols: int):
    """``bias + sum_k weight[:, k] * ext[:, first - (K - 1) + k + t]``
    for ``cols`` values of t: the plain form's sum, in its order."""
    taps = weight.shape[1]
    out = bias
    for k in range(taps):
        out = out + weight[:, k:k + 1] * _columns(
            ext, first - (taps - 1) + k, cols)
    return out


def _cols_before(before_ref, block):
    """The lane tile before a block, in float32. The sequence's first
    block has none: it reads zero."""
    return jnp.where(block == 0, 0.0, before_ref[0].astype(_F32))


def _cols_after(after_ref, block):
    """The lane tile after a block, in float32; the sequence's last
    block has none. For a gradient it reads zero (no output lies after
    the end); ``x`` there is only ever multiplied by that zero."""
    last = block == pl.num_programs(2) - 1
    return jnp.where(last, 0.0, after_ref[0].astype(_F32))


def _fwd_kernel(x_ref, before_ref, w_ref, b_ref, y_ref):
    cols = x_ref.shape[2]
    ext = jnp.concatenate(
        [_cols_before(before_ref, pl.program_id(2)), x_ref[0].astype(_F32)],
        axis=1)
    pre = _pre_activation(ext, w_ref[...], b_ref[...], LANES, cols)
    y_ref[0] = (pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)


def _bwd_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref, w_ref,
                b_ref, dx_ref, dwb_ref):
    batch, block = pl.program_id(1), pl.program_id(2)
    cols = x_ref.shape[2]
    weight = w_ref[...]
    taps = weight.shape[1]
    x_ext = jnp.concatenate(
        [_cols_before(before_ref, block), x_ref[0].astype(_F32),
         _cols_after(after_ref, block)], axis=1)
    dy_ext = jnp.concatenate(
        [dy_ref[0].astype(_F32), _cols_after(dy_after_ref, block)], axis=1)

    # the block's own positions and the lane tile after it, whose
    # pre-activations read this block's last positions
    pre = _pre_activation(x_ext, weight, b_ref[...], LANES, cols + LANES)
    sig = jax.nn.sigmoid(pre)
    dpre = dy_ext * (sig * (1.0 + pre * (1.0 - sig)))

    dx = jnp.zeros((dx_ref.shape[1], cols), _F32)
    for k in range(taps):
        shift = taps - 1 - k                  # x_t feeds pre_{t + shift}
        dx = dx + weight[:, k:k + 1] * _columns(dpre, shift, cols)
    dx_ref[0] = dx.astype(dx_ref.dtype)

    # the taps' gradients in lanes 0..K-1 and the bias's in lane K of
    # one float32 block a channel block, summed over batch and sequence
    own = dpre[:, :cols]
    sums = [
        jnp.sum(own * _columns(x_ext, LANES - (taps - 1) + k, cols),
                axis=1, keepdims=True)
        for k in range(taps)
    ] + [jnp.sum(own, axis=1, keepdims=True)]
    lane = jax.lax.broadcasted_iota(jnp.int32, dwb_ref.shape, 1)
    dwb = jnp.zeros(dwb_ref.shape, _F32)
    for i, total in enumerate(sums):
        dwb = jnp.where(lane == i, total, dwb)

    @pl.when((batch == 0) & (block == 0))
    def _():
        dwb_ref[...] = jnp.zeros_like(dwb_ref)

    dwb_ref[...] += dwb


# (block_c, block_s) up to which the blocks grow: the chip's best of
# those tried at bf16[1, 4352, 8192] (PERF.md, PR 30). The backward
# kernel holds about four times the forward's float32 temporaries a
# position, and computes one lane tile more a block
FWD_BLOCKS = (64, 4096)
BWD_BLOCKS = (16, 8192)


def _blocks(seq, channels, first, blocks, caps):
    """``blocks`` if they tile the input, the largest up to ``caps``
    that do where none are given."""
    if blocks is None:
        return (_largest_block(channels, SUBLANES, caps[0], first),
                _largest_block(seq, LANES, caps[1]))
    block_c, block_s = blocks
    if (channels % block_c or first % block_c or block_c % SUBLANES
            or seq % block_s or block_s % LANES):
        raise ValueError(
            f"causal_conv: blocks ({block_c}, {block_s}) do not tile "
            f"{channels} channels from {first} on over {seq} positions "
            f"in units of ({SUBLANES}, {LANES})"
        )
    return block_c, block_s


def _tiles(order, seq, first, block_c, block_s):
    """Block specs over an array [B, channels, S] for a grid whose
    indices ``order`` maps to (batch, channel block, sequence block),
    the channels counted from ``first`` on: a block, the lane tile
    before it, the lane tile after it (both held inside the array at
    the sequence's ends, where the kernels read zero for them)."""
    tiles, last = block_s // LANES, seq // LANES - 1

    def spec(cols, at):
        def index_map(*grid):
            b, c, s = order(*grid)
            return b, first // block_c + c, at(s)
        return pl.BlockSpec((1, block_c, cols), index_map)

    return (
        spec(block_s, lambda s: s),
        spec(LANES, lambda s: jnp.maximum(s * tiles - 1, 0)),
        spec(LANES, lambda s: jnp.minimum((s + 1) * tiles, last)),
    )


def _channel_spec(order, block_c, width):
    """``width`` lanes of a block's channels, of an array [C, width]."""
    return pl.BlockSpec((block_c, width), lambda *grid: (order(*grid)[1], 0))


def _forward(x, weight, bias, first, blocks, interpret):
    batch, seq, _ = x.shape
    taps, channels = weight.shape
    block_c, block_s = _blocks(seq, channels, first, blocks, FWD_BLOCKS)

    def order(b, c, s):
        return b, c, s

    x_block, x_before, _ = _tiles(order, seq, first, block_c, block_s)
    y_block, _, _ = _tiles(order, seq, 0, block_c, block_s)
    xt = x.swapaxes(1, 2)
    yt = named_pallas_call(
        "causal_conv_fwd",
        _fwd_kernel,
        grid=(batch, channels // block_c, seq // block_s),
        in_specs=[x_block, x_before, _channel_spec(order, block_c, taps),
                  _channel_spec(order, block_c, 1)],
        out_specs=y_block,
        out_shape=jax.ShapeDtypeStruct((batch, channels, seq), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(xt, xt, weight.astype(_F32).T, bias.astype(_F32).reshape(channels, 1))
    return yt.swapaxes(1, 2)


def _backward(x, weight, bias, dy, first, blocks, interpret):
    batch, seq, width = x.shape
    taps, channels = weight.shape
    block_c, block_s = _blocks(seq, channels, first, blocks, BWD_BLOCKS)

    # the sums over batch and sequence run innermost, into one output
    # block a channel block
    def order(c, b, s):
        return b, c, s

    x_block, x_before, x_after = _tiles(order, seq, first, block_c, block_s)
    dy_block, _, dy_after = _tiles(order, seq, 0, block_c, block_s)
    xt, dyt = x.swapaxes(1, 2), dy.swapaxes(1, 2)
    dxt, dwb = named_pallas_call(
        "causal_conv_bwd",
        _bwd_kernel,
        grid=(channels // block_c, batch, seq // block_s),
        in_specs=[x_block, x_before, x_after, dy_block, dy_after,
                  _channel_spec(order, block_c, taps),
                  _channel_spec(order, block_c, 1)],
        out_specs=(dy_block, _channel_spec(order, block_c, LANES)),
        out_shape=(
            jax.ShapeDtypeStruct((batch, channels, seq), x.dtype),
            jax.ShapeDtypeStruct((channels, LANES), _F32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(xt, xt, xt, dyt, dyt, weight.astype(_F32).T,
      bias.astype(_F32).reshape(channels, 1))
    dx = jnp.pad(dxt.swapaxes(1, 2),
                 ((0, 0), (0, 0), (first, width - first - channels)))
    return (dx, dwb[:, :taps].T.astype(weight.dtype),
            dwb[:, taps].astype(bias.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _conv_silu(x, weight, bias, first, fwd_blocks, bwd_blocks, interpret):
    return _forward(x, weight, bias, first, fwd_blocks, interpret)


def _conv_silu_fwd(x, weight, bias, first, fwd_blocks, bwd_blocks, interpret):
    y = _forward(x, weight, bias, first, fwd_blocks, interpret)
    return y, (x, weight, bias)


def _conv_silu_bwd(first, fwd_blocks, bwd_blocks, interpret, saved, dy):
    return _backward(*saved, dy, first, bwd_blocks, interpret)


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def causal_conv_silu_kernel(x, weight, bias, first=0, fwd_blocks=None,
                            bwd_blocks=None, interpret=None):
    """``silu(causal_conv1d(x[..., first:first + C], weight, bias))`` in
    ``x``'s dtype, [B, S, C].

    ``x`` [B, S, at least first + C], ``weight`` [K, C], ``bias`` [C],
    with ``S % LANES == 0``, C and ``first`` multiples of ``SUBLANES``
    and ``K <= MAX_TAPS``. ``fwd_blocks`` and ``bwd_blocks`` are
    ``(block_c, block_s)``; left out, the largest that tile the input
    up to ``FWD_BLOCKS`` and ``BWD_BLOCKS``."""
    (_, seq, width), (taps, channels) = x.shape, weight.shape
    if width < first + channels or not kernel_takes(
            seq, channels, first, taps):
        raise ValueError(
            f"causal_conv: {channels} channels from {first} on of x "
            f"{x.shape} with {taps} taps are not tiled by the kernel "
            f"(sequence in {LANES}s, channels and the first in "
            f"{SUBLANES}s, at most {MAX_TAPS} taps); "
            "ops/ssd.py:causal_conv_silu dispatches such shapes to the "
            "plain form"
        )
    if interpret is None:
        interpret = use_interpret()
    return _conv_silu(x, weight, bias, first, fwd_blocks, bwd_blocks,
                      interpret)
