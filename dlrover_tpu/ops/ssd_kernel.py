"""The Mamba-2 chunked scan as one Pallas kernel pair.

What ``ops/ssd.py:ssd_scan``'s plain form computes (its docstring has
the recurrence and the chunked form), in the same precision: ``dt``,
the running sums and every ``exp`` in float32 with the mask under the
``exp``, the chunk-sized matmuls with operands in the activations'
dtype and float32 accumulation, the carried state float32 and decayed
in float32. The plain form writes the masked decay ``[B, c, G, h, l,
s]``, ``C B^T`` and their product to memory and reads them back in each
of its three passes; here they live in fast memory only, and the state
goes from chunk to chunk in a float32 scratch.

The kernels see every operand with the **sequence on the lanes**: ``x``
and ``y`` as [B, H*P, S], ``dt`` and the running sum as [B, H, S], ``B``
and ``C`` as [B, G, N, S]. That is how the compiler lays the mixer out
where the heads are 64 wide, and ``causal_conv_fwd`` hands ``x``, ``B``
and ``C`` on that way, so the ``swapaxes`` round the calls fold into
their neighbours (PERF.md, PR 30, has what the other choice cost; a
second copy of ``B`` and ``C`` with the states on the lanes, 2 MB each,
made the compiler transpose the convolution's whole output for it:
PERF.md, PR 33). A grid step is one chunk of 8 or 16 heads of one batch
row, the heads innermost: ``C B^T`` is computed once a chunk and group,
and the gradients of ``B`` and ``C`` are summed over a group's heads in
their output block.

Forward, a head and chunk (``l`` an output position, ``s`` a source)::

    M[s, l]  = (B^T C)[s, l] * exp(cum_l - cum_s)       for l >= s, else 0
    y[:, l]  = (dt x) M + exp(cum_l) H_in C + D x
    H_out    = exp(cum_end) H_in + ((dt x) exp(cum_end - cum_s)) B^T

The backward kernel sweeps the chunks in reverse carrying ``dH`` [P, N]
in float32 scratch, reads each chunk's entering state, which the
forward rule's call wrote ([B, chunks, H*P, N] float32, alive inside
one layer's backward), and recomputes ``M``. The running sum's gradient
needs no [l, s]-sized reduction: ``cum_l`` scales the whole of
``y - D x`` at ``l`` and ``cum_s`` everything that ``(dt x)_s`` feeds,
so it is ``sum_p dy (y - D x) - sum_p (dt x) d(dt x)``, with the end
state's share at the chunk's last position. The cumulative sum, its
transpose and ``dt * a`` stay outside the kernels, on [B, H, S] float32
(2 MB where ``x`` is 67).

``ops/ssd.py:ssd_scan`` is the entry and decides which shapes come
here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.common.backend import use_interpret
from dlrover_tpu.ops.named import named_pallas_call

# the unit of the chunk and of the state size: one lane tile
LANES = 128
# the unit of the head size: one bf16 sublane tile (two float32 tiles)
SUBLANES = 16
# heads a grid step, the most and the least: the least is the unit of
# the heads of a group, the rows of one float32 tile, which a block of
# ``dt`` [B, H, S] holds. On the chip at the cell's size 16 heads a
# step ran the pair 8% faster than 8 (PERF.md, PR 33)
HEAD_BLOCKS = (16, 8)
# the most a block of ``x``, [heads a step * head size, chunk], may
# hold: the backward kernel keeps about thirty times that in the 16 MiB
# of fast memory a kernel may use (16 heads of float32 at the cell's
# size are refused by the compiler, 8 are taken)
MAX_BLOCK_BYTES = 512 * 1024
_F32 = jnp.float32


def kernel_takes(seq: int, chunk: int, heads: int, groups: int, head: int,
                 state: int) -> bool:
    """Whether the kernels' blocks tile ``heads`` heads of ``head``
    channels in ``groups`` groups with ``state`` states over ``seq``
    positions cut into chunks of ``chunk``, and the least block of
    float32 fits."""
    return (
        chunk % LANES == 0 and seq % chunk == 0
        and head % SUBLANES == 0 and state % LANES == 0
        and heads % groups == 0 and (heads // groups) % HEAD_BLOCKS[-1] == 0
        and HEAD_BLOCKS[-1] * head * chunk * 4 <= MAX_BLOCK_BYTES
    )


def _head_block(x, groups: int, chunk: int) -> int:
    """Heads a grid step for ``x`` [B, S, H, P]: the largest of
    ``HEAD_BLOCKS`` that divides a group's heads and whose block of
    ``x`` fits."""
    heads, head = x.shape[2:]
    return next(
        n for n in HEAD_BLOCKS
        if (heads // groups) % n == 0 and (
            n * head * chunk * x.dtype.itemsize <= MAX_BLOCK_BYTES
            or n == HEAD_BLOCKS[-1])
    )


def _dot(lhs, rhs, contract=((1,), (0,))):
    """A matmul with float32 accumulation; ``contract`` names the
    contracted dimension of each operand."""
    return jax.lax.dot_general(
        lhs, rhs, (contract, ((), ())), preferred_element_type=_F32)


# both operands' rows contracted (lhs^T rhs), both operands' columns
# (lhs rhs^T)
_ROWS = ((0,), (0,))
_COLS = ((1,), (1,))


def _decay_between(cum_to, cum_from, to_axis: int):
    """``exp(cum_to - cum_from)`` where the position along ``to_axis``
    is at or after the other axis's, 0 elsewhere; one of the two is a
    column and the other a row of one chunk's running sum. The mask
    sits under the ``exp``, as in the plain form."""
    size = max(cum_to.shape + cum_from.shape)
    to = jax.lax.broadcasted_iota(jnp.int32, (size, size), to_axis)
    frm = jax.lax.broadcasted_iota(jnp.int32, (size, size), 1 - to_axis)
    return jnp.exp(jnp.where(to >= frm, cum_to - cum_from, -jnp.inf))


def _at_end(cum):
    """[1, 1], the running sum [1, chunk] at the chunk's last position:
    summed out from under a mask, which lands where a broadcast over a
    whole tile can start (the slice ``cum[:, -1:]`` stays in lane 127,
    and Mosaic does not broadcast from there in a chunk of one tile)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, cum.shape, 1)
    return jnp.sum(jnp.where(lane == cum.shape[1] - 1, cum, 0.0), axis=1,
                   keepdims=True)


def _entering(state_scr, rows):
    """The state that enters this chunk, [P, N] float32: what the grid
    step of the chunk before left in the scratch."""
    return state_scr[rows, :]


def _entering_gradient(dstate_scr, rows):
    """The gradient of the state that leaves this chunk, [P, N]
    float32: what the grid step of the chunk after left."""
    return dstate_scr[rows, :]


def _column(columns, i):
    """[chunk, 1], column ``i`` (traced) of ``columns`` [chunk, heads a
    step]: summed out from under a mask, because a lane cannot be
    indexed by a traced number."""
    lane = jax.lax.broadcasted_iota(jnp.int32, columns.shape, 1)
    return jnp.sum(jnp.where(lane == i, columns, 0.0), axis=1, keepdims=True)


def _each_head(heads: int, body):
    """``body(i)`` for each head of the block: traced once and unrolled
    when the kernel is lowered. Unrolled in Python, the pair's 70
    operations a head took 1.7 s to trace for each of a step program's
    calls, 7 s of a run's set-up; left a loop on the chip, the heads do
    not overlap and the pair runs in 2.28 ms for 1.52 (PERF.md, PR
    33)."""
    def step(i, carry):
        body(i)
        return carry

    jax.lax.fori_loop(0, heads, step, 0, unroll=True)


def _fwd_kernel(d_ref, x_ref, dt_ref, cum_ref, cumc_ref, bn_ref, cn_ref,
                y_ref, *rest, head: int, per_group: int, keep_states: bool):
    if keep_states:
        states_ref, state_scr, cbt_scr = rest
    else:
        state_scr, cbt_scr = rest
    chunk_no, block = pl.program_id(1), pl.program_id(2)
    heads = dt_ref.shape[1]
    dtype = x_ref.dtype
    first = pl.multiple_of(block * (heads * head), heads * head)
    bn, cn = bn_ref[0, 0], cn_ref[0, 0]

    @pl.when(chunk_no == 0)
    def _():
        state_scr[pl.ds(first, heads * head), :] = jnp.zeros(
            (heads * head, state_scr.shape[1]), _F32)

    # B^T C of this chunk, once a group: its first block of heads
    @pl.when(block % per_group == 0)
    def _():
        cbt_scr[...] = _dot(bn, cn, _ROWS)              # [s, l]

    def one_head(i):
        at = pl.ds(pl.multiple_of(i * head, head), head)
        rows = pl.ds(pl.multiple_of(first + i * head, head), head)
        x = x_ref[0, at, :].astype(_F32)
        dt = dt_ref[0, pl.ds(i, 1), :]
        cum = cum_ref[0, pl.ds(i, 1), :]
        total = _at_end(cum)
        xdt = (x * dt).astype(dtype)
        # 1. inside the chunk
        mixed = (cbt_scr[...] * _decay_between(
            cum, _column(cumc_ref[0, 0], i), 1)).astype(dtype)
        y = _dot(xdt, mixed)
        # 4. what the entering state adds
        entering = _entering(state_scr, rows)
        if keep_states:
            states_ref[0, 0, at, :] = entering
        y = y + _dot(entering.astype(dtype), cn) * jnp.exp(cum)
        # 2. and 3. the state that leaves
        xdt_end = (xdt.astype(_F32) * jnp.exp(total - cum)).astype(dtype)
        state_scr[rows, :] = jnp.exp(total) * entering + _dot(
            xdt_end, bn, _COLS)
        y = y + x * d_ref[block * heads + i]
        y_ref[0, at, :] = y.astype(dtype)

    _each_head(heads, one_head)


def _bwd_kernel(d_ref, x_ref, dy_ref, dt_ref, cum_ref, cumc_ref, bn_ref,
                cn_ref, states_ref, dx_ref, ddt_ref, dcum_ref,
                dd_ref, db_ref, dc_ref, dstate_scr, cb_scr, dcb_scr,
                xdt_end_scr, dy_in_scr, dleaving_scr, *, head: int,
                per_group: int):
    step, block = pl.program_id(1), pl.program_id(2)
    heads = dt_ref.shape[1]
    size = x_ref.shape[2]
    dtype = x_ref.dtype
    first = pl.multiple_of(block * (heads * head), heads * head)
    bn, cn = bn_ref[0, 0], cn_ref[0, 0]

    # the sequence's last chunk comes first: no state leaves it
    @pl.when(step == 0)
    def _():
        dstate_scr[pl.ds(first, heads * head), :] = jnp.zeros(
            (heads * head, dstate_scr.shape[1]), _F32)

    @pl.when(block % per_group == 0)
    def _():
        cb_scr[...] = _dot(cn, bn, _ROWS)               # [l, s]
        dcb_scr[...] = jnp.zeros_like(dcb_scr)

    def one_head(i):
        at = pl.ds(pl.multiple_of(i * head, head), head)
        rows = pl.ds(pl.multiple_of(first + i * head, head), head)
        row = pl.ds(i, 1)
        x = x_ref[0, at, :].astype(_F32)
        dy = dy_ref[0, at, :]
        dyf = dy.astype(_F32)
        dt = dt_ref[0, row, :]
        cum = cum_ref[0, row, :]
        total = _at_end(cum)
        skip = d_ref[block * heads + i]
        entering = states_ref[0, 0, at, :]
        dleaving = _entering_gradient(dstate_scr, rows)
        dleaving_lo = dleaving.astype(dtype)
        dleaving_scr[at, :] = dleaving_lo

        xdt = (x * dt).astype(dtype)
        xdtf = xdt.astype(_F32)
        from_start, to_end = jnp.exp(cum), jnp.exp(total - cum)
        decay = _decay_between(_column(cumc_ref[0, 0], i), cum, 0)  # [l, s]
        mixed = (cb_scr[...] * decay).astype(dtype)
        # y - D x again: its product with dy is the gradient of cum_l
        y = _dot(xdt, mixed, _COLS) \
            + _dot(entering.astype(dtype), cn) * from_start
        dy_in = (dyf * from_start).astype(dtype)
        dy_in_scr[at, :] = dy_in
        xdt_end_scr[at, :] = (xdtf * to_end).astype(dtype)

        dxdt_end = _dot(dleaving_lo, bn) * to_end
        dxdt = _dot(dy, mixed) + dxdt_end
        dx_ref[0, at, :] = (dxdt * dt + dyf * skip).astype(dtype)
        ddt_ref[0, row, :] = jnp.sum(dxdt * x, axis=0, keepdims=True)
        dd_ref[0, row, :] = jnp.sum(dyf * x, axis=0, keepdims=True)
        # the end state holds exp(cum_end) twice: on the entering state
        # and on every position's share
        decayed = jnp.exp(total) * dleaving
        at_end = jnp.sum(decayed * entering) + jnp.sum(dxdt_end * xdtf)
        last = jax.lax.broadcasted_iota(jnp.int32, (1, size), 1) == size - 1
        dcum_ref[0, row, :] = (
            jnp.sum(dyf * y - xdtf * dxdt, axis=0, keepdims=True)
            + jnp.where(last, at_end, 0.0))
        dstate_scr[rows, :] = decayed + _dot(dy_in, cn, _COLS)
        # d(C B^T) of this head, summed over the group's
        dcb_scr[...] += _dot(dy, xdt, _ROWS) * decay

    _each_head(heads, one_head)

    # the states' share of dB and dC, all the block's heads in one
    # contraction; then, from the group's last block, C B^T's
    db = _dot(dleaving_scr[...], xdt_end_scr[...], _ROWS)
    dc = _dot(states_ref[0, 0].astype(dtype), dy_in_scr[...], _ROWS)

    @pl.when(block % per_group == 0)
    def _():
        db_ref[0, 0] = jnp.zeros_like(db_ref[0, 0])
        dc_ref[0, 0] = jnp.zeros_like(dc_ref[0, 0])

    db_ref[0, 0] += db
    dc_ref[0, 0] += dc

    @pl.when(block % per_group == per_group - 1)
    def _():
        dcb = dcb_scr[...].astype(dtype)
        db_ref[0, 0] += _dot(cn, dcb)
        dc_ref[0, 0] += _dot(bn, dcb, _COLS)


def _operands(x, dt, a, b, c, chunk):
    """Heads a grid step, and the kernels' views of the scan's
    operands: the sequence on the lanes, the running sum of ``dt * a``
    inside each chunk."""
    batch, seq, heads, head = x.shape
    groups, state = b.shape[2], b.shape[3]
    block = _head_block(x, groups, chunk)
    xt = x.reshape(batch, seq, heads * head).swapaxes(1, 2)
    dtt = dt.astype(_F32).swapaxes(1, 2)                    # [B, H, S]
    decay = dtt * a.astype(_F32)[:, None]
    cum = jnp.cumsum(
        decay.reshape(batch, heads, seq // chunk, chunk), axis=-1
    ).reshape(batch, heads, seq)
    # a block's heads side by side: each a column to set against a row
    cumc = cum.reshape(batch, heads // block, block, seq).swapaxes(2, 3)
    bn = b.reshape(batch, seq, groups * state).swapaxes(1, 2)
    cn = c.reshape(batch, seq, groups * state).swapaxes(1, 2)
    to_groups = (batch, groups, state, seq)
    return (block, xt, dtt, cum, cumc, bn.reshape(to_groups),
            cn.reshape(to_groups))


def _specs(chunk, block, head, state, per_group, chunk_at):
    """Block specs for a grid (batch, chunk step, block of ``block``
    heads), ``per_group`` blocks a group; ``chunk_at`` maps the step to
    the chunk."""
    rows = block * head

    def spec(shape, index):
        return pl.BlockSpec(shape, lambda b, z, h: index(b, chunk_at(z), h))

    return dict(
        skip=pl.BlockSpec(memory_space=pltpu.SMEM),
        channels=spec((1, rows, chunk), lambda b, z, h: (b, h, z)),
        heads=spec((1, block, chunk), lambda b, z, h: (b, h, z)),
        columns=spec((1, 1, chunk, block), lambda b, z, h: (b, h, z, 0)),
        states=spec((1, 1, state, chunk),
                    lambda b, z, h: (b, h // per_group, 0, z)),
        entering=spec((1, 1, rows, state), lambda b, z, h: (b, z, h, 0)),
    )


# the grid's axes: batch rows are independent; a chunk follows the one
# before it (after it, backward), and a group's blocks of heads follow
# each other into one block of dB and dC
_SEMANTICS = ("parallel", "arbitrary", "arbitrary")


def _forward(x, dt, a, b, c, d, chunk, keep_states, interpret):
    batch, seq, heads, head = x.shape
    groups, state = b.shape[2], b.shape[3]
    block, xt, dtt, cum, cumc, bn, cn = _operands(x, dt, a, b, c, chunk)
    per_group = heads // groups // block
    spec = _specs(chunk, block, head, state, per_group, lambda z: z)
    out_specs = [spec["channels"]]
    out_shape = [jax.ShapeDtypeStruct(xt.shape, x.dtype)]
    if keep_states:
        out_specs.append(spec["entering"])
        out_shape.append(jax.ShapeDtypeStruct(
            (batch, seq // chunk, heads * head, state), _F32))
    out = named_pallas_call(
        "ssd_scan_fwd",
        functools.partial(_fwd_kernel, head=head, per_group=per_group,
                          keep_states=keep_states),
        grid=(batch, seq // chunk, heads // block),
        in_specs=[spec["skip"], spec["channels"], spec["heads"],
                  spec["heads"], spec["columns"], spec["states"],
                  spec["states"]],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads * head, state), _F32),
                        pltpu.VMEM((chunk, chunk), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS),
        interpret=interpret,
    )(d.astype(_F32), xt, dtt, cum, cumc, bn, cn)
    y = out[0].swapaxes(1, 2).reshape(x.shape)
    return y, (out[1] if keep_states else None)


def _backward(x, dt, a, b, c, d, states, dy, chunk, interpret):
    batch, seq, heads, head = x.shape
    groups, state = b.shape[2], b.shape[3]
    block, xt, dtt, cum, cumc, bn, cn = _operands(x, dt, a, b, c, chunk)
    chunks, per_group = seq // chunk, heads // groups // block
    dyt = dy.reshape(batch, seq, heads * head).swapaxes(1, 2)
    spec = _specs(chunk, block, head, state, per_group,
                  lambda z: chunks - 1 - z)
    rows = block * head
    by_head = jax.ShapeDtypeStruct(dtt.shape, _F32)
    by_state = jax.ShapeDtypeStruct(bn.shape, _F32)
    dxt, ddt, dcum, dd, dbn, dcn = named_pallas_call(
        "ssd_scan_bwd",
        functools.partial(_bwd_kernel, head=head, per_group=per_group),
        grid=(batch, chunks, heads // block),
        in_specs=[spec["skip"], spec["channels"], spec["channels"],
                  spec["heads"], spec["heads"], spec["columns"],
                  spec["states"], spec["states"], spec["entering"]],
        out_specs=[spec["channels"], spec["heads"], spec["heads"],
                   spec["heads"], spec["states"], spec["states"]],
        out_shape=[jax.ShapeDtypeStruct(xt.shape, x.dtype), by_head,
                   by_head, by_head, by_state, by_state],
        scratch_shapes=[
            pltpu.VMEM((heads * head, state), _F32),     # dH, every head
            pltpu.VMEM((chunk, chunk), _F32),            # C B^T
            pltpu.VMEM((chunk, chunk), _F32),            # its gradient
            pltpu.VMEM((rows, chunk), x.dtype),          # (dt x) to the end
            pltpu.VMEM((rows, chunk), x.dtype),          # dy from the start
            pltpu.VMEM((rows, state), x.dtype),          # dH in x's dtype
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS),
        interpret=interpret,
    )(d.astype(_F32), xt, dyt, dtt, cum, cumc, bn, cn, states)
    # back through the running sum and dt * a
    ddecay = jax.lax.cumsum(
        dcum.reshape(batch, heads, chunks, chunk), axis=3, reverse=True
    ).reshape(batch, heads, seq)
    af = a.astype(_F32)
    ddt = (ddt + ddecay * af[:, None]).swapaxes(1, 2)
    return (
        dxt.swapaxes(1, 2).reshape(x.shape),
        ddt.astype(dt.dtype),
        jnp.sum(ddecay * dtt, axis=(0, 2)).astype(a.dtype),
        dbn.reshape(batch, groups * state, seq).swapaxes(1, 2).reshape(
            b.shape).astype(b.dtype),
        dcn.reshape(batch, groups * state, seq).swapaxes(1, 2).reshape(
            c.shape).astype(c.dtype),
        jnp.sum(dd, axis=(0, 2)).astype(d.dtype),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, dt, a, b, c, d, chunk, interpret):
    return _forward(x, dt, a, b, c, d, chunk, False, interpret)[0]


def _scan_fwd(x, dt, a, b, c, d, chunk, interpret):
    y, states = _forward(x, dt, a, b, c, d, chunk, True, interpret)
    return y, (x, dt, a, b, c, d, states)


def _scan_bwd(chunk, interpret, saved, dy):
    return _backward(*saved, dy, chunk, interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan_kernel(x, dt, a, b, c, d, chunk: int, interpret=None):
    """``ssd.ssd_scan``'s plain form by the kernel pair: ``x``
    [B, S, H, P], ``dt`` [B, S, H], ``a`` and ``d`` [H], ``b`` and ``c``
    [B, S, G, N] -> ``y`` [B, S, H, P] in ``x``'s dtype, for the shapes
    :func:`kernel_takes` names."""
    (_, seq, heads, head), (groups, state) = x.shape, b.shape[2:]
    if not kernel_takes(seq, chunk, heads, groups, head, state):
        raise ValueError(
            f"ssd_scan: x {x.shape} with states {b.shape} in chunks of "
            f"{chunk} is not tiled by the kernels (chunk and state size "
            f"in {LANES}s, the sequence in whole chunks, the head size "
            f"in {SUBLANES}s, the heads of a group in {HEAD_BLOCKS[-1]}s, "
            f"{HEAD_BLOCKS[-1]} heads of a chunk in float32 within "
            f"{MAX_BLOCK_BYTES} bytes); "
            "ops/ssd.py:ssd_scan dispatches such shapes to the plain form"
        )
    if interpret is None:
        interpret = use_interpret()
    return _scan(x, dt, a, b, c, d, chunk, interpret)
