"""The gated delta rule's chunked form as a Pallas kernel pair.

What ``ops/gated_delta.py``'s chunked form computes (its docstring has
the recurrence and the WY / UT form), in the same precision: the running
sum ``gamma``, every ``exp``, the triangular inverse and the state that
goes from chunk to chunk in float32, every exponent a difference
``gamma_i - gamma_j`` with ``i >= j`` under its mask, the matmuls with
operands in the activations' dtype and float32 accumulation. The plain
form writes ``K K^T``, the masked decay, six doublings of the inverse,
``T``, ``W`` and ``U`` to memory as ``[.., C, C]`` float32 stacks and
walks the chunks in a ``lax.scan``; here a chunk's blocks live in fast
memory only and the state is a float32 scratch.

The chunk is the algorithm's tile, not the model's mathematics: the
kernels work on chunks of one lane tile, ``CHUNK`` = 128 positions,
whatever chunk the plain form was asked for. They see every operand
with the **sequence on the lanes**: ``q`` and ``k`` as [B, H*dk, S],
``v`` and ``o`` as [B, H*dv, S], ``gamma`` and ``beta`` as rows of
[B, H / heads a step, heads a step, S]. Heads of 96 and 192 channels
are then whole sublane tiles, nothing is padded in memory, and it is how
``causal_conv_fwd`` hands ``q``, ``k`` and ``v`` on. A grid step is one
chunk of a block of heads, the chunks innermost; the [C, C] matrices are
held as their transposes, ``[j, i]`` with the source position ``j`` on
the sublanes, so that all but two of the forward's matmuls contract a
left operand's lanes with a right operand's sublanes.

Forward, a head and chunk (``i`` an output position, ``j`` a source;
``S`` [dv, dk] is the state's transpose)::

    Gamma[j, i] = exp(gamma_i - gamma_j)                for i >= j, else 0
    A[j, i]     = beta_i (k_j . k_i) Gamma[j, i]        for i >  j
    X           = (I + A)^{-1}                          float32, upper
    [W; U]      = [k exp(gamma) beta; v beta] X         (X^T diag(beta) = T)
    V'          = U - S W
    o           = S (q exp(gamma)) + V' ((k_j . q_i) Gamma[j, i])
    S_out       = exp(gamma_C) S + V' (k exp(gamma_C - gamma))^T

``beta`` scales ``k`` and ``v`` where the plain form scales ``T``'s
columns: the same product, rounded to the activations' dtype at another
place. **The inverse** multiplies bounded inverses only, as the plain
form's does: the 32 x 32 diagonal blocks by elimination (``X <- X (I -
e_k n_k^T)``, ``n_k`` the block's row ``k``: forward substitution), all
of a grid step's heads and blocks side by side in a few registers, a
column broadcast inside its block by a lane gather; then two doublings
(``inv [[X1, B], [0, X2]] = [[X1, -X1 B X2], [0, X2]]``) as float32
matmuls at full precision over the rows that change, half the chunk.
Of a call's time at the cell's size four fifths are the inverse (PERF.md,
PR 38, has the split).

The backward kernel sweeps the chunks in reverse carrying ``dS`` in a
float32 scratch, reads each chunk's entering state, which the forward
rule's call wrote ([B, chunks, H*dv, dk] float32, alive inside one
layer's backward), and recomputes the chunk. The inverse's gradient is
``dA = -X^T dX X^T``: two float32 matmuls, no second solve. ``gamma``'s
needs no [C, C]-sized reduction: ``gamma_i`` scales ``q_i`` and ``k_i``
where they stand for an output position and ``-gamma_j`` scales ``k_j``
where it stands for a source, so it is ``q_i . dq_i + k_i . (dk_i as a
target - dk_i as a source)``, with the end state's share at the chunk's
last position. The running sum and its transpose stay outside the
kernels, on [B, H, S] float32.

``ops/gated_delta.py:gated_delta_rule`` is the entry and decides which
shapes come here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.common.backend import use_interpret
from dlrover_tpu.ops.named import named_pallas_call
from dlrover_tpu.ops.ssd_kernel import (
    _COLS,
    _ROWS,
    LANES,
    SUBLANES,
    _at_end,
    _dot,
    _each_head,
)

# positions a chunk: one lane tile
CHUNK = LANES
# the diagonal blocks the elimination inverts; the doublings take them
# to the chunk. On the chip at the cell's size the pair (a forward call
# that keeps the states, and the backward) ran in 7.7 ms a layer with
# blocks of 32, 8.4 with 16 (a third doubling) and 9.8 with 64 (PERF.md,
# PR 38, as every time below)
BASE = 32
# heads a grid step, at most: each is unrolled into the kernel's body,
# and all go through the elimination's loop together: 9.2 ms with 6,
# 8.1 with 15, 8.5 with 30
MAX_HEADS = 15
# the elimination's steps a loop iteration: 8.1 ms with 1, 7.7 with 2,
# 7.5 with 4; unrolled whole, 10.7
_STEPS = 4
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def kernel_takes(seq: int, heads: int, dk: int, dv: int) -> bool:
    """Whether the kernels' blocks tile ``heads`` heads of ``dk`` key
    and ``dv`` value channels over ``seq`` positions: whole chunks of
    128, head sizes in whole sublane tiles, and keys that fill half the
    state's lanes at the least (narrower ones leave its tiles and the
    matmul unit mostly empty: the plain form's business)."""
    return (
        seq % CHUNK == 0
        and dk % SUBLANES == 0 and dv % SUBLANES == 0
        and 2 * dk >= LANES
    )


def _head_block(heads: int) -> int:
    """Heads a grid step: the largest divisor of ``heads`` within
    ``MAX_HEADS``."""
    return next(n for n in range(MAX_HEADS, 0, -1) if heads % n == 0)


def _dot32(lhs, rhs):
    """A float32 matmul at full precision: the inverse's."""
    return jax.lax.dot_general(
        lhs, rhs, (((1,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=_F32)


def _positions():
    """Source position ``j`` (sublanes) and output position ``i``
    (lanes) of a chunk's [j, i] matrices."""
    j = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
    return j, i


def _decay_between(gamma):
    """``Gamma[j, i] = exp(gamma_i - gamma_j)`` for ``i >= j``, 0
    elsewhere, from the row ``gamma`` [1, chunk]; the mask sits under
    the ``exp``. The column is the row's broadcast, transposed."""
    j, i = _positions()
    rows = jnp.broadcast_to(gamma, (CHUNK, CHUNK))
    return jnp.exp(jnp.where(i >= j, rows - rows.T, -jnp.inf))


def _block_of(position, size: int = BASE):
    """The number of the ``size``-wide diagonal block a position lies
    in; ``size`` is a power of two, so a shift."""
    return jnp.right_shift(position, size.bit_length() - 1)


def _pack(a):
    """The diagonal ``BASE`` x ``BASE`` blocks of ``a`` [chunk, chunk]
    side by side: [BASE, chunk], block ``b`` in lanes ``b * BASE``
    on."""
    block = _block_of(jax.lax.broadcasted_iota(jnp.int32, (BASE, CHUNK), 1))
    packed = jnp.zeros((BASE, CHUNK), _F32)
    for b in range(CHUNK // BASE):
        packed = jnp.where(
            block == b, a[b * BASE:(b + 1) * BASE, :], packed)
    return packed


def _unpack(packed):
    """:func:`_pack`'s inverse: the blocks back on the diagonal of a
    [chunk, chunk] matrix that is zero elsewhere."""
    block = _block_of(jax.lax.broadcasted_iota(jnp.int32, (BASE, CHUNK), 1))
    return jnp.concatenate([
        jnp.where(block == b, packed, 0.0) for b in range(CHUNK // BASE)
    ], axis=0)


def _block_inverses(rows_ref):
    """``(I + N)^{-1}`` for every strictly upper triangular ``BASE`` x
    ``BASE`` block ``N`` packed in ``rows_ref`` [heads, BASE, chunk]
    float32 scratch, written back over it: ``X <- X - X[:, k] n_k^T``
    for ``k`` = 0, 1, ...: bounded inverses only. The heads go through
    one loop together: a step is a chain of a gather, a product and a
    difference, and the heads' chains overlap."""
    heads = rows_ref.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (BASE, CHUNK), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (BASE, CHUNK), 1)
    first = _block_of(lane) * BASE              # a lane's block's first lane
    eye = jnp.where(lane - first == row, 1.0, 0.0).astype(_F32)

    def step(at, inverses):
        for k in range(_STEPS):
            k = at * _STEPS + k
            inverses = tuple(
                x - jnp.take_along_axis(x, first + k, axis=1)
                * rows_ref[h, pl.ds(k, 1), :]
                for h, x in enumerate(inverses)
            )
        return inverses

    inverses = jax.lax.fori_loop(0, BASE // _STEPS, step, (eye,) * heads)
    for h, inverse in enumerate(inverses):
        rows_ref[h] = inverse


def _doubled(inverse, a):
    """The diagonal blocks' inverses ``inverse`` [chunk, chunk], zero
    elsewhere, doubled until they are ``(I + a)^{-1}``, ``a`` strictly
    upper triangular: the block above the diagonal of each pair is
    ``-X1 B X2``, so only the rows of each pair's first block change,
    and the two float32 matmuls at full precision of a doubling run on
    those rows alone, half the chunk."""
    size = BASE
    while size < CHUNK:
        blocks = [slice(at, at + size) for at in range(0, CHUNK, size)]
        lane = jax.lax.broadcasted_iota(jnp.int32, (size, CHUNK), 1)
        # B X2 of every pair, the pairs' first blocks' rows stacked
        right = _dot32(jnp.concatenate([
            jnp.where(_block_of(lane, size) == b + 1, a[blocks[b]], 0.0)
            for b in range(0, len(blocks), 2)
        ], axis=0), inverse)
        # the same rows in their places, zero between them
        zero = jnp.zeros((size, CHUNK), _F32)
        spread = jnp.concatenate([
            right[b // 2 * size:(b // 2 + 1) * size] if b % 2 == 0 else zero
            for b in range(len(blocks))
        ], axis=0)
        corners = _dot32(jnp.concatenate(
            [inverse[blocks[b]] for b in range(0, len(blocks), 2)], axis=0),
            spread)
        inverse = jnp.concatenate([
            inverse[blocks[b]] - corners[b // 2 * size:(b // 2 + 1) * size]
            if b % 2 == 0 else inverse[blocks[b]]
            for b in range(len(blocks))
        ], axis=0)
        size *= 2
    return inverse


def _entering(state_scr, rows):
    """The state that enters this chunk, [dv, dk] float32: what the
    grid step of the chunk before left in the scratch."""
    return state_scr[rows, :]


def _entering_gradient(dstate_scr, rows):
    """The gradient of the state that leaves this chunk, [dv, dk]
    float32: what the grid step of the chunk after left."""
    return dstate_scr[rows, :]


def _stateless(q_ref, k_ref, gamma_ref, beta_ref, between_scr, kk_scr,
               kq_scr, rows_scr, dk: int):
    """What needs no state, every head of the block: ``Gamma``,
    ``(k_j . k_i) Gamma`` above the diagonal and ``k_j . q_i`` into
    scratch, and the inverses of ``I + A``'s diagonal blocks, packed,
    into ``rows_scr``."""
    j, i = _positions()

    def one_head(h):
        at = pl.ds(pl.multiple_of(h * dk, dk), dk)
        k = k_ref[0, at, :]
        between = _decay_between(gamma_ref[0, 0, pl.ds(h, 1), :])
        kq = _dot(k, jnp.concatenate([k, q_ref[0, at, :]], axis=1), _ROWS)
        kk = jnp.where(i > j, kq[:, :CHUNK] * between, 0.0)
        between_scr[h] = between
        kk_scr[h] = kk
        kq_scr[h] = kq[:, CHUNK:]
        rows_scr[h] = _pack(beta_ref[0, 0, pl.ds(h, 1), :] * kk)

    _each_head(gamma_ref.shape[2], one_head)
    _block_inverses(rows_scr)


def _written(k, v, gamma, beta, inverse, dtype):
    """``[k exp(gamma) beta; v beta]`` [dk + dv, chunk] in ``dtype``,
    and its product with the inverse: ``W`` [dk, chunk] in ``dtype``
    and ``U`` [dv, chunk] float32."""
    dk = k.shape[0]
    scaled = jnp.concatenate([
        (k.astype(_F32) * (jnp.exp(gamma) * beta)).astype(dtype),
        (v.astype(_F32) * beta).astype(dtype),
    ], axis=0)
    wu = _dot(scaled, inverse.astype(dtype))
    return scaled, wu[:dk].astype(dtype), wu[dk:]


def _fwd_kernel(q_ref, k_ref, v_ref, gamma_ref, beta_ref, o_ref, *rest,
                dk: int, dv: int, keep_states: bool):
    if keep_states:
        states_ref, state_scr, between_scr, kk_scr, kq_scr, rows_scr = rest
    else:
        state_scr, between_scr, kk_scr, kq_scr, rows_scr = rest
    dtype = q_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_scr[...] = jnp.zeros_like(state_scr)

    _stateless(q_ref, k_ref, gamma_ref, beta_ref, between_scr, kk_scr,
               kq_scr, rows_scr, dk)

    def one_head(h):
        keys = pl.ds(pl.multiple_of(h * dk, dk), dk)
        values = pl.ds(pl.multiple_of(h * dv, dv), dv)
        k, v = k_ref[0, keys, :], v_ref[0, values, :]
        gamma = gamma_ref[0, 0, pl.ds(h, 1), :]
        beta = beta_ref[0, 0, pl.ds(h, 1), :]
        total = _at_end(gamma)
        inverse = _doubled(_unpack(rows_scr[h]), beta * kk_scr[h])
        _, w, u = _written(k, v, gamma, beta, inverse, dtype)
        entering = _entering(state_scr, values)
        if keep_states:
            states_ref[0, 0, values, :] = entering
        q_in = (q_ref[0, keys, :].astype(_F32) * jnp.exp(gamma)).astype(dtype)
        # S [W | q exp(gamma)]
        reads = _dot(entering.astype(dtype),
                     jnp.concatenate([w, q_in], axis=1))
        written = (u - reads[:, :CHUNK]).astype(dtype)
        mixed = (kq_scr[h] * between_scr[h]).astype(dtype)
        o_ref[0, values, :] = (
            reads[:, CHUNK:] + _dot(written, mixed)).astype(dtype)
        k_out = (k.astype(_F32) * jnp.exp(total - gamma)).astype(dtype)
        state_scr[values, :] = jnp.exp(total) * entering + _dot(
            written, k_out, _COLS)

    _each_head(gamma_ref.shape[2], one_head)


def _bwd_kernel(q_ref, k_ref, v_ref, gamma_ref, beta_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dgamma_ref, dbeta_ref, dstate_scr,
                between_scr, kk_scr, kq_scr, rows_scr, *, dk: int, dv: int):
    dtype = q_ref.dtype

    # the sequence's last chunk comes first: no state leaves it
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_scr[...] = jnp.zeros_like(dstate_scr)

    _stateless(q_ref, k_ref, gamma_ref, beta_ref, between_scr, kk_scr,
               kq_scr, rows_scr, dk)
    j, i = _positions()
    last = jax.lax.broadcasted_iota(jnp.int32, (1, CHUNK), 1) == CHUNK - 1

    def one_head(h):
        keys = pl.ds(pl.multiple_of(h * dk, dk), dk)
        values = pl.ds(pl.multiple_of(h * dv, dv), dv)
        row = pl.ds(h, 1)
        q, k, v = q_ref[0, keys, :], k_ref[0, keys, :], v_ref[0, values, :]
        qf, kf, vf = q.astype(_F32), k.astype(_F32), v.astype(_F32)
        gamma, beta = gamma_ref[0, 0, row, :], beta_ref[0, 0, row, :]
        total = _at_end(gamma)
        from_start, to_end = jnp.exp(gamma), jnp.exp(total - gamma)
        between, kk = between_scr[h], kk_scr[h]
        do = do_ref[0, values, :]

        # the chunk again
        inverse = _doubled(_unpack(rows_scr[h]), beta * kk)
        scaled, w, u = _written(k, v, gamma, beta, inverse, dtype)
        entering = states_ref[0, 0, values, :]
        entering_lo = entering.astype(dtype)
        written = (u - _dot(entering_lo, w)).astype(dtype)
        mixed = (kq_scr[h] * between).astype(dtype)
        q_in = (qf * from_start).astype(dtype)
        k_out = (kf * to_end).astype(dtype)

        # what the outputs and the state that leaves hand back
        dleaving = _entering_gradient(dstate_scr, values)
        dleaving_lo = dleaving.astype(dtype)
        dwritten = (_dot(do, mixed, _COLS)
                    + _dot(dleaving_lo, k_out)).astype(dtype)
        both = jnp.concatenate([do, dwritten], axis=1)
        # S^T [do | dV']: d(q exp(gamma)) and -dW
        reads = _dot(entering_lo, both, _ROWS)
        dq_in, dw = reads[:, :CHUNK], -reads[:, CHUNK:]
        dk_out = _dot(dleaving_lo, written, _ROWS)
        decayed = jnp.exp(total) * dleaving
        dstate_scr[values, :] = decayed + _dot(
            both, jnp.concatenate([q_in, -w], axis=1), _COLS)
        at_end = jnp.sum(decayed * entering) + jnp.sum(
            dk_out * k_out.astype(_F32))

        # through [W; U] = [k exp(gamma) beta; v beta] X
        dwu = jnp.concatenate([dw.astype(dtype), dwritten], axis=0)
        dscaled = _dot(dwu, inverse.astype(dtype), _COLS)
        dk_scaled, dv_scaled = dscaled[:dk], dscaled[dk:]
        dv_ref[0, values, :] = (dv_scaled * beta).astype(dtype)
        dk_scaled_k = jnp.sum(dk_scaled * kf, axis=0, keepdims=True)
        dbeta = jnp.sum(dv_scaled * vf, axis=0, keepdims=True) \
            + dk_scaled_k * from_start

        # through the inverse: dA = -X^T dX X^T, above the diagonal
        transposed = inverse.T
        da = jnp.where(i > j, -_dot32(
            transposed, _dot32(_dot(scaled, dwu, _ROWS), transposed)), 0.0)
        dbeta_ref[0, 0, row, :] = dbeta + jnp.sum(
            da * kk, axis=0, keepdims=True)
        # from o: d(k_j . q_i) Gamma = V'^T do
        dmixed = _dot(written, do, _ROWS)
        dproducts = jnp.concatenate([
            (da * beta * between).astype(dtype),
            (dmixed * between).astype(dtype),
        ], axis=1)
        # a position as an output's (q_i; k_i in A's column i) ...
        targets = _dot(k, dproducts)
        dq_ref[0, keys, :] = (
            targets[:, CHUNK:] + dq_in * from_start).astype(dtype)
        # ... and as a source's (k_j)
        sources = _dot(jnp.concatenate([k, q], axis=1), dproducts, _COLS)
        dk_ref[0, keys, :] = (
            targets[:, :CHUNK] + dk_scaled * (from_start * beta)
            + sources + dk_out * to_end).astype(dtype)
        # gamma_i scales what position i receives and -gamma_j what
        # position j sends: Gamma's gradient times Gamma, summed over
        # the sources of a target less over the targets of a source,
        # and the three scalings of q and k by exp(+-gamma)
        through = da * (beta * kk) + dmixed * (kq_scr[h] * between)
        dgamma_ref[0, 0, row, :] = (
            jnp.sum(through, axis=0, keepdims=True)
            - jnp.sum(through.T, axis=0, keepdims=True)
            + (jnp.sum(dq_in * qf, axis=0, keepdims=True)
               + dk_scaled_k * beta) * from_start
            - jnp.sum(dk_out * kf, axis=0, keepdims=True) * to_end
            + jnp.where(last, at_end, 0.0))

    _each_head(gamma_ref.shape[2], one_head)


def _operands(q, k, v, g, beta):
    """Heads a grid step, and the kernels' views of the rule's
    operands: the sequence on the lanes, ``g``'s running sum inside each
    chunk and ``beta`` as rows a block of heads."""
    batch, seq, heads, dk = q.shape
    block = _head_block(heads)

    def channels(x):
        return x.reshape(batch, seq, -1).swapaxes(1, 2)

    def rows(x):
        return x.reshape(batch, heads // block, block, seq)

    gt = g.astype(_F32).swapaxes(1, 2)                      # [B, H, S]
    gamma = jnp.cumsum(
        gt.reshape(batch, heads, seq // CHUNK, CHUNK), axis=-1)
    return (block, channels(q), channels(k), channels(v), rows(gamma),
            rows(beta.astype(_F32).swapaxes(1, 2)))


def _specs(block, dk, dv, chunk_at):
    """Block specs for a grid (batch, block of ``block`` heads, chunk
    step); ``chunk_at`` maps the step to the chunk."""
    def spec(shape, index):
        return pl.BlockSpec(shape, lambda b, h, z: index(b, h, chunk_at(z)))

    return dict(
        keys=spec((1, block * dk, CHUNK), lambda b, h, z: (b, h, z)),
        values=spec((1, block * dv, CHUNK), lambda b, h, z: (b, h, z)),
        rows=spec((1, 1, block, CHUNK), lambda b, h, z: (b, h, 0, z)),
        entering=spec((1, 1, block * dv, dk), lambda b, h, z: (b, z, h, 0)),
    )


def _scratch(block, dk, dv):
    """The state (or its gradient) of a block's heads, and a chunk's
    [j, i] matrices a head: ``Gamma``, ``A``, ``k_j . q_i``, and the
    packed diagonal blocks."""
    square = pltpu.VMEM((block, CHUNK, CHUNK), _F32)
    return [pltpu.VMEM((block * dv, dk), _F32), square, square, square,
            pltpu.VMEM((block, BASE, CHUNK), _F32)]


# the grid's axes: batch rows and blocks of heads are independent; a
# chunk follows the one before it (after it, backward). Fifteen heads'
# blocks, twice each, and their [j, i] matrices are past the 16 MiB a
# kernel gets of the chip's 128 MiB of fast memory unasked
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024,
)


def _forward(q, k, v, g, beta, keep_states, interpret):
    batch, seq, heads, dk = q.shape
    dv = v.shape[-1]
    block, qt, kt, vt, gamma, betas = _operands(q, k, v, g, beta)
    spec = _specs(block, dk, dv, lambda z: z)
    out_specs = [spec["values"]]
    out_shape = [jax.ShapeDtypeStruct(vt.shape, v.dtype)]
    if keep_states:
        out_specs.append(spec["entering"])
        out_shape.append(jax.ShapeDtypeStruct(
            (batch, seq // CHUNK, heads * dv, dk), _F32))
    out = named_pallas_call(
        "gdn_chunk_fwd",
        functools.partial(_fwd_kernel, dk=dk, dv=dv, keep_states=keep_states),
        grid=(batch, heads // block, seq // CHUNK),
        in_specs=[spec["keys"], spec["keys"], spec["values"], spec["rows"],
                  spec["rows"]],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=_scratch(block, dk, dv),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(qt, kt, vt, gamma, betas)
    o = out[0].swapaxes(1, 2).reshape(v.shape)
    return o, (out[1] if keep_states else None)


def _backward(q, k, v, g, beta, states, do, interpret):
    batch, seq, heads, dk = q.shape
    dv = v.shape[-1]
    block, qt, kt, vt, gamma, betas = _operands(q, k, v, g, beta)
    chunks = seq // CHUNK
    dot = do.reshape(batch, seq, heads * dv).swapaxes(1, 2)
    spec = _specs(block, dk, dv, lambda z: chunks - 1 - z)
    by_row = jax.ShapeDtypeStruct(gamma.shape, _F32)
    dqt, dkt, dvt, dgamma, dbeta = named_pallas_call(
        "gdn_chunk_bwd",
        functools.partial(_bwd_kernel, dk=dk, dv=dv),
        grid=(batch, heads // block, chunks),
        in_specs=[spec["keys"], spec["keys"], spec["values"], spec["rows"],
                  spec["rows"], spec["entering"], spec["values"]],
        out_specs=[spec["keys"], spec["keys"], spec["values"], spec["rows"],
                   spec["rows"]],
        out_shape=[jax.ShapeDtypeStruct(qt.shape, q.dtype),
                   jax.ShapeDtypeStruct(kt.shape, k.dtype),
                   jax.ShapeDtypeStruct(vt.shape, v.dtype), by_row, by_row],
        scratch_shapes=_scratch(block, dk, dv),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(qt, kt, vt, gamma, betas, states, dot)
    # back through the running sum
    dg = jax.lax.cumsum(
        dgamma.reshape(batch, heads, chunks, CHUNK), axis=3, reverse=True
    ).reshape(batch, heads, seq)
    return (
        dqt.swapaxes(1, 2).reshape(q.shape),
        dkt.swapaxes(1, 2).reshape(k.shape),
        dvt.swapaxes(1, 2).reshape(v.shape),
        dg.swapaxes(1, 2).astype(g.dtype),
        dbeta.reshape(batch, heads, seq).swapaxes(1, 2).astype(beta.dtype),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, interpret):
    return _forward(q, k, v, g, beta, False, interpret)[0]


def _rule_fwd(q, k, v, g, beta, interpret):
    o, states = _forward(q, k, v, g, beta, True, interpret)
    return o, (q, k, v, g, beta, states)


def _rule_bwd(interpret, saved, do):
    return _backward(*saved, do, interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule_kernel(q, k, v, g, beta, interpret=None):
    """``gated_delta.gated_delta_rule_chunked`` by the kernel pair:
    ``q``, ``k`` [B, S, H, dk], ``v`` [B, S, H, dv], ``g`` and ``beta``
    [B, S, H] -> ``o`` [B, S, H, dv] in ``v``'s dtype, for the shapes
    :func:`kernel_takes` names."""
    (_, seq, heads, dk), dv = q.shape, v.shape[-1]
    if not kernel_takes(seq, heads, dk, dv):
        raise ValueError(
            f"gated_delta_rule: q {q.shape} with values {v.shape} is not "
            f"tiled by the kernels (the sequence in chunks of {CHUNK}, the "
            f"head sizes in {SUBLANES}s, keys of {LANES // 2} channels at "
            "the least); ops/gated_delta.py:gated_delta_rule dispatches "
            "such shapes to the plain forms"
        )
    if interpret is None:
        interpret = use_interpret()
    dtype = v.dtype
    return _rule(q.astype(dtype), k.astype(dtype), v, g, beta, interpret)
