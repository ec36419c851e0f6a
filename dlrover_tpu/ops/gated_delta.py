"""The gated delta rule (Gated DeltaNet, Yang, Kautz & Hatamizadeh
2024, arXiv:2412.06464): a linear-attention recurrence whose state is
decayed and *corrected* at every position.

One head, ``S`` in R^{dk x dv}, ``k`` of unit length::

    S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T k_t)^T
        = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``g_t <= 0`` is the log of the decay and ``beta_t`` in [0, 2] the
strength of the correction (above 1 the transition has a negative
eigenvalue). Where Mamba-2's scan (``ops/ssd.py``) multiplies its state
by a scalar, this one multiplies it by ``I - beta k k^T`` too, so the
state a position leaves depends on the states of the positions before
it *inside* the chunk, and the chunked form has to solve for them.

The chunked form (the WY / UT representation). In a chunk of C
positions write ``gamma_i`` for the running sum of ``g`` inside the
chunk, ``Gamma_ij = exp(gamma_i - gamma_j)`` for ``i >= j``, and
``v'_i = beta_i (v_i - exp(g_i) S_{i-1}^T k_i)`` for what position i
really writes. Then ``S_i = exp(gamma_i) S_in + sum_{j<=i} Gamma_ij
k_j v'_j^T``, and putting that into the definition of ``v'``::

    (I + A) V' = diag(beta) (V - (K * exp(gamma)) S_in)
    A = strict_lower(diag(beta) (K K^T * Gamma))
    T = (I + A)^{-1} diag(beta),   W = T (K * exp(gamma)),   U = T V
    V'    = U - W S_in
    O     = (Q * exp(gamma)) S_in + lower(Q K^T * Gamma) V'
    S_out = exp(gamma_C) S_in + (K * exp(gamma_C - gamma))^T V'

``T``, ``W`` and ``U`` need no state and are made for all chunks at
once; the state goes from chunk to chunk in a scan whose body is the
two matmuls that need it (``W S_in`` and the update), and the outputs
are made for all chunks at once from the states the scan hands back.
``(I + A)^{-1}`` is the inverse of a unit lower-triangular [C, C]
matrix: built from the inverses of its diagonal blocks, 1 x 1 first and
doubled until they are the matrix (``inv [[L1, 0], [B, L2]] = [[inv L1,
0], [-inv L2 B inv L1, inv L2]]``), which multiplies bounded inverses
only. The Neumann product ``(I - A)(I + A^2)(I + A^4)...`` is the same
matrix on paper and sums powers of ``A`` that, with ``beta`` near 2 and
keys that repeat, reach 1e20 before they cancel.

Precision: ``gamma``, every ``exp``, the triangular inverse and the
state that goes from chunk to chunk are float32; the matmuls take
operands in the activations' dtype (bf16 in a bf16 model) and
accumulate in float32. Every exponent is a difference ``gamma_i -
gamma_j`` with ``i >= j``, so no ``exp`` exceeds 1.

:func:`gated_delta_rule` is the entry: one algorithm in three forms,
picked from the shapes and the mesh alone by the rule ``ssd_scan``
applies (``ssd._kernel_mesh``). Where the kernels' blocks tile the input
(the sequence in chunks of 128, head sizes in whole sublane tiles, keys
of 64 channels at the least: ``gated_delta_kernel.kernel_takes``), no
``seq`` mesh axis is active and the batch divides over ``data`` /
``fsdp``: the Pallas kernel pair of ``ops/gated_delta_kernel.py``
(``gdn_chunk_fwd``, ``gdn_chunk_bwd``), mapped over the mesh's batch
axes. It is the chunked form above at the same precision on chunks of
128 positions (the chunk is the algorithm's tile, not the model's
mathematics), keeps every [C, C] block of a chunk in fast memory, and
hands its backward pass the inputs and each chunk's entering state.
Everywhere else the forms of this file, plain ``jax.numpy``
differentiated by JAX, which partition over the batch as the compiler
partitions any array program and are the kernels' reference: the chunked
form (:func:`gated_delta_rule_chunked`) where the sequence is whole
chunks and no ``seq`` axis is active (no state is handed across sequence
shards: the toy widths and short sequences of the CPU tests), the
recurrence itself (:func:`gated_delta_rule_plain`, one position at a
time: the tests' yardstick) for a ragged tail or a sequence-sharded
mesh. The gauge ``model.gdn.impl`` says which was traced: ``kernel``,
``chunked`` or ``plain``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dlrover_tpu.common import telemetry

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def gated_delta_rule(q, k, v, g, beta, chunk: int):
    """``q``, ``k`` [B, S, H, dk] (``k`` of unit length a head, ``q``
    scaled by the caller), ``v`` [B, S, H, dv], ``g`` [B, S, H] the log
    of the decay (<= 0, float32), ``beta`` [B, S, H] -> ``o`` [B, S, H,
    dv] in ``v``'s dtype. The state starts at zero."""
    from dlrover_tpu.ops import gated_delta_kernel
    from dlrover_tpu.ops.ssd import _kernel_mesh, _over_batch
    from dlrover_tpu.parallel.mesh import axis_size

    (batch, seq, heads, dk), dv = q.shape, v.shape[-1]
    try:
        seq_shards = axis_size("seq")
    except RuntimeError:            # no mesh at all
        seq_shards = 1
    chunked = seq % chunk == 0 and seq_shards == 1
    mesh, batch_axes = _kernel_mesh(
        "model.gdn.impl", batch,
        gated_delta_kernel.kernel_takes(seq, heads, dk, dv),
        otherwise="chunked" if chunked else "plain")
    with jax.named_scope("gdn_rule"):
        if batch_axes is not None:
            return _over_batch(
                gated_delta_kernel.gated_delta_rule_kernel, mesh,
                batch_axes, "xxxxx")(q, k, v, g, beta)
        if chunked:
            return gated_delta_rule_chunked(q, k, v, g, beta, chunk)
        return gated_delta_rule_plain(q, k, v, g, beta)


def gated_delta_rule_plain(q, k, v, g, beta):
    """The recurrence, one position at a time, in float32."""
    dtype = v.dtype
    q, k, v, g, beta = (x.astype(_F32) for x in (q, k, v, g, beta))

    def step(state, at):
        qt, kt, vt, gt, bt = at                 # [B, H, ...]
        state = jnp.exp(gt)[..., None, None] * state
        read = jnp.einsum("bhkv,bhk->bhv", state, kt, precision=_HIGHEST)
        write = bt[..., None] * (vt - read)
        state = state + kt[..., :, None] * write[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt,
                                 precision=_HIGHEST)

    batch, _seq, heads, dk = k.shape
    state = jnp.zeros((batch, heads, dk, v.shape[-1]), _F32)
    out = jax.lax.scan(
        step, state, tuple(x.swapaxes(0, 1) for x in (q, k, v, g, beta)))[1]
    return out.swapaxes(0, 1).astype(dtype)


def unit_lower_inverse(a):
    """``(I + a)^{-1}`` for ``a`` [..., n, n] strictly lower triangular,
    n a power of two, float32: the diagonal blocks' inverses, 1 x 1
    first, doubled ``log2 n`` times."""
    *lead, n, _ = a.shape
    if n & (n - 1):
        raise ValueError(f"a chunk of {n} positions is no power of two")
    inv = jnp.ones((*lead, n, 1, 1), _F32)
    size = 1
    while size < n:
        pairs = n // (2 * size)
        # the block under the diagonal of each pair of diagonal blocks
        under = a.reshape(*lead, pairs, 2, size, pairs, 2, size)[
            ..., :, 1, :, :, 0, :]
        under = jnp.moveaxis(
            jnp.diagonal(under, axis1=-4, axis2=-2), -1, -3)
        paired = inv.reshape(*lead, pairs, 2, size, size)
        top, bottom = paired[..., 0, :, :], paired[..., 1, :, :]
        corner = -jnp.matmul(
            jnp.matmul(bottom, under, precision=_HIGHEST), top,
            precision=_HIGHEST)
        inv = jnp.concatenate([
            jnp.concatenate([top, jnp.zeros_like(top)], -1),
            jnp.concatenate([corner, bottom], -1),
        ], -2)
        size *= 2
    return inv[..., 0, :, :]


def gated_delta_rule_chunked(q, k, v, g, beta, chunk: int):
    """The chunked form of the module's docstring; the sequence is a
    whole number of chunks."""
    batch, seq, heads, dk = k.shape
    dv = v.shape[-1]
    dtype = v.dtype
    n = seq // chunk

    def chunks(x):
        """[B, S, H, ...] -> [B, n, H, C, ...]"""
        x = x.reshape(batch, n, chunk, heads, *x.shape[3:])
        return jnp.moveaxis(x, 2, 3)

    q, k, v = chunks(q.astype(dtype)), chunks(k.astype(dtype)), chunks(v)
    g, beta = chunks(g.astype(_F32)), chunks(beta.astype(_F32))
    gamma = jnp.cumsum(g, axis=-1)                          # [B, n, H, C]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # Gamma_ij for i >= j, 0 above: the mask sits under the exp
    between = jnp.exp(jnp.where(
        lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))

    kk = jnp.einsum("bnhik,bnhjk->bnhij", k, k, preferred_element_type=_F32)
    a = jnp.where(jnp.tril(lower, -1), beta[..., None] * kk * between, 0.0)
    t = (unit_lower_inverse(a) * beta[..., None, :]).astype(dtype)
    from_start = jnp.exp(gamma)[..., None]
    k32 = k.astype(_F32)
    k_in = (k32 * from_start).astype(dtype)
    w = jnp.einsum("bnhij,bnhjk->bnhik", t, k_in,
                   preferred_element_type=_F32).astype(dtype)
    u = jnp.einsum("bnhij,bnhjv->bnhiv", t, v, preferred_element_type=_F32)
    to_end = jnp.exp(gamma[..., -1:] - gamma)[..., None]
    k_out = (k32 * to_end).astype(dtype)
    decay = jnp.exp(gamma[..., -1])                         # [B, n, H]

    def step(state, at):
        w_c, u_c, k_c, decay_c = at
        entering = state.astype(dtype)
        written = (u_c - jnp.einsum(
            "bhik,bhkv->bhiv", w_c, entering, preferred_element_type=_F32
        )).astype(dtype)
        state = decay_c[..., None, None] * state + jnp.einsum(
            "bhik,bhiv->bhkv", k_c, written, preferred_element_type=_F32)
        return state, (entering, written)

    state = jnp.zeros((batch, heads, dk, dv), _F32)
    _, (entering, written) = jax.lax.scan(
        step, state,
        tuple(jnp.moveaxis(x, 1, 0) for x in (w, u, k_out, decay)))
    entering = jnp.moveaxis(entering, 0, 1)                 # [B, n, H, dk, dv]
    written = jnp.moveaxis(written, 0, 1)                   # [B, n, H, C, dv]

    qk = jnp.einsum("bnhik,bnhjk->bnhij", q, k, preferred_element_type=_F32)
    q_in = (q.astype(_F32) * from_start).astype(dtype)
    out = jnp.einsum("bnhik,bnhkv->bnhiv", q_in, entering,
                     preferred_element_type=_F32)
    out = out + jnp.einsum(
        "bnhij,bnhjv->bnhiv", (qk * between).astype(dtype), written,
        preferred_element_type=_F32)
    return jnp.moveaxis(out, 3, 2).reshape(batch, seq, heads, dv).astype(dtype)
