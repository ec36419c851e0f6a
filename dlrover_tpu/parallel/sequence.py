"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Equivalent capability: the reference's DistributedSelfAttention
(atorch/atorch/modules/distributed_transformer/distributed_attention.py:79)
shards the sequence across ranks and normalises softmax statistics across
the sequence group (allgathered micro-q + DistributedSoftmax + reduce-
scatter, dual-stream overlap). TPU redesign — two idiomatic schedules over
a ``seq`` mesh axis instead of a translation:

- :func:`ring_attention` — blockwise attention where each device keeps its
  q shard resident and the k/v shards rotate around the ring via
  ``lax.ppermute``; a running online-softmax (m, l, o) merges each visiting
  block, so memory is O(S_local^2) per step and the permute traffic rides
  the ICI torus neighbour links. This is the Liu et al. ring-attention
  schedule; causality is enforced with global-position masks so chunked
  semantics exactly match single-device causal attention.
- :func:`ulysses_attention` — all-to-all swaps the sharded dimension from
  sequence to heads (``lax.all_to_all`` tiled), runs the full-sequence
  Pallas flash kernel locally on ``heads/n`` heads, and swaps back.
  Cheaper when heads >= ring size; exactly one pair of all-to-alls.

Both are pure ``shard_map``-compatible functions (q/k/v are per-device
shards, layout [batch, heads, seq_local, head_dim]) and differentiable;
:func:`sequence_sharded_attention` wraps either in ``shard_map`` over the
active mesh for callers holding globally-sharded arrays.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.common.backend import use_interpret
from dlrover_tpu.ops.attention import NEG_INF, flash_attention
from dlrover_tpu.parallel.mesh import get_mesh

__all__ = [
    "ring_attention",
    "ulysses_attention",
    "sequence_sharded_attention",
]


def _block_attn(q, k, v, q_chunk, kv_chunk, sm_scale, causal):
    """One (q_shard x kv_shard) block: unnormalised output + stats.

    Positions are global: row r of this q shard is ``q_chunk*Sq + r``.
    GQA is handled by grouping q heads against their kv head in the
    einsum — the raw kv shards are never repeated, so the ring permutes
    (and the scan carries) only kv_heads worth of bytes.
    Returns (o_blk [b,h,sq,d] fp32, m [b,h,sq,1], l [b,h,sq,1]).
    """
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    qg = q.reshape(b, kvh, h // kvh, sq, d)
    s = jnp.einsum(
        "bkgqd,bkld->bkgql", qg, k, preferred_element_type=jnp.float32
    ) * sm_scale
    if causal:
        rows = q_chunk * sq + lax.broadcasted_iota(jnp.int32, s.shape, 3)
        cols = kv_chunk * sk + lax.broadcasted_iota(jnp.int32, s.shape, 4)
        s = jnp.where(cols <= rows, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    # a fully-masked row has m == NEG_INF; clamp so exp(s - m) is 0, not 1
    p = jnp.exp(s - jnp.maximum(m, NEG_INF / 2))
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgql,bkld->bkgqd", p, v.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    return (o.reshape(b, h, sq, d), m.reshape(b, h, sq, 1),
            l.reshape(b, h, sq, 1))


# ---------------------------------------------------------------------------
# ring attention with the Pallas flash kernel as the inner block
# ---------------------------------------------------------------------------
#
# The einsum block above is numerically exact but leaves the packed-grid
# flash kernel's efficiency on the table on a real seq mesh; this path
# (the default for causal rings) runs each visiting block through
# ops/attention.py ring_fwd_block (dynamic global-position masking) and
# merges normalized (o, lse) pairs online. The backward is a second ring
# pass through the flash dq/dkv kernels against the GLOBAL lse/delta —
# p = exp(s - LSE_global) reproduces the softmax weights blockwise, so
# no per-block statistics need saving. The forward rotates kv as ONE
# stacked ppermute per tick; the backward needs two (kv in the model
# dtype, cotangents in f32 — not stackable) serialized with an
# optimization_barrier: XLA:CPU reorders independent collectives per
# device and deadlocks the test mesh otherwise. Blocks entirely in the
# future of this device's q shard are skipped on TPU via the pipeline
# _gated pattern (computed-and-discarded on the CPU mesh, where
# branch-divergent thunk streams deadlock).


def _merge_block(o_acc, lse_acc, o_blk, lse_blk):
    """Merge a normalized block (o, lse) into the running pair."""
    m = jnp.maximum(lse_acc, lse_blk)
    m_safe = jnp.maximum(m, NEG_INF / 2)
    w_acc = jnp.where(lse_acc <= NEG_INF / 2, 0.0,
                      jnp.exp(lse_acc - m_safe))
    w_blk = jnp.where(lse_blk <= NEG_INF / 2, 0.0,
                      jnp.exp(lse_blk - m_safe))
    w_sum = w_acc + w_blk
    w_safe = jnp.where(w_sum == 0.0, 1.0, w_sum)
    o = (o_acc * w_acc + o_blk.astype(jnp.float32) * w_blk) / w_safe
    lse = jnp.where(
        w_sum == 0.0, NEG_INF, m_safe + jnp.log(w_safe))
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash(q, k, v, axis_name, n, sm_scale, block_q, block_k):
    o, _ = _ring_flash_fwd(q, k, v, axis_name, n, sm_scale, block_q,
                           block_k)
    return o


def _ring_flash_fwd(q, k, v, axis_name, n, sm_scale, block_q, block_k):
    from dlrover_tpu.ops.attention import STATS_W, ring_fwd_block

    idx = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]
    b, h, sq, d = q.shape
    sk = k.shape[2]

    from dlrover_tpu.parallel.pipeline import _gated

    def step(carry, t):
        kv_cur, o_acc, lse_acc = carry
        kv_chunk = (idx - t) % n

        def _visible(kv):
            o_blk, lse_blk = ring_fwd_block(
                q, kv[0], kv[1], idx * sq, kv_chunk * sk, sm_scale,
                block_q=block_q, block_k=block_k,
            )
            return o_blk.astype(jnp.float32), lse_blk[..., :1]

        def _future(kv):
            return (jnp.zeros((b, h, sq, d), jnp.float32),
                    jnp.full((b, h, sq, 1), NEG_INF, jnp.float32))

        o_blk, lse_blk = _gated(
            kv_chunk <= idx, _visible, _future, kv_cur)
        o_acc, lse_acc = _merge_block(o_acc, lse_acc, o_blk, lse_blk)
        return (lax.ppermute(kv_cur, axis_name, perm), o_acc,
                lse_acc), None

    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    lse0 = jnp.full((b, h, sq, 1), NEG_INF, jnp.float32)
    (_, o, lse), _ = lax.scan(
        step, (jnp.stack([k, v]), o0, lse0), jnp.arange(n), length=n)
    lse_w = jnp.broadcast_to(lse, lse.shape[:-1] + (STATS_W,))
    return o.astype(q.dtype), (q, k, v, o.astype(q.dtype), lse_w)


def _ring_flash_bwd(axis_name, n, sm_scale, block_q, block_k, res, do):
    from dlrover_tpu.ops.attention import (
        STATS_W, ring_dkv_block, ring_dq_block,
    )

    q, k, v, o, lse = res
    idx = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]
    sq, sk = q.shape[2], k.shape[2]
    dof = do.astype(jnp.float32) * o.astype(jnp.float32)
    delta = jnp.broadcast_to(
        dof.sum(-1, keepdims=True), lse.shape[:-1] + (STATS_W,))

    from dlrover_tpu.parallel.pipeline import _gated

    def step(carry, t):
        kv_cur, dkv_cur, dq_acc = carry
        k_cur, v_cur = kv_cur[0], kv_cur[1]
        kv_chunk = (idx - t) % n

        def _visible(kv):
            dqb = ring_dq_block(
                q, kv[0], kv[1], do, lse, delta, idx * sq,
                kv_chunk * sk, sm_scale, block_q=block_q,
                block_k=block_k,
            )
            dkb, dvb = ring_dkv_block(
                q, kv[0], kv[1], do, lse, delta, idx * sq,
                kv_chunk * sk, sm_scale, block_q=block_q,
                block_k=block_k,
            )
            return dqb, jnp.stack([dkb, dvb])

        def _future(kv):
            return (jnp.zeros(q.shape, jnp.float32),
                    jnp.zeros((2,) + k.shape, jnp.float32))

        dqb, dkvb = _gated(kv_chunk <= idx, _visible, _future, kv_cur)
        dq_acc = dq_acc + dqb
        dkv_cur = dkv_cur + dkvb
        # two stacked permutes (kv in model dtype, cotangents in f32):
        # the barrier serializes them — XLA:CPU may otherwise reorder
        # independent collectives across devices and deadlock the mesh
        kv_next = lax.ppermute(kv_cur, axis_name, perm)
        kv_next, dkv_cur = lax.optimization_barrier((kv_next, dkv_cur))
        dkv_next = lax.ppermute(dkv_cur, axis_name, perm)
        return (kv_next, dkv_next, dq_acc), None

    dkv0 = jnp.zeros((2,) + k.shape, jnp.float32)
    dq0 = jnp.zeros(q.shape, jnp.float32)
    (_, dkv, dq), _ = lax.scan(
        step, (jnp.stack([k, v]), dkv0, dq0), jnp.arange(n), length=n)
    return (dq.astype(q.dtype), dkv[0].astype(k.dtype),
            dkv[1].astype(v.dtype))


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(
    q, k, v,
    axis_name: str = "seq",
    axis_size: Optional[int] = None,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    use_kernel: bool = True,
    block_q: int = 512,
    block_k: int = 512,
):
    """Ring attention over a named mesh axis (call inside shard_map).

    Args:
      q: this device's query shard [batch, heads, seq_local, head_dim].
      k, v: this device's kv shards [batch, kv_heads, seq_local, head_dim].
      axis_name: mesh axis the sequence is sharded over.
      axis_size: static ring size; defaults to the active mesh's axis size
        (must be static — it is the scan length).
      use_kernel: run each visiting block through the packed Pallas
        flash kernel (interpret mode on CPU); the einsum block remains
        as the fallback for non-causal rings and head dims the hardware
        kernels cannot tile (head_dim % 128 on TPU).
    Returns the attention output shard, same shape/dtype as q.
    """
    if axis_size is None:
        axis_size = get_mesh().shape[axis_name]
    n = int(axis_size)
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    kernel_ok = use_kernel and causal and (
        use_interpret() or q.shape[-1] % 128 == 0
    )
    if kernel_ok and n > 1:
        return _ring_flash(q, k, v, axis_name, n, float(sm_scale),
                           int(block_q), int(block_k))
    if n == 1:
        if kernel_ok:
            return flash_attention(
                q, k, v, causal=True, sm_scale=sm_scale,
                block_q=block_q, block_k=block_k)
        o, _, l = _block_attn(q, k, v, 0, 0, sm_scale, causal)
        l = jnp.where(l == 0.0, 1.0, l)
        return (o / l).astype(q.dtype)

    idx = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]
    b, h, sq, d = q.shape

    @jax.checkpoint
    def step(carry, t):
        k_cur, v_cur, o_acc, m_acc, l_acc = carry
        # after t forward permutes, this device holds the shard that
        # started life on device (idx - t) mod n
        kv_chunk = (idx - t) % n
        o_blk, m_blk, l_blk = _block_attn(
            q, k_cur, v_cur, idx, kv_chunk, sm_scale, causal)
        m_new = jnp.maximum(m_acc, m_blk)
        alpha = jnp.exp(m_acc - m_new)
        beta = jnp.exp(m_blk - m_new)
        o_acc = o_acc * alpha + o_blk * beta
        l_acc = l_acc * alpha + l_blk * beta
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, o_acc, m_new, l_acc), None

    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq, 1), jnp.float32)
    (_, _, o, _, l), _ = lax.scan(
        step, (k, v, o0, m0, l0), jnp.arange(n), length=n)
    l = jnp.where(l == 0.0, 1.0, l)
    return (o / l).astype(q.dtype)


def ulysses_attention(
    q, k, v,
    axis_name: str = "seq",
    axis_size: Optional[int] = None,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
):
    """Ulysses/DeepSpeed-style SP: all-to-all heads<->seq, local flash, back.

    Requires heads (and kv_heads) divisible by the axis size. Shards are
    [batch, heads, seq_local, head_dim]; after the first all-to-all each
    device holds [batch, heads/n, seq_global, head_dim] and runs the
    full-sequence Pallas flash kernel on its head group.
    """
    if axis_size is None:
        axis_size = get_mesh().shape[axis_name]
    n = int(axis_size)
    if n == 1:
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               interpret=interpret)
    if q.shape[1] % n or k.shape[1] % n:
        raise ValueError(
            f"ulysses needs heads divisible by axis size: "
            f"q heads {q.shape[1]}, kv heads {k.shape[1]}, axis {n}")

    def fwd(x):  # [b, h, s_loc, d] -> [b, h/n, s_glob, d]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def rev(x):  # [b, h/n, s_glob, d] -> [b, h, s_loc, d]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    o = flash_attention(fwd(q), fwd(k), fwd(v), causal=causal,
                        sm_scale=sm_scale, interpret=interpret)
    return rev(o)


def sequence_sharded_attention(
    q, k, v,
    mesh=None,
    axis: str = "seq",
    batch_axes=("data", "fsdp"),
    head_axis: str = "tensor",
    impl: str = "ring",
    causal: bool = True,
    sm_scale: Optional[float] = None,
):
    """Attention over globally (batch, head, seq)-sharded arrays.

    Wraps :func:`ring_attention` / :func:`ulysses_attention` in
    ``shard_map`` over ``mesh`` with batch on ``batch_axes``, heads on
    ``head_axis`` and sequence on ``axis`` — the composition the reference
    reaches with nested process groups (distributed.py:321) falls out of
    one mesh here.
    """
    from jax.sharding import PartitionSpec as P

    mesh = mesh or get_mesh()
    n = mesh.shape.get(axis, 1)
    spec = P(tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1) or None,
             head_axis if mesh.shape.get(head_axis, 1) > 1 else None,
             axis if n > 1 else None,
             None)
    if impl == "ring":
        fn = functools.partial(ring_attention, axis_name=axis, axis_size=n,
                               causal=causal, sm_scale=sm_scale)
    elif impl == "ulysses":
        fn = functools.partial(ulysses_attention, axis_name=axis, axis_size=n,
                               causal=causal, sm_scale=sm_scale)
    else:
        raise ValueError(f"unknown sequence-parallel impl {impl!r}")
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
