"""Pipeline parallelism over the ``pipe`` mesh axis.

Equivalent capability: the reference's PiPPy/DeepSpeed pipeline path
(atorch/atorch/auto/opt_lib/pipeline_parallel_optimization.py:56 graph
partition + interleaved schedules; ds_3d_parallel_optimization.py:184
LayerSpec conversion) which moves activations between stage *processes*
with torch RPC / p2p sends.

TPU redesign: there are no stage processes and no RPC. The model keeps
its layer-stacked parameter layout ([L, ...] arrays scanned with
``lax.scan``); activating pipelining means (1) sharding the leading
layer axis over the ``pipe`` mesh axis so each device group holds L/S
contiguous layers, and (2) running a GPipe microbatch schedule *inside
the jitted step* with ``jax.lax.ppermute`` rotating activations
stage→stage over ICI. The whole schedule is one ``lax.scan`` over
M + S - 1 ticks, so it is a single compiled program, differentiable by
construction (``ppermute`` transposes to the reverse permute — XLA
derives the backward 1F1B-equivalent schedule from autodiff).

Only the ``pipe`` axis is manual (``shard_map(axis_names={"pipe"})``);
batch/fsdp/tensor axes stay in GSPMD-auto mode, so tensor parallelism
and ZeRO sharding compose with pipelining without any model changes.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.common import telemetry
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.parallel.mesh import get_mesh

logger = get_logger(__name__)

AXIS = "pipe"


_opt_barrier = jax.lax.optimization_barrier


def pipe_size() -> int:
    """Active ``pipe`` axis size (1 = pipelining off)."""
    try:
        return get_mesh().shape.get(AXIS, 1)
    except RuntimeError:
        return 1


def _gated(pred, true_fn, false_fn, operand):
    """Branch that is divergent ACROSS pipe stages, uniform within
    every fsdp/tensor collective group.

    On TPU this is a real ``lax.cond`` — collectives execute in program
    order, the untaken branch's FLOPs are skipped (the whole point: the
    head/loss vjp only costs where it runs). XLA:CPU's thunk-executor
    collective rendezvous deadlocks when different devices run
    different thunk streams (observed on pipe x tensor meshes even with
    collective-free branches), so there both branches are computed and
    ``where``-selected — the uniform-computation behaviour the CPU test
    mesh requires, at the old every-stage cost."""
    if jax.default_backend() != "tpu":
        tv = true_fn(operand)
        fv = false_fn(operand)
        return jax.tree.map(
            lambda a, b: jnp.where(pred, a, b), tv, fv
        )
    return jax.lax.cond(pred, true_fn, false_fn, operand)


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x,
    *broadcast_args,
    n_microbatches: int = 0,
    mesh=None,
):
    """Run ``stage_fn`` as a GPipe pipeline over the ``pipe`` mesh axis.

    Args:
      stage_fn: ``(local_params, h, *broadcast_args) -> (h_out, aux)``
        applying this stage's layer block. ``aux`` is a scalar f32
        auxiliary loss (0 if unused). Called once per schedule tick.
      stage_params: pytree whose leaves are stacked ``[L, ...]`` arrays
        with the leading (layer) axis sharded over ``pipe``; inside the
        shard_map each stage sees its local ``[L/S, ...]`` shard.
      x: activations ``[B, ...]``; B must be divisible by
        ``n_microbatches``, and B/M by the batch-sharding axes.
      broadcast_args: extra per-microbatch inputs with leading batch dim
        (e.g. positions) — microbatched alongside ``x``.
      n_microbatches: M; default ``2 * S`` (bubble fraction (S-1)/(M+S-1)).

    Returns ``(out, aux_total)`` with ``out`` shaped like ``x`` and
    replicated over ``pipe`` (other mesh axes keep GSPMD shardings).
    """
    mesh = mesh if mesh is not None else get_mesh()
    S = mesh.shape.get(AXIS, 1)
    if S == 1:
        out, aux = stage_fn(stage_params, x, *broadcast_args)
        return out, aux

    M = int(n_microbatches) if n_microbatches else 2 * S
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")

    def to_micro(a):
        return a.reshape((M, a.shape[0] // M) + a.shape[1:])

    x_mb = to_micro(x)
    extra_mb = tuple(to_micro(a) for a in broadcast_args)

    # XLA:CPU (jax 0.9.0) CHECK-fails ("invalid binary instruction opcode
    # copy") when differentiating bf16 select patterns at the manual-
    # region *input* boundary. Keep the input boundary f32 and compute in
    # the model's dtype inside; the output crosses the boundary in
    # compute dtype (stacked P(pipe) + slice, no select/psum involved).
    compute_dtype = x.dtype
    cast_boundary = (
        jnp.issubdtype(compute_dtype, jnp.floating)
        and compute_dtype != jnp.float32
    )
    if cast_boundary:
        x_mb = x_mb.astype(jnp.float32)

    from jax.sharding import PartitionSpec as P

    def schedule(params_local, x_mb, *extra_mb):
        if cast_boundary:
            x_mb = x_mb.astype(compute_dtype)
        stage = jax.lax.axis_index(AXIS)
        T = M + S - 1

        state0 = jnp.zeros_like(x_mb[0])
        outbuf0 = jnp.zeros_like(x_mb)

        def tick(carry, t):
            state, outbuf, aux_sum = carry
            # serialize the per-tick (loop-invariant) param all-gathers
            # behind the previous tick's ppermute — see the matching
            # barrier in pipeline_loss_1f1b for why (XLA:CPU rendezvous
            # mispairing across scan iterations)
            params_t, state = _opt_barrier(
                (params_local, state)
            )
            feed = jnp.clip(t, 0, M - 1)
            inject = jax.lax.dynamic_index_in_dim(
                x_mb, feed, 0, keepdims=False
            )
            cur = jnp.where(stage == 0, inject, state)
            extras = tuple(
                jax.lax.dynamic_index_in_dim(e, feed, 0, keepdims=False)
                for e in extra_mb
            )
            out, aux = stage_fn(params_t, cur, *extras)
            # Valid (non-bubble) ticks for this stage process microbatch
            # t - stage; mask the aux contribution of bubble garbage.
            valid = (t >= stage) & (t < M + stage)
            aux_sum = aux_sum + jnp.where(valid, aux, 0.0)
            # Last stage commits finished microbatch t-(S-1) to the buffer.
            widx = jnp.clip(t - (S - 1), 0, M - 1)
            committed = jax.lax.dynamic_update_index_in_dim(
                outbuf, out.astype(outbuf.dtype), widx, 0
            )
            write = (stage == S - 1) & (t >= S - 1)
            outbuf = jnp.where(write, committed, outbuf)
            nxt = jax.lax.ppermute(
                out, AXIS, [(i, i + 1) for i in range(S - 1)]
            )
            return (nxt, outbuf, aux_sum), None

        (_, outbuf, aux_sum), _ = jax.lax.scan(
            tick,
            (state0, outbuf0, jnp.zeros((), jnp.float32)),
            jnp.arange(T),
        )
        # The result lives on the last stage only. Return the per-stage
        # buffers stacked over ``pipe`` (out_specs P(AXIS)); the caller
        # slices out the last stage's piece, which GSPMD lowers to a
        # one-hop transfer from its owner — cheaper than the previous
        # masked psum of the whole buffer (an all-reduce where a
        # broadcast suffices).
        # Each valid tick contributed one per-microbatch mean; average
        # over M so aux matches the dense path's full-batch mean.
        aux_total = jax.lax.psum(aux_sum, AXIS) / M
        return outbuf[None], aux_total

    n_extra = len(extra_mb)
    out_stacked, aux_total = jax.shard_map(
        schedule,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P(AXIS), stage_params),
            P(),
        ) + (P(),) * n_extra,
        out_specs=(P(AXIS), P()),
        axis_names={AXIS},
        check_vma=False,
    )(stage_params, x_mb, *extra_mb)
    # one-hop broadcast: slice the last stage's shard of the stacked
    # [S, M, ...] output (physically [1, ...] per stage)
    out_mb = jax.lax.slice_in_dim(out_stacked, S - 1, S, axis=0)[0]
    return out_mb.reshape(x.shape), aux_total


def pipeline_loss_1f1b(
    stage_fn: Callable,
    last_fn: Callable,
    stage_params,
    last_params,
    x,
    stage_extras=(),
    last_extras=(),
    n_microbatches: int = 0,
    mesh=None,
):
    """1F1B pipeline schedule with the loss computed in the last stage.

    The reference's default pipeline schedule is interleaved 1F1B
    (atorch/atorch/auto/opt_lib/pipeline_parallel_optimization.py:98
    ``Interleaved1F1B``): backward of microbatch m starts as soon as its
    forward reaches the last stage, while later microbatches are still
    in flight, which bounds the stored boundary activations per stage to
    O(S) instead of O(M). That property requires the output cotangent
    *during* the schedule — i.e. the loss must live inside the pipeline
    — so unlike :func:`pipeline_apply` this variant takes the last-stage
    head/loss as ``last_fn`` and returns the scalar loss.

    TPU redesign: one fused fwd+bwd schedule inside a single
    ``lax.scan`` under ``shard_map`` over the ``pipe`` axis. At tick t,
    stage s runs forward for microbatch ``f = t - s`` and backward (a
    local ``jax.vjp`` re-linearisation at the saved stage input) for
    ``b = t - 2(S-1) + s``; activation messages ``ppermute`` up, cotangent
    messages down, each one microbatch in size. Stage inputs live in a
    ring buffer of ``2S-1`` slots — in-flight microbatch activations are
    bounded by the pipeline depth, independent of M. Because gradients
    are linear in the scalar loss cotangent, the whole thing is a
    ``jax.custom_vjp`` whose forward also produces the grads and whose
    backward just scales them — no AD through the schedule.

    Args:
      stage_fn: ``(local_params, h, *stage_extras_mb) -> (h, aux)``.
      last_fn: ``(last_params, h, *last_extras_mb) -> scalar`` loss for
        one microbatch (e.g. final norm + head + CE mean). The total
        loss is the mean over microbatches of ``last_fn`` plus the mean
        aux — mean-of-microbatch-means, which equals the global mean
        when every microbatch has the same valid-token count.
      stage_params: stacked ``[L, ...]`` pytree sharded over ``pipe``.
      last_params: pytree replicated over ``pipe`` (head weights).
      x: activations ``[B, ...]``; ``stage_extras``/``last_extras`` are
        microbatched alongside (leading batch dim) and treated as
        non-differentiable (zero cotangents).

    Returns the scalar loss (CE mean + aux mean).
    """
    mesh = mesh if mesh is not None else get_mesh()
    S = mesh.shape.get(AXIS, 1)
    if S == 1:
        # Honour the per-microbatch last_fn contract (it may scale by
        # M/valid_total): run it per microbatch and average, exactly as
        # the eval primal below does.
        M1 = int(n_microbatches) if n_microbatches else 1
        if M1 <= 1 or x.shape[0] % M1:
            h, aux = stage_fn(stage_params, x, *stage_extras)
            return last_fn(last_params, h, *last_extras) + aux
        xm = x.reshape((M1, x.shape[0] // M1) + x.shape[1:])
        sxm = tuple(
            a.reshape((M1, a.shape[0] // M1) + a.shape[1:])
            for a in stage_extras)
        lxm = tuple(
            a.reshape((M1, a.shape[0] // M1) + a.shape[1:])
            for a in last_extras)
        total = 0.0
        for m in range(M1):
            h, aux = stage_fn(stage_params, xm[m], *(e[m] for e in sxm))
            total = total + last_fn(
                last_params, h, *(e[m] for e in lxm)) + aux
        return total / M1

    M = int(n_microbatches) if n_microbatches else 2 * S
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")

    def to_micro(a):
        return a.reshape((M, a.shape[0] // M) + a.shape[1:])

    x_mb = to_micro(x)
    sx_mb = tuple(to_micro(a) for a in stage_extras)
    lx_mb = tuple(to_micro(a) for a in last_extras)

    from jax.sharding import PartitionSpec as P

    R = 2 * S - 1        # ring-buffer slots: max in-flight stage inputs
    T = M + 2 * (S - 1)  # fwd drains at M+S-2, bwd at M-1+2(S-1)

    def _idx(a, i):
        return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)

    def schedule(params_local, last_params_, x_mb_, sx_mb_, lx_mb_):
        stage = jax.lax.axis_index(AXIS)
        is_last = stage == S - 1
        mb_shape = x_mb_.shape[1:]

        def f32_zeros_like(tree):
            return jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), tree
            )

        carry0 = (
            jnp.zeros(mb_shape, x_mb_.dtype),            # fwd_msg
            jnp.zeros(mb_shape, jnp.float32),            # bwd_msg
            jnp.zeros((R,) + mb_shape, x_mb_.dtype),     # inbuf
            f32_zeros_like(params_local),                # d_params
            f32_zeros_like(last_params_),                # d_last
            jnp.zeros(x_mb_.shape, jnp.float32),         # d_x
            jnp.zeros((), jnp.float32),                  # ce_acc
            jnp.zeros((), jnp.float32),                  # aux_acc
        )

        def tick(carry, t):
            (fwd_msg, bwd_msg, inbuf, d_params, d_last, d_x,
             ce_acc, aux_acc) = carry
            # Tie this tick's (loop-invariant) param use to the carry:
            # without the barrier, GSPMD's per-tick param all-gathers
            # (fsdp/tensor axes) depend only on the invariant params, so
            # XLA:CPU may start iteration k+1's all-gather while a peer
            # is still in iteration k's ppermute — the rendezvous keys
            # collide across iterations and the program deadlocks. TPU
            # executes collectives in program order, so this only pins
            # down an ordering the hardware imposes anyway.
            (params_t, last_params_t), fwd_msg = (
                _opt_barrier(
                    ((params_local, last_params_), fwd_msg)
                )
            )
            f = t - stage
            b = t - 2 * (S - 1) + stage
            valid_f = (f >= 0) & (f < M)
            valid_b = (b >= 0) & (b < M)
            fidx = jnp.clip(f, 0, M - 1)
            bidx = jnp.clip(b, 0, M - 1)

            cur = jnp.where(stage == 0, _idx(x_mb_, fidx), fwd_msg)
            saved = _idx(inbuf, jnp.mod(bidx, R))
            # save this tick's input; gate on valid_f or the clipped
            # index would clobber slot 0 during bubbles
            inbuf = jnp.where(
                valid_f,
                jax.lax.dynamic_update_index_in_dim(
                    inbuf, cur, jnp.mod(fidx, R), 0
                ),
                inbuf,
            )

            # Every stage runs the SAME computation each tick (inputs/
            # seeds selected by `where`) — divergent `lax.cond` branches
            # deadlock because GSPMD inserts different resharding
            # collectives per branch. The last stage's vjp microbatch is
            # its fwd one (b == f there), so one vjp serves both roles.
            vidx = jnp.where(is_last, fidx, bidx)
            valid_v = jnp.where(is_last, valid_f, valid_b)
            sx_f = tuple(_idx(e, fidx) for e in sx_mb_)
            sx_v = tuple(_idx(e, vidx) for e in sx_mb_)
            lx_v = tuple(_idx(e, vidx) for e in lx_mb_)
            cur_v = jnp.where(is_last, cur, saved)

            def stage_at_v(p_, c_):
                return stage_fn(p_, c_, *sx_v)

            (h_v, aux_v), stage_vjp = jax.vjp(
                stage_at_v, params_t, cur_v
            )
            # Head/loss vjp only where it matters. The branch predicate
            # (is_last) is uniform within every fsdp/tensor collective
            # group (those axes live inside one pipe stage), and
            # last_params are pre-replicated before the schedule, so the
            # taken branch contains no GSPMD resharding collectives —
            # the divergent-collectives deadlock that forces the
            # stage_fn vjp to stay uniform does not apply here.
            def _head(op):
                lp_, h_ = op
                ce_, ce_vjp = jax.vjp(
                    lambda l, h: last_fn(l, h, *lx_v), lp_, h_
                )
                d_lp_, d_h_ = ce_vjp(jnp.ones((), ce_.dtype))
                return (jnp.float32(ce_), d_lp_,
                        d_h_.astype(jnp.float32))

            def _head_zero(op):
                lp_, h_ = op
                return (jnp.float32(0.0),
                        jax.tree.map(jnp.zeros_like, lp_),
                        jnp.zeros(h_.shape, jnp.float32))

            ce, d_lp, d_h_ce = _gated(
                is_last, _head, _head_zero, (last_params_t, h_v)
            )
            seed_h = jnp.where(is_last, d_h_ce, bwd_msg).astype(
                h_v.dtype)
            d_p, d_c = stage_vjp((seed_h, jnp.ones((), aux_v.dtype)))
            # On the last stage the vjp primal IS fwd(cur) (its vjp
            # microbatch equals its fwd microbatch): _gated skips the
            # duplicate chain forward on TPU and balances tick cost
            # (last = vjp + head, others = vjp + chain fwd).
            out_chain = _gated(
                is_last,
                lambda _: h_v,
                lambda _: stage_fn(params_t, cur, *sx_f)[0],
                None,
            )

            d_c = jnp.where(valid_v, d_c, 0).astype(jnp.float32)
            d_params = jax.tree.map(
                lambda acc, g: acc + jnp.where(valid_v, g, 0).astype(
                    jnp.float32
                ),
                d_params, d_p,
            )
            d_last = jax.tree.map(
                lambda acc, g: acc + jnp.where(
                    is_last & valid_f, g, 0
                ).astype(jnp.float32),
                d_last, d_lp,
            )
            ce = jnp.where(is_last & valid_f, ce, 0.0).astype(
                jnp.float32
            )
            aux = jnp.where(valid_v, aux_v, 0.0).astype(jnp.float32)
            d_x = jnp.where(
                valid_b & (stage == 0),
                jax.lax.dynamic_update_index_in_dim(d_x, d_c, bidx, 0),
                d_x,
            )
            ce_acc = ce_acc + ce
            aux_acc = aux_acc + aux

            fwd_msg = jax.lax.ppermute(
                out_chain, AXIS, [(i, i + 1) for i in range(S - 1)]
            )
            # order the two permutes: they are data-independent, and
            # XLA:CPU's thunk executor may start them in a different
            # order on different devices — a rendezvous deadlock. The
            # barrier makes the cotangent permute depend on the
            # activation permute's completion.
            d_c, fwd_msg = _opt_barrier((d_c, fwd_msg))
            bwd_msg = jax.lax.ppermute(
                d_c, AXIS, [(i, i - 1) for i in range(1, S)]
            )
            return (fwd_msg, bwd_msg, inbuf, d_params, d_last, d_x,
                    ce_acc, aux_acc), None

        (_, _, _, d_params, d_last, d_x, ce_acc, aux_acc), _ = (
            jax.lax.scan(tick, carry0, jnp.arange(T))
        )
        # head grads live on the last stage only; psum replicates them
        # (other stages hold zeros), d_x likewise from stage 0, and the
        # scalars from their owners. Fuse everything into ONE psum of a
        # flat f32 vector: one rendezvous, and no mutually-independent
        # collectives the CPU thunk executor could reorder per device.
        reduce_leaves, reduce_def = jax.tree.flatten(
            (ce_acc, aux_acc, d_last, d_x)
        )
        sizes = [leaf.size for leaf in reduce_leaves]
        flat = jnp.concatenate([leaf.ravel() for leaf in reduce_leaves])
        flat = jax.lax.psum(flat, AXIS)
        parts, off = [], 0
        for leaf, size in zip(reduce_leaves, sizes):
            parts.append(flat[off:off + size].reshape(leaf.shape))
            off += size
        ce_acc, aux_acc, d_last, d_x = jax.tree.unflatten(
            reduce_def, parts
        )
        loss = (ce_acc + aux_acc) / M
        d_params = jax.tree.map(
            lambda g, p: (g / M).astype(p.dtype), d_params, params_local
        )
        d_last = jax.tree.map(
            lambda g, p: (g / M).astype(p.dtype), d_last, last_params_
        )
        d_x = (d_x / M).astype(x_mb_.dtype)
        return loss, d_params, d_last, d_x

    def run_schedule(sp, lp, x_, sx, lx):
        # Replicate the head params ONCE before the schedule: their
        # per-tick use inside the scan then needs no GSPMD all-gather,
        # which (a) keeps the cond-gated head vjp free of collectives
        # and (b) hoists a loop-invariant gather out of the scan.
        from jax.sharding import NamedSharding

        lp = jax.tree.map(
            lambda a: jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, P())
            ),
            lp,
        )
        return jax.shard_map(
            schedule,
            mesh=mesh,
            in_specs=(
                jax.tree.map(lambda _: P(AXIS), sp),
                jax.tree.map(lambda _: P(), lp),
                P(),
                jax.tree.map(lambda _: P(), sx),
                jax.tree.map(lambda _: P(), lx),
            ),
            out_specs=(
                P(),
                jax.tree.map(lambda _: P(AXIS), sp),
                jax.tree.map(lambda _: P(), lp),
                P(),
            ),
            axis_names={AXIS},
            check_vma=False,
        )(sp, lp, x_, sx, lx)

    def _zero_cotangent(a):
        if jnp.issubdtype(a.dtype, jnp.inexact):
            return jnp.zeros_like(a)
        return np.zeros(a.shape, jax.dtypes.float0)

    @jax.custom_vjp
    def _loss(sp, lp, x_, sx, lx):
        # non-differentiated primal (eval): forward-only GPipe schedule
        # + per-microbatch head — the fused schedule would pay the whole
        # backward for a loss that is never differentiated
        out_mb, aux = pipeline_apply(
            stage_fn, sp, x_.reshape((-1,) + x_.shape[2:]),
            *tuple(e.reshape((-1,) + e.shape[2:]) for e in sx),
            n_microbatches=M, mesh=mesh,
        )
        out_mb = out_mb.reshape(x_.shape)
        ce = 0.0
        for m in range(M):
            ce = ce + last_fn(lp, out_mb[m], *(e[m] for e in lx))
        return ce / M + aux

    def _loss_fwd(sp, lp, x_, sx, lx):
        out, d_sp, d_lp, d_x = run_schedule(sp, lp, x_, sx, lx)
        return out, (d_sp, d_lp, d_x, sx, lx)

    def _loss_bwd(res, ct):
        d_sp, d_lp, d_x, sx, lx = res

        def scale(tree):
            return jax.tree.map(
                lambda g: (ct * g.astype(jnp.float32)).astype(g.dtype),
                tree,
            )

        return (
            scale(d_sp),
            scale(d_lp),
            scale(d_x),
            jax.tree.map(_zero_cotangent, sx),
            jax.tree.map(_zero_cotangent, lx),
        )

    _loss.defvjp(_loss_fwd, _loss_bwd)
    return _loss(stage_params, last_params, x_mb, sx_mb, lx_mb)


def interleaved_layer_order(L: int, S: int, V: int):
    """Stacked-row order the interleaved schedule applies layers in.

    Under ``virtual_stages=V`` the pipe-sharded stack [L, ...] is
    interpreted chunk-major per device: effective position
    ``e = vs*Lc + i`` (virtual stage ``vs = v*S + s``) maps to stacked
    row ``s*(L/S) + v*Lc + i``. A dense model equals the interleaved
    one when its layers are permuted with this order (useful for parity
    tests and for importing externally-ordered weights)."""
    Lc = L // (S * V)
    order = []
    for vs in range(S * V):
        s, v = vs % S, vs // S
        for i in range(Lc):
            order.append(s * (L // S) + v * Lc + i)
    return np.asarray(order, dtype=np.int64)


def pipeline_loss_1f1b_interleaved(
    stage_fn: Callable,
    last_fn: Callable,
    stage_params,
    last_params,
    x,
    stage_extras=(),
    last_extras=(),
    n_microbatches: int = 0,
    virtual_stages: int = 2,
    mesh=None,
):
    """Interleaved (virtual-stage) 1F1B: each device runs V
    non-contiguous layer chunks (reference default schedule,
    pipeline_parallel_optimization.py:98 Interleaved1F1B), cutting the
    pipeline bubble by ~V versus plain 1F1B.

    TPU redesign: the whole schedule stays ONE ``lax.scan`` under
    ``shard_map``; a trace-time event simulation
    (:func:`_interleaved_tables`) precomputes per-(tick, device) unit
    tables and message-routing tables that ride into the kernel as
    int32 constants, so every tick runs the SAME program (one chain
    forward + one stage vjp, ``where``-indexed) — no divergent
    collectives. Activation messages ride a full ``ppermute`` ring
    (wrap edge S-1 -> 0 carries chunk transitions); the per-chunk input
    ring buffer doubles as the fwd-message mailbox.

    The local layer stack [L/S, ...] is interpreted as [V, L/(S*V)]
    chunk-major; see :func:`interleaved_layer_order` for the effective
    layer order.
    """
    mesh = mesh if mesh is not None else get_mesh()
    S = mesh.shape.get(AXIS, 1)
    V = int(virtual_stages)
    if S == 1 or V <= 1:
        return pipeline_loss_1f1b(
            stage_fn, last_fn, stage_params, last_params, x,
            stage_extras=stage_extras, last_extras=last_extras,
            n_microbatches=n_microbatches, mesh=mesh,
        )
    M = int(n_microbatches) if n_microbatches else 2 * S
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    L_local = jax.tree.leaves(stage_params)[0].shape[0] // S
    if L_local % V:
        raise ValueError(
            f"local layer count {L_local} not divisible by "
            f"virtual_stages {V}"
        )
    tables_np, T, R = _interleaved_tables(S, V, M)

    def to_micro(a):
        return a.reshape((M, a.shape[0] // M) + a.shape[1:])

    x_mb = to_micro(x)
    sx_mb = tuple(to_micro(a) for a in stage_extras)
    lx_mb = tuple(to_micro(a) for a in last_extras)

    from jax.sharding import PartitionSpec as P

    # [8, T, S]: fm fv bm bv rfm rfv rbm rbv
    keys = ("fm", "fv", "bm", "bv", "rfm", "rfv", "rbm", "rbv")
    tab_all = jnp.asarray(
        np.stack([tables_np[k] for k in keys], axis=0)
    )

    def _idx(a, i):
        return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)

    def _chunk(tree, v):
        """Select chunk v of the local [L/S, ...] stack (-> [Lc, ...])."""
        def sel(a):
            lc = a.shape[0] // V
            return jax.lax.dynamic_index_in_dim(
                a.reshape((V, lc) + a.shape[1:]), v, 0, keepdims=False
            )
        return jax.tree.map(sel, tree)

    def _chunk_add(tree, v, grads, valid):
        def add(acc, g):
            lc = acc.shape[0] // V
            stacked = acc.reshape((V, lc) + acc.shape[1:])
            stacked = stacked.at[v].add(
                jnp.where(valid, g, 0).astype(stacked.dtype)
            )
            return stacked.reshape(acc.shape)
        return jax.tree.map(add, tree, grads)

    def schedule(params_local, last_params_, x_mb_, sx_mb_, lx_mb_):
        stage = jax.lax.axis_index(AXIS)
        is_last = stage == S - 1
        mb_shape = x_mb_.shape[1:]

        def f32_zeros_like(tree):
            return jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), tree
            )

        carry0 = (
            jnp.zeros(mb_shape, x_mb_.dtype),              # fwd_msg
            jnp.zeros(mb_shape, jnp.float32),              # bwd_msg
            jnp.zeros((V, R) + mb_shape, x_mb_.dtype),     # inbuf
            jnp.zeros((V, R) + mb_shape, jnp.float32),     # cotbuf
            f32_zeros_like(params_local),                  # d_params
            f32_zeros_like(last_params_),                  # d_last
            jnp.zeros(x_mb_.shape, jnp.float32),           # d_x
            jnp.zeros((), jnp.float32),                    # ce_acc
            jnp.zeros((), jnp.float32),                    # aux_acc
        )

        def _buf_get(buf, v, m):
            return _idx(_idx(buf, v), jnp.mod(m, R))

        def _buf_set(buf, v, m, val, gate):
            upd = jax.lax.dynamic_update_index_in_dim(
                _idx(buf, v), val.astype(buf.dtype), jnp.mod(m, R), 0
            )
            upd = jax.lax.dynamic_update_index_in_dim(buf, upd, v, 0)
            return jnp.where(gate, upd, buf)

        def tick(carry, t):
            (fwd_msg, bwd_msg, inbuf, cotbuf, d_params, d_last, d_x,
             ce_acc, aux_acc) = carry
            (params_t, last_params_t), fwd_msg = (
                _opt_barrier(
                    ((params_local, last_params_), fwd_msg)
                )
            )
            vals = tab_all[:, t, :]
            (fm, fv, bm, bv, rfm, rfv, rbm, rbv) = tuple(
                _idx(vals[i], stage) for i in range(8)
            )
            valid_f = fm >= 0
            valid_b = bm >= 0
            fmi = jnp.clip(fm, 0, M - 1)
            fvi = jnp.clip(fv, 0, V - 1)
            bmi = jnp.clip(bm, 0, M - 1)
            bvi = jnp.clip(bv, 0, V - 1)

            # 1) deliver incoming messages into the mailboxes
            inbuf = _buf_set(
                inbuf, jnp.clip(rfv, 0, V - 1),
                jnp.clip(rfm, 0, M - 1), fwd_msg, rfm >= 0,
            )
            cotbuf = _buf_set(
                cotbuf, jnp.clip(rbv, 0, V - 1),
                jnp.clip(rbm, 0, M - 1), bwd_msg, rbm >= 0,
            )

            # 2) forward unit: input = injection (stage 0 chunk 0) or
            # the mailbox; store it as the saved input for the vjp
            inject = _idx(x_mb_, fmi)
            cur = jnp.where(
                (stage == 0) & (fvi == 0), inject,
                _buf_get(inbuf, fvi, fmi),
            )
            inbuf = _buf_set(inbuf, fvi, fmi, cur, valid_f)
            sx_f = tuple(_idx(e, fmi) for e in sx_mb_)
            params_f = _chunk(params_t, fvi)

            # 3) vjp unit at its saved input
            saved = _buf_get(inbuf, bvi, bmi)
            sx_v = tuple(_idx(e, bmi) for e in sx_mb_)
            lx_v = tuple(_idx(e, bmi) for e in lx_mb_)
            params_b = _chunk(params_t, bvi)

            (h_v, aux_v), stage_vjp = jax.vjp(
                lambda p_, c_: stage_fn(p_, c_, *sx_v), params_b, saved
            )
            lastv_b = is_last & (bvi == V - 1)

            def _head(op):
                lp_, h_ = op
                ce_, ce_vjp = jax.vjp(
                    lambda l, h: last_fn(l, h, *lx_v), lp_, h_
                )
                d_lp_, d_h_ = ce_vjp(jnp.ones((), ce_.dtype))
                return (jnp.float32(ce_), d_lp_,
                        d_h_.astype(jnp.float32))

            def _head_zero(op):
                lp_, h_ = op
                return (jnp.float32(0.0),
                        jax.tree.map(jnp.zeros_like, lp_),
                        jnp.zeros(h_.shape, jnp.float32))

            ce, d_lp, d_h_ce = _gated(
                lastv_b, _head, _head_zero, (last_params_t, h_v)
            )
            seed_h = jnp.where(
                lastv_b, d_h_ce, _buf_get(cotbuf, bvi, bmi)
            ).astype(h_v.dtype)
            d_p, d_c = stage_vjp((seed_h, jnp.ones((), aux_v.dtype)))
            # chain fwd; on the fused last-virtual tick the vjp primal
            # IS fwd(cur) (tables guarantee (fm,fv)==(bm,bv) there)
            lastv_f = is_last & (fvi == V - 1)
            out_chain = _gated(
                lastv_f,
                lambda _: h_v,
                lambda _: stage_fn(params_f, cur, *sx_f)[0],
                None,
            )

            d_c = jnp.where(valid_b, d_c, 0).astype(jnp.float32)
            d_params = _chunk_add(d_params, bvi, d_p, valid_b)
            d_last = jax.tree.map(
                lambda acc, g: acc + jnp.where(
                    lastv_b & valid_b, g, 0
                ).astype(jnp.float32),
                d_last, d_lp,
            )
            ce_acc = ce_acc + jnp.where(
                lastv_b & valid_b, ce, 0.0
            ).astype(jnp.float32)
            aux_acc = aux_acc + jnp.where(valid_b, aux_v, 0.0).astype(
                jnp.float32
            )
            d_x = jnp.where(
                valid_b & (stage == 0) & (bvi == 0),
                jax.lax.dynamic_update_index_in_dim(
                    d_x, d_c, bmi, 0
                ),
                d_x,
            )

            fwd_msg = jax.lax.ppermute(
                out_chain, AXIS,
                [(i, (i + 1) % S) for i in range(S)],
            )
            d_c, fwd_msg = _opt_barrier((d_c, fwd_msg))
            bwd_msg = jax.lax.ppermute(
                d_c, AXIS, [(i, (i - 1) % S) for i in range(S)]
            )
            return (fwd_msg, bwd_msg, inbuf, cotbuf, d_params, d_last,
                    d_x, ce_acc, aux_acc), None

        (_, _, _, _, d_params, d_last, d_x, ce_acc, aux_acc), _ = (
            jax.lax.scan(tick, carry0, jnp.arange(T))
        )
        reduce_leaves, reduce_def = jax.tree.flatten(
            (ce_acc, aux_acc, d_last, d_x)
        )
        sizes = [leaf.size for leaf in reduce_leaves]
        flat = jnp.concatenate([leaf.ravel() for leaf in reduce_leaves])
        flat = jax.lax.psum(flat, AXIS)
        parts, off = [], 0
        for leaf, size in zip(reduce_leaves, sizes):
            parts.append(flat[off:off + size].reshape(leaf.shape))
            off += size
        ce_acc, aux_acc, d_last, d_x = jax.tree.unflatten(
            reduce_def, parts
        )
        loss = (ce_acc + aux_acc) / M
        d_params = jax.tree.map(
            lambda g, p: (g / M).astype(p.dtype), d_params, params_local
        )
        d_last = jax.tree.map(
            lambda g, p: (g / M).astype(p.dtype), d_last, last_params_
        )
        d_x = (d_x / M).astype(x_mb_.dtype)
        return loss, d_params, d_last, d_x

    def run_schedule(sp, lp, x_, sx, lx):
        from jax.sharding import NamedSharding

        lp = jax.tree.map(
            lambda a: jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, P())
            ),
            lp,
        )
        return jax.shard_map(
            schedule,
            mesh=mesh,
            in_specs=(
                jax.tree.map(lambda _: P(AXIS), sp),
                jax.tree.map(lambda _: P(), lp),
                P(),
                jax.tree.map(lambda _: P(), sx),
                jax.tree.map(lambda _: P(), lx),
            ),
            out_specs=(
                P(),
                jax.tree.map(lambda _: P(AXIS), sp),
                jax.tree.map(lambda _: P(), lp),
                P(),
            ),
            axis_names={AXIS},
            check_vma=False,
        )(sp, lp, x_, sx, lx)

    def _eval_primal(sp, lp, x_, sx, lx):
        """V GPipe ring passes in virtual-stage order (chunk v of every
        stage before chunk v+1) — the forward the fused schedule's
        gradients correspond to."""
        h = x_.reshape((-1,) + x_.shape[2:])
        sx_flat = tuple(e.reshape((-1,) + e.shape[2:]) for e in sx)
        aux_total = 0.0
        for v in range(V):
            def chunk_v(a, v=v):
                lc = a.shape[0] // (S * V)
                return a.reshape((S, V, lc) + a.shape[1:])[:, v].reshape(
                    (S * lc,) + a.shape[1:]
                )
            sp_v = jax.tree.map(chunk_v, sp)
            h, aux = pipeline_apply(
                stage_fn, sp_v, h, *sx_flat,
                n_microbatches=M, mesh=mesh,
            )
            aux_total = aux_total + aux
        h = h.reshape(x_.shape)
        ce = 0.0
        for m in range(M):
            ce = ce + last_fn(lp, h[m], *(e[m] for e in lx))
        return ce / M + aux_total

    def _zero_cotangent(a):
        if jnp.issubdtype(a.dtype, jnp.inexact):
            return jnp.zeros_like(a)
        return np.zeros(a.shape, jax.dtypes.float0)

    @jax.custom_vjp
    def _loss(sp, lp, x_, sx, lx):
        return _eval_primal(sp, lp, x_, sx, lx)

    def _loss_fwd(sp, lp, x_, sx, lx):
        out, d_sp, d_lp, d_x = run_schedule(sp, lp, x_, sx, lx)
        return out, (d_sp, d_lp, d_x, sx, lx)

    def _loss_bwd(res, ct):
        d_sp, d_lp, d_x, sx, lx = res

        def scale(tree):
            return jax.tree.map(
                lambda g: (ct * g.astype(jnp.float32)).astype(g.dtype),
                tree,
            )

        return (
            scale(d_sp),
            scale(d_lp),
            scale(d_x),
            jax.tree.map(_zero_cotangent, sx),
            jax.tree.map(_zero_cotangent, lx),
        )

    _loss.defvjp(_loss_fwd, _loss_bwd)
    return _loss(stage_params, last_params, x_mb, sx_mb, lx_mb)


def _interleaved_tables(S: int, V: int, M: int):
    """Build the interleaved-1F1B tick tables by event simulation.

    Device ``s`` owns chunks ``v*S + s`` (Megatron layout, reference
    pipeline_parallel_optimization.py:98 Interleaved1F1B). Units follow
    the standard order (groups of S microbatches per chunk round); the
    simulation advances tick by tick with 1-tick message latency and
    the fused last-virtual-stage rule (its bwd runs in the same tick as
    its fwd — the vjp serves both), recording for every (tick, device):

      fm/fv: fwd unit (microbatch, chunk) or -1 (bubble)
      bm/bv: bwd unit or -1
      rfm/rfv: routing of the INCOMING fwd message (what the ring
               predecessor sent last tick; -1 = ignore)
      rbm/rbv: routing of the incoming cotangent message

    Returns (tables dict of int32 [T, S] arrays, T, R) where R is the
    smallest per-chunk ring-buffer depth with no live-slot collision.
    """
    if M % S != 0:
        raise ValueError(
            f"interleaved 1F1B needs microbatches ({M}) divisible by "
            f"pipe size ({S})"
        )
    total = M * V

    def unit(k: int, forward: bool):
        v = (k // S) % V
        if not forward:
            v = V - 1 - v
        m = (k // (S * V)) * S + k % S
        return m, v

    warmup = [
        min(total, (S - s - 1) * 2 + (V - 1) * S) for s in range(S)
    ]

    # per-device progress
    fidx = [0] * S
    bidx = [0] * S
    # fwd inputs available: (m, v) -> earliest tick usable
    avail_f = [dict() for _ in range(S)]
    avail_b = [dict() for _ in range(S)]
    for m in range(M):
        avail_f[0][(m, 0)] = 0  # injected from x_mb
    # in-flight messages: (arrive_tick, dest, kind, m, v)
    msgs = []
    rows = {k: [] for k in
            ("fm", "fv", "bm", "bv", "rfm", "rfv", "rbm", "rbv")}
    live = [set() for _ in range(S)]    # (m, v) saved inputs in use
    max_live = [dict() for _ in range(S)]  # v -> peak concurrent m set
    live_by_chunk = [
        {v: set() for v in range(V)} for _ in range(S)
    ]
    peak = 0
    t = 0
    guard = 4 * (total + 2 * S * V) + 64
    while any(b < total for b in bidx):
        if t > guard:
            raise RuntimeError(
                f"interleaved schedule did not converge "
                f"(S={S} V={V} M={M})"
            )
        row = {k: [-1] * S for k in rows}
        # deliveries
        arriving = [m_ for m_ in msgs if m_[0] == t]
        msgs = [m_ for m_ in msgs if m_[0] != t]
        for _, dest, kind, m, v in arriving:
            if kind == "f":
                row["rfm"][dest], row["rfv"][dest] = m, v
                avail_f[dest][(m, v)] = t
            else:
                row["rbm"][dest], row["rbv"][dest] = m, v
                avail_b[dest][(m, v)] = t
        for s in range(S):
            ran_f = ran_b = None
            # Each fused tick runs one fwd unit AND one vjp unit. A fwd
            # runs when its input has arrived AND in-flight microbatch
            # inputs stay within the warmup bound (the 1F1B memory
            # cap: runaway stage-0 fwds would degenerate to GPipe
            # buffering); a bwd runs whenever its cotangent is here.
            if fidx[s] < total:
                m, v = unit(fidx[s], True)
                if avail_f[s].get((m, v), 10 ** 9) <= t and (
                    fidx[s] - bidx[s] <= warmup[s]
                ):
                    ran_f = (m, v)
            if bidx[s] < total:
                m, v = unit(bidx[s], False)
                is_lastv = s == S - 1 and v == V - 1
                if is_lastv:
                    # fused: runs in the same tick as its own fwd (the
                    # one vjp serves both roles, seeded by the head)
                    if ran_f == (m, v):
                        ran_b = (m, v)
                elif avail_b[s].get((m, v), 10 ** 9) <= t:
                    ran_b = (m, v)
            if ran_f is not None:
                m, v = ran_f
                row["fm"][s], row["fv"][s] = m, v
                fidx[s] += 1
                live_by_chunk[s][v].add(m)
                peak = max(peak, max(
                    len(x) for x in live_by_chunk[s].values()
                ))
                # message to the next virtual stage
                if not (s == S - 1 and v == V - 1):
                    dest = (s + 1) % S
                    nv = v if s < S - 1 else v + 1
                    msgs.append((t + 1, dest, "f", m, nv))
            if ran_b is not None:
                m, v = ran_b
                row["bm"][s], row["bv"][s] = m, v
                bidx[s] += 1
                live_by_chunk[s][v].discard(m)
                if not (s == 0 and v == 0):
                    dest = (s - 1) % S
                    nv = v if s > 0 else v - 1
                    msgs.append((t + 1, dest, "b", m, nv))
        for k in rows:
            rows[k].append(row[k])
        t += 1

    T = t
    tables = {
        k: np.asarray(rows[k], dtype=np.int32) for k in rows
    }
    # ring depth: smallest R where concurrently-live microbatches of a
    # chunk never collide mod R in EITHER mailbox (validated by replay:
    # inbuf saved-input slots AND cotbuf cotangent slots — a collision
    # in either silently corrupts gradients in the table machine)
    R = max(peak, 1)
    while R <= M:
        ok = True
        live_slots = [
            {v: {} for v in range(V)} for _ in range(S)
        ]
        cot_slots = [
            {v: {} for v in range(V)} for _ in range(S)
        ]
        for tt in range(T):
            for s in range(S):
                # cotangent mailbox: the delivery (_buf_set step 1)
                # lands BEFORE this tick's bwd read (step 3), so a
                # differing occupant is corruption even when the
                # occupant is consumed later this same tick
                rbm, rbv = tables["rbm"][tt][s], tables["rbv"][tt][s]
                if rbm >= 0:
                    slot = rbm % R
                    if cot_slots[s][rbv].get(slot, rbm) != rbm:
                        ok = False
                    cot_slots[s][rbv][slot] = rbm
                rfm, rfv = tables["rfm"][tt][s], tables["rfv"][tt][s]
                if rfm >= 0:
                    slot = rfm % R
                    if live_slots[s][rfv].get(slot, rfm) != rfm:
                        ok = False
                    live_slots[s][rfv][slot] = rfm
                fm, fv = tables["fm"][tt][s], tables["fv"][tt][s]
                if fm >= 0:
                    slot = fm % R
                    if live_slots[s][fv].get(slot, fm) != fm:
                        ok = False
                    live_slots[s][fv][slot] = fm
                # bwd reads (step 3) come AFTER this tick's deliveries
                # and the fwd saved-input write — pop only after every
                # write was collision-checked against the live occupant
                bm, bv = tables["bm"][tt][s], tables["bv"][tt][s]
                if bm >= 0:
                    live_slots[s][bv].pop(bm % R, None)
                    cot_slots[s][bv].pop(bm % R, None)
            if not ok:
                break
        if ok:
            break
        R += 1
    return tables, T, R


def policy_or_names(policy, names):
    """OR a remat save policy with a ``save_only_these_names`` policy,
    respecting offload policies' non-boolean verdicts: an Offloadable
    marker (has ``.dst``) must win, and the truthy Recompute sentinel
    must NOT read as a save — ``save_from_both_policies`` can merge
    neither, which is why this is hand-rolled (single home for the
    sentinel contract; models compose their own name policies with it
    too, e.g. llama's offload+attn_out variant)."""
    def p(prim, *args, **kwargs):
        verdict = policy(prim, *args, **kwargs)
        if verdict is True or hasattr(verdict, "dst"):
            return verdict
        return names(prim, *args, **kwargs)

    return p


def minimal_save_policy(offload: bool = False):
    """What remat level "minimal" keeps from forward to backward: the
    dots without batch dimensions (the weight matmuls), the attention
    kernel's output and row statistics (``o`` and ``lse``, tagged
    "attn_out" in ops/attention.py), the costliest thing a layer could
    recompute, and whatever a layer that keeps its input names beside
    it (``layer_input(keep=...)``, handed over under ``KEPT``).
    ``offload`` sends the dots to pinned host memory and keeps the
    named values in HBM.

    The single home of that set: every checkpoint level of the step
    program reads it (accelerate._remat_wrap round the whole loss,
    stage_layer_scan's default round each layer, llama's "dots_attn" /
    "dots_attn_offload"). A level that leaves the names out drops
    ``o``/``lse`` in the forward pass, and the level inside it then
    runs the forward kernel a second time to have them. The outermost
    level also rules what the first forward pass hands to a
    ``layer_input`` layer's backward pass: that layer decides what it
    reads (its input and its names), this set whether the named values
    are there or made again by running the layer's forward pass."""
    dots = (
        jax.checkpoint_policies.offload_dot_with_no_batch_dims(
            "device", "pinned_host")
        if offload
        else jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    )
    return policy_or_names(
        dots, jax.checkpoint_policies.save_only_these_names("attn_out", KEPT)
    )


def quant_aware_policy(policy):
    """Adapt a remat save policy to the int8 quantized-matmul path.

    Two adjustments, both no-ops for unquantized models:

    1. NEVER save integer dot_generals: the qa @ qb accumulators are
       int32 [*, out]-shaped — the dots_* policies would save them
       stacked per scan layer (measured: 5.5 GB for the gate/up
       accumulator alone at the ``llama2-1b`` preset, the difference between
       fitting HBM and OOM). The backward never consumes the
       accumulator (the custom_vjp residuals are the small int8
       operands), so nothing is recomputed from excluding it.
    2. ALWAYS save tensors named "qdot_out" (the bf16 result of a
       quantized matmul, tagged in ops/quantization.py): the useful
       output is elementwise-scaled from the excluded accumulator, so
       no dots_* policy would save it — without the name the backward
       re-runs every projection's quantize+matmul chain, which costs
       the int8 path its step-time win. Saving it restores exactly the
       bytes the bf16 path's saved dot outputs occupy."""
    merged = policy_or_names(
        policy,
        jax.checkpoint_policies.save_only_these_names(
            "qdot_out", "qdot_res"),
    )

    def p(prim, *args, **params):
        if getattr(prim, "name", "") == "dot_general":
            pe = params.get("preferred_element_type")
            if pe is not None and jnp.issubdtype(pe, jnp.integer):
                return False
        return merged(prim, *args, **params)

    return p


@dataclasses.dataclass(frozen=True)
class layer_input:
    """``stage_layer_scan``'s ``policy`` for a layer that keeps, from
    forward to backward, its input and the values its model names in
    ``keep`` (tagged with ``jax.ad_checkpoint.checkpoint_name`` in the
    layer), and recomputes the rest (:func:`_recomputed_from_inputs`).
    A model states ``keep`` from what it knows of its own layer: a
    value that is costly to compute again and small beside what the
    chip has spare, times the layers."""

    keep: tuple = ()


# a layer that keeps its input and nothing else
LAYER_INPUT = layer_input()
# the name under which a ``layer_input`` layer hands its input and what
# it keeps to the checkpoint that encloses it
KEPT = "layer_kept"


def _handed_over(value):
    return checkpoint_name(value, KEPT)


def _recomputed_from_inputs(body, keep=(), kind=""):
    """``body`` with its forward pass recomputed in the backward pass
    from its inputs, whatever checkpoint encloses it, but for the
    values named in ``keep``, which are kept beside the inputs, so that
    what produced them is not run again. A ``jax.checkpoint`` cannot
    promise that: an enclosing checkpoint's policy
    (accelerate._remat_wrap round the whole loss) rules the first
    forward pass right through it and keeps what *it* names, the weight
    matmuls' outputs. Those are 0.44 GiB a layer at 8192 tokens and
    hidden 2048 with a 4x feed-forward and a 2x state-space mixer: the
    difference between ten such layers fitting one chip and not. The
    residuals of a ``custom_vjp`` are what its forward rule returns:
    here the pullback of the layer under a checkpoint of its own that
    keeps the names, whose leaves are the inputs and the kept values.

    Which level keeps what: this one decides what the backward pass of
    a layer *reads*; the enclosing checkpoint still decides what of it
    the first forward pass *hands over*, and makes the rest again by
    replaying the chain of layers in the backward pass. So the forward
    rule gives what the backward pass reads, the layer's input (the
    scan's carry) and the kept values, the name ``KEPT``, which
    :func:`minimal_save_policy` keeps ("minimal" and "offload"): they
    go from the first forward pass to the layer's backward pass as they
    are, stacked once over the layers, and no layer is replayed to make
    its successor's input (without the name a carry is no residual: the
    enclosing level keeps the weight matmuls' outputs of every layer
    and runs whatever lies between them again, a routed expert's
    grouped matmuls for one; a kept value under a name it does not
    know is stacked a second time by that replay).
    :func:`stage_layer_scan` names the last layer's output likewise,
    for whatever reads it next. Under ``Strategy.remat="full"`` the
    enclosing level keeps nothing and replays the layers' forward pass
    once, as it did before there were names: naming saves nothing
    there.

    The gauge ``model.remat.kept`` (labels ``kind``, ``name``) says, as
    the forward rule's trace finds it, how many bytes a layer keeps
    beside its inputs."""
    kept = jax.checkpoint(
        body, policy=jax.checkpoint_policies.save_only_these_names(*keep))

    @jax.custom_vjp
    def run(*args):
        return body(*args)

    def forward(carry, *rest):
        args = (jax.tree.map(_handed_over, carry), *rest)
        out, pullback = jax.vjp(kept, *args)
        if keep:
            # the pullback's leaves are the inputs, as they came, and
            # the kept values
            inputs = {id(leaf) for leaf in jax.tree.leaves(args)}
            telemetry.gauge_set(
                "model.remat.kept",
                sum(leaf.size * leaf.dtype.itemsize
                    for leaf in jax.tree.leaves(pullback)
                    if id(leaf) not in inputs),
                kind=kind, name="+".join(keep))
            pullback = jax.tree.map(
                lambda leaf: leaf if id(leaf) in inputs
                else _handed_over(leaf), pullback)
        return out, pullback

    def backward(pullback, cotangent):
        # the checkpoint's own barrier (prevent_cse) ties the
        # recomputation to the cotangent's arrival: without one the
        # compiler may merge it with the first forward pass (a run of
        # one layer is no loop) and keep it all
        return pullback(cotangent)

    run.defvjp(forward, backward)
    return run


def stage_layer_scan(
    layer_fn: Callable,
    remat: bool = True,
    policy=None,
    layer_axes=None,
    kind: str = "",
):
    """Build a ``stage_fn`` that scans ``layer_fn`` over this stage's
    local stacked layers (the in-stage analogue of the model's full-depth
    ``lax.scan``), accumulating per-layer aux losses.

    ``layer_fn(h, one_layer_params, *extras) -> (h, aux)``. Whatever
    save policy applies (passed, or :func:`minimal_save_policy` by
    default) is adapted to the int8 quantized path via
    :func:`quant_aware_policy`. ``policy=LAYER_INPUT`` keeps a layer's
    input alone, ``policy=layer_input(keep=names)`` also what the layer
    tags with those names (:func:`_recomputed_from_inputs`; ``kind``
    labels its gauge): the level that decides what a layer's backward
    pass reads, under whatever checkpoint encloses the scan.

    ``layer_axes`` (a pytree matching ONE layer's params whose leaves
    are logical-axis tuples) opts the scan into collective–compute
    overlap when ``overlap_autocast`` is active: the fsdp param gather
    for layer *k+1* is issued while layer *k* computes, double-buffered
    through the scan carry (parallel/overlap.py). Without the axes the
    scan cannot know which dims are fsdp-sharded and runs the plain
    schedule.
    """

    def body(carry, layer_params, *extras):
        h, aux_sum = carry
        out, aux = layer_fn(h, layer_params, *extras)
        return (out, aux_sum + aux), None

    def stage_fn(local_params, h, *extras):
        from dlrover_tpu.ops.fp8 import remat_disabled
        from dlrover_tpu.parallel.overlap import layer_gather_fn

        # the strategy's remat="none" wins over the model config: a
        # no-remat trace must emit no checkpoint at any layer
        do_remat = remat and not remat_disabled()
        from_inputs = isinstance(policy, layer_input)
        # layer_input wraps the layer itself, operands as arguments (a
        # custom_vjp must not close over what is differentiated); any
        # other policy is a jax.checkpoint round the scan's body
        step = (
            _recomputed_from_inputs(body, policy.keep, kind)
            if do_remat and from_inputs else body
        )

        def checkpointed(scan_body):
            if not do_remat or from_inputs:
                return scan_body
            return jax.checkpoint(scan_body, policy=quant_aware_policy(
                policy or minimal_save_policy()
            ))

        def last(h):
            # what reads the last layer's output in the backward pass
            # (the head) finds it kept, not made again from layer 0 on
            if do_remat and from_inputs:
                return jax.tree.map(_handed_over, h)
            return h

        gather = layer_gather_fn(layer_axes)
        if gather is not None:
            L = jax.tree.leaves(local_params)[0].shape[0]

            def fetch(i):
                sl = jax.tree.map(
                    lambda p: jax.lax.dynamic_index_in_dim(
                        p, i, 0, keepdims=False
                    ),
                    local_params,
                )
                return gather(sl)

            def overlap_body(carry, i):
                (h, aux_sum), p_cur = carry
                # issue the NEXT layer's gather before this layer's
                # compute: no data dependency between them, so the
                # scheduler can overlap the collective with the matmuls
                # (the last iteration re-fetches its own layer — the
                # buffer is unused but keeps one compiled body)
                p_next = fetch(jnp.minimum(i + 1, L - 1))
                inner, _ = step((h, aux_sum), p_cur, *extras)
                return (inner, p_next), None

            overlap_body = checkpointed(overlap_body)
            carry0 = (
                (h, jnp.zeros((), jnp.float32)),
                fetch(jnp.int32(0)),
            )
            ((h, aux_sum), _), _ = jax.lax.scan(
                overlap_body, carry0, jnp.arange(L, dtype=jnp.int32)
            )
            return last(h), aux_sum

        def scan_body(carry, layer_params):
            return step(carry, layer_params, *extras)

        scan_body = checkpointed(scan_body)
        (h, aux_sum), _ = jax.lax.scan(
            scan_body, (h, jnp.zeros((), jnp.float32)), local_params
        )
        return last(h), aux_sum

    return stage_fn


def layer_runs(layer_types):
    """A declared pattern of layer kinds, one entry a layer, as its
    runs of like layers in order: ``("mamba",) * 5 + ("attention",) +
    ("mamba",) * 4 -> [("mamba", 5), ("attention", 1), ("mamba", 4)]``.
    A run is what one stacked parameter tree and one scan can hold."""
    return [
        (kind, len(list(group)))
        for kind, group in itertools.groupby(layer_types)
    ]


def stage_run_scan(
    layer_fns: dict,
    runs,
    remat: bool = True,
    policy=None,
    layer_axes=None,
):
    """A ``stage_fn`` over a stack of unlike layers: ``runs`` is
    ``[(name, kind)]`` in the stack's order, ``layer_fns[kind]`` the
    layer body of a kind (``stage_layer_scan``'s contract) and
    ``local_params[name]`` the run's parameters stacked on axis 0. Each
    run goes through :func:`stage_layer_scan` — one compiled body a
    kind and position in the pattern, the same save policy, remat gate
    and overlap hook as a homogeneous stack — and the runs are chained
    in order. ``policy`` and ``layer_axes`` are keyed by kind."""
    stages = {
        kind: stage_layer_scan(
            fn, remat=remat, policy=(policy or {}).get(kind),
            layer_axes=(layer_axes or {}).get(kind), kind=kind,
        )
        for kind, fn in layer_fns.items()
    }

    def stage_fn(local_params, h, *extras):
        aux_sum = jnp.zeros((), jnp.float32)
        for name, kind in runs:
            h, aux = stages[kind](local_params[name], h, *extras)
            aux_sum = aux_sum + aux
        return h, aux_sum

    return stage_fn
