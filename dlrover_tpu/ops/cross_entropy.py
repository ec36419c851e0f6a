"""Fused softmax cross-entropy, plus the vocab-parallel variant.

Equivalent capability: reference fused cross-entropy
(atorch/atorch/modules/transformer/cross_entropy.py) and the TP
cross-entropy (modules/distributed_modules/cross_entropy.py) which
computes the softmax over a vocab-sharded logits tensor with allreduces.
TPU redesign: the fused form is a logsumexp-minus-gather that XLA fuses
into the projection matmul's epilogue; the vocab-parallel form runs inside
``shard_map`` over the ``tensor`` axis using two psums (max and sumexp) so
the full logits row never materialises on one device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec


def softmax_cross_entropy(logits, labels, ignore_index: int = -100):
    """Token-level CE. logits [..., V] float, labels [...] int.

    Returns (per-token loss [...], valid mask [...]). Loss is 0 where
    ignored; caller averages by mask sum.
    """
    logits = logits.astype(jnp.float32)
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, safe_labels[..., None], axis=-1
    )[..., 0]
    loss = jnp.where(valid, lse - picked, 0.0)
    return loss, valid


def vocab_parallel_cross_entropy(
    logits_shard, labels, axis_name: str = "tensor", ignore_index: int = -100
):
    """CE over logits sharded on the vocab dim along ``axis_name``.

    Must be called inside shard_map/jit with ``axis_name`` in scope.
    logits_shard [..., V/n]; labels are *global* vocab ids.
    """
    logits_shard = logits_shard.astype(jnp.float32)
    shard_v = logits_shard.shape[-1]
    shard_idx = jax.lax.axis_index(axis_name)
    vocab_start = shard_idx * shard_v

    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    local = safe_labels - vocab_start
    in_shard = (local >= 0) & (local < shard_v)
    local_clamped = jnp.clip(local, 0, shard_v - 1)

    local_max = jnp.max(logits_shard, axis=-1)
    global_max = jax.lax.pmax(local_max, axis_name)
    sumexp = jnp.sum(
        jnp.exp(logits_shard - global_max[..., None]), axis=-1
    )
    global_sumexp = jax.lax.psum(sumexp, axis_name)
    lse = global_max + jnp.log(global_sumexp)

    picked_local = jnp.take_along_axis(
        logits_shard, local_clamped[..., None], axis=-1
    )[..., 0]
    picked = jax.lax.psum(
        jnp.where(in_shard, picked_local, 0.0), axis_name
    )
    loss = jnp.where(valid, lse - picked, 0.0)
    return loss, valid


def _rms(x, scale, eps):
    """RMSNorm, expression-identical to models/llama.py _rms_norm (the
    chunked CE recomputes the model's final norm chunk by chunk)."""
    var = jnp.mean(
        jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True
    )
    normed = x * jax.lax.rsqrt(var + eps).astype(x.dtype)
    return normed * scale.astype(x.dtype)


def _ce_chunks(h, labels, n: int):
    B, S, D = h.shape
    hc = h.reshape(B, n, S // n, D).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n, S // n).transpose(1, 0, 2)
    return hc, lc


def _chunk_loss_fn(cfg):
    n, ignore_index, eps, use_norm = cfg

    def chunk_loss(h_c, w, norm_scale, lab_c):
        x = _rms(h_c, norm_scale, eps) if use_norm else h_c
        logits = (x @ w).astype(jnp.float32)
        loss, _valid = softmax_cross_entropy(
            logits, lab_c, ignore_index=ignore_index
        )
        return loss.sum()

    return chunk_loss


def _chunked_ce_fwd_scan(cfg, h, w, norm_scale, labels, pinned=False):
    n, ignore_index, eps, use_norm = cfg
    hc, lc = _ce_chunks(h, labels, n)

    def body(carry, inp):
        h_c, lab_c = inp
        x = _rms(h_c, norm_scale, eps) if use_norm else h_c
        logits = x @ w
        if pinned:
            # one rounding of the logits that every reader sees: in the
            # per-chip form the TPU compiler gave the label's pick an
            # f32 copy made before the bf16 rounding, and the sum the
            # rounded one; near a memorised batch that moved the loss
            # by about its own size (PERF.md, PR 40)
            logits = jax.lax.optimization_barrier(logits)
        logits = logits.astype(jnp.float32)
        loss, valid = softmax_cross_entropy(
            logits, lab_c, ignore_index=ignore_index
        )
        ls, vs = carry
        return (ls + loss.sum(), vs + valid.sum()), None

    (loss_sum, valid_sum), _ = jax.lax.scan(
        body,
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (hc, lc),
    )
    return loss_sum, valid_sum


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _chunked_ce(cfg, pinned, h, w, norm_scale, labels):
    return _chunked_ce_fwd_scan(cfg, h, w, norm_scale, labels, pinned)


def _chunked_ce_fwd(cfg, pinned, h, w, norm_scale, labels):
    out = _chunked_ce_fwd_scan(cfg, h, w, norm_scale, labels, pinned)
    # residuals are the INPUTS only — exactly what the old
    # nothing-saveable jax.checkpoint kept, minus its custom-call
    return out, (h, w, norm_scale, labels)


def _chunk_grads(cfg, h, w, norm_scale, labels):
    """``(dh, dw, dns)`` of the loss sum, the chunks' ``dw`` and ``dns``
    summed into accumulators of ``w``'s and ``norm_scale``'s dtype."""
    n, _ignore_index, _eps, _use_norm = cfg
    hc, lc = _ce_chunks(h, labels, n)
    grad_fn = jax.grad(_chunk_loss_fn(cfg), argnums=(0, 1, 2))

    def body(carry, inp):
        dw_acc, dns_acc = carry
        h_c, lab_c = inp
        # recompute this chunk's logits and differentiate just it: one
        # [B, S/n, V] logits buffer lives at a time, same peak memory
        # as the forward
        dh_c, dw_c, dns_c = grad_fn(h_c, w, norm_scale, lab_c)
        return (dw_acc + dw_c, dns_acc + dns_c), dh_c

    (dw, dns), dh_chunks = jax.lax.scan(
        body,
        (jnp.zeros_like(w), jnp.zeros_like(norm_scale)),
        (hc, lc),
    )
    dh = dh_chunks.transpose(1, 0, 2, 3).reshape(h.shape)
    return dh, dw, dns


def _scaled(g_loss, grads):
    """``grads`` times the loss sum's cotangent, each in its dtype."""
    g = g_loss.astype(jnp.float32)
    return tuple(x * g.astype(x.dtype) for x in grads)


def _no_cotangent(labels):
    # integer input: cotangent must be float0 (custom_vjp contract)
    return np.zeros(labels.shape, dtype=jax.dtypes.float0)


def _chunked_ce_bwd(cfg, pinned, res, cts):
    h, w, norm_scale, labels = res
    g_loss, _g_valid = cts  # valid_sum is integer: float0 cotangent
    grads = _chunk_grads(cfg, h, w, norm_scale, labels)
    return (*_scaled(g_loss, grads), _no_cotangent(labels))


_chunked_ce.defvjp(_chunked_ce_fwd, _chunked_ce_bwd)


# The per-chip form: the loop runs on each chip over its own rows, and
# the matrix [D, V] enters as its shard, rows over ``fsdp``. Both maps
# live inside the custom VJP's rules, so nothing transposes them and
# their ``psum``s are sums, not the ``psum`` a map's transpose makes of
# one: ``check_vma`` can stay off, as the scans' carries need.
_W_ROWS = PartitionSpec("fsdp")


def _per_chip(mesh, local, in_specs, out_specs):
    return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _gathered(w_shard):
    return jax.lax.all_gather(w_shard, "fsdp", axis=0, tiled=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _chunked_ce_per_chip(cfg, split, h, w, norm_scale, labels):
    return _chunked_ce_per_chip_fwd(cfg, split, h, w, norm_scale, labels)[0]


def _chunked_ce_per_chip_fwd(cfg, split, h, w, norm_scale, labels):
    mesh, batch_axes = split
    rows = PartitionSpec(batch_axes)

    def local(h, w, norm_scale, labels):
        out = _chunked_ce_fwd_scan(cfg, h, _gathered(w), norm_scale, labels,
                                   pinned=True)
        return tuple(jax.lax.psum(x, batch_axes) for x in out)

    out = _per_chip(mesh, local, (rows, _W_ROWS, PartitionSpec(), rows),
                    PartitionSpec())(h, w, norm_scale, labels)
    # the residual is the shard: the gathered matrix is not kept through
    # the layers' backward pass, where the step's peak lies
    return out, (h, w, norm_scale, labels)


def _chunked_ce_per_chip_bwd(cfg, split, res, cts):
    mesh, batch_axes = split
    rows = PartitionSpec(batch_axes)
    h, w, norm_scale, labels = res
    others = tuple(a for a in batch_axes if a != "fsdp")

    def local(h, w, norm_scale, labels, g_loss):
        dh, dw, dns = _chunk_grads(cfg, h, _gathered(w), norm_scale, labels)
        # the chunks' partial sums meet once, after the last chunk
        dw = jax.lax.psum_scatter(dw, "fsdp", scatter_dimension=0,
                                  tiled=True)
        if others:
            dw = jax.lax.psum(dw, others)
        return _scaled(g_loss, (dh, dw, jax.lax.psum(dns, batch_axes)))

    grads = _per_chip(
        mesh, local,
        (rows, _W_ROWS, PartitionSpec(), rows, PartitionSpec()),
        (rows, _W_ROWS, PartitionSpec()),
    )(h, w, norm_scale, labels, cts[0])
    return (*grads, _no_cotangent(labels))


_chunked_ce_per_chip.defvjp(_chunked_ce_per_chip_fwd, _chunked_ce_per_chip_bwd)


def fused_linear_cross_entropy(
    h, w, labels, n_chunks: int = 8, norm_fn=None,
    ignore_index: int = -100, norm_scale=None, norm_eps: float = 1e-5,
    pinned: bool = False,
):
    """CE of ``softmax(norm(h) @ w)`` without materialising the full
    [B, S, V] logits.

    The sequence is processed in chunks with a hand-written VJP: the
    forward holds one [B, S/n, V] logits chunk at a time and the
    backward RECOMPUTES each chunk's logits instead of storing them —
    peak logits memory drops by n_chunks at the cost of one extra head
    matmul pass. At 32k vocab this is what makes large per-device
    batches HBM-feasible (fp32 logits + their cotangent otherwise cost
    ~8 bytes * B * S * V). Equivalent capability: the reference gets
    this from fused CUDA CE losses.

    The recompute used to ride ``jax.checkpoint`` — whose lowering left
    a ``checkpoint`` custom-call in the compiled step (25.7 ms/step on
    the remat=none headline arm of a pre-PR-1 chip run, its #3 op).
    The ``custom_vjp`` form expresses
    the identical recompute schedule with zero remat machinery, so a
    remat="none" step is now genuinely checkpoint-free
    (``tests/test_remat_gate.py::TestNoRematGate`` pins it on the
    jaxpr).

    ``norm_scale``/``norm_eps``: fuse the model's final RMSNorm into
    each chunk (the production path — models/llama.py). ``norm_fn``
    (an arbitrary closure) is the legacy generic hook; it cannot ride
    the custom VJP (closure tracers) and keeps the old
    ``jax.checkpoint`` scan, checkpoint custom-call included.

    **Where the matrix crosses the chips.** Where ``w`` is sharded over
    ``fsdp`` alone (``_head_mesh``; gauge ``model.head.impl``) the loop
    runs per chip over its own rows, ``per_chip``: each rule gathers
    the shard once before its loop (two gathers a step), the backward
    sums the chunks' gradients on the chip and reduce-scatters them
    once after the last chunk, and the residual stays the shard. In
    the ``plain`` form, on one chip, nothing crosses; left to the
    partitioner over a sharded ``w`` it gathered the matrix inside
    every chunk of both loops and all-reduced each chunk's gradient
    (``olmo-hybrid-7b.steady``: 24 crossings of 771 MB a step, 175 ms
    of 1285; PERF.md, PR 40).

    ``pinned``: the plain form's forward pins each chunk's bf16 logits
    as the per-chip form always does, so that the label's pick and the
    log-sum-exp read one rounding (a model whose cell's step program
    is held to a fingerprint from before the pin leaves it off).

    Returns ``(loss_sum, valid_count)`` over all tokens.
    """
    if norm_fn is not None and norm_scale is not None:
        raise ValueError("pass norm_fn OR norm_scale, not both")
    B, S, D = h.shape
    n = max(1, min(int(n_chunks), S))
    # pad to a chunk multiple rather than silently collapsing to n=1
    # (the common S = seq_len - 1 is odd): padded rows carry
    # ignore_index labels, so they contribute zero loss and zero valid
    pad = (-S) % n
    if pad:
        h = jnp.concatenate(
            [h, jnp.zeros((B, pad, D), h.dtype)], axis=1
        )
        labels = jnp.concatenate(
            [labels, jnp.full((B, pad), ignore_index, labels.dtype)],
            axis=1,
        )
        S += pad

    if norm_fn is not None:
        hc, lc = _ce_chunks(h, labels, n)

        def body(carry, inp):
            h_c, lab_c = inp
            logits = (norm_fn(h_c) @ w).astype(jnp.float32)
            loss, valid = softmax_cross_entropy(
                logits, lab_c, ignore_index=ignore_index
            )
            ls, vs = carry
            return (ls + loss.sum(), vs + valid.sum()), None

        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable
        )
        (loss_sum, valid_sum), _ = jax.lax.scan(
            body,
            (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
            (hc, lc),
        )
        return loss_sum, valid_sum

    use_norm = norm_scale is not None
    if not use_norm:
        # zero-size placeholder: the custom_vjp signature is fixed and
        # the kernel never reads it when use_norm is False
        norm_scale = jnp.zeros((0,), h.dtype)
    cfg = (n, int(ignore_index), float(norm_eps), use_norm)
    split = _head_mesh(B)
    if split[1] is None:
        return _chunked_ce(cfg, pinned, h, w, norm_scale, labels)
    return _chunked_ce_per_chip(cfg, split, h, w, norm_scale, labels)


def _head_mesh(batch: int):
    """``(mesh, batch axes)`` where the per-chip form runs: the matrix
    is sharded over ``fsdp`` alone (no ``tensor``, ``seq`` or ``pipe``
    axis, no manual axis) and the batch divides over ``data`` /
    ``fsdp``; ``(mesh, None)`` for the plain form. Sets the gauge
    ``model.head.impl``, label ``impl`` ``per_chip`` or ``plain``."""
    from dlrover_tpu.ops.ssd import _kernel_mesh
    from dlrover_tpu.parallel.overlap import gather_mesh

    mesh = gather_mesh()
    amesh = jax.sharding.get_abstract_mesh()
    sharded = (mesh is not None and mesh.shape.get("tensor", 1) == 1
               and (amesh.empty or not amesh.manual_axes))
    return _kernel_mesh("model.head.impl", batch, sharded, chosen="per_chip")
