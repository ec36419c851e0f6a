"""State-space (Mamba-2 / SSD) scan and its causal depthwise convolution.

The recurrence of a Mamba-2 layer (Dao & Gu 2024, "Transformers are
SSMs"), one scalar decay a head::

    H_t = exp(dt_t * A) * H_{t-1} + dt_t * x_t B_t^T      H in R^{P x N}
    y_t = H_t C_t + D * x_t

computed in its chunked ("state-space dual") form: the sequence is cut
into chunks of ``chunk`` positions; inside a chunk the output is the
masked product ``((C B^T) o L) (dt x)`` with ``L_ij = exp(cum_i -
cum_j)`` for ``i >= j`` (``cum`` the running sum of ``dt * A`` inside
the chunk); each chunk's end state is summed from its own positions;
the end states are passed from chunk to chunk; and the state that
enters a chunk adds ``exp(cum_i) C_i H_in`` to its outputs. The work is
matmuls over chunk-sized blocks, which is what the MXU wants, where the
recurrence itself is S dependent steps.

In either form of the scan ``dt``, ``A``, the running sums and every
``exp`` stay in float32; the chunk-sized matmul operands take the
activations' dtype (bf16 in a bf16 model); the state that goes from
chunk to chunk is float32, decayed in float32: that pass is a
thousandth of the work and carries the state across the whole sequence.

The scan, :func:`ssd_scan`, and the convolution with its activation,
:func:`causal_conv_silu`, are each one algorithm in two forms, chosen
by what the input shows and by nothing else: a Pallas kernel pair
(``ops/ssd_kernel.py``, ``ops/causal_conv.py``) where its blocks tile
the input and the mesh does not split the sequence, mapped over the
mesh's batch axes; everywhere else (the short sequences and toy widths
of the CPU tests, a ``seq`` mesh axis: neither a halo nor a state
handed across sequence shards is built) the plain form, ``jax.numpy``
differentiated by JAX, which is also the kernels' reference. The scan's
kernels take a chunk and a state size in multiples of 128, a head size
in multiples of 16 and each group's heads in eights; the convolution's
a sequence in multiples of 128, channels and the first of them in
multiples of 16. The gauges ``model.ssd.impl`` and ``model.conv.impl``
say which form was traced.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from dlrover_tpu.common import telemetry
from dlrover_tpu.ops import causal_conv, ssd_kernel


def causal_conv1d(x, weight, bias):
    """Depthwise causal convolution along the sequence.

    ``x`` [B, S, C], ``weight`` [K, C], ``bias`` [C]:
    ``y_t = bias + sum_k weight[k] * x_{t - (K - 1) + k}``, positions
    before the sequence's start reading zero (``torch.nn.Conv1d`` with
    ``groups=C, padding=K - 1``, cut to the first S outputs).
    Accumulates in float32; returns float32."""
    taps, seq = weight.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    weight = weight.astype(jnp.float32)
    out = bias.astype(jnp.float32)
    for k in range(taps):
        out = out + weight[k] * padded[:, k:k + seq]
    return out


def causal_conv_silu(x, weight, bias, first: int = 0):
    """``silu(causal_conv1d(x[..., first:first + C], weight, bias))`` in
    ``x``'s dtype: the Mamba-2 mixer's convolution with its activation,
    over the C channels of ``x`` [B, S, first + C or more] that
    ``weight`` [K, C] has. The module's docstring has the rule that
    picks the kernel pair or the plain form; the gauge
    ``model.conv.impl`` says which was traced."""
    taps, channels = weight.shape
    mesh, batch_axes = _kernel_mesh(
        "model.conv.impl", x.shape[0],
        causal_conv.kernel_takes(x.shape[1], channels, first, taps))
    if batch_axes is None:
        x = x[..., first:first + channels]
        return jax.nn.silu(causal_conv1d(x, weight, bias)).astype(x.dtype)
    run = functools.partial(causal_conv.causal_conv_silu_kernel, first=first)
    return _over_batch(run, mesh, batch_axes, "x..")(x, weight, bias)


def _kernel_mesh(gauge: str, batch: int, tiled: bool,
                 otherwise: str = "plain"):
    """Where a kernel pair may run: ``(mesh, its batch axes)`` if the
    kernels' blocks tile the input (``tiled``), no ``seq`` mesh axis is
    active and the batch divides over the ``data`` / ``fsdp`` axes;
    ``(mesh, None)`` for the plain form. Sets ``gauge`` under the label
    ``impl`` = ``kernel`` or ``otherwise``, the plain form's name: the
    choice is made at trace time, here."""
    from dlrover_tpu.parallel.mesh import get_mesh

    try:
        mesh = get_mesh()
        axes = dict(mesh.shape)
    except RuntimeError:
        mesh, axes = None, {}
    batch_axes = tuple(a for a in ("data", "fsdp") if axes.get(a, 1) > 1)
    kernel = (
        tiled and axes.get("seq", 1) == 1
        and batch % math.prod(axes[a] for a in batch_axes) == 0
    )
    telemetry.gauge_set(gauge, 1, impl="kernel" if kernel else otherwise)
    return mesh, batch_axes if kernel else None


def _over_batch(run, mesh, batch_axes, split: str):
    """``run`` mapped over the mesh's batch axes: ``pallas_call`` does
    not partition itself. ``split`` has a letter an operand, ``x`` for
    one split along its first dimension, as the output is."""
    if not batch_axes:
        return run
    rows = PartitionSpec(batch_axes)
    return jax.shard_map(
        run,
        mesh=mesh,
        in_specs=tuple(
            rows if letter == "x" else PartitionSpec() for letter in split),
        out_specs=rows,
        check_vma=False,
    )


def _decay_between(cum):
    """``cum`` [..., T], the running sum of the steps' log-decays ->
    [..., T, T]: ``exp(cum_i - cum_j)``, the decay from position j to
    position i, for ``i >= j`` and 0 where j is in i's future. The mask
    sits under the ``exp``: above the diagonal the difference is
    positive and would overflow."""
    size = cum.shape[-1]
    diff = cum[..., :, None] - cum[..., None, :]
    lower = jnp.tril(jnp.ones((size, size), bool))
    return jnp.exp(jnp.where(lower, diff, -jnp.inf))


def ssd_scan(x, dt, a, b, c, d, chunk: int):
    """The chunked scan.

    ``x``  [B, S, H, P]  per-head inputs (after convolution and silu)
    ``dt`` [B, S, H]     step sizes, positive (after softplus), float32
    ``a``  [H]           ``-exp(A_log)``, negative, float32
    ``b``, ``c`` [B, S, G, N]  input and output projections of the
                         state, each group shared by H / G heads
    ``d``  [H]           skip weight
    -> ``y`` [B, S, H, P] in ``x``'s dtype.

    The sequence must be a whole number of chunks: a shorter tail would
    need padding that the caller, which knows what a padded position
    means for its loss, has to choose."""
    batch, seq, heads, head = x.shape
    groups, state = b.shape[2], b.shape[3]
    if seq % chunk:
        raise ValueError(
            f"ssd_scan: sequence length {seq} is not a multiple of the "
            f"chunk size {chunk}; pad the sequence or choose a chunk "
            "that divides it"
        )
    if heads % groups:
        raise ValueError(
            f"ssd_scan: {heads} heads do not divide into {groups} groups"
        )
    mesh, batch_axes = _kernel_mesh(
        "model.ssd.impl", batch,
        ssd_kernel.kernel_takes(seq, chunk, heads, groups, head, state))
    with jax.named_scope("ssd_scan"):
        if batch_axes is None:
            return ssd_scan_plain(x, dt, a, b, c, d, chunk)
        run = functools.partial(ssd_kernel.ssd_scan_kernel, chunk=chunk)
        return _over_batch(run, mesh, batch_axes, "xx.xx.")(x, dt, a, b, c, d)


def ssd_scan_plain(x, dt, a, b, c, d, chunk: int):
    """:func:`ssd_scan` in plain ``jax.numpy`` einsums, differentiated
    by JAX: the masked decay and ``C B^T`` are arrays."""
    batch, seq, heads, head = x.shape
    groups, state = b.shape[2], b.shape[3]
    n_chunks, per_group = seq // chunk, heads // groups
    dtype = x.dtype
    f32 = jnp.float32

    dt = dt.astype(f32)
    # [B, S, ...] -> [B, chunks, chunk, G, heads a group, ...]
    xc = x.reshape(batch, n_chunks, chunk, groups, per_group, head)
    dtc = dt.reshape(batch, n_chunks, chunk, groups, per_group)
    bc = b.reshape(batch, n_chunks, chunk, groups, state)
    cc = c.reshape(batch, n_chunks, chunk, groups, state)
    # log-decay of every step, and its running sum inside the chunk
    decay = dtc * a.astype(f32).reshape(groups, per_group)
    decay = decay.transpose(0, 1, 3, 4, 2)          # [B, c, G, h, l]
    cum = jnp.cumsum(decay, axis=-1)
    # dt x: what a position adds to the state, per unit of B. Rounded
    # once to the matmuls' dtype and read twice: kept in float32 for
    # the end states it is twice the bytes and 2.8 ms of a 514 ms
    # step at granite-4.0-h-micro's widths (PERF.md, PR 29)
    xdt = (xc.astype(f32) * dtc[..., None]).astype(dtype)

    # 1. inside a chunk: ((C B^T) o L) (dt x)
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc,
                    preferred_element_type=f32)
    within = _decay_between(cum)                    # [B, c, G, h, l, s]
    mixed = (cb[:, :, :, None] * within).astype(dtype)
    y = jnp.einsum("bcghls,bcsghp->bclghp", mixed, xdt,
                   preferred_element_type=f32)

    # 2. each chunk's own end state: sum_s decay(s -> end) B_s (dt x)_s
    to_end = jnp.exp(cum[..., -1:] - cum)           # [B, c, G, h, s]
    xdt_end = (xdt.astype(f32)
               * to_end.transpose(0, 1, 4, 2, 3)[..., None]).astype(dtype)
    own = jnp.einsum("bcsgn,bcsghp->bcghpn", bc, xdt_end,
                     preferred_element_type=f32)

    # 3. from chunk to chunk: the state that enters chunk z is the
    # sum of the earlier chunks' own end states, each decayed over
    # the whole chunks between (float32, full precision)
    total = cum[..., -1].transpose(0, 2, 3, 1)      # [B, G, h, c]
    carry = _decay_between(
        jnp.pad(jnp.cumsum(total, axis=-1), ((0, 0),) * 3 + ((1, 0),)))
    entering = jnp.einsum(
        "bghzc,bcghpn->bzghpn", carry[..., :-1, 1:], own,
        precision=jax.lax.Precision.HIGHEST,
    )

    # 4. what the entering state adds: exp(cum_l) C_l H_in
    from_start = jnp.exp(cum).transpose(0, 1, 4, 2, 3)  # [B, c, l, G, h]
    y_in = jnp.einsum("bclgn,bcghpn->bclghp", cc, entering.astype(dtype),
                      preferred_element_type=f32)
    y = y + y_in * from_start[..., None]

    y = y.reshape(batch, seq, heads, head)
    y = y + x.astype(f32) * d.astype(f32)[:, None]
    return y.astype(dtype)
