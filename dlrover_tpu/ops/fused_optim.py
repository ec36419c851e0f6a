"""One-pass fused optimizer step (Pallas).

Equivalent capability: the reference's fused CUDA optimizers
(quantization_optimizer.cu applies the whole 8-bit Adam update in one
kernel). The optax tree path dispatches a chain of small ops PER LEAF —
for 8-bit Adam that is four quantization kernels plus the EMA math per
leaf, a dispatch tail measured as pure overhead at headline scale (the
"small-op overhead" half of the MFU gap named in the ROADMAP).

TPU redesign: every leaf is padded to the quantization BLOCK and
concatenated into one flat ``[rows, BLOCK]`` buffer; grad-norm
clipping, the Adam moment update, the parameter update, and (for 8-bit
state) the moment decode/encode all run in ONE ``pallas_call`` over
that buffer — a bounded dispatch count regardless of how many leaves
the model has (``tests/test_hot_loop.py`` counts the ``pallas_call``s
in the update's jaxpr). Because each leaf starts at a
block boundary, the 8-bit blockwise scales are identical to the
per-leaf kernels' and the state stays checkpoint-compatible
(plain pytree of arrays).

Parity contracts (tests/test_hot_loop.py):
- ``bits=32`` is BIT-EXACT against the reference optax chain
  ``clip_by_global_norm? -> scale_by_adam -> add_decayed_weights? ->
  scale(-lr)`` (same expression graph, element-wise).
- ``bits=8`` matches ``optimizers.low_bit.adam8bit`` within its
  documented quantization tolerance (stochastic rounding draws differ:
  one fused uniform field vs per-leaf seeds).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.common.backend import use_interpret
from dlrover_tpu.ops.named import named_pallas_call
from dlrover_tpu.ops.quantization import (
    BLOCK,
    LOG_FLOOR,
    TILE_ROWS,
    _LOG_LEVELS,
    row_spec,
    scale_spec,
)

__all__ = [
    "fused_adamw",
    "FusedAdamState",
    "FusedAdam8bitState",
    "flatten_to_blocks",
    "unflatten_from_blocks",
]

# ---------------------------------------------------------------------------
# flat block layout
# ---------------------------------------------------------------------------


class FlatMeta(NamedTuple):
    treedef: object
    shapes: tuple      # per-leaf shapes
    dtypes: tuple      # per-leaf dtypes
    rows: tuple        # per-leaf row counts (leaf starts at a row edge)
    total_rows: int    # padded to the grid tile


def _leaf_rows(shape) -> int:
    n = 1
    for d in shape:
        n *= d
    return -(-max(n, 1) // BLOCK)


def flatten_meta(tree) -> FlatMeta:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    rows = tuple(_leaf_rows(s) for s in shapes)
    raw = sum(rows)
    tile = min(TILE_ROWS, raw)
    total = -(-raw // tile) * tile
    return FlatMeta(treedef, shapes, dtypes, rows, total)


def flatten_to_blocks(tree, meta: FlatMeta):
    """Pytree -> one f32 ``[total_rows, BLOCK]`` buffer.

    Each leaf is padded to its own whole-row count so quantization
    blocks never straddle leaves (the per-leaf kernels' block layout,
    bit for bit)."""
    leaves = jax.tree_util.tree_leaves(tree)
    parts = []
    for leaf, rows in zip(leaves, meta.rows):
        flat = leaf.reshape(-1).astype(jnp.float32)
        pad = rows * BLOCK - flat.shape[0]
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
        parts.append(flat)
    tail = meta.total_rows - sum(meta.rows)
    if tail:
        parts.append(jnp.zeros((tail * BLOCK,), jnp.float32))
    return jnp.concatenate(parts).reshape(meta.total_rows, BLOCK)


def unflatten_from_blocks(flat, meta: FlatMeta):
    """Inverse of :func:`flatten_to_blocks` (dtype-restoring)."""
    out, row = [], 0
    vec = flat.reshape(-1)
    for shape, dtype, rows in zip(meta.shapes, meta.dtypes, meta.rows):
        n = 1
        for d in shape:
            n *= d
        start = row * BLOCK
        out.append(
            jax.lax.dynamic_slice_in_dim(vec, start, n)
            .reshape(shape).astype(dtype)
        )
        row += rows
    return jax.tree_util.tree_unflatten(meta.treedef, out)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
#
# Scalars ride in one SMEM row: [neg_lr, bc1, bc2, g_norm]. The
# hyperparameters (b1, b2, eps, wd, clip) are compile-time constants
# (functools.partial) — they never change across steps, so baking them
# in avoids SMEM traffic and keeps the expression graph identical to
# the optax chain for the bit-exactness contract.

_LOG_LO = float(jnp.log(jnp.float32(LOG_FLOOR)))
_LOG_STEP = -_LOG_LO / (_LOG_LEVELS - 1)


def _clip_grads(g, sc_ref, clip_norm):
    if clip_norm is None:
        return g
    g_norm = sc_ref[0, 3]
    # optax.clip_by_global_norm: select(norm < max, g, g / norm * max)
    return jnp.where(
        g_norm < clip_norm, g, (g / g_norm) * clip_norm
    )


def _adam_math(g, mu, nu, p, sc_ref, *, b1, b2, eps, wd):
    """The shared Adam expression — optax's op graph, element-wise."""
    mu = (1 - b1) * g + b1 * mu
    nu = (1 - b2) * (g * g) + b2 * nu
    mu_hat = mu / sc_ref[0, 1]
    nu_hat = nu / sc_ref[0, 2]
    upd = mu_hat / (jnp.sqrt(nu_hat) + eps)
    if wd:
        upd = upd + wd * p
    return upd * sc_ref[0, 0], mu, nu


def _fused_adam_kernel(sc_ref, g_ref, mu_ref, nu_ref, p_ref,
                       upd_ref, mu_out, nu_out,
                       *, b1, b2, eps, wd, clip_norm):
    g = _clip_grads(g_ref[:], sc_ref, clip_norm)
    upd, mu, nu = _adam_math(
        g, mu_ref[:], nu_ref[:], p_ref[:], sc_ref,
        b1=b1, b2=b2, eps=eps, wd=wd,
    )
    upd_ref[:] = upd
    mu_out[:] = mu
    nu_out[:] = nu


def _fused_adam8bit_kernel(sc_ref, g_ref, mu_q_ref, mu_s_ref,
                           nu_q_ref, nu_s_ref, p_ref, u_ref,
                           upd_ref, mu_q_out, mu_s_out,
                           nu_q_out, nu_s_out,
                           *, b1, b2, eps, wd, clip_norm):
    g = _clip_grads(g_ref[:], sc_ref, clip_norm)
    # ---- decode the 8-bit moments (low_bit.py dequantize pair) ----
    mu = mu_q_ref[:].astype(jnp.float32) * mu_s_ref[:]
    nq = nu_q_ref[:].astype(jnp.int32)
    # log-codebook decode, analytic form of quantization._log_codebook:
    # index 0 -> exact zero, 1..255 -> geomspace(LOG_FLOOR, 1)
    nu = jnp.where(
        nq == 0,
        0.0,
        jnp.exp(_LOG_LO + (nq - 1).astype(jnp.float32) * _LOG_STEP),
    ) * nu_s_ref[:]
    # ---- EMA + update (low_bit.py update_fn op order) ----
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * g * g
    mu_hat = mu / sc_ref[0, 1]
    nu_hat = nu / sc_ref[0, 2]
    upd = mu_hat / (jnp.sqrt(nu_hat) + eps)
    if wd:
        upd = upd + wd * p_ref[:]
    upd_ref[:] = upd * sc_ref[0, 0]
    # ---- re-encode ----
    # mu: linear absmax int8 with stochastic rounding (floor(x + u))
    absmax = jnp.max(jnp.abs(mu), axis=-1, keepdims=True)
    scale = jnp.where(absmax == 0.0, 1.0, absmax / 127.0)
    q = jnp.floor(mu / scale + u_ref[:])
    mu_q_out[:] = jnp.clip(q, -127, 127).astype(jnp.int8)
    mu_s_out[:] = scale
    # nu: non-negative log codebook (quantize_pos_log)
    vmax = jnp.max(nu, axis=-1, keepdims=True)
    vscale = jnp.where(vmax == 0.0, 1.0, vmax)
    rel = nu / vscale
    log_rel = jnp.log(jnp.maximum(rel, LOG_FLOOR))
    idx = jnp.clip(
        jnp.round((log_rel - _LOG_LO) / _LOG_STEP) + 1, 1, _LOG_LEVELS
    )
    # through int32: Mosaic lowers no float32 -> uint8 cast
    nu_q_out[:] = jnp.where(rel > 0.0, idx, 0.0).astype(
        jnp.int32).astype(jnp.uint8)
    nu_s_out[:] = vscale.astype(jnp.float32)


def _smem_spec():
    return pl.BlockSpec((1, 4), lambda i: (0, 0),
                        memory_space=pltpu.SMEM)


# ---------------------------------------------------------------------------
# optax-compatible transformations
# ---------------------------------------------------------------------------


class FusedAdamState(NamedTuple):
    count: jnp.ndarray
    mu: jnp.ndarray  # f32 [rows, BLOCK]
    nu: jnp.ndarray  # f32 [rows, BLOCK]


class FusedAdam8bitState(NamedTuple):
    count: jnp.ndarray
    mu_q: jnp.ndarray      # int8 [rows, BLOCK]
    mu_scale: jnp.ndarray  # f32 [rows, 1]
    nu_q: jnp.ndarray      # uint8 [rows, BLOCK]
    nu_scale: jnp.ndarray  # f32 [rows, 1]


def _global_norm(updates):
    # optax.global_norm's exact reduction order: per-leaf sums in leaf
    # order, Python sum, one sqrt — bit-parity with the reference chain
    return jnp.sqrt(sum(
        jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(updates)
    ))


def _scalars(count, count_inc, lr, b1, b2, g_norm):
    if callable(lr):
        # optax.scale_by_schedule evaluates at the PRE-increment count
        lr_t = lr(count)
    else:
        lr_t = lr
    bc1 = 1 - b1 ** count_inc
    bc2 = 1 - b2 ** count_inc
    return jnp.stack([
        jnp.asarray(-lr_t, jnp.float32),
        jnp.asarray(bc1, jnp.float32),
        jnp.asarray(bc2, jnp.float32),
        jnp.asarray(g_norm, jnp.float32),
    ]).reshape(1, 4)


def fused_adamw(
    learning_rate: float | optax.Schedule = 1e-3,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    clip_norm: Optional[float] = None,
    bits: int = 32,
    interpret: bool | None = None,
) -> optax.GradientTransformation:
    """AdamW with grad-norm clipping as ONE fused pass over the
    flattened leaves.

    ``bits=32`` keeps f32 moments (bit-exact vs the optax chain);
    ``bits=8`` stores them 8-bit (int8 linear mu / log-codebook nu —
    the ``low_bit.adam8bit`` state format, fused). The update applies
    through ``optax.apply_updates`` like any GradientTransformation, so
    ``auto_accelerate`` needs no special casing.
    """
    if bits not in (32, 8):
        raise ValueError(f"bits must be 32 or 8, got {bits}")

    def init_fn(params):
        meta = flatten_meta(params)
        r = meta.total_rows
        if bits == 32:
            return FusedAdamState(
                count=jnp.zeros((), jnp.int32),
                mu=jnp.zeros((r, BLOCK), jnp.float32),
                nu=jnp.zeros((r, BLOCK), jnp.float32),
            )
        return FusedAdam8bitState(
            count=jnp.zeros((), jnp.int32),
            mu_q=jnp.zeros((r, BLOCK), jnp.int8),
            mu_scale=jnp.ones((r, 1), jnp.float32),
            nu_q=jnp.zeros((r, BLOCK), jnp.uint8),
            nu_scale=jnp.ones((r, 1), jnp.float32),
        )

    def update_fn(updates, state, params=None):
        if weight_decay and params is None:
            raise ValueError(optax.base.NO_PARAMS_MSG)
        ipret = use_interpret() if interpret is None else interpret
        meta = flatten_meta(updates)
        r = meta.total_rows
        tile = min(TILE_ROWS, r)
        grid = (r // tile,)
        count_inc = optax.safe_int32_increment(state.count)
        g_norm = (
            _global_norm(updates) if clip_norm is not None
            else jnp.zeros((), jnp.float32)
        )
        sc = _scalars(
            state.count, count_inc, learning_rate, b1, b2, g_norm
        )
        g = flatten_to_blocks(updates, meta)
        if weight_decay:
            p = flatten_to_blocks(params, meta)
        else:
            # placeholder keeps one kernel signature; wd=0 never reads it
            p = g
        fbuf = functools.partial(
            jax.ShapeDtypeStruct, (r, BLOCK)
        )
        sbuf = functools.partial(jax.ShapeDtypeStruct, (r, 1))
        if bits == 32:
            upd, mu, nu = named_pallas_call(
                "fused_adamw",
                functools.partial(
                    _fused_adam_kernel, b1=b1, b2=b2, eps=eps,
                    wd=weight_decay, clip_norm=clip_norm,
                ),
                grid=grid,
                in_specs=[_smem_spec()] + [row_spec(tile)] * 4,
                out_specs=(row_spec(tile),) * 3,
                out_shape=(
                    fbuf(jnp.float32), fbuf(jnp.float32),
                    fbuf(jnp.float32),
                ),
                interpret=ipret,
            )(sc, g, state.mu, state.nu, p)
            new_state = FusedAdamState(count=count_inc, mu=mu, nu=nu)
        else:
            # fresh uniform field per step: stochastic rounding stays
            # unbiased across steps (the fused analogue of the per-leaf
            # per-step seeds)
            u = jax.random.uniform(
                jax.random.fold_in(jax.random.key(0), count_inc),
                (r, BLOCK), jnp.float32,
            )
            upd, mu_q, mu_s, nu_q, nu_s = named_pallas_call(
                "fused_adamw_8bit",
                functools.partial(
                    _fused_adam8bit_kernel, b1=b1, b2=b2, eps=eps,
                    wd=weight_decay, clip_norm=clip_norm,
                ),
                grid=grid,
                in_specs=[
                    _smem_spec(),
                    row_spec(tile),    # g
                    row_spec(tile),    # mu_q
                    scale_spec(tile),  # mu_scale
                    row_spec(tile),    # nu_q
                    scale_spec(tile),  # nu_scale
                    row_spec(tile),    # p
                    row_spec(tile),    # u
                ],
                out_specs=(
                    row_spec(tile), row_spec(tile), scale_spec(tile),
                    row_spec(tile), scale_spec(tile),
                ),
                out_shape=(
                    fbuf(jnp.float32),
                    fbuf(jnp.int8), sbuf(jnp.float32),
                    fbuf(jnp.uint8), sbuf(jnp.float32),
                ),
                interpret=ipret,
            )(sc, g, state.mu_q, state.mu_scale, state.nu_q,
              state.nu_scale, p, u)
            new_state = FusedAdam8bitState(
                count=count_inc, mu_q=mu_q, mu_scale=mu_s,
                nu_q=nu_q, nu_scale=nu_s,
            )
        return unflatten_from_blocks(upd, meta), new_state

    return optax.GradientTransformation(init_fn, update_fn)
