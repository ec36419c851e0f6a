"""Mixture-of-Experts: the router and two forms of the routed layer.

Equivalent capability: the reference's MOELayer
(atorch/atorch/modules/moe/moe_layer.py:161) with its explicit ``_AllToAll``
autograd function (:87), expert process groups (:29) and top-k/switch
gating (topk_gating.py, switch_gating.py). TPU redesign — the GShard
einsum formulation instead of a translated all-to-all:

- tokens live in groups ``[G, T, D]`` (G = the data-sharded batch rows);
- :func:`top_k_gating` builds one-hot dispatch and weighted combine
  tensors ``[G, T, E, C]`` with per-expert capacity C, slot-major
  priority (every token's 1st choice beats any token's 2nd choice) and
  the Switch/GShard load-balancing auxiliary loss + router z-loss;
- :func:`moe_ffn` dispatches with one einsum to ``[E, G, C, D]``, runs
  the stacked expert FFN (a single batched matmul on the MXU — E is a
  leading einsum dim) and combines back. E carries the logical axis
  ``expert``, so on an ``expert`` mesh axis GSPMD makes the two einsums
  all-to-alls; the one-hot tensors keep this form at toy sizes.

Everything is differentiable jnp; no process groups, no custom autograd.

:func:`route` is the router of the routed layer: a token's top-k of
the scores over all the model's experts, softmax or sigmoid (with a
correction bias that takes part in the choice and not in the weights),
the weights normalised over the k and scaled.

:func:`routed_experts` is the other form: no capacity and no dropped
token. A token's ``k`` choices are ``k`` token-expert pairs; the pairs
are sorted by their expert, the experts run as grouped matmuls over
groups of unequal size, and the results go back to the pairs' places
times the router's weight, a token's pairs summed into its row. The
layer is told which experts of the model's it holds (``held = (first,
count)``): it takes choices over all of them and computes its own
experts' part of the result. The exchange over an ``expert`` mesh axis
is not here yet, and nothing stands in for it: an ``expert`` axis
larger than 1 is refused.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.parallel.sharding import shard_logical

__all__ = ["MoEConfig", "top_k_gating", "moe_ffn", "moe_init", "route",
           "routed_experts", "routed_counts", "COUNTS"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25

    def capacity(self, tokens_per_group: int) -> int:
        c = int(self.capacity_factor * self.top_k * tokens_per_group
                / self.n_experts)
        return max(c, self.top_k)


def top_k_gating(logits, config: MoEConfig):
    """Top-k routing with capacity. logits: [G, T, E] fp32.

    Returns (dispatch [G,T,E,C] bool-ish float, combine [G,T,E,C] float,
    aux_metrics dict with ``aux_loss`` and ``z_loss``).
    """
    g, t, e = logits.shape
    c = config.capacity(t)
    k = config.top_k
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    gate_vals, gate_idx = jax.lax.top_k(probs, k)            # [G,T,k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )
    masks = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)   # [G,T,k,E]

    # slot-major priority: all 1st choices first, then 2nd choices —
    # [G, k*T, E] cumulative position of each (token, slot) in its expert
    mask_flat = masks.transpose(0, 2, 1, 3).reshape(g, k * t, e)
    pos_flat = jnp.cumsum(mask_flat, axis=1) - mask_flat     # pre-count
    pos = pos_flat.reshape(g, k, t, e).transpose(0, 2, 1, 3)  # [G,T,k,E]
    within_cap = (pos < c) * masks                           # [G,T,k,E]
    slot_pos = jnp.sum(pos * within_cap, axis=-1)            # [G,T,k]
    slot_exp = within_cap                                    # one-hot E

    cap_onehot = jax.nn.one_hot(
        slot_pos.astype(jnp.int32), c, dtype=jnp.float32
    )                                                        # [G,T,k,C]
    # [G,T,k,E,C] -> sum over slots
    dispatch = jnp.einsum("gtke,gtkc->gtec", slot_exp, cap_onehot)
    combine = jnp.einsum(
        "gtke,gtkc,gtk->gtec", slot_exp, cap_onehot, gate_vals
    )

    # Switch-style load-balancing loss on 1st-choice routing
    me = jnp.mean(probs, axis=(0, 1))                        # [E]
    ce = jnp.mean(masks[:, :, 0, :], axis=(0, 1))            # [E]
    aux_loss = e * jnp.sum(me * ce)
    z = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    z_loss = jnp.mean(z ** 2)
    metrics = {
        "aux_loss": aux_loss,
        "z_loss": z_loss,
        # fraction of (token, slot) routes dropped by capacity
        "dropped": 1.0 - jnp.sum(within_cap) / (g * t * k),
    }
    return dispatch, combine, metrics


def moe_init(rng, n_experts: int, dim: int, mlp_dim: int):
    """Stacked expert weights (llama-style gated FFN) + router."""
    ks = jax.random.split(rng, 4)
    scale = dim ** -0.5
    return {
        "router": jax.random.normal(ks[0], (dim, n_experts)) * scale,
        "w_gate": jax.random.normal(
            ks[1], (n_experts, dim, mlp_dim)) * scale,
        "w_up": jax.random.normal(ks[2], (n_experts, dim, mlp_dim)) * scale,
        "w_down": jax.random.normal(
            ks[3], (n_experts, mlp_dim, dim)) * (mlp_dim ** -0.5),
    }


def moe_ffn(x, params, config: MoEConfig, rules=None):
    """MoE feed-forward. x: [G, T, D] (G = batch rows). Returns
    (y [G,T,D], metrics). Params from :func:`moe_init`; expert weights'
    leading E dim carries the logical axis ``expert`` so under an active
    ``expert`` mesh axis the dispatch/combine einsums become all-to-alls.
    """
    dtype = x.dtype
    logits = jnp.einsum(
        "gtd,de->gte", x, params["router"].astype(dtype)
    )
    dispatch, combine, metrics = top_k_gating(logits, config)
    dispatch = dispatch.astype(dtype)
    combine = combine.astype(dtype)

    # [E, G, C, D]: token shuffling into expert buffers (the all-to-all)
    expert_in = jnp.einsum("gtec,gtd->egcd", dispatch, x)
    expert_in = shard_logical(
        expert_in, ("expert", "batch", None, "embed"), rules
    )
    w_gate = params["w_gate"].astype(dtype)
    w_up = params["w_up"].astype(dtype)
    w_down = params["w_down"].astype(dtype)
    h = jax.nn.silu(jnp.einsum("egcd,edm->egcm", expert_in, w_gate))
    h = h * jnp.einsum("egcd,edm->egcm", expert_in, w_up)
    expert_out = jnp.einsum("egcm,emd->egcd", h, w_down)
    expert_out = shard_logical(
        expert_out, ("expert", "batch", None, "embed"), rules
    )

    y = jnp.einsum("egcd,gtec->gtd", expert_out, combine)
    return y, metrics


# ---------------------------------------------------------------------------
# routed experts without capacity
# ---------------------------------------------------------------------------

# the router's scores: bf16 operands, float32 products and sums (one
# pass on the chip; XLA:CPU takes no bf16 x bf16 -> float32 dot at the
# default precision)
_EXACT = jax.lax.Precision.HIGHEST


def route(y, router, *, scoring: str, top_k: int, scale: float,
          bias=None):
    """(choice [..., k] int32, weight [..., k] float32) of the tokens
    ``y [..., D]`` over all the model's experts, ``router [D, E]``,
    in float32:

    - ``scoring="softmax"``: ``p = softmax(y W_r)``, the top-k of ``p``;
    - ``scoring="sigmoid"``: ``p = sigmoid(y W_r)``, the top-k of ``p +
      bias`` (``bias [E]``, the correction bias of DeepSeek-V3's
      ``noaux_tc`` selection: it takes part in the choice and not in
      the weights, and no gradient reaches it).

    ``weight = scale * p[choice] / sum(p[choice])`` either way."""
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"scoring {scoring!r}: softmax or sigmoid")
    with jax.named_scope("router"):
        scores = jnp.matmul(y, router.astype(y.dtype), precision=_EXACT,
                            preferred_element_type=jnp.float32)
        if scoring == "softmax":
            probs = jax.nn.softmax(scores, axis=-1)
            top, choice = jax.lax.top_k(probs, top_k)
        else:
            probs = jax.nn.sigmoid(scores)
            select = probs if bias is None else probs + jax.lax.stop_gradient(
                bias.astype(jnp.float32))
            choice = jax.lax.top_k(select, top_k)[1]
            top = jnp.take_along_axis(probs, choice, -1)
        weight = top / jnp.sum(top, -1, keepdims=True) * scale
    return choice.astype(jnp.int32), weight


# The grouped matmul: ``jax.lax.ragged_dot``, which XLA:TPU compiles to
# its own kernels; their device events are named ``ragged-dot-*``
# (``ragged-dot-metadata`` the group bookkeeping, ``ragged-dot-none``
# the matmuls: forward, both transposes). Measured against megablox's
# ``gmm`` at 16,384 rows of 2048, 8 of 16 experts (PERF.md, Findings,
# PR 34): this op forward and backward 11.4 ms against 9.9 at gmm's
# best tiling. Kept for being one path on every backend, no tiling to
# choose and none that runs out of VMEM; the 14% are a ``perf_opt``'s.


@jax.custom_vjp
def _permute(x, perm, inverse):
    """``x[perm]`` along axis 0, ``perm`` a permutation and ``inverse``
    its inverse: the cotangent goes back as a gather too, where the
    transpose of a gather is a scatter-add."""
    return jnp.take(x, perm, axis=0)


def _permute_fwd(x, perm, inverse):
    return jnp.take(x, perm, axis=0), (perm, inverse)


def _permute_bwd(res, g):
    perm, inverse = res
    return jnp.take(g, inverse, axis=0), None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pairs(h, order, inverse, k):
    """The rows of the pairs in ``order``: pair ``i`` is token ``i //
    k``'s. The cotangent goes back as a gather to the pairs' own order
    and a sum over a token's ``k``, where the transpose of the gather
    would be a scatter-add."""
    return jnp.take(h, order // k, axis=0)


def _pairs_fwd(h, order, inverse, k):
    return _pairs(h, order, inverse, k), inverse


def _pairs_bwd(k, inverse, g):
    back = jnp.take(g, inverse, axis=0).reshape(-1, k, g.shape[1])
    return jnp.sum(back, axis=1, dtype=jnp.float32).astype(g.dtype), \
        None, None


_pairs.defvjp(_pairs_fwd, _pairs_bwd)


def _routed_rows(h, choice, weight, w_in, w_out, first, k=1):
    """The op on one device's rows: h [T, D]; choice and weight [T k],
    a token's ``k`` pairs side by side (``k == 1``: a pair is a
    token)."""
    n, count = choice.shape[0], w_in.shape[0]
    mid_dim = w_out.shape[1]
    local = choice.astype(jnp.int32) - first
    here = (local >= 0) & (local < count)
    # pairs of absent experts sort last and belong to no group: the
    # grouped matmuls do not visit their rows
    key = jnp.where(here, local, count)
    with jax.named_scope("moe_dispatch"):
        order = jnp.argsort(key, stable=True)
        inverse = jnp.zeros((n,), jnp.int32).at[order].set(
            jnp.arange(n, dtype=jnp.int32))
        sizes = jnp.sum(
            key[:, None] == jnp.arange(count, dtype=jnp.int32), axis=0,
            dtype=jnp.int32)
        held = jnp.arange(n, dtype=jnp.int32) < jnp.sum(sizes)
        # the mask is for the way back: what the matmuls leave in the
        # unvisited rows of a cotangent must not reach ``h``
        rows = jnp.where(
            held[:, None], _permute(h, order, inverse) if k == 1
            else _pairs(h, order, inverse, k), 0)
    with jax.named_scope("moe_experts"):
        gate_up = jax.lax.ragged_dot(
            rows, w_in, sizes, preferred_element_type=h.dtype)
        mid = jax.nn.silu(gate_up[:, :mid_dim]) * gate_up[:, mid_dim:]
        out = jax.lax.ragged_dot(
            mid, w_out, sizes, preferred_element_type=h.dtype)
    with jax.named_scope("moe_combine"):
        scale = jnp.take(weight.astype(jnp.float32), order)
        if k == 1:
            out = jnp.where(
                held[:, None], out.astype(jnp.float32) * scale[:, None], 0
            ).astype(h.dtype)
            return _permute(out, inverse, order)
        # the rows the matmuls did not visit hold whatever their buffer
        # held, NaN among it on the chip: they are masked before the
        # weight multiplies them, or the weight's gradient reads NaN x 0
        # (top-1 keeps the order it had, which its cell's step program
        # is held to)
        out = (jnp.where(held[:, None], out, 0).astype(jnp.float32)
               * scale[:, None]).astype(h.dtype)
        out = _permute(out, inverse, order)
        # a token's k weighted rows, each rounded as a top-1 token's
        # is, summed in float32 into one: no float32 copy of the pairs
        return jnp.sum(out.reshape(-1, k, out.shape[1]), axis=1,
                       dtype=jnp.float32).astype(h.dtype)


def routed_experts(h, choice, weight, experts, held):
    """``sum_j weight_j * FFN_choice_j(h)`` a token over its ``k``
    choices, for the experts held here; a pair whose expert is not held
    adds 0. No pair is dropped: there is no capacity.

    h [B, S, D]; choice [B, S, k] int, the model's experts a token
    chose (or [B, S], one a token); weight of choice's shape, the
    router's; experts ``{"w_in": [count, D, 2 M] (gate | up), "w_out":
    [count, M, D]}``, ``FFN(h) = (silu(h Wg) * (h Wu)) Wd``; ``held =
    (first, count)``: the experts ``first .. first + count - 1`` of the
    model's are the ``count`` stacked here. The router's scoring, the
    normalisation of its weights and any shared expert are the model's.

    Differentiable in ``h``, ``weight`` and the experts. On a mesh
    whose ``data`` / ``fsdp`` axes divide the batch, each device sorts
    and computes its own rows. An ``expert`` mesh axis larger than 1 is
    refused: the exchange of tokens between the chips that share a
    layer is not written, and nothing here stands in for it. The two
    other MoE forms, :func:`moe_ffn` (capacity, dropped tokens) and
    ``rl/generation.py:moe_mixture`` (every expert run and masked), are
    still to fold into this one."""
    from dlrover_tpu.parallel.mesh import get_mesh
    from dlrover_tpu.parallel.sharding import logical_to_mesh_axes

    first, count = held
    w_in, w_out = experts["w_in"], experts["w_out"]
    if w_in.shape[0] != count or w_out.shape[0] != count:
        raise ValueError(
            f"held = {held} names {count} experts, the weights hold "
            f"{w_in.shape[0]} and {w_out.shape[0]}"
        )
    try:
        mesh = get_mesh()
    except RuntimeError:
        mesh = None
    if mesh is not None and mesh.shape.get("expert", 1) > 1:
        raise NotImplementedError(
            "routed_experts on an expert mesh axis of "
            f"{mesh.shape['expert']}: the exchange of tokens (their "
            "token-expert pairs) between the chips that share a layer "
            "is not implemented; use a mesh with expert=1 and tell the "
            "layer what it holds"
        )
    batch, seq, dim = h.shape
    k = choice.shape[-1] if choice.ndim == h.ndim else 1

    def rows(h, choice, weight, w_in, w_out):
        out = _routed_rows(
            h.reshape(-1, dim), choice.reshape(-1), weight.reshape(-1),
            w_in, w_out, first, k)
        return out.reshape(h.shape)

    if mesh is None or all(
        mesh.shape.get(a, 1) == 1 for a in ("data", "fsdp")
    ):
        return rows(h, choice, weight, w_in, w_out)
    from jax.sharding import PartitionSpec as P

    batch_axes = logical_to_mesh_axes(
        ("batch",), (("batch", ("data", "fsdp")),))[0]
    return jax.shard_map(
        rows, mesh=mesh,
        in_specs=(P(batch_axes), P(batch_axes), P(batch_axes), P(), P()),
        out_specs=P(batch_axes), check_vma=False,
    )(h, choice, weight, w_in, w_out)


# the counts a routed layer hands back beside the loss, in this order
COUNTS = ("moe.tokens_routed", "moe.tokens_held",
          "moe.expert_load_max", "moe.expert_load_mean")


def routed_counts(choice, held):
    """One layer's ``COUNTS`` of ``choice`` (int, any shape: a token's
    choices on the last axis where it makes several), float32 [4]:
    token-expert pairs routed, pairs whose expert is held here, and the
    largest and the mean load of a held expert in pairs."""
    first, count = held
    experts = first + jnp.arange(count, dtype=jnp.int32)
    load = jnp.sum(choice[..., None] == experts,
                   axis=tuple(range(choice.ndim)), dtype=jnp.float32)
    return jnp.stack([
        jnp.asarray(choice.size, jnp.float32), jnp.sum(load),
        jnp.max(load), jnp.mean(load),
    ])
