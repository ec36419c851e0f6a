"""``auto_accelerate`` — one call from (model fns, optimizer) to a fully
sharded, jitted train step.

Equivalent capability: atorch.auto_accelerate
(atorch/atorch/auto/accelerate.py:406): the reference builds a
ModelContext, searches/loads a Strategy, then *wraps* the model per method
(DDP/FSDP/TP rewrite/pipe). TPU redesign: a Strategy is just shardings;
"applying" it = (1) build the mesh, (2) compute NamedShardings for every
state leaf from its logical axes, (3) jit the step with those shardings
and let GSPMD insert collectives. There is no wrapping and no module
rewriting; the same model code runs under every strategy.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

from dlrover_tpu.common.backend import require_backend
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.parallel.mesh import build_mesh, set_mesh
from dlrover_tpu.parallel.sharding import (
    logical_to_mesh_axes,
    shard_logical,
)
from dlrover_tpu.parallel.strategy import Strategy

logger = get_logger(__name__)


@dataclasses.dataclass
class TrainState:
    """Minimal functional train state (params, optax opt state, step)."""

    step: Any
    params: Any
    opt_state: Any

    def tree_flatten(self):
        return (self.step, self.params, self.opt_state), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _register_trainstate():
    import jax

    try:
        jax.tree_util.register_pytree_node(
            TrainState,
            TrainState.tree_flatten,
            lambda aux, ch: TrainState(*ch),
        )
    except ValueError:
        pass  # already registered


_register_trainstate()


@dataclasses.dataclass
class AccelerateResult:
    """What auto_accelerate hands back (the AutoAccelerateResult analogue,
    accelerate.py:372)."""

    mesh: Any
    strategy: Strategy
    state: TrainState
    state_shardings: TrainState
    train_step: Callable  # (state, batch, rng) -> (state, metrics)
    eval_step: Optional[Callable] = None


def _compute_cast(params, dtype):
    import jax
    import jax.numpy as jnp

    if dtype is None:
        return params
    target = jnp.dtype(dtype)

    def cast(p):
        if hasattr(p, "dtype") and jnp.issubdtype(p.dtype, jnp.floating):
            return p.astype(target)
        return p

    return jax.tree.map(cast, params)


def _remat_wrap(loss_fn, policy_name: str):
    import jax

    from dlrover_tpu.parallel.pipeline import (
        minimal_save_policy,
        quant_aware_policy,
    )

    if policy_name == "none":
        return loss_fn
    if policy_name in ("minimal", "offload"):
        # "offload": selective activation offloading (reference
        # selective_offloading_checkpoint.py:1): the dots "minimal"
        # would keep in HBM round-trip to pinned host memory instead —
        # HBM high-water drops toward the "full" level while the
        # backward re-reads saves over PCIe/DMA instead of recomputing.
        # Both keep the attention kernel's outputs: this checkpoint's
        # policy also rules the model's per-layer checkpoints on the
        # first forward pass, and what it drops there they recompute.
        policy = minimal_save_policy(offload=policy_name == "offload")
    else:  # "full"
        policy = jax.checkpoint_policies.nothing_saveable
    # same int8 adaptation the per-layer scan applies: without it, a
    # model with config.remat=False under strategy remat would save the
    # stacked int32 qa@qb accumulators (HBM OOM) and recompute every
    # quantization chain in the backward. No-op for unquantized models.
    return jax.checkpoint(loss_fn, policy=quant_aware_policy(policy))


def rules_for_mesh(rules, mesh):
    """Adjust a logical-rule table for the active mesh: with a real
    ``pipe`` axis the stacked ``layer`` dim shards across stages
    (pipelining is layer-stack sharding under GSPMD). Shared by
    auto_accelerate and every other sharding consumer (RL ModelEngine)
    so a per-role Strategy with pipe > 1 cannot silently replicate the
    layer stack."""
    if mesh.shape.get("pipe", 1) <= 1:
        return rules
    from dlrover_tpu.parallel.sharding import DEFAULT_RULES

    rules = tuple(rules if rules is not None else DEFAULT_RULES)
    rules = tuple(
        ("layer", "pipe") if name == "layer" else (name, ax)
        for name, ax in rules
    )
    if not any(name == "layer" for name, _ in rules):
        rules = rules + (("layer", "pipe"),)
    return rules


def param_shardings_for(param_logical_axes, mesh, rules=None):
    """NamedShardings for a params pytree from its logical axis names."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from dlrover_tpu.parallel.sharding import DEFAULT_RULES

    rules = rules if rules is not None else DEFAULT_RULES
    param_specs = jax.tree.map(
        lambda axes: logical_to_mesh_axes(axes, rules),
        param_logical_axes,
        is_leaf=lambda x: isinstance(x, tuple) or x is None,
    )
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_specs,
        is_leaf=lambda s: isinstance(s, PartitionSpec),
    )


def compute_state_shardings(
    init_fn, optimizer, param_logical_axes, mesh, rules=None, seed: int = 0
):
    """(param_shardings, opt_shardings) for a model + optax optimizer.

    Optimizer-state subtrees that mirror the params pytree (optax
    mu/nu/trace/...) take the param shardings element-wise; everything
    else (counts, schedules) replicates. Structural matching avoids
    collisions between same-shaped params with different layouts.
    Pass ``optimizer=None`` for frozen models (opt_shardings is None).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    param_shardings = param_shardings_for(param_logical_axes, mesh, rules)
    if optimizer is None:
        return param_shardings, None
    abstract_params = jax.eval_shape(init_fn, jax.random.key(seed))
    abstract_opt = jax.eval_shape(optimizer.init, abstract_params)
    params_struct = jax.tree.structure(abstract_params)
    abstract_param_leaves = jax.tree.leaves(abstract_params)
    replicated = NamedSharding(mesh, PartitionSpec())

    def _is_param_tree(sub):
        try:
            if jax.tree.structure(sub) != params_struct:
                return False
            leaves = jax.tree.leaves(sub)
        except Exception:  # noqa: BLE001 - exotic nodes: not a match
            return False
        return all(
            getattr(l, "shape", None) == p.shape
            and getattr(l, "dtype", None) == p.dtype
            for l, p in zip(leaves, abstract_param_leaves)
        )

    opt_shardings = jax.tree.map(
        lambda sub: param_shardings if _is_param_tree(sub) else (
            jax.tree.map(lambda _: replicated, sub)
        ),
        abstract_opt,
        is_leaf=_is_param_tree,
    )
    return param_shardings, opt_shardings


def auto_accelerate(
    loss_fn: Callable,  # (params, batch, rng) -> scalar loss (or (loss, aux))
    init_fn: Callable,  # (rng) -> params
    optimizer,  # optax GradientTransformation
    param_logical_axes,  # pytree matching params: tuples of logical names
    strategy: Optional[Strategy] = None,
    batch_logical_axes=("batch", "seq"),
    devices=None,
    has_aux: bool = False,
    seed: int = 0,
    infer_out_shardings: bool = False,
    reuse_state: Optional[TrainState] = None,
) -> AccelerateResult:
    """Build mesh + sharded state + jitted train step for ``strategy``.

    The returned ``train_step`` performs ``strategy.grad_accum``
    microbatch accumulation with a ``lax.scan`` (keeping one compiled
    program regardless of accumulation count) and applies the optimizer
    update under the same shardings.

    ``infer_out_shardings``: set True when the MODEL applies a host-
    offload checkpoint policy internally (e.g. LlamaConfig
    remat_policy="dots_attn_offload") — explicit out_shardings plus
    offload placement annotations trip an XLA RET_CHECK in this build;
    strategy.remat="offload" switches automatically.

    ``reuse_state``: skip the jitted init and adopt an existing
    TrainState (already laid out on THIS mesh's shardings — the elastic
    in-process reshape hands the resharded live state back in here so a
    membership change rebuilds the step function without
    re-initializing or restoring anything).
    """
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    if devices is None:
        # workers that never called init_distributed() still must not
        # train on JAX's silent CPU fallback (explicit devices are the
        # caller's word for where to run)
        require_backend()
    strategy = strategy or Strategy()
    mesh = build_mesh(strategy.mesh, devices=devices)
    set_mesh(mesh)
    rules = rules_for_mesh(strategy.rules, mesh)

    param_shardings, opt_shardings = compute_state_shardings(
        init_fn, optimizer, param_logical_axes, mesh, rules, seed=seed
    )
    replicated = NamedSharding(mesh, PartitionSpec())
    state_shardings = TrainState(
        step=replicated, params=param_shardings, opt_state=opt_shardings
    )

    # ---- sharded init ------------------------------------------------------
    def init_state(rng):
        params = init_fn(rng)
        opt_state = optimizer.init(params)
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=params, opt_state=opt_state
        )

    if reuse_state is not None:
        state = reuse_state
    else:
        with mesh:
            state = jax.jit(init_state, out_shardings=state_shardings)(
                jax.random.key(seed)
            )

    # ---- train step --------------------------------------------------------
    compute_dtype = strategy.compute_dtype
    # low-precision compute (reference Fp8Optimization analogue):
    # params/activations stay bf16; the model's qdot matmuls quantize
    # while the quant_autocast trace flag is up. "int8" is the
    # TPU-native mode (2x MXU throughput on v5e); "fp8" is EMULATED on
    # TPUs without fp8 units and measured ~20% slower than bf16 there.
    quant = compute_dtype if compute_dtype in ("fp8", "int8") else None
    if quant == "fp8":
        import jax as _jax

        kinds = {
            getattr(d, "device_kind", "")
            for d in (devices if devices is not None else _jax.devices())
        }
        if not any("v6" in k or "v7" in k for k in kinds):
            # fp8 is EMULATED (e4m3 round-trip) on TPUs without fp8
            # units — measured ~+20-28% step time vs bf16 on v5e. int8
            # does NOT warn: int8 x int8 -> int32 dots hit the MXU's 2x
            # int8 path (DESIGN.md "Low-precision compute") and the
            # einsum-form projections stay quantized via qeinsum, so
            # the int8 step is measured FASTER than bf16 on this
            # hardware. int8 remains opt-in (quantization changes
            # numerics); the engine's candidate generator proposes
            # neither dtype.
            logger.warning(
                "compute_dtype='fp8' on %s: no fp8 units — the e4m3 "
                "emulation is measured SLOWER than bf16 (~+20%% step "
                "time). Use 'int8' (2x MXU path) or keep bfloat16.",
                sorted(kinds) or "unknown devices",
            )
    cast_dtype = "bfloat16" if quant else compute_dtype
    inner_loss = _remat_wrap(loss_fn, strategy.remat)
    accum = max(int(strategy.grad_accum), 1)

    def microbatch_grads(params, batch, rng):
        import contextlib

        from dlrover_tpu.ops.fp8 import no_remat_autocast, quant_autocast
        from dlrover_tpu.parallel.overlap import overlap_autocast

        cparams = _compute_cast(params, cast_dtype)
        ctx = (
            quant_autocast(quant, sites=strategy.quant_sites)
            if quant else contextlib.nullcontext()
        )
        # remat="none" means NONE: suppress the model's own per-layer
        # jax.checkpoint and the qdot residual name-tags at trace time —
        # otherwise a no-remat headline still pays a checkpoint
        # custom-call for quantized dot residuals (measured ~7% of step)
        rctx = (
            no_remat_autocast() if strategy.remat == "none"
            else contextlib.nullcontext()
        )
        # collective–compute overlap: the layer scan double-buffers the
        # per-layer fsdp gathers while this trace flag is up. The
        # EFFECTIVE rule table rides along so the gather plans agree
        # with the actual leaf shardings under custom Strategy.rules
        octx = (
            overlap_autocast(strategy.overlap_collectives, rules=rules)
            if getattr(strategy, "overlap_collectives", "off") != "off"
            else contextlib.nullcontext()
        )
        with ctx, rctx, octx:
            if has_aux:
                grad_fn = jax.value_and_grad(inner_loss, has_aux=True)
                (loss, aux), grads = grad_fn(cparams, batch, rng)
            else:
                grad_fn = jax.value_and_grad(inner_loss)
                loss, grads = grad_fn(cparams, batch, rng)
                aux = {}
        grads = jax.tree.map(
            lambda g, p: g.astype(p.dtype), grads, params
        )
        return loss, aux, grads

    def _batch_axes_for(ndim: int):
        if ndim >= len(batch_logical_axes):
            return tuple(batch_logical_axes) + (None,) * (
                ndim - len(batch_logical_axes)
            )
        # lower-rank leaf (lengths, weights): shard the batch dim only
        return (batch_logical_axes[0],) + (None,) * (ndim - 1)

    def _shard_batch_leaf(x):
        ndim = getattr(x, "ndim", None)
        if ndim is None:
            return x
        return shard_logical(x, _batch_axes_for(ndim), rules)

    def train_step(state: TrainState, batch, rng):
        batch = jax.tree.map(_shard_batch_leaf, batch)
        if accum == 1:
            loss, aux, grads = microbatch_grads(state.params, batch, rng)
        else:
            def split(x):
                if getattr(x, "ndim", 0) < 1 or x.shape[0] % accum:
                    raise ValueError(
                        f"batch dim {getattr(x, 'shape', ())} not divisible "
                        f"by grad_accum={accum}"
                    )
                mb = x.reshape((accum, x.shape[0] // accum) + x.shape[1:])
                # keep microbatches sharded like the batch (avoids an SPMD
                # full-remat on the reshape)
                return shard_logical(
                    mb, (None,) + _batch_axes_for(x.ndim), rules
                )

            micro = jax.tree.map(split, batch)
            zero_grads = jax.tree.map(jnp.zeros_like, state.params)

            def body(carry, inp):
                g_acc, l_acc = carry
                mb, idx = inp
                mb_rng = jax.random.fold_in(rng, idx)
                loss, aux, grads = microbatch_grads(state.params, mb, mb_rng)
                g_acc = jax.tree.map(jnp.add, g_acc, grads)
                return (g_acc, l_acc + loss), aux

            (grads, loss_sum), aux_stack = jax.lax.scan(
                body, (zero_grads, jnp.zeros(())),
                (micro, jnp.arange(accum)),
            )
            grads = jax.tree.map(lambda g: g / accum, grads)
            loss = loss_sum / accum
            aux = jax.tree.map(lambda a: jnp.mean(a, axis=0), aux_stack)
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params
        )
        params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            step=state.step + 1, params=params, opt_state=opt_state
        )
        metrics = {"loss": loss, **aux}
        return new_state, metrics

    donate = (0,) if strategy.donate else ()
    with mesh:
        # remat="offload": explicit out_shardings combined with the
        # host-offload placement annotations trip an XLA RET_CHECK
        # ("Side-effect HLO must have sharding", spmd_partitioner.cc)
        # in this jax/XLA build — let the output shardings be inferred
        # from the (identically-pinned) input shardings instead
        out_sh = (
            None
            if strategy.remat == "offload" or infer_out_shardings
            else (state_shardings, None)
        )
        jitted_step = jax.jit(
            train_step,
            in_shardings=(state_shardings, None, None),
            out_shardings=out_sh,
            donate_argnums=donate,
        )

    def stepper(state, batch, rng):
        with mesh:
            return jitted_step(state, batch, rng)

    logger.info("auto_accelerate ready: %s", strategy.describe())
    return AccelerateResult(
        mesh=mesh,
        strategy=strategy,
        state=state,
        state_shardings=state_shardings,
        train_step=stepper,
    )
