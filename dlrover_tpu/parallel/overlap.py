"""Collective–compute overlap for the fsdp layer scan.

Equivalent capability: DeepSpeed/FSDP prefetch and the Megatron
overlapped-collective schedules — layer *k*'s param all-gather runs
while layer *k-1* computes, and the grad reduce-scatter of layer *k*
hides behind layer *k-1*'s backward.

TPU redesign: the scanned-layer axis already chunks the fsdp
collectives per layer (GSPMD gathers one layer's params per scan
iteration). What serialises the loop is the *dependency*: inside one
iteration the gather must finish before the first matmul starts. The
overlapped scan (``parallel/pipeline.py stage_layer_scan``) breaks the
dependency by double-buffering the gathered params through the scan
carry — iteration *k* computes with the params gathered during
iteration *k-1* while issuing the gather for layer *k+1*, so the
collective and the compute of one iteration are independent and the
scheduler can run them concurrently.

Two gather mechanisms, both behind ``Strategy.overlap_collectives``:

- ``"xla"``: the gather is a ``with_sharding_constraint`` to the
  fsdp-stripped spec — GSPMD emits its native all-gather, but at the
  double-buffered position. Works under any mesh.
- ``"manual"``: the gather is a per-leaf ``shard_map`` running the
  ppermute ring from ``ops/collectives.py`` — N-1 independently
  schedulable steps XLA cannot re-serialise into one op
  (``tests/test_hot_loop.py::TestOverlappedScan`` counts the
  ``ppermute``s in the step's jaxpr). The ring's transpose is
  itself a ring, so the backward reduce-scatter stays decomposed too.

The mode is a trace-time ambient flag (like ``quant_autocast``), set by
``auto_accelerate`` from the Strategy so model code never threads it.
"""

from __future__ import annotations

import contextlib
from typing import Optional

from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)

__all__ = [
    "overlap_autocast",
    "overlap_mode",
    "layer_gather_fn",
    "OVERLAP_MODES",
]

OVERLAP_MODES = ("off", "xla", "manual")

# mesh axis the overlap decomposes (the ZeRO-3 param/grad axis)
_GATHER_AXIS = "fsdp"


class _Flag:
    mode: str = "off"
    rules = None  # effective logical rules (rules_for_mesh output)


def overlap_mode() -> str:
    """The active collective-overlap mode (trace-time)."""
    return _Flag.mode


@contextlib.contextmanager
def overlap_autocast(mode: str = "xla", rules=None):
    """Trace-time switch: the layer scan double-buffers fsdp gathers
    while this is active. Set by auto_accelerate for
    ``Strategy.overlap_collectives`` in ("xla", "manual").

    ``rules`` is the EFFECTIVE logical-rule table the params were
    sharded with (``rules_for_mesh(strategy.rules, mesh)``): the gather
    plans must agree with the actual leaf shardings, so a Strategy with
    custom rules rides them through this ambient slot — model code
    calling :func:`layer_gather_fn` never threads them. None keeps
    DEFAULT_RULES."""
    if mode not in OVERLAP_MODES:
        raise ValueError(
            f"overlap mode must be one of {OVERLAP_MODES}, got {mode!r}"
        )
    prev, prev_rules = _Flag.mode, _Flag.rules
    _Flag.mode, _Flag.rules = mode, rules
    try:
        yield
    finally:
        _Flag.mode, _Flag.rules = prev, prev_rules


def _strip_axis(entry, axis: str):
    """Remove ``axis`` from one PartitionSpec entry."""
    if entry is None:
        return None
    flat = (entry,) if isinstance(entry, str) else tuple(entry)
    kept = tuple(a for a in flat if a != axis)
    if not kept:
        return None
    return kept[0] if len(kept) == 1 else kept


def _gather_dim(spec) -> Optional[int]:
    """Index of the dim sharded over the gather axis, or None."""
    for i, entry in enumerate(spec):
        if entry is None:
            continue
        flat = (entry,) if isinstance(entry, str) else tuple(entry)
        if _GATHER_AXIS in flat:
            return i
    return None


def layer_gather_fn(layer_axes, rules=None):
    """Build the per-layer gather for the overlapped scan.

    ``layer_axes`` is a pytree matching ONE layer's params (the stacked
    tree minus its leading ``layer`` dim) whose leaves are logical-axis
    tuples. Returns ``gather(layer_params) -> layer_params`` with every
    fsdp-sharded leaf gathered (replicated over fsdp, other axes
    untouched), or ``None`` when overlap does not apply here: mode off,
    no mesh, fsdp extent 1, or an active manual mesh (the pipeline's
    shard_map — per-device there, nothing to gather).

    ``rules=None`` falls back to the ambient table installed by
    :func:`overlap_autocast` (the effective rules the params were
    sharded with), then to DEFAULT_RULES.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from dlrover_tpu.parallel.mesh import get_mesh
    from dlrover_tpu.parallel.sharding import logical_to_mesh_axes

    mode = overlap_mode()
    if mode == "off" or layer_axes is None:
        return None
    if rules is None:
        rules = _Flag.rules
    try:
        mesh = get_mesh()
    except RuntimeError:
        return None
    if mesh.empty or mesh.shape.get(_GATHER_AXIS, 1) <= 1:
        return None
    if mesh.shape.get("pipe", 1) > 1:
        # the pipeline schedule runs stage scans inside its own manual
        # shard_map; sharding constraints from in there would target
        # the wrong mesh — stages keep the plain schedule
        return None
    amesh = jax.sharding.get_abstract_mesh()
    if not amesh.empty and _GATHER_AXIS in set(amesh.manual_axes):
        return None
    n = int(mesh.shape[_GATHER_AXIS])

    is_axes_leaf = lambda x: isinstance(x, tuple) or x is None  # noqa: E731
    flat_axes, axes_def = jax.tree_util.tree_flatten(
        layer_axes, is_leaf=is_axes_leaf
    )
    plans = []  # (sharded_spec, gathered_spec, fsdp_dim | None)
    for axes in flat_axes:
        spec = logical_to_mesh_axes(axes, rules)
        dim = _gather_dim(spec)
        gathered = PartitionSpec(
            *(_strip_axis(e, _GATHER_AXIS) for e in spec)
        )
        plans.append((spec, gathered, dim))

    if mode == "manual":
        from dlrover_tpu.ops.collectives import ring_all_gather

        def gather_leaf(leaf, plan):
            spec, gathered, dim = plan
            if dim is None or leaf.ndim <= dim:
                return leaf

            def ring(shard):
                return ring_all_gather(shard, _GATHER_AXIS, n, dim=dim)

            return jax.shard_map(
                ring, mesh=mesh, in_specs=spec, out_specs=gathered,
                check_vma=False,
            )(leaf)
    else:  # "xla"

        def gather_leaf(leaf, plan):
            _spec, gathered, dim = plan
            if dim is None or getattr(leaf, "ndim", 0) <= dim:
                return leaf
            return jax.lax.with_sharding_constraint(
                leaf, NamedSharding(mesh, gathered)
            )

    def gather(layer_params):
        leaves, treedef = jax.tree_util.tree_flatten(layer_params)
        if len(leaves) != len(plans):
            # structure drifted from the declared axes (defensive: an
            # adapter-described model may disagree) — skip overlapping
            logger.warning(
                "overlap: %d param leaves vs %d axis leaves — "
                "gather skipped", len(leaves), len(plans),
            )
            return layer_params
        return jax.tree_util.tree_unflatten(
            treedef,
            [gather_leaf(l, p) for l, p in zip(leaves, plans)],
        )

    return gather
