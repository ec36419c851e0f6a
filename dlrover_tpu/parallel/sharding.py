"""Logical-axis sharding rules.

Equivalent capability: the reference expresses TP/FSDP/SP by *rewriting
modules* (atorch/atorch/modules/distributed_modules/layers.py RowParallel/
ColumnParallel etc. and FSDP wrapping). TPU redesign: models annotate
arrays with *logical* axis names ("embed", "mlp", "heads", ...) and a rule
table maps logical names to mesh axes. Changing the parallelism strategy
changes the rule table, never the model code — the GSPMD analogue of
swapping wrappers.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

LogicalRules = Sequence[Tuple[str, object]]

# Default rule table: how model-logical dims map onto mesh axes.
# FSDP shards the embed dim (ZeRO-3 analogue); tensor parallelism splits
# heads/mlp; batch splits over data+fsdp; sequence over seq.
DEFAULT_RULES: LogicalRules = (
    ("batch", ("data", "fsdp")),
    ("seq", "seq"),
    ("embed", "fsdp"),
    ("heads", "tensor"),
    ("kv_heads", "tensor"),
    ("mlp", "tensor"),
    ("vocab", "tensor"),
    ("expert", "expert"),
    ("head_dim", None),
    ("kv", None),
    ("layer", None),
    ("stage", "pipe"),
)


def _rule_table(rules: Optional[LogicalRules]):
    return dict(rules if rules is not None else DEFAULT_RULES)


def logical_to_mesh_axes(
    logical_axes: Sequence[Optional[str]],
    rules: Optional[LogicalRules] = None,
):
    """Map a tuple of logical axis names to a PartitionSpec.

    ``None`` (no annotation at all) replicates, same as ``()``."""
    from jax.sharding import PartitionSpec

    if logical_axes is None:
        return PartitionSpec()
    table = _rule_table(rules)
    mesh_axes = []
    used = set()
    for name in logical_axes:
        axis = table.get(name) if name is not None else None
        # An axis may appear in a spec only once; later dims fall back
        # to replicated (same resolution flax.linen.partitioning uses).
        if axis is not None:
            flat = (axis,) if isinstance(axis, str) else tuple(axis)
            if any(a in used for a in flat):
                axis = None
            else:
                used.update(flat)
        mesh_axes.append(axis)
    while mesh_axes and mesh_axes[-1] is None:
        mesh_axes.pop()
    return PartitionSpec(*mesh_axes)


def logical_sharding(
    logical_axes: Sequence[Optional[str]],
    mesh=None,
    rules: Optional[LogicalRules] = None,
):
    """NamedSharding for an array annotated with logical axis names."""
    from jax.sharding import NamedSharding

    from dlrover_tpu.parallel.mesh import get_mesh

    mesh = mesh if mesh is not None else get_mesh()
    return NamedSharding(mesh, logical_to_mesh_axes(logical_axes, rules))


def shard_logical(x, logical_axes, rules: Optional[LogicalRules] = None):
    """``with_sharding_constraint`` by logical names, inside jit.

    No-op when no mesh is active (single-device/unit-test use)."""
    import jax
    from jax.sharding import NamedSharding

    from dlrover_tpu.parallel.mesh import get_mesh

    try:
        mesh = get_mesh()
    except RuntimeError:
        return x
    if mesh.empty:
        return x
    spec = logical_to_mesh_axes(logical_axes, rules)

    # Inside a partial-manual shard_map (e.g. the pipeline schedule) the
    # constraint must target the current *abstract* mesh, with manual
    # axes stripped from the spec (they are per-device there).
    from jax.sharding import PartitionSpec, get_abstract_mesh

    amesh = get_abstract_mesh()
    if not amesh.empty and amesh.manual_axes:
        manual = set(amesh.manual_axes)

        def strip(entry):
            if entry is None:
                return None
            flat = (entry,) if isinstance(entry, str) else tuple(entry)
            kept = tuple(a for a in flat if a not in manual)
            if not kept:
                return None
            return kept[0] if len(kept) == 1 else kept

        spec = PartitionSpec(*(strip(e) for e in spec))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(amesh, spec)
        )
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def unsharded(mesh=None):
    """Fully-replicated NamedSharding."""
    from jax.sharding import NamedSharding, PartitionSpec

    from dlrover_tpu.parallel.mesh import get_mesh

    mesh = mesh if mesh is not None else get_mesh()
    return NamedSharding(mesh, PartitionSpec())


def tree_logical_shardings(abstract_tree, mesh=None, rules=None):
    """Map a pytree of ShapeDtypeStruct-with-logical-names (as produced by
    ``nn.get_partition_spec`` style metadata or our models' ``logical_axes``
    trees) to concrete NamedShardings.

    ``abstract_tree`` leaves are tuples of logical names (or None).
    """
    import jax

    return jax.tree.map(
        lambda axes: logical_sharding(axes, mesh=mesh, rules=rules),
        abstract_tree,
        is_leaf=lambda x: isinstance(x, tuple) or x is None,
    )
