"""Device-mesh construction with named parallelism axes.

Equivalent capability: reference atorch create_parallel_group
(atorch/atorch/distributed/distributed.py:321) which slices the world into
nested process groups per parallelism dim ("tensor", "pipe", "data", ...).
TPU redesign: one ``jax.sharding.Mesh`` whose axis order is chosen so that
the most communication-hungry axes map to the innermost (fastest-ICI)
device dimensions. No process groups — XLA derives collectives from
shardings over the mesh.

Canonical axis names (a superset of the reference's dim names):

- ``data``    pure data parallelism (gradient psum only)
- ``fsdp``    data parallelism with ZeRO-3-style parameter sharding
- ``seq``     sequence/context parallelism (ring attention)
- ``tensor``  Megatron-style tensor parallelism
- ``expert``  MoE expert parallelism (all_to_all)
- ``pipe``    pipeline stages
"""

from __future__ import annotations

import dataclasses
import math
import threading
from typing import Optional, Sequence, Tuple

from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)

# Axis order matters: jax places the *last* mesh axis on the most
# tightly-coupled device dimension. Tensor parallelism is the most
# latency-sensitive collective traffic, so it goes last; pipeline
# stages tolerate DCN so they go first.
AXIS_ORDER: Tuple[str, ...] = ("pipe", "data", "fsdp", "expert", "seq", "tensor")

# Axes allowed to span slice boundaries (DCN) in a hybrid mesh. Pipeline
# traffic is point-to-point activations between adjacent stages (small,
# latency-tolerant); data/fsdp gradient reduction is a once-per-step
# allreduce that DCN bandwidth can sustain when the per-slice model shard
# is small relative to the step time. tensor/seq/expert collectives are
# per-layer and must stay on ICI.
DCN_AXES: Tuple[str, ...] = ("pipe", "data", "fsdp")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Sizes for each named axis; 1 means the axis is inactive.

    ``data=-1`` (or any single axis set to -1) means "absorb all
    remaining devices", mirroring torchrun-style world-size inference.

    Multi-slice (hybrid ICI x DCN) meshes — the TPU-native equivalent of
    the reference's nested cross-node process groups
    (atorch/atorch/distributed/distributed.py:321-427, NCCL within a
    node / across nodes): ``dcn_pipe``/``dcn_data``/``dcn_fsdp`` give the
    number of *slices* the corresponding axis spans. The axis total still
    includes the DCN factor (e.g. ``data=4, dcn_data=2`` = 2 slices x 2
    ICI-local data shards). Only DCN-tolerant axes may span slices.
    """

    pipe: int = 1
    data: int = -1
    fsdp: int = 1
    expert: int = 1
    seq: int = 1
    tensor: int = 1
    # slices spanned per axis (1 = within one ICI domain)
    dcn_pipe: int = 1
    dcn_data: int = 1
    dcn_fsdp: int = 1

    def sizes(self, n_devices: int) -> dict:
        sizes = {a: getattr(self, a) for a in AXIS_ORDER}
        wildcard = [a for a, s in sizes.items() if s == -1]
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if len(wildcard) > 1:
            raise ValueError(f"only one axis may be -1, got {wildcard}")
        if wildcard:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {fixed}"
                )
            sizes[wildcard[0]] = n_devices // fixed
        total = math.prod(sizes.values())
        if total != n_devices:
            raise ValueError(
                f"mesh axes {sizes} use {total} devices, have {n_devices}"
            )
        for axis, dcn in self.dcn_sizes().items():
            if sizes[axis] % dcn != 0:
                raise ValueError(
                    f"axis {axis}={sizes[axis]} not divisible by its "
                    f"DCN slice factor {dcn}"
                )
        return sizes

    def dcn_sizes(self) -> dict:
        """Per-axis slice counts (only non-1 entries)."""
        out = {}
        for axis in DCN_AXES:
            dcn = getattr(self, f"dcn_{axis}", 1)
            if dcn != 1:
                out[axis] = dcn
        return out

    @property
    def n_slices(self) -> int:
        return math.prod(self.dcn_sizes().values()) if self.dcn_sizes() else 1

    @property
    def active_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in AXIS_ORDER if getattr(self, a) != 1)


def _slice_groups(devices) -> list:
    """Group devices into ICI granules ("slices") for the hybrid
    fallback path. Preference order: TPU ``slice_index`` attr (real
    multi-slice), then ``process_index`` (multi-host CPU/testing), else
    a single group."""
    import collections

    by_key = collections.OrderedDict()
    for attr in ("slice_index", "process_index"):
        by_key.clear()
        for d in devices:
            key = getattr(d, attr, None)
            if key is None:
                break
            by_key.setdefault(key, []).append(d)
        else:
            if len(by_key) > 1:
                return [by_key[k] for k in sorted(by_key)]
    return [list(devices)]


def build_mesh(
    config: Optional[MeshConfig] = None,
    devices: Optional[Sequence] = None,
):
    """Build a ``jax.sharding.Mesh`` over ``devices`` (default: all).

    Uses ``mesh_utils.create_device_mesh`` so that on real TPU slices the
    logical axes are laid out along the physical ICI torus; the CPU's
    virtual devices take a plain reshape (:func:`_device_array`).

    When ``config`` carries DCN slice factors (``dcn_data``/``dcn_pipe``/
    ``dcn_fsdp``), builds a hybrid ICI x DCN mesh via
    ``mesh_utils.create_hybrid_device_mesh``: within a slice the axes ride
    the ICI torus; the DCN factors stride across slices so only the
    DCN-tolerant axes generate cross-slice traffic. Fallback for
    virtual/CPU platforms groups devices by slice/process index (or
    contiguous chunks) and strides the DCN axes across the groups.
    """
    import jax
    from jax.sharding import Mesh

    config = config or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    sizes = config.sizes(len(devices))
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    dcn = config.dcn_sizes()
    if dcn:
        dev_array = _hybrid_device_array(devices, sizes, dcn)
        mesh = Mesh(dev_array, AXIS_ORDER)
        logger.info(
            "built hybrid mesh %s (DCN slices: %s)",
            {a: sizes[a] for a in AXIS_ORDER}, dcn,
        )
        return mesh
    mesh = Mesh(_device_array(shape, devices), AXIS_ORDER)
    logger.info("built mesh %s", {a: sizes[a] for a in AXIS_ORDER})
    return mesh


def _device_array(shape, devices):
    """Devices laid out along the physical ICI torus by ``mesh_utils``.
    Only the CPU's virtual devices, which have no topology to honour,
    take a plain reshape: on an accelerator a layout ``mesh_utils``
    refuses is an error, not a mesh that routes collectives blind."""
    import numpy as np
    from jax.experimental import mesh_utils

    if devices[0].platform == "cpu":
        return np.asarray(devices, dtype=object).reshape(shape)
    return mesh_utils.create_device_mesh(
        shape, devices=devices, allow_split_physical_axes=True
    )


def _hybrid_device_array(devices, sizes: dict, dcn: dict):
    """Device array for a hybrid mesh: ICI shape x DCN shape.

    ``sizes`` are the *total* per-axis sizes; the ICI (per-slice) shape
    divides out the DCN slice factors.
    """
    import numpy as np
    from jax.experimental import mesh_utils

    ici_shape = tuple(
        sizes[a] // dcn.get(a, 1) for a in AXIS_ORDER
    )
    dcn_shape = tuple(dcn.get(a, 1) for a in AXIS_ORDER)
    n_slices = math.prod(dcn_shape)
    if len(devices) % n_slices != 0:
        raise ValueError(
            f"{len(devices)} devices not divisible into {n_slices} slices"
        )
    have_slice_idx = all(
        getattr(d, "slice_index", None) is not None for d in devices
    ) and len({d.slice_index for d in devices}) > 1
    if have_slice_idx:
        # real multi-slice hardware: a config/hardware mismatch must be
        # an error, not a silent contiguous-chunk layout that would
        # route ICI-only axes across DCN
        return mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devices,
            allow_split_physical_axes=True,
        )
    groups = _slice_groups(devices)
    per_slice = len(devices) // n_slices
    if len(groups) > 1:
        # real slice/process structure (multi-host): it must match the
        # configured DCN factors exactly
        if len(groups) != n_slices or any(
            len(g) != per_slice for g in groups
        ):
            raise ValueError(
                f"config wants {n_slices} DCN slices of {per_slice} "
                f"devices, but the platform has "
                f"{[len(g) for g in groups]} devices per slice/process"
                " — fix the dcn_* factors to match the hardware"
            )
    else:
        # single-process virtual platform: contiguous chunks are the
        # slices (deterministic, good enough for compile validation)
        flat = groups[0]
        groups = [
            flat[i * per_slice:(i + 1) * per_slice]
            for i in range(n_slices)
        ]
    # per-slice ICI layout, then stitch: the result axis a has the DCN
    # factor as its *outer* (slowest) stride so crossing a slice boundary
    # means moving along a DCN-tolerant axis only
    slice_arrays = [_device_array(ici_shape, g) for g in groups]
    stacked = np.asarray(slice_arrays, dtype=object).reshape(
        dcn_shape + ici_shape
    )
    # interleave [dcn_0..dcn_5, ici_0..ici_5] -> per-axis (dcn_a, ici_a)
    n = len(AXIS_ORDER)
    perm = []
    for i in range(n):
        perm.extend([i, n + i])
    total_shape = tuple(sizes[a] for a in AXIS_ORDER)
    return stacked.transpose(perm).reshape(total_shape)


# -- process-global mesh (the analogue of atorch's module-level
#    _parallel_group registry, distributed.py:83-117) ------------------------

_state = threading.local()
_global_mesh = None
_global_lock = threading.Lock()


def set_mesh(mesh) -> None:
    global _global_mesh
    with _global_lock:
        _global_mesh = mesh


def get_mesh():
    """The active mesh: an enclosing ``with mesh:`` context if present,
    else the process-global one set by :func:`set_mesh`."""
    from jax._src.mesh import thread_resources

    env_mesh = thread_resources.env.physical_mesh
    if env_mesh is not None and not env_mesh.empty:
        return env_mesh
    if _global_mesh is None:
        raise RuntimeError("no mesh: call build_mesh()+set_mesh() first")
    return _global_mesh


def axis_size(axis: str) -> int:
    """Size of a named axis on the active mesh (atorch parallel_group_size)."""
    mesh = get_mesh()
    return mesh.shape.get(axis, 1)


def axis_index(axis: str):
    """Inside jit/shard_map: this device's index along ``axis``
    (atorch parallel_rank)."""
    import jax

    return jax.lax.axis_index(axis)
