"""Serializable parallelism strategies + the heuristic planner.

Equivalent capability: atorch Strategy objects and the
``load_strategy`` fast path (atorch/atorch/auto/accelerate.py:530-577) and
the strategy-search engine's output (auto/engine/). TPU redesign: a
Strategy is a MeshConfig + sharding-rule table + precision/remat knobs;
"applying" it costs nothing at runtime because it only changes shardings
handed to jit. ``auto_strategy`` is the deterministic planner (the
analogue of atorch auto_config heuristics); a measured search can layer on
top by scoring compiled-step timings.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional, Tuple

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.parallel.mesh import AXIS_ORDER, MeshConfig
from dlrover_tpu.parallel.sharding import DEFAULT_RULES, LogicalRules

logger = get_logger(__name__)


@dataclasses.dataclass
class Strategy:
    """A complete, serializable acceleration plan."""

    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    rules: LogicalRules = DEFAULT_RULES
    # compute precision for matmuls/activations; params stay fp32 master.
    compute_dtype: str = "bfloat16"
    # remat policy name: none | minimal | offload | full — what the
    # step program's checkpoints keep from forward to backward.
    # "minimal" keeps the dots without batch dimensions (the weight
    # matmuls) and the attention kernel's output and row statistic
    # (pipeline.minimal_save_policy); "offload" keeps the same set but
    # round-trips the dots through pinned host memory — HBM relief
    # without recompute; "full" keeps nothing and recomputes the whole
    # forward pass, attention too; "none" sets no checkpoint at all.
    # Under int8/fp8 compute every level is quant-adapted
    # (pipeline.quant_aware_policy): even "full" still saves the
    # quantized-matmul outputs, because recomputing a quantization
    # chain in the backward costs more HBM traffic than the int8 saves
    # occupy — "full recompute" is a memory contract for *bf16*
    # tensors, not the int accumulators. No-op for unquantized models.
    remat: str = "minimal"
    # number of microbatches for gradient accumulation (elastic trainer
    # raises this as world size shrinks to keep global batch fixed).
    grad_accum: int = 1
    # optional donation of params/opt-state buffers in the train step.
    donate: bool = True
    # collective–compute overlap for the fsdp layer scan
    # (parallel/overlap.py): "off" = plain scan; "xla" = double-buffered
    # per-layer gathers through the scan carry, GSPMD collectives +
    # latency-hiding scheduler; "manual" = same schedule with the
    # gathers decomposed into ppermute rings (ops/collectives.py) the
    # scheduler can interleave step-by-step. Like int8, the product
    # default comes from measured selection (parallel/engine.py's
    # dry-runner), not from hardcoding "on".
    overlap_collectives: str = "off"
    # which qdot/qeinsum call sites quantize under compute_dtype=
    # "int8"/"fp8": "all", or a comma-separated subset of the site
    # labels models tag ("attn_qkv", "attn_out", "mlp"). Per-site
    # selection lets the measured search keep e.g. the MLP einsums
    # int8 while holding attention projections in bf16 where parity
    # (or speed) fails site-wise.
    quant_sites: str = "all"
    # one-pass fused optimizer step (ops/fused_optim.py): consumed by
    # the optimizer factories (optimizers.low_bit.adam8bit(fused=...),
    # fused_adamw) — recorded here so a serialized strategy captures
    # the whole measured selection.
    fused_optim: bool = False

    def to_json(self) -> str:
        d = dataclasses.asdict(self)
        d["rules"] = [list(r) for r in self.rules]
        return json.dumps(d, indent=2)

    @classmethod
    def from_json(cls, s: str) -> "Strategy":
        d = json.loads(s)
        d["mesh"] = MeshConfig(**d["mesh"])
        d["rules"] = tuple(
            (name, tuple(ax) if isinstance(ax, list) else ax)
            for name, ax in d["rules"]
        )
        return cls(**d)

    def describe(self) -> str:
        active = {
            a: getattr(self.mesh, a)
            for a in AXIS_ORDER
            if getattr(self.mesh, a) != 1
        }
        extras = ""
        if self.overlap_collectives != "off":
            extras += f", overlap={self.overlap_collectives}"
        if self.quant_sites != "all":
            extras += f", qsites={self.quant_sites}"
        if self.fused_optim:
            extras += ", fused_optim"
        return (
            f"Strategy(mesh={active or 'dp-only'}, dtype={self.compute_dtype},"
            f" remat={self.remat}, accum={self.grad_accum}{extras})"
        )


def save_strategy(strategy: Strategy, path: str) -> None:
    # dlint: allow-chaos(operator-invoked config dump, not a recovery seam)
    with open(path, "w") as f:
        f.write(strategy.to_json())


def load_strategy(path: str) -> Strategy:
    with open(path) as f:
        return Strategy.from_json(f.read())


def _remat_for(param_bytes_per_device: float, hbm_bytes: float) -> str:
    # Params + optimizer state (Adam: 2x fp32) + grads ~ 4x param bytes.
    if param_bytes_per_device * 4 > hbm_bytes * 0.6:
        return "full"
    if param_bytes_per_device * 4 > hbm_bytes * 0.3:
        return "minimal"
    return "none"


def auto_strategy(
    n_devices: int,
    param_count: int,
    seq_len: int = 2048,
    hbm_gb: float = 16.0,
    devices_per_host: int = 4,
    moe: bool = False,
    n_experts: int = 1,
    long_context_threshold: int = 32768,
    n_slices: int = 1,
) -> Strategy:
    """Deterministic planner (the atorch auto_config analogue).

    Heuristics, TPU-first:
    - Prefer FSDP (ZeRO-3 on the ``fsdp`` axis) until per-device param+opt
      state fits comfortably; it has the best compute/communication ratio
      on ICI and no model-code requirements.
    - Add tensor parallelism only when a single FSDP shard of the layer
      activations/params would still blow HBM, capping ``tensor`` at the
      per-host device count so TP collectives never cross DCN.
    - Activate ``seq`` (ring attention) for very long sequences.
    - Activate ``expert`` for MoE models (expert count capped at device
      count).
    - Multi-slice (``n_slices > 1``): the slice boundary rides the
      ``data`` axis (pure DP over DCN — one gradient allreduce per
      step), carved out of the fsdp extent; per-slice FSDP stays on
      ICI. For finer control use the search engine's DCN-aware
      candidates (engine.candidate_strategies(n_slices=...)).
    """
    param_bytes = param_count * 4.0  # fp32 master params
    hbm = hbm_gb * (1 << 30)

    tensor = 1
    # With pure FSDP over all devices IN ONE SLICE (params replicate
    # across slices), per-device footprint:
    sharded_devices = n_devices // max(n_slices, 1)
    per_dev = param_bytes * 4 / max(sharded_devices, 1)
    if per_dev > hbm * 0.5:
        tensor = min(devices_per_host, n_devices)

    seq = 1
    if seq_len >= long_context_threshold:
        # shard sequence enough that activations fit; activations scale
        # ~seq^2 in attention score blocks but ring attention keeps them
        # linear; 1 axis step per 32k tokens is a safe default.
        seq = min(max(seq_len // long_context_threshold, 1), n_devices // tensor)
        while (n_devices // tensor) % seq != 0:
            seq -= 1

    expert = 1
    if moe and n_experts > 1:
        expert = min(n_experts, max(n_devices // (tensor * seq), 1))
        while (n_devices // (tensor * seq)) % expert != 0:
            expert -= 1

    fsdp = n_devices // (tensor * seq * expert)
    data = 1
    dcn_data = 1
    if n_slices > 1:
        if fsdp % n_slices != 0:
            raise ValueError(
                f"{n_slices} slices do not divide the fsdp extent "
                f"{fsdp} (n_devices={n_devices}, tensor={tensor}, "
                f"seq={seq}, expert={expert})"
            )
        # DP across slices (gradient allreduce tolerates DCN), FSDP
        # within each slice (param all-gathers stay on ICI)
        data = n_slices
        dcn_data = n_slices
        fsdp //= n_slices
    mesh = MeshConfig(
        pipe=1, data=data, fsdp=fsdp, expert=expert, seq=seq,
        tensor=tensor, dcn_data=dcn_data,
    )
    # params are REPLICATED across the data (slice) axis: the per-device
    # model-state share divides by the sharded extents only
    remat = _remat_for(param_bytes / (n_devices // max(n_slices, 1)), hbm)
    strategy = Strategy(mesh=mesh, remat=remat)
    logger.info("auto_strategy: %s", strategy.describe())
    return strategy
