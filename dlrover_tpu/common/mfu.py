"""Model-FLOPs utilization accounting for the trainer's per-step
``train.mfu`` gauge.

The FLOPs model is the dense estimate, 6 FLOPs per parameter per token
(fwd 2 + bwd 4), unless the caller hands the Trainer its own count
(``TrainingArgs.model_flops_per_token``).

Peak FLOP/s comes from ONE table keyed by the device kind JAX reports.
An accelerator that is not in it is an error, never a default: a
utilization against somebody else's peak is not a measurement. The CPU
has no row, so a CPU run reports no MFU at all. The bf16 peak is
deliberately conservative for int8-selected arms, whose dots run the 2x
int8 MXU path.
"""

from __future__ import annotations

# bf16 peak FLOP/s of one chip, by ``jax.Device.device_kind``
PEAK_FLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s (bf16) per chip
    "TPU v5 lite": 197e12,
}


def peak_flops(device) -> float | None:
    """Published peak of ``device`` (a ``jax.Device``). None on the
    CPU — it has no row, and no MFU is reported there; an accelerator
    missing from the table raises."""
    peak = PEAK_FLOPS.get(device.device_kind)
    if peak is None and device.platform != "cpu":
        raise ValueError(
            f"no published peak FLOP/s for device kind "
            f"{device.device_kind!r}: add it to common/mfu.PEAK_FLOPS "
            f"with its source"
        )
    return peak


def mfu(flops_per_step: float, step_seconds: float, peak: float) -> float:
    """Fraction of ``peak`` the step achieved; 0 when unmeasurable."""
    if step_seconds <= 0 or peak <= 0:
        return 0.0
    return flops_per_step / step_seconds / peak
