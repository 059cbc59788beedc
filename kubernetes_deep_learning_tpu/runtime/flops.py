"""Device-peak table and FLOP counting.

The engine's status page reports the peak, the bucket audit the lowered
FLOPs/image.  MFU itself is computed off-box: ``kdlt_engine_images_total``'s
rate x the audit page's FLOPs/image over the peak.  How busy the device is
comes from the dispatcher's ``kdlt_pipeline_*_seconds_total`` counters.

FLOPs come from XLA's own cost analysis of the **non-fused flax graph**:
cost analysis cannot see inside Pallas custom calls, so the fused fast path
under-reports (7.5 vs ~17 GFLOPs/img for Xception).
"""

from __future__ import annotations

import logging

log = logging.getLogger(__name__)

# Per-chip dense peak (TFLOP/s) for the compute dtype, keyed by substrings
# of jax's Device.device_kind.  An unknown device reports MFU as None
# rather than guessing.
PEAK_TFLOPS_BY_KIND = {
    "v5 lite": {"bfloat16": 197.0, "float32": 98.5},   # v5e datasheet
    "v5e": {"bfloat16": 197.0, "float32": 98.5},
    "v5p": {"bfloat16": 459.0, "float32": 229.5},
    "v4": {"bfloat16": 275.0, "float32": 137.5},
    "v6 lite": {"bfloat16": 918.0, "float32": 459.0},  # Trillium
    "v6e": {"bfloat16": 918.0, "float32": 459.0},
}


def peak_tflops(device, dtype_name: str) -> float | None:
    kind = getattr(device, "device_kind", "").lower()
    for sub, peaks in PEAK_TFLOPS_BY_KIND.items():
        if sub in kind:
            return peaks.get(dtype_name)
    return None


def lowered_flops_per_image(jitted, batch: int, *example_args) -> float | None:
    """FLOPs/image from the LOWERED (pre-compile) cost analysis.

    The serving process must never pay an XLA compile for an audit page,
    so the runtime uses the lowering-level analysis: trace + HLO emission
    only, seconds of host time, no device involvement.  For the
    conv/attention families served here the flop count is dominated by ops
    fusion does not remove, so it tracks the compiled figure closely.
    """
    try:
        ca = jitted.lower(*example_args).cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        flops = float(ca.get("flops", 0.0))
        return flops / batch if flops > 0 else None
    except Exception as e:  # noqa: BLE001 - cost analysis is best-effort
        log.info("lowered cost analysis unavailable: %r", e)
        return None
