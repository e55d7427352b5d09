"""MFU (model FLOPs utilization) accounting for bench.py.

MFU = model FLOPs per second / peak bf16 FLOPs of the chip. Since round
5 the model-FLOP count comes from an exact jaxpr walk of the per-step
train function (`paddle_tpu.ops.kernel_flops.train_step_flops`): dot and
conv FLOPs, scan bodies multiplied by their static length, pallas kernel
bodies multiplied by their grid size. XLA's own cost analysis
(`flops_of_compiled` below) remains as the fallback basis, but it counts
a scan/while body ONCE regardless of trip count and cannot see inside
pallas_call custom calls — which understated the recurrent legs' MFU
several-fold through round 4 (restated in RESULTS.md). When the
fallback is used with pallas kernels in the step, their analytic counts
(recorded at trace time) are added to partially compensate. `bench.py` can additionally
capture an xplane trace of the timed window (PADDLE_TPU_BENCH_TRACE_DIR)
for profile-level verification of the step time; the trace is for
inspection, the MFU number printed in the bench JSON comes from the
formula above.

Peak numbers are per jax device (= one chip on v4+), bf16, from Google's
published TPU specs. Unknown device kinds yield None (MFU omitted, never
guessed).
"""

from __future__ import annotations

from typing import Optional

# the peak table lives with the FLOP accounting in the package (the
# trainer's MFU logging uses it too); re-exported here for callers
from paddle_tpu.ops.kernel_flops import peak_tflops  # noqa: F401


def flops_of_compiled(compiled) -> Optional[float]:
    """FLOPs of one execution of an AOT-compiled jit (XLA cost analysis).

    The caller compiles once (``jitted.lower(*args).compile()``) and uses
    the SAME executable for the timed loop, so the analysis describes
    exactly what ran."""
    try:
        flops = compiled.cost_analysis().get("flops")
        return float(flops) if flops else None
    except Exception:
        return None


def mfu(flops_per_step: Optional[float], step_time_s: float,
        device_kind: str) -> Optional[float]:
    """Fraction of peak bf16 FLOP/s sustained; None if either the FLOP
    count or the chip's peak is unknown."""
    peak = peak_tflops(device_kind)
    if flops_per_step is None or peak is None or step_time_s <= 0:
        return None
    return (flops_per_step / step_time_s) / (peak * 1e12)
