"""Achieved HBM bytes/s of the fixed-effect kernels over the chip's peak,
%: bytes one call must move (roofline.fused_glm_call, from the config's
shapes) x calls in the slice, over those calls' device time."""

import roofline
import trace_reduce
from layer_metrics_common import fixed_shape

KERNELS = ("fused_glm_value_grad", "fused_glm_hvp")


def read(readings):
    p = readings["profile"]
    if not p:
        return None
    seconds, calls = trace_reduce.time_of(p["ops_self"], *KERNELS)
    if seconds <= 0:
        return None
    n, d, storage = fixed_shape(readings["config"])
    need = roofline.fused_glm_call(n // readings["chips"], d, storage)
    peak = roofline.peaks_for(readings["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * need["bytes"] * calls / seconds / peak
