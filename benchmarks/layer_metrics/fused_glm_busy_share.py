"""Device time of the fixed-effect kernels over device busy time, %."""

import trace_reduce

KERNELS = ("fused_glm_value_grad", "fused_glm_hvp")


def read(readings):
    p = readings["profile"]
    if not p or p["busy_s"] <= 0:
        return None
    seconds, _ = trace_reduce.time_of(p["ops_self"], *KERNELS)
    return 100.0 * seconds / (p["busy_s"] * p["chips"])
