"""Passes over the fixed design per fit: calls of the fixed-effect kernels
in the traced slice over the fits the slice holds."""

import trace_reduce

KERNELS = ("fused_glm_value_grad", "fused_glm_hvp")


def read(readings):
    p, fits = readings["profile"], readings["measured"].get("slice_fits")
    if not p or not fits:
        return None
    _, calls = trace_reduce.time_of(p["ops_self"], *KERNELS)
    return calls / fits if calls else None
