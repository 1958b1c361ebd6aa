"""Passes over a chip's shard of the fixed design per fit: calls of the
fixed-effect kernels in the traced slice (``ops_self`` sums them over the
chips) over the chips and the fits the slice holds.  ``fixed_passes_per_fit``
for a cell on more than one chip, where that reader counts every chip's
call."""

import trace_reduce

KERNELS = ("fused_glm_value_grad", "fused_glm_hvp")


def read(readings):
    p, fits = readings["profile"], readings["measured"].get("slice_fits")
    if not p or not fits:
        return None
    _, calls = trace_reduce.time_of(p["ops_self"], *KERNELS)
    return calls / p["chips"] / fits if calls else None
