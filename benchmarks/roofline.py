"""What a kernel must move and compute, from its shapes, and the chip's
peaks.  Kept with the benchmark: the arithmetic a roofline share rests on
is the yardstick's, not the program's.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4, None: 4}


def peaks_for(device_kind: str, path: str | None = None) -> dict:
    """The chip's published peaks (peaks.json).  A device that is not in
    the table is an error, not a default: a share of another chip's peak
    is wrong."""
    with open(path or os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}: add it to peaks.json with its "
                       f"source (known: {sorted(table)})")
    return table[device_kind]


def fused_glm_call(n: int, d: int, storage_dtype: str | None) -> dict:
    """One call of ``fused_glm_value_grad`` or ``fused_glm_hvp``: one pass
    over the [n, d] design at its storage width, the stacked
    label/offset/weight rows [3, n] in float32, and O(d) coefficients and
    accumulators.  2 flops per design entry for the margins and 2 for
    X^T r."""
    return {"bytes": n * d * _ITEMSIZE[storage_dtype] + 3 * n * 4 + 16 * d * 4,
            "flops": 4 * n * d}
