"""What a sparse full-sample rescore MUST move, from its shapes alone.

Kept with the benchmark, beside ``roofline.py``: the bytes a roofline share
rests on are the yardstick's, not the program's.  A rescore of ``n`` rows
of ``k`` (index, value) pairs against per-entity coefficients reads every
pair once (an int32 index and a float32 value: 8 bytes), one float32
coefficient a pair (4 bytes: the gather's, whatever implements it; a
coefficient read twice is still counted once a pair, since which pairs
share one is the data's) and writes one float32 score a row.  Padding pairs
are counted: the layout stores and reads them.
"""

from __future__ import annotations

import re


def rescore_call(n: int, k: int) -> dict:
    """One sparse rescore of [n, k] pairs: bytes it must move, flops."""
    return {"bytes": n * k * 8 + n * k * 4 + 4 * n, "flops": 2 * n * k}


def sparse_coordinates() -> dict:
    """{coordinate id: the attributes of its ``coord.rescore_layout`` span}
    for the coordinates whose full-sample layout is ``sparse``; empty where
    the program records none."""
    import layer_join

    return {s["attrs"]["coordinate"]: s["attrs"]
            for s in layer_join.program_spans("coord.rescore_layout")
            if s["attrs"].get("layout") == "sparse"}


def rescore_seconds(readings: dict) -> float | None:
    """Device self seconds, over the traced slice, of the instructions
    under ``photon.rescore`` of a coordinate whose layout is sparse."""
    import layer_join

    # a coordinate id as ``photon.update.<cid>`` spells it
    # (``obs.trace.device_scope`` sanitises ids the same way)
    mine = {re.sub(r"[^A-Za-z0-9_]", "_", cid) for cid in sparse_coordinates()}
    if not mine:
        return None

    def key(path):
        return (layer_join.coordinate_of(path) in mine
                and layer_join.layer_of(path) == "rescore")

    seconds = layer_join.seconds_by(readings, key)
    return None if seconds is None else seconds.get(True, 0.0)
