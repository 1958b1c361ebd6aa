"""Device self time of the per-entity solves of the capacity classes of
256 rows and over, over device busy time, %: the op-to-layer join by
(coordinate, ``entity_solve.b<n>``) x the capacities ``coord.bucket``
recorded."""

import re

import class_join
import layer_join

TAIL_CAPACITY = 256


def scope_id(coordinate: str) -> str:
    """A coordinate id as ``photon.update.<cid>`` spells it
    (``obs.trace.device_scope`` sanitises ids the same way)."""
    return re.sub(r"[^A-Za-z0-9_]", "_", coordinate)


def read(readings):
    tail = {(scope_id(cid), f"entity_solve.b{k}")
            for cid, c in class_join.classes().items()
            for k, cap in enumerate(c["capacities"]) if cap >= TAIL_CAPACITY}
    seconds = layer_join.seconds_by(
        readings, lambda path: (layer_join.coordinate_of(path),
                                layer_join.layer_of(path)))
    if seconds is None or not tail or readings["profile"]["busy_s"] <= 0:
        return None
    profile = readings["profile"]
    return (100.0 * sum(s for key, s in seconds.items() if key in tail)
            / (profile["busy_s"] * profile["chips"]))
