"""Kept columns over compact columns (lanes x d_proj) of the compact
coordinates' ``coord.bucket`` spans, %.  None where the program records no
compact coordinate."""

import layer_join


def read(readings):
    found = [s["attrs"] for s in layer_join.program_spans("coord.bucket")
             if s["attrs"].get("compact_columns")]
    if not found:
        return None
    return (100.0 * sum(a["kept_columns"] for a in found)
            / sum(a["compact_columns"] for a in found))
