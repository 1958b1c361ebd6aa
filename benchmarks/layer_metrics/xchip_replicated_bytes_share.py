"""Of what the coordinates keep on the device, the share every chip holds
whole, %: ``bytes_replicated`` over ``bytes_sharded + bytes_replicated`` of
the program's ``coord.upload`` spans.  None where they carry no such
attributes (the parent of the PR that added them)."""

import layer_join


def read(readings):
    spans = [s["attrs"] for s in layer_join.program_spans("coord.upload")
             if "bytes_replicated" in s["attrs"]]
    held = sum(a["bytes_sharded"] + a["bytes_replicated"] for a in spans)
    if not held:
        return None
    return 100.0 * sum(a["bytes_replicated"] for a in spans) / held
