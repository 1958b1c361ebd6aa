"""From the end of the main program's ``descent.device_table`` span to the
end of the benchmark's ``warm_fit`` span that holds it, seconds.

A traced run builds the executable inside ``descent.device_table`` (the
last one inside ``warm_fit`` is the program the window runs: ``jit_program``,
``jit_validated`` in the tuning cell), so the dispatch that follows finds it
in jit's own caches: what is left of ``warm_fit`` is the executable's load
on the chips, the first execution and the benchmark's fence.  None where no
such span lies inside a ``warm_fit`` (an untraced run, a serving cell).
"""

import layer_join


def read(readings):
    tables = layer_join.program_spans("descent.device_table")
    for name, t0, t1 in readings["spans"]:
        if name != "warm_fit":
            continue
        ends = [r["ts_ns"] + r["dur_ns"] for r in tables
                if t0 <= r["ts_ns"] and r["ts_ns"] + r["dur_ns"] <= t1]
        if ends:
            return (t1 - max(ends)) * 1e-9
    return None
