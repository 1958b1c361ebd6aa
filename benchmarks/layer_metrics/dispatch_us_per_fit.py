"""Mean ``descent.dispatch`` span (argument preparation + enqueue of the
descent program, no fence) over the fits inside the window: the spans that
start inside one of the benchmark's own ``fit`` spans, microseconds."""

import layer_join


def read(readings):
    fits = [(t0, t1) for name, t0, t1 in readings["spans"] if name == "fit"]
    inside = [r["dur_ns"] for r in layer_join.program_spans("descent.dispatch")
              if any(t0 <= r["ts_ns"] < t1 for t0, t1 in fits)]
    if not inside:
        return None
    return sum(inside) * 1e-3 / len(inside)
