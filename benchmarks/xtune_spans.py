"""What the ``xtune_*`` readers share: the benchmark's ``trial`` spans and
the program's spans that lie inside or between them (nanoseconds, both on
``perf_counter_ns``)."""

import layer_join


def trials(readings) -> list:
    return [(t0, t1) for name, t0, t1 in readings["spans"] if name == "trial"]


def inside_trials(readings, name: str) -> list:
    """Durations of the program's spans ``name`` that start inside a trial."""
    found = trials(readings)
    return [r["dur_ns"] for r in layer_join.program_spans(name)
            if any(t0 <= r["ts_ns"] < t1 for t0, t1 in found)]


def between_trials(readings, name: str):
    """Durations of the program's spans ``name`` from the first trial's
    start to the last trial's end; None where the program records none."""
    found, spans = trials(readings), layer_join.program_spans(name)
    if not found or not spans:
        return None
    return [r["dur_ns"] for r in spans
            if found[0][0] <= r["ts_ns"] < found[-1][1]]
