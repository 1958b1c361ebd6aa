"""Mean ``validate.evaluate`` span (the host's part of the metric suite) of
the trials inside the window: the spans that start inside one of the
benchmark's own ``trial`` spans, milliseconds."""

import xtune_spans


def read(readings):
    inside = xtune_spans.inside_trials(readings, "validate.evaluate")
    if not inside:
        return None
    return sum(inside) * 1e-6 / len(xtune_spans.trials(readings))
