"""``tune.propose`` spans (the search choosing the next candidate) between
the window's first trial's start and its last trial's end, over the trials,
milliseconds."""

import xtune_spans


def read(readings):
    between = xtune_spans.between_trials(readings, "tune.propose")
    if between is None:
        return None
    return sum(between) * 1e-6 / len(xtune_spans.trials(readings))
