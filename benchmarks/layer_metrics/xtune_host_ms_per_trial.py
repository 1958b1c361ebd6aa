"""What the chip waits for a trial, milliseconds: the benchmark's ``trial``
intervals less the program's ``descent.fused_validated`` spans inside them
(fenced: dispatch to the program's end), plus the ``tune.propose`` spans
between the trials, over the trials.  None where the program records no
proposal span (the parent of the PR that added it)."""

import xtune_spans


def read(readings):
    trials = xtune_spans.trials(readings)
    fused = xtune_spans.inside_trials(readings, "descent.fused_validated")
    proposals = xtune_spans.between_trials(readings, "tune.propose")
    if not trials or not fused or proposals is None:
        return None
    waited = sum(t1 - t0 for t0, t1 in trials) - sum(fused) + sum(proposals)
    return waited * 1e-6 / len(trials)
