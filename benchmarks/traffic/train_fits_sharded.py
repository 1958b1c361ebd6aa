"""Traffic kind ``train_fits_sharded``: ``train_fits_passive``, and the last
fit's FIXED effect held to the plain reference on rows of every chip.

``train_fits_passive`` holds the random effects to the reference, each
against the program's OWN scores of the other coordinates.  The fixed
effect it holds by the AUC band and the loss gate alone, and under a mesh
that lets the one fault a sharded fit can have go by: with the all-reduce
of an objective evaluation left out (``ShardMapObjective._psum``), every
chip fits the fixed effect to ITS rows.  At 6.25M rows a chip that costs
about d / (2 n) = 1e-5 of the loss per row, a hundredth of the loss gate's
room and the size of what the solver's own tolerance leaves undone, so
neither the gates nor a comparison with a reference SOLVE can see it.  What
such a fit cannot have is ONE fixed model: the chips' coefficients differ
in the third digit, each chip scores its own rows by its own, and the
coefficients the fit publishes (what the host reads: the first chip's) are
not the ones that scored the other chips' rows.

**The fixed rows** (``fixed_rows_check``, gate ``fixed_score_tol``): a
seeded sample of ``fixed_rows`` rows out of EACH of the cell's ``chips``
equal parts of the sample axis (a chip's shard of the design), fetched from
where they lie (a device shard gives its own rows: the design is never
gathered), and the fit's fixed score of each against ``x . w_published`` by
the plain forward reference (``reference/glmix_forward.scores``: float32,
``highest`` precision, no import from the program).  The reading is the
largest absolute difference over the largest reference score of the sample:
float32 rounding of ``d`` products in a sound fit on any number of chips,
and the distance between two chips' models where the chips did not
exchange.  ``reference_dtype`` rounds the reference's features here as in
the two other checks.
"""

from __future__ import annotations

import numpy as np


def rows_of_design(x, rows: np.ndarray) -> np.ndarray:
    """``x[rows]`` on the host, float32.  A device design gives each row
    from the shard that holds it; nothing is gathered across devices."""
    if isinstance(x, np.ndarray):
        return np.asarray(x[rows], np.float32)
    out = np.zeros((len(rows), x.shape[1]), np.float32)
    for shard in x.addressable_shards:
        lo, hi, _ = shard.index[0].indices(x.shape[0])
        here = np.flatnonzero((rows >= lo) & (rows < hi))
        if len(here):
            out[here] = np.asarray(shard.data[rows[here] - lo], np.float32)
    return out


def fixed_rows_check(ctx, cfg, data, published, scores_host) -> dict:
    """{"err", "err_by_part", "rows"} of the fixed coordinate over a
    seeded sample of the rows of every chip's part of the sample axis."""
    j, spec = next((j, c) for j, c in enumerate(cfg["coordinates"])
                   if c["kind"] == "fixed")
    passive = ctx.catalog.module("traffic", "train_fits_passive")
    forward = ctx.catalog.module("reference", "glmix_forward")
    x = data["features"][spec["feature_shard"]]
    n, parts = len(data["y"]), int(ctx.chips)
    part = -(-x.shape[0] // parts)  # a chip's rows, padding among the last's
    rng = np.random.default_rng([ctx.seed, 6])
    chosen = []
    for k in range(parts):
        mine = np.arange(k * part, min((k + 1) * part, n))
        chosen.append(np.sort(rng.choice(
            mine, size=min(int(ctx.traffic["fixed_rows"]), len(mine)),
            replace=False)))
    rows = np.concatenate(chosen)
    w = np.asarray(published[j], np.float32)[: x.shape[1]]
    ref, _ = forward.scores(
        passive.reference_features(ctx, rows_of_design(x, rows)), w, [])
    far = np.abs(scores_host[j][rows] - ref)
    largest = max(float(np.max(np.abs(ref))), 1e-30)
    ends = np.cumsum([len(c) for c in chosen])
    return {"err": float(np.max(far)) / largest,
            "err_by_part": [float(np.max(f)) / largest
                            for f in np.split(far, ends[:-1])],
            "rows": int(len(rows))}


def run(ctx) -> dict:
    passive = ctx.catalog.module("traffic", "train_fits_passive")
    # the last fit's outputs reach the mix's checks only: a copy of our own
    # of the mix below, one of its checks wrapped (as it wraps train_fits)
    base = passive.own_copy(ctx.catalog, "traffic", "train_fits_passive")
    passive_rows_check, seen = base.passive_rows_check, {}

    def passive_and_fixed(ctx, cfg, data, coords, published, scores_host):
        seen["fixed"] = fixed_rows_check(ctx, cfg, data, published,
                                         scores_host)
        return passive_rows_check(ctx, cfg, data, coords, published,
                                  scores_host)

    base.passive_rows_check = passive_and_fixed
    result = base.run(ctx)
    fixed = seen["fixed"]
    result["detail"].update(fixed_rows_err=fixed["err"],
                            fixed_rows_err_by_part=fixed["err_by_part"],
                            fixed_rows_checked=fixed["rows"])
    tol = ctx.workload.get("gates", {}).get("fixed_score_tol")
    if tol is not None:
        result["checks"]["fixed_rows_scored"] = bool(fixed["err"] <= tol)
    return result
