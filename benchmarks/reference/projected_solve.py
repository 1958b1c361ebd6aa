"""Plain reference: ONE entity's regularised GLM solved in the sub-space of
the columns it observed (upstream's ``IndexMapProjector``), with the
features-to-samples bound (``numFeaturesToSamplesRatioUpperBound``: a
Pearson top-k an entity), and the forward pass over sparse rows.

From the raw row-sparse pairs ``[rows, k]`` of the entity's ACTIVE rows,
in float64 numpy on the host, entity by entity: densify the rows over all
``dim`` columns (duplicate columns of a row accumulate), find the columns
with a nonzero entry, rank them by |Pearson correlation| with the label
(two passes: centre, then correlate; a column that does not vary scores
0; the intercept is pinned first), keep the ``max(1, ceil(ratio x rows))``
best (ties to the lower column), solve
``min_b sum_s wt_s l(y_s, x_s . b + off_s) + l2/2 |b|^2`` (logistic ``l``)
on the kept columns by plain Newton steps until the step is under 1e-10,
and scatter ``b`` into ``dim`` columns: every column not kept is exactly
0.  No import from ``photon_ml_tpu``: no buckets, no lanes, no index maps.

**Ties.**  Where the last kept score and the first dropped one differ by
less than ``TIE`` the kept set is not decided by the data: the entity is
reported as a tie, and a caller that knows which columns the system under
test kept may hand them over (``program_kept``); they are taken if they
are a valid choice (every column that ranks clear above the tie, the rest
out of the tied ones, the same number of columns), and refused otherwise.
"""

from __future__ import annotations

import numpy as np

TIE = 1e-6
NEWTON_STEPS = 100
STEP_TOL = 1e-10


def densify(indices: np.ndarray, values: np.ndarray, dim: int) -> np.ndarray:
    """[rows, k] pairs -> [rows, dim] float64; duplicates accumulate."""
    x = np.zeros((len(indices), dim), np.float64)
    np.add.at(x, (np.arange(len(indices))[:, None], indices),
              np.asarray(values, np.float64))
    return x


def pearson(x: np.ndarray, y: np.ndarray, wt: np.ndarray) -> np.ndarray:
    """|Pearson correlation| of each column of ``x`` with ``y`` over
    weighted rows; 0 for a column (or a label) that does not vary."""
    w = wt / wt.sum()
    dx = x - w @ x
    dy = y - w @ y
    vx = w @ (dx * dx)
    vy = w @ (dy * dy)
    steady = vx <= 1e-12 * np.maximum(1.0, (w @ x) ** 2)
    denom = np.sqrt(vx * vy)
    with np.errstate(invalid="ignore", divide="ignore"):
        score = np.abs((w * dy) @ dx) / denom
    return np.where(steady | ~(denom > 0), 0.0, score)


def kept_columns(x: np.ndarray, y: np.ndarray, wt: np.ndarray,
                 ratio: float | None, intercept: int | None,
                 program_kept: np.ndarray | None = None) -> tuple:
    """(kept columns ascending, tie?) of one entity's dense active rows."""
    observed = np.flatnonzero(np.any(x != 0.0, axis=0))
    if ratio is None or not observed.size:
        return observed, False
    keep_n = max(1, int(np.ceil(ratio * len(x))))
    if observed.size <= keep_n:
        return observed, False
    score = pearson(x[:, observed], y, wt)
    if intercept is not None:
        score[observed == intercept] = np.inf
    order = np.argsort(-score, kind="stable")        # ties to the lower column
    last, first_out = score[order[keep_n - 1]], score[order[keep_n]]
    tie = bool(np.isfinite(last) and last - first_out < TIE)
    kept = np.sort(observed[order[:keep_n]])
    if tie and program_kept is not None:
        clear = observed[score > last + TIE]
        tied = observed[np.abs(score - last) <= TIE]
        theirs = np.asarray(program_kept)
        if (len(theirs) == keep_n and np.all(np.isin(clear, theirs))
                and np.all(np.isin(theirs, np.r_[clear, tied]))):
            kept = np.sort(theirs)
    return kept, tie


def newton(x: np.ndarray, y: np.ndarray, off: np.ndarray, wt: np.ndarray,
           l2: float) -> np.ndarray:
    """The minimiser of the regularised weighted logistic loss, float64."""
    b = np.zeros(x.shape[1])
    for _ in range(NEWTON_STEPS):
        p = 1.0 / (1.0 + np.exp(-(x @ b + off)))
        g = x.T @ (wt * (p - y)) + l2 * b
        h = (x * (wt * p * (1.0 - p))[:, None]).T @ x + l2 * np.eye(len(b))
        step = np.linalg.solve(h, g)
        b = b - step
        if np.max(np.abs(step)) < STEP_TOL:
            break
    return b


def solve_entity(indices, values, y, off, wt, dim: int, l2: float,
                 ratio: float | None = None, intercept: int | None = None,
                 program_kept: np.ndarray | None = None,
                 rank_wt: np.ndarray | None = None) -> dict:
    """One entity from the raw pairs of its active rows ``[rows, k]``, its
    labels, offsets and weights ``[rows]``: {"w": [dim] float64, "kept":
    columns ascending, "tie": bool}.  The ranking reads the rows at the
    weights the data arrive with (``rank_wt``, 1 each where not given: the
    reservoir's rescale is the solve's alone); the solve at ``wt``."""
    x = densify(indices, values, dim)
    y = np.asarray(y, np.float64)
    kept, tie = kept_columns(
        x, y, np.ones(len(x)) if rank_wt is None
        else np.asarray(rank_wt, np.float64), ratio, intercept, program_kept)
    w = np.zeros(dim)
    w[kept] = newton(x[:, kept], y, np.asarray(off, np.float64),
                     np.asarray(wt, np.float64), l2)
    return {"w": w, "kept": kept, "tie": tie}


def forward(w: np.ndarray, indices: np.ndarray, values: np.ndarray
            ) -> np.ndarray:
    """``sum_k w[indices[i, k]] * values[i, k]`` for every row, float64."""
    return np.einsum("nk,nk->n", np.asarray(w, np.float64)[indices],
                     np.asarray(values, np.float64))
