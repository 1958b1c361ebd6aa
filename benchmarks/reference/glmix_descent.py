"""Plain reference: block coordinate descent of a GLMix, as the model is
defined (Zhang et al., KDD 2016, algorithm 1).

For each sweep and each coordinate in order: the offsets are the other
coordinates' current scores; a fixed effect is ONE regularised GLM over all
rows; a random effect is one regularised GLM per entity over that entity's
own ACTIVE rows, each at weight ``rows of the entity / active rows`` (1
where nothing was capped out); then EVERY row of the entity is scored with
its new coefficients, the passive ones too.  Every solve is
``reference/newton_solve.py``'s damped Newton (float32 ``jax.numpy`` at
``highest`` matmul precision) from zero coefficients.  No import from
``photon_ml_tpu``: no buckets, no capacity classes, no lanes; the entities
of a coordinate are stacked to the longest one's length with rows of weight
exactly 0, which add exactly 0 to every gradient and Hessian.

Departures from the reference implementation's ``RandomEffectDataset``:
which rows of a capped entity are active is an INPUT here (the problem, not
its answer: the caller reads it from the system under test, whose reservoir
keys are its own), where the reference draws them by a hash of the row's
unique id; the weight is ``count / kept`` (the reference's ``count / cap``
whenever kept = cap); there is no lower bound on active rows (every entity
with a row gets a model), no per-entity feature projection and no
down-sampling of the fixed effect; coefficients restart from zero at every
update (the minimiser of a strictly convex problem does not depend on the
start).
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np


def _newton_solve():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "newton_solve.py")
    spec = importlib.util.spec_from_file_location("bench_newton_solve", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def entity_rows(ids: np.ndarray) -> dict:
    """{entity id: its rows, ascending}."""
    order = np.argsort(ids, kind="stable")
    uniq, starts = np.unique(ids[order], return_index=True)
    return {int(e): rows for e, rows in
            zip(uniq, np.split(order, starts[1:]))}


def solve_entities(solve, x, y, offsets, rows_of, active_of, l2) -> dict:
    """{entity id: coefficients [d]}: every entity's GLM over its active
    rows in ONE stacked call, each entity's rows followed by weight-0 rows
    up to the longest entity's length."""
    entities = sorted(rows_of)
    longest = max(len(active_of[e]) for e in entities)
    d = x.shape[1]
    xs = np.zeros((len(entities), longest, d), np.float32)
    ys = np.zeros((len(entities), longest), np.float32)
    offs = np.zeros_like(ys)
    wts = np.zeros_like(ys)
    for k, e in enumerate(entities):
        act = active_of[e]
        xs[k, :len(act)] = x[act]
        ys[k, :len(act)] = y[act]
        offs[k, :len(act)] = offsets[act]
        wts[k, :len(act)] = len(rows_of[e]) / len(act)
    w = np.asarray(solve(xs, ys, offs, wts, l2))
    return {e: w[k] for k, e in enumerate(entities)}


def descend(y, features: dict, id_tags: dict, coordinates: list,
            sweeps: int, l2: float, active: dict) -> tuple:
    """``coordinates``: the configuration's list ({"id", "kind" "fixed" or
    "random", "feature_shard", "entity"}), in update order.  ``active``:
    {coordinate id: {entity id: active rows}} for the random effects; an
    entity it leaves out trains on all its rows.  Returns ``(coefficients,
    scores)``: {id: [d]} for a fixed effect and {id: {entity id: [d]}} for a
    random one; {id: [n] float32} the score of every row."""
    solve = _newton_solve().solve
    y = np.asarray(y, np.float32)
    n = len(y)
    scores = {c["id"]: np.zeros(n, np.float32) for c in coordinates}
    coefficients = {}
    for _ in range(sweeps):
        for c in coordinates:
            x = np.asarray(features[c["feature_shard"]], np.float32)
            offsets = sum(s for cid, s in scores.items() if cid != c["id"])
            if c["kind"] == "fixed":
                w = np.asarray(solve(x[None], y[None], offsets[None],
                                     np.ones((1, n), np.float32), l2))[0]
                coefficients[c["id"]] = w
                scores[c["id"]] = (x @ w).astype(np.float32)
                continue
            rows_of = entity_rows(np.asarray(id_tags[c["entity"]]))
            given = active.get(c["id"], {})
            active_of = {e: np.asarray(given.get(e, rows), np.int64)
                         for e, rows in rows_of.items()}
            ws = solve_entities(solve, x, y, offsets, rows_of, active_of, l2)
            coefficients[c["id"]] = ws
            s = np.zeros(n, np.float32)
            for e, rows in rows_of.items():  # every row, passive ones too
                s[rows] = x[rows] @ ws[e]
            scores[c["id"]] = s
    return coefficients, scores
