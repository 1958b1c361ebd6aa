"""Plain reference: what a tuning trial reports of a held-out set.

Held-out scores of an exported GLMix through ``reference/glmix_forward.py``
(an entity the training set never saw has slot -1 and adds 0), the rank AUC
of all rows with average ranks on ties, and the per-group AUC as upstream's
``MultiEvaluator`` defines it (MultiEvaluator.scala:36-70): a plain loop
over the groups, each group's own rank AUC, averaged over the groups that
have both classes.  ``numpy`` and float32 ``jax.numpy`` at ``highest``
matmul precision; no sort on the device, no layout, no segment arithmetic
and no import from ``photon_ml_tpu``.

The program's ``auc:<tag>`` counts a group that has rows but one class as
0.5 (its single-evaluator convention, and what it has always reported);
upstream leaves such a group out.  ``per_group_auc`` gives both means and
the number of single-class groups, so that the comparison can hold the
program to ITS definition and say how far that lies from upstream's.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np


def _forward():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "glmix_forward.py")
    spec = importlib.util.spec_from_file_location("bench_glmix_forward", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def slots_of(entities: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Row of each id in the sorted ``entities`` of a coefficient table,
    -1 for an id that is not among them."""
    entities = np.asarray(entities, np.int64)
    at = np.clip(np.searchsorted(entities, ids), 0, len(entities) - 1)
    return np.where(entities[at] == ids, at, -1).astype(np.int64)


def heldout_scores(x_fixed, w_fixed, random_effects, rows=None) -> np.ndarray:
    """Scores [rows] of a held-out set: ``random_effects`` a list of
    (x [n, d], table [entities, d], sorted entity ids [entities], the rows'
    ids [n]); ``rows`` picks a sample of them."""
    take = (lambda a: a) if rows is None else (lambda a: a[rows])
    parts = [(take(x), table, slots_of(entities, take(ids)))
             for x, table, entities, ids in random_effects]
    return _forward().scores(take(x_fixed), w_fixed, parts)[0]


def rank_auc(y: np.ndarray, s: np.ndarray) -> float:
    """Rank AUC with average ranks on ties; NaN with one class."""
    y = np.asarray(y, bool)
    order = np.argsort(s, kind="stable")
    s_sorted = np.asarray(s)[order]
    ranks = np.empty(len(s), np.float64)
    ranks[order] = np.arange(1, len(s) + 1, dtype=np.float64)
    starts = np.flatnonzero(np.r_[True, s_sorted[1:] != s_sorted[:-1]])
    if len(starts) != len(s):
        ends = np.r_[starts[1:], len(s)]
        ranks[order] = np.repeat((starts + 1 + ends) / 2.0, ends - starts)
    n1 = int(y.sum())
    n0 = len(y) - n1
    if n1 == 0 or n0 == 0:
        return float("nan")
    return float((ranks[y].sum() - n1 * (n1 + 1) / 2) / (n1 * n0))


def per_group_auc(y: np.ndarray, s: np.ndarray, groups: np.ndarray) -> dict:
    """{"both_classes": mean AUC over the groups with both classes
    (upstream's rule), "half_for_one_class": the mean over ALL groups with a
    one-class group counted 0.5 (the program's), "groups", "one_class"}."""
    order = np.argsort(groups, kind="stable")
    y, s, g = np.asarray(y)[order], np.asarray(s)[order], groups[order]
    starts = np.flatnonzero(np.r_[True, g[1:] != g[:-1]])
    ends = np.r_[starts[1:], len(g)]
    total, one_class = 0.0, 0
    for a, b in zip(starts, ends):
        auc = rank_auc(y[a:b], s[a:b])
        if np.isnan(auc):
            one_class += 1
        else:
            total += auc
    both = len(starts) - one_class
    return {"both_classes": total / max(both, 1),
            "half_for_one_class": (total + 0.5 * one_class)
            / max(len(starts), 1),
            "groups": int(len(starts)), "one_class": int(one_class)}
