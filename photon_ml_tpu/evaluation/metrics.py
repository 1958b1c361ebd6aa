"""Evaluation metrics as pure JAX reductions.

Reference: photon-api .../evaluation/** — AreaUnderROCCurveLocalEvaluator.scala:33-72
(exact sort-based AUC with tie handling), AUPR, RMSE, pointwise-loss metrics,
PrecisionAtKLocalEvaluator.

TPU shape: metrics are weighted, statically-shaped reductions over
(score, label, weight) arrays; invalid/padded rows carry weight 0.  AUC uses a
full sort — exact, like the reference's local evaluator, not a histogram
approximation; ties are handled by trapezoidal integration over tied-score
runs.  The rank metrics are ONE ``lax.sort`` that carries the weights along
and segmented scans over rows of 128 (``segmented_scan``): the same code
serves the whole sample (one segment) and the per-id-tag "multi" form
(``grouped_metric``: one segment a group, sorted by (group, score)).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array


def _wsum(x: Array, w: Array) -> Array:
    return jnp.sum(x * w)


def rmse(scores: Array, labels: Array, weights: Array) -> Array:
    """Weighted RMSE (reference RMSEEvaluator.scala)."""
    tot = jnp.sum(weights)
    se = _wsum((scores - labels) ** 2, weights)
    return jnp.sqrt(se / jnp.where(tot == 0, 1.0, tot))


def squared_loss_metric(scores: Array, labels: Array, weights: Array) -> Array:
    from photon_ml_tpu.core.losses import squared_loss

    return _wsum(squared_loss.loss(scores, labels), weights)


def logistic_loss_metric(scores: Array, labels: Array, weights: Array) -> Array:
    from photon_ml_tpu.core.losses import logistic_loss

    return _wsum(logistic_loss.loss(scores, labels), weights)


def poisson_loss_metric(scores: Array, labels: Array, weights: Array) -> Array:
    from photon_ml_tpu.core.losses import poisson_loss

    return _wsum(poisson_loss.loss(scores, labels), weights)


def smoothed_hinge_loss_metric(scores: Array, labels: Array, weights: Array) -> Array:
    from photon_ml_tpu.core.losses import smoothed_hinge_loss

    return _wsum(smoothed_hinge_loss.loss(scores, labels), weights)


_LANES = 128  # the segmented scans work on rows of this many


def _shift(a: Array, k: int, fill) -> Array:
    """``a`` moved ``k`` places up its last axis, ``fill`` coming in."""
    return jnp.pad(a[..., :-k], [(0, 0)] * (a.ndim - 1) + [(k, 0)],
                   constant_values=fill)


def _scan_lanes(v: Array, f: Array, op, identity):
    """Segmented inclusive scan along the LAST axis (length at most a few
    hundred), Hillis-Steele: ``f`` marks the elements that start a segment;
    returns (scanned values, "a start lies at or before me on this axis")."""
    k = 1
    while k < v.shape[-1]:
        v = jnp.where(f, v, op(v, _shift(v, k, identity)))
        f = f | _shift(f, k, False)
        k *= 2
    return v, f


def segmented_scan(v: Array, start: Array, op=jnp.add, identity=0) -> Array:
    """Inclusive scan of ``v`` [..., n] along its last axis under ``op``
    (``identity`` its neutral element over the values scanned) that
    restarts wherever ``start`` [n] is True.  No gather, no scatter and no
    global running total: rows of 128 are scanned in registers (seven
    shifted combines), the rows' last values are scanned the same way one
    level up, and a row takes the carry of the rows before it up to its
    first start.  The error of a sum is that of its own segment's
    magnitude, whatever lies before it."""
    n = v.shape[-1]
    if n <= _LANES:
        return _scan_lanes(v, start, op, identity)[0]
    pad = -n % _LANES
    if pad:
        v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, pad)])
        start = jnp.pad(start, (0, pad), constant_values=True)
    v2, f2 = _scan_lanes(v.reshape(*v.shape[:-1], -1, _LANES),
                         start.reshape(-1, _LANES), op, identity)
    # what the rows before hand a row: the scan of the rows' last values,
    # one row back; a row with a start in it hands on its own tail only
    carry = _shift(segmented_scan(v2[..., -1], f2[:, -1], op, identity), 1,
                   identity)
    out = jnp.where(f2, v2, op(carry[..., None], v2))
    out = out.reshape(*v.shape[:-1], -1)
    return out[..., :n] if pad else out


def _sorted_by_group(key: Optional[Array], operands, gid: Optional[Array],
                     num_groups: int):
    """Rows sorted by (group ascending, ``key`` ascending), stably: returns
    (sorted key, sorted operands, group-start flags, group-end flags), every
    array padded to whole rows of ``_LANES`` with rows of a group of their
    own behind the last (key +inf, operands 0).  ``gid`` None: one group;
    ``key`` None: by group alone."""
    n = operands[0].shape[0]
    pad = -n % _LANES
    if pad:
        if key is not None:
            key = jnp.pad(key, (0, pad), constant_values=jnp.inf)
        operands = [jnp.pad(o, (0, pad)) for o in operands]
        if gid is not None:
            gid = jnp.pad(gid, (0, pad), constant_values=num_groups)
    keys = [a for a in (gid, key) if a is not None]
    out = jax.lax.sort((*keys, *operands), num_keys=len(keys), is_stable=True)
    operands = list(out[len(keys):])
    edge = jnp.ones((1,), bool)
    if gid is None:
        grp_start = jnp.arange(n + pad) == 0
    else:
        grp_start = jnp.concatenate([edge, out[0][1:] != out[0][:-1]])
    grp_end = jnp.concatenate([grp_start[1:], edge])
    return (out[len(keys) - 1] if key is not None else None, operands,
            grp_start, grp_end)


def _over_groups(values: Array, total_weight: Array, grp_end: Array) -> Array:
    """Mean of the per-group ``values`` (read at the rows that end a group)
    over the groups with weight (reference MultiEvaluator.evaluate:36-70)."""
    has_w = grp_end & (total_weight > 0)
    return (jnp.sum(jnp.where(has_w, values, 0.0))
            / jnp.maximum(jnp.sum(has_w), 1))


def _rank_terms(scores: Array, labels: Array, weights: Array,
                gid: Optional[Array], num_groups: int):
    """Rows by (group, score descending); per row, relative to its GROUP:
    cumulative weighted TP / FP through the row, the same before the row's
    tied-score run, and the flags that end a run and a group.

    Tie handling: a tied-score run is integrated once, at its end, as the
    trapezoid between the counts before it and through it (the reference's
    grouped iteration, AreaUnderROCCurveLocalEvaluator.scala:45-70)."""
    pos_w = weights * (labels > 0.5)
    neg_w = weights * (labels <= 0.5)
    key, both, grp_start, grp_end = _sorted_by_group(
        -scores, [pos_w, neg_w], gid, num_groups)
    edge = jnp.ones((1,), bool)
    tie_start = grp_start | jnp.concatenate([edge, key[1:] != key[:-1]])
    tie_end = jnp.concatenate([tie_start[1:], edge])
    through = segmented_scan(jnp.stack(both), grp_start)  # [2, n]: TP, FP
    # the counts through the row before a run's first (0 at a group's
    # first), carried along the run: counts never fall, so a running maximum
    # holds the latest run's
    prev = jnp.where(grp_start, 0.0, _shift(through, 1, 0.0))
    before = segmented_scan(jnp.where(tie_start, prev, 0.0), grp_start,
                            jnp.maximum)
    return (through[0], through[1], before[0], before[1], tie_end, grp_start,
            grp_end)


def _auc_roc(scores, labels, weights, gid=None, num_groups=1):
    ctp, cfp, prev_tp, prev_fp, tie_end, grp_start, grp_end = _rank_terms(
        scores, labels, weights, gid, num_groups)
    # per tied run (counted once at its end): trapezoid on the ROC curve
    # between (prev_fp, prev_tp) and (cfp, ctp)
    area = segmented_scan(
        jnp.where(tie_end, (cfp - prev_fp) * 0.5 * (ctp + prev_tp), 0.0),
        grp_start)
    degenerate = (ctp == 0) | (cfp == 0)  # at a group's end: its totals
    auc = jnp.where(degenerate, 0.5,
                    area / jnp.where(degenerate, 1.0, ctp * cfp))
    if gid is None:
        return auc[-1]
    return _over_groups(auc, ctp + cfp, grp_end)


def _auc_pr(scores, labels, weights, gid=None, num_groups=1):
    ctp, cfp, prev_tp, prev_fp, tie_end, grp_start, grp_end = _rank_terms(
        scores, labels, weights, gid, num_groups)
    prec_end = ctp / jnp.maximum(ctp + cfp, 1e-30)
    prec_prev = jnp.where(prev_tp + prev_fp > 0,
                          prev_tp / jnp.maximum(prev_tp + prev_fp, 1e-30), 1.0)
    # recall runs over the group's positives: divided out at the group's end
    area = segmented_scan(
        jnp.where(tie_end, (ctp - prev_tp) * 0.5 * (prec_end + prec_prev),
                  0.0), grp_start)
    aupr = jnp.where(ctp == 0, 0.0, area / jnp.where(ctp == 0, 1.0, ctp))
    if gid is None:
        return aupr[-1]
    return _over_groups(aupr, ctp + cfp, grp_end)


@jax.jit
def auc_roc(scores: Array, labels: Array, weights: Array) -> Array:
    """Exact weighted ROC AUC with tie handling (trapezoidal).

    Degenerate inputs (no positives or no negatives) return 0.5, the
    convention downstream model selection relies on.

    One sort that carries the weights along, then scans: no gather and no
    scatter of n indices (on a v5e an index costs 7 ns: PERF.md section 5).
    jitted at definition: the pipeline otherwise dispatches op by op;
    inside an outer jit the decorator is a no-op (inlined)."""
    return _auc_roc(scores, labels, weights)


@jax.jit
def auc_pr(scores: Array, labels: Array, weights: Array) -> Array:
    """Weighted area under the precision-recall curve (linear interpolation
    in recall, like the reference's Spark BinaryClassificationMetrics).
    jitted at definition for the same reason as auc_roc."""
    return _auc_pr(scores, labels, weights)


def _precision_at_k(k, scores, labels, weights, gid=None, num_groups=1):
    valid = weights > 0
    masked = jnp.where(valid, scores, -jnp.inf)
    dtype = scores.dtype
    _, (hit, valid, w), grp_start, grp_end = _sorted_by_group(
        -masked, [((labels > 0.5) & valid).astype(dtype),
                  valid.astype(dtype), weights], gid, num_groups)
    top = segmented_scan(jnp.ones_like(w), grp_start) <= k
    hits, ranked, tot_w = segmented_scan(
        jnp.stack([jnp.where(top, hit, 0.0), jnp.where(top, valid, 0.0), w]),
        grp_start)
    value = hits / jnp.maximum(ranked, 1)
    if gid is None:
        return value[-1]
    return _over_groups(value, tot_w, grp_end)


def precision_at_k(k: int, scores: Array, labels: Array, weights: Array) -> Array:
    """Unweighted precision among the top-k scores (reference
    PrecisionAtKLocalEvaluator; the reference ignores weights here too).
    Rows with weight 0 (padding) are pushed out of the ranking."""
    return _precision_at_k(k, scores, labels, weights)


def _grouped_sum(term: Array, weights: Array, gid: Array, num_groups: int,
                 finish=None) -> Array:
    """Mean over the groups with weight of ``finish(sum of term, sum of
    weight)`` (the sum itself where ``finish`` is None)."""
    _, (term, w), grp_start, grp_end = _sorted_by_group(
        None, [term, weights], gid, num_groups)
    total, tot_w = segmented_scan(jnp.stack([term, w]), grp_start)
    return _over_groups(total if finish is None else finish(total, tot_w),
                        tot_w, grp_end)


def grouped_metric(kind: str, scores: Array, labels: Array, weights: Array,
                   gid: Array, num_groups: int, k: int = 0) -> Array:
    """The metric ``kind`` (an ``EvaluatorType`` value) of every group of
    rows, averaged over the groups with weight (reference
    MultiEvaluator.evaluate:36-70): ``gid`` [n] int32 names each row's group
    in ``[0, num_groups)``.  Traceable; O(n) memory whatever the largest
    group: one sort by (group, score) that carries the weights along, then
    segmented scans.  Weights must not be negative."""
    if kind == "auc":
        return _auc_roc(scores, labels, weights, gid, num_groups)
    if kind == "aupr":
        return _auc_pr(scores, labels, weights, gid, num_groups)
    if kind == "precision_at_k":
        return _precision_at_k(k, scores, labels, weights, gid, num_groups)
    if kind == "rmse":
        return _grouped_sum(
            (scores - labels) ** 2 * weights, weights, gid, num_groups,
            lambda se, tot: jnp.sqrt(se / jnp.where(tot == 0, 1.0, tot)))
    from photon_ml_tpu.core import losses

    loss = getattr(losses, kind)  # logistic_loss, poisson_loss, ...
    return _grouped_sum(loss.loss(scores, labels) * weights, weights, gid,
                        num_groups)
