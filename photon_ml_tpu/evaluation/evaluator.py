"""Evaluator objects, suites, and grouped (per-id-tag) evaluation.

Reference: photon-lib .../evaluation/Evaluator.scala:69 (betterThan + evaluate),
EvaluatorType.scala (AUC, AUPR, RMSE, LogisticLoss, PoissonLoss, SquaredLoss,
SmoothedHingeLoss, PrecisionAtK), MultiEvaluator.scala:36-70 (group by id tag,
evaluate each group with a LocalEvaluator, average the per-group metrics),
EvaluationSuite.scala:33-115 (evaluator set + distinguished primary).

Grouped evaluation on TPU: the groups of a sample set are a LAYOUT built once
(``GroupLayout``: each row's dense group index, uploaded once); a call is one
sort by (group, score) and segmented scans on the device, O(n) memory whatever
the largest group (``metrics.grouped_metric``) — the reference's
shuffle-and-iterate becomes one jitted program.  ``EvaluationSuite.
device_inputs`` + ``trace_evaluate`` are the traced form the validated sweep
runs inside its program (game/fused.py).
"""

from __future__ import annotations

import dataclasses
import enum
import functools
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.evaluation import metrics as M

Array = jax.Array
MetricFn = Callable[[Array, Array, Array], Array]


class EvaluatorType(enum.Enum):
    AUC = "auc"
    AUPR = "aupr"
    RMSE = "rmse"
    LOGISTIC_LOSS = "logistic_loss"
    POISSON_LOSS = "poisson_loss"
    SQUARED_LOSS = "squared_loss"
    SMOOTHED_HINGE_LOSS = "smoothed_hinge_loss"
    PRECISION_AT_K = "precision_at_k"


_LARGER_IS_BETTER = {
    EvaluatorType.AUC, EvaluatorType.AUPR, EvaluatorType.PRECISION_AT_K,
}

_METRIC_FNS: Dict[EvaluatorType, MetricFn] = {
    EvaluatorType.AUC: M.auc_roc,
    EvaluatorType.AUPR: M.auc_pr,
    EvaluatorType.RMSE: M.rmse,
    EvaluatorType.LOGISTIC_LOSS: M.logistic_loss_metric,
    EvaluatorType.POISSON_LOSS: M.poisson_loss_metric,
    EvaluatorType.SQUARED_LOSS: M.squared_loss_metric,
    EvaluatorType.SMOOTHED_HINGE_LOSS: M.smoothed_hinge_loss_metric,
}


@dataclasses.dataclass(frozen=True)
class Evaluator:
    """A named metric with an ordering (reference Evaluator.betterThan).

    ``group_ids`` (set at construction for Multi- evaluators): per-sample group
    labels; the metric is computed per group and averaged, reference
    MultiEvaluator semantics.
    """

    kind: EvaluatorType
    k: int = 0  # PRECISION_AT_K only
    group_name: Optional[str] = None  # None = single evaluator

    @property
    def name(self) -> str:
        base = f"{self.kind.value}@{self.k}" if self.kind == EvaluatorType.PRECISION_AT_K else self.kind.value
        return f"{base}:{self.group_name}" if self.group_name else base

    @property
    def larger_is_better(self) -> bool:
        return self.kind in _LARGER_IS_BETTER

    def better_than(self, a: float, b: float) -> bool:
        return a > b if self.larger_is_better else a < b

    def metric_fn(self) -> MetricFn:
        if self.kind == EvaluatorType.PRECISION_AT_K:
            k = self.k
            return lambda s, l, w: M.precision_at_k(k, s, l, w)
        return _METRIC_FNS[self.kind]

    def trace_evaluate(self, scores: Array, labels: Array, weights: Array,
                       layout: "Optional[GroupLayout]" = None) -> Array:
        """Traceable: the metric as a device scalar; for a Multi- evaluator
        over the rows' groups in ``layout``."""
        if self.group_name is None:
            return self.metric_fn()(scores, labels, weights)
        if layout is None:
            raise ValueError(f"evaluator {self.name} needs group ids '{self.group_name}'")
        return M.grouped_metric(self.kind.value, scores, labels, weights,
                                layout.gid, layout.num_groups, k=self.k)

    def evaluate(self, scores: Array, labels: Array, weights: Array,
                 group_ids: "Optional[np.ndarray | GroupLayout]" = None) -> float:
        """Host: the metric as a float.  ``group_ids``: the rows' group
        labels, or the ``GroupLayout`` a caller that evaluates the same
        rows again built of them once."""
        if self.group_name is None:
            return float(self.metric_fn()(scores, labels, weights))
        if group_ids is None:
            raise ValueError(f"evaluator {self.name} needs group ids '{self.group_name}'")
        layout = (group_ids if isinstance(group_ids, GroupLayout)
                  else GroupLayout.build(group_ids))
        return float(_grouped_jit(self, jnp.asarray(scores), jnp.asarray(labels),
                                  jnp.asarray(weights), layout))


def make_evaluator(spec: str) -> Evaluator:
    """Parse an evaluator spec: 'auc', 'rmse', 'precision@5', 'auc:userId'
    (grouped), 'precision@3:songId' (reference MultiEvaluatorType grammar)."""
    group = None
    if ":" in spec:
        spec, group = spec.split(":", 1)
    if spec.startswith("precision@"):
        return Evaluator(EvaluatorType.PRECISION_AT_K, k=int(spec.split("@")[1]), group_name=group)
    return Evaluator(EvaluatorType(spec), group_name=group)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class GroupLayout:
    """The groups of one sample set under one id tag, built ONCE
    (``np.unique`` on the host, one upload): ``gid`` [n] int32 on the device,
    each row's group as a dense index in ``[0, num_groups)``.  The groups of
    a held-out set never change during a fit or a search; what changes is
    the scores, and those are sorted on the device."""

    gid: Array
    num_groups: int

    @classmethod
    def build(cls, group_ids: np.ndarray) -> "GroupLayout":
        uniq, inverse = np.unique(np.asarray(group_ids), return_inverse=True)
        return cls(gid=jnp.asarray(inverse.astype(np.int32)),
                   num_groups=len(uniq))

    def tree_flatten(self):
        return (self.gid,), self.num_groups

    @classmethod
    def tree_unflatten(cls, num_groups, children):
        return cls(gid=children[0], num_groups=num_groups)


@functools.partial(jax.jit, static_argnums=0)
def _grouped_jit(evaluator: Evaluator, scores, labels, weights, layout):
    return evaluator.trace_evaluate(scores, labels, weights, layout)


def grouped_evaluate(metric_fn: MetricFn, group_ids: np.ndarray, scores: Array,
                     labels: Array, weights: Array) -> float:
    """The padded ORACLE of ``metrics.grouped_metric``, for small sizes
    (tests): per-group metric, unweighted-averaged over groups with >0 total
    weight (reference MultiEvaluator.evaluate:36-70).

    Pads groups to the max group size and vmaps the metric (groups x largest
    group slots, rebuilt on the host on every call: at 90,112 users with up
    to 1,790 held-out rows that is 1.9 GB for 2.6M rows, which is why
    nothing in the package calls it any more); padding rows have weight 0.
    """
    group_ids = np.asarray(group_ids)
    uniq, inverse, counts = np.unique(group_ids, return_inverse=True, return_counts=True)
    g, smax = len(uniq), int(counts.max()) if len(counts) else 0
    if g == 0:
        return float("nan")
    order = np.argsort(inverse, kind="stable")
    # slot position of each sample within its group
    pos = np.arange(len(group_ids)) - np.concatenate([[0], np.cumsum(counts)])[inverse[order]]

    def pad(a, fill=0.0):
        out = np.full((g, smax), fill, np.asarray(a).dtype)
        out[inverse[order], pos] = np.asarray(a)[order]
        return jnp.asarray(out)

    ps, pl, pw = pad(np.asarray(scores)), pad(np.asarray(labels)), pad(np.asarray(weights))
    vals = jax.vmap(metric_fn)(ps, pl, pw)
    has_w = jnp.sum(pw, axis=1) > 0
    denom = jnp.maximum(jnp.sum(has_w), 1)
    return float(jnp.sum(jnp.where(has_w, vals, 0.0)) / denom)


@dataclasses.dataclass
class EvaluationResults:
    """Metric name -> value, with the primary distinguished
    (reference EvaluationResults.scala)."""

    values: Dict[str, float]
    primary_name: str

    @property
    def primary(self) -> float:
        return self.values[self.primary_name]


@dataclasses.dataclass
class EvaluationSuite:
    """Evaluator set + primary (reference EvaluationSuite.scala:33-115)."""

    evaluators: List[Evaluator]
    primary: Evaluator

    def __post_init__(self):
        if self.primary not in self.evaluators:
            self.evaluators = [self.primary] + list(self.evaluators)

    @classmethod
    def from_specs(cls, specs: Sequence[str], primary: Optional[str] = None) -> "EvaluationSuite":
        evs = [make_evaluator(s) for s in specs]
        prim = make_evaluator(primary) if primary else evs[0]
        return cls(evaluators=evs, primary=prim)

    def evaluate(self, scores: Array, labels: Array, weights: Array,
                 group_ids: Optional[Dict[str, np.ndarray]] = None) -> EvaluationResults:
        out = {}
        for ev in self.evaluators:
            gids = (group_ids or {}).get(ev.group_name) if ev.group_name else None
            out[ev.name] = ev.evaluate(scores, labels, weights, gids)
        return EvaluationResults(values=out, primary_name=self.primary.name)

    def device_inputs(self, labels, weights, group_ids: Optional[Dict[str, np.ndarray]],
                      dtype) -> dict:
        """What ``trace_evaluate`` reads besides the scores, on the device,
        built ONCE per sample set: labels, weights and one ``GroupLayout``
        per id tag a Multi- evaluator groups by.  A pytree: it enters a
        jitted program as an argument."""
        tags = {ev.group_name for ev in self.evaluators if ev.group_name}
        missing = tags - set(group_ids or {})
        if missing:
            raise ValueError(f"the suite groups by {sorted(missing)}: no such id tag")
        return {"labels": jnp.asarray(np.asarray(labels, dtype)),
                "weights": jnp.asarray(np.asarray(weights, dtype)),
                "layouts": {t: GroupLayout.build(group_ids[t]) for t in sorted(tags)}}

    def trace_evaluate(self, scores: Array, inputs: dict) -> Array:
        """Traceable: every evaluator's metric of ``scores`` [n] against
        ``device_inputs``, one device array [evaluators] in the suite's
        order, each under its own ``photon.evaluate.<metric>`` scope."""
        from photon_ml_tpu.obs.trace import device_scope

        out = []
        for ev in self.evaluators:
            with device_scope("evaluate", ev.name):
                out.append(ev.trace_evaluate(
                    scores, inputs["labels"], inputs["weights"],
                    inputs["layouts"].get(ev.group_name)))
        return jnp.stack(out).astype(scores.dtype)

    def results(self, values) -> EvaluationResults:
        """One row of ``trace_evaluate``'s output, fetched, as results."""
        return EvaluationResults(
            values={ev.name: float(v) for ev, v in zip(self.evaluators, values)},
            primary_name=self.primary.name)

    def better_than(self, a: EvaluationResults, b: Optional[EvaluationResults]) -> bool:
        if b is None:
            return True
        return self.primary.better_than(a.primary, b.primary)
