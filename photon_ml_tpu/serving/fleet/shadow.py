"""Shadow scoring: score both generations, serve the old, measure drift.

Photon ML reference counterpart: none — offline validation in the
reference world is a batch AUC job over a holdout set.  Shadow mode is
the online complement: the CANDIDATE generation scores the live request
stream at full fidelity (real features, real entity mix, real buckets)
while the ACTIVE generation's scores are the ones served, so a bad
candidate can be observed for as long as needed at zero user risk — the
read-only half of the canary policy.

Per-request ``|shadow - primary|`` drift is recorded into the labeled
histogram family ``fleet_shadow_drift{model=, bucket=}`` — bucketed by
the micro-batch bucket the pair scored under, because drift that only
appears at one padded shape is a kernel problem, not a model problem —
plus a ``fleet_shadow_pairs_total{model=}`` pair count
(``ServingMetrics.fleet_view()["shadow"]``).

Both legs run under ONE photonpulse trace: ``score`` wraps them in
``fleet.serve`` / ``fleet.shadow`` spans stamped with the requests' trace
ids, and the engine's ``serve.execute`` spans inherit the same ids from
the requests themselves — so a ``tools/tracemerge.py`` timeline shows the
primary and shadow executions of one request joined under one trace id.

Executables come from the shared ``KernelCache``: a same-shape shadow
store warms for free, and the whole shadow episode performs zero
compiles — the overhead is exactly one extra execution per batch.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from photon_ml_tpu.obs.trace import enabled as obs_enabled
from photon_ml_tpu.obs.trace import span as obs_span
from photon_ml_tpu.serving.batcher import Request
from photon_ml_tpu.serving.coefficient_store import CoefficientStore
from photon_ml_tpu.serving.fleet.registry import ModelHandle


class ShadowScorer:
    """Dual-leg scorer for one model handle (module docstring)."""

    def __init__(self, handle: ModelHandle, shadow: CoefficientStore,
                 warm: bool = True):
        self.handle = handle
        self.shadow = shadow
        if warm:
            # free when the shadow store's signature matches a live one
            handle.engine.warm(store=shadow)

    def _trace_attrs(self, requests: Sequence[Request]) -> dict:
        if not obs_enabled():
            return {}
        tids = sorted({r.ctx[0] for r in requests if r.ctx is not None})
        return {"traces": tids} if tids else {}

    def score(self, requests: Sequence[Request],
              predict_mean: bool = False) -> np.ndarray:
        """Score both legs; SERVE the primary (active generation).  The
        shadow leg's scores never leave this method — they exist only to
        be differenced."""
        engine = self.handle.engine
        n = len(requests)
        if n == 0:
            return engine.score_requests(requests,
                                         predict_mean=predict_mean)
        attrs = self._trace_attrs(requests)
        with obs_span("fleet.serve", model=self.handle.model_id,
                      rows=n, **attrs):
            primary = engine.score_requests(requests,
                                            predict_mean=predict_mean)
        with obs_span("fleet.shadow", model=self.handle.model_id,
                      rows=n, **attrs):
            shadowed = engine.score_requests(requests,
                                             predict_mean=predict_mean,
                                             store=self.shadow)
        self._record_drift(requests, primary, shadowed)
        return primary

    def _record_drift(self, requests: Sequence[Request],
                      primary: np.ndarray, shadowed: np.ndarray) -> None:
        """Attribute each pair's drift to the micro-batch bucket it scored
        under — the SAME plan both legs used (one batcher, one n)."""
        metrics = self.handle.engine.metrics
        drift = np.abs(np.asarray(shadowed) - np.asarray(primary))
        for mb in self.handle.engine.batcher.plan(len(requests)):
            for i in range(mb.start, mb.stop):
                metrics.observe_shadow_drift(self.handle.model_id,
                                             mb.bucket, float(drift[i]))

    def drift_view(self) -> dict:
        """This model's slice of ``ServingMetrics.fleet_view()['shadow']``
        (``{"pairs": n, "drift": {bucket: histogram-snapshot}}``)."""
        view = self.handle.engine.metrics.fleet_view()["shadow"]
        return view.get(self.handle.model_id, {"pairs": 0, "drift": {}})


def shadow_overhead_ratio(dual_s: float, single_s: float) -> float:
    """Bench helper: wall-time ratio of dual-leg to single-leg scoring
    (ideal ~2.0 for same-shape legs; >> 2 would mean the shadow leg is
    compiling, which the shared kernel cache forbids)."""
    return dual_s / single_s if single_s > 0 else 0.0
