"""Hyperparameter search: Sobol quasi-random + GP Bayesian optimization.

Reference: photon-lib .../hyperparameter/search/RandomSearch.scala:46-124
(Sobol sequence candidates; find/findWithPriors loop) and
GaussianProcessSearch.scala:52-123 (fit GP on observations, draw 250 Sobol
candidates, pick the best Expected Improvement, evaluate, repeat).

``SearchDomain`` handles the reference's VectorRescaling (hyperparameters live
in [0,1]^d for the search; linear or log transform to the real range).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np
from scipy.stats import qmc

from photon_ml_tpu.obs.trace import span as obs_span
from photon_ml_tpu.tune.acquisition import expected_improvement
from photon_ml_tpu.tune.gp import GaussianProcess

EvalFn = Callable[[np.ndarray], float]  # real-space params -> metric


@dataclasses.dataclass(frozen=True)
class DomainDim:
    name: str
    low: float
    high: float
    log_scale: bool = False  # reg weights etc. tune in log space


@dataclasses.dataclass
class SearchDomain:
    """[0,1]^d <-> real-space transform (reference VectorRescaling.scala:150)."""

    dims: List[DomainDim]

    @property
    def d(self) -> int:
        return len(self.dims)

    def to_real(self, unit: np.ndarray) -> np.ndarray:
        out = np.empty_like(unit, float)
        for j, dim in enumerate(self.dims):
            u = unit[..., j]
            if dim.log_scale:
                lo, hi = np.log(dim.low), np.log(dim.high)
                out[..., j] = np.exp(lo + u * (hi - lo))
            else:
                out[..., j] = dim.low + u * (dim.high - dim.low)
        return out

    def to_unit(self, real: np.ndarray) -> np.ndarray:
        out = np.empty_like(real, float)
        for j, dim in enumerate(self.dims):
            r = real[..., j]
            if dim.log_scale:
                lo, hi = np.log(dim.low), np.log(dim.high)
                out[..., j] = (np.log(r) - lo) / (hi - lo)
            else:
                out[..., j] = (r - dim.low) / (dim.high - dim.low)
        return np.clip(out, 0.0, 1.0)


@dataclasses.dataclass
class Observation:
    params: np.ndarray  # real space
    value: float  # metric, minimization orientation


class RandomSearch:
    """Sobol quasi-random search (reference RandomSearch.scala:46-124).

    ``batch_size``: candidates proposed (and evaluated) per round.  1 is
    the reference's sequential loop; >1 enables BATCH evaluation — find()
    hands each round's candidates to ``evaluate_batch`` so backends that
    can amortize a multi-candidate fit (FusedSweep.run_grid: one vmapped
    program sharing the design-matrix streams) pay far less than
    batch_size sequential retrains.

    Every round's proposal (here a Sobol draw; the GP fit and the expected
    improvement of its candidates in ``GaussianProcessSearch``) is the span
    ``tune.propose`` (``mode``, ``observations``, ``candidates``): the host
    work that stands between one trial and the next."""

    mode = "random"

    def __init__(self, domain: SearchDomain, minimize: bool = True, seed: int = 0,
                 batch_size: int = 1):
        self.domain = domain
        self.minimize = minimize
        self.seed = seed
        self.batch_size = max(1, int(batch_size))
        self._sobol = qmc.Sobol(domain.d, scramble=True, seed=seed)
        self.observations: List[Observation] = []

    def _record(self, params: np.ndarray, raw_value: float) -> None:
        v = raw_value if self.minimize else -raw_value
        self.observations.append(Observation(params=params, value=v))

    def next_candidates(self, q: int) -> List[np.ndarray]:
        u = self._sobol.random(q)
        return [self.domain.to_real(u[i]) for i in range(q)]

    def next_candidate(self) -> np.ndarray:
        return self.next_candidates(1)[0]

    def _pool(self, q: int) -> int:
        """Candidates the next proposal of ``q`` is chosen among."""
        return q

    def find(self, evaluate: EvalFn, n: int,
             priors: Optional[Sequence[Tuple[np.ndarray, float]]] = None,
             evaluate_batch=None) -> Tuple[np.ndarray, float]:
        """Evaluate n candidates; returns (best params, best raw value).
        ``priors``: previous observations to seed the search
        (reference findWithPriors:61-93).  ``evaluate_batch``: optional
        callable(list of params) -> list of values used for rounds of more
        than one candidate (see batch_size)."""
        for p, v in priors or []:
            self._record(np.asarray(p, float), v)
        done = 0
        while done < n:
            q = min(self.batch_size, n - done)
            with obs_span("tune.propose", mode=self.mode,
                          observations=len(self.observations),
                          candidates=self._pool(q)):
                cands = self.next_candidates(q)
            if evaluate_batch is not None and len(cands) > 1:
                values = evaluate_batch(cands)
            else:
                values = [evaluate(c) for c in cands]
            for c, v in zip(cands, values):
                self._record(c, float(v))
            done += len(cands)
        best = min(self.observations, key=lambda o: o.value)
        return best.params, (best.value if self.minimize else -best.value)


class GaussianProcessSearch(RandomSearch):
    """Bayesian search: GP posterior + Expected Improvement over Sobol
    candidates (reference GaussianProcessSearch.scala:52-123).

    With batch_size q > 1 each round proposes the TOP-q EI candidates from
    the Sobol draw (batch Bayesian optimization's simplest portfolio: the
    250-candidate pool is quasi-random, so the top-q are well-separated in
    practice) — the GP refits once per round instead of once per fit."""

    def __init__(self, domain: SearchDomain, minimize: bool = True, seed: int = 0,
                 n_candidates: int = 250, n_initial: int = 3,
                 batch_size: int = 1):
        super().__init__(domain, minimize, seed, batch_size)
        self.n_candidates = n_candidates  # reference draws 250
        self.n_initial = n_initial

    mode = "bayesian"

    def _pool(self, q: int) -> int:
        return (q if len(self.observations) < self.n_initial
                else self.n_candidates)

    def next_candidates(self, q: int) -> List[np.ndarray]:
        n_obs = len(self.observations)
        if n_obs < self.n_initial:
            # fill the initial design first (possibly the whole round)
            return super().next_candidates(min(q, self.n_initial - n_obs))
        x = self.domain.to_unit(np.stack([o.params for o in self.observations]))
        y = np.asarray([o.value for o in self.observations])
        gp = GaussianProcess().fit(x, y, seed=self.seed + n_obs)
        cand = self._sobol.random(self.n_candidates)
        mu, sigma = gp.predict(cand)
        ei = expected_improvement(mu, sigma, best=float(y.min()))
        top = np.argsort(-ei)[:q]
        return [self.domain.to_real(cand[int(i)]) for i in top]
