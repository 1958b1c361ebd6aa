"""GAME hyperparameter tuning glue: vectorize per-coordinate regularization
weights and retrain through the estimator.

Reference: photon-client .../estimators/GameEstimatorEvaluationFunction.scala:40-244
(GameOptimizationConfiguration <-> log-scale DenseVector; apply() retrains via
estimator.fit) and GameTrainingDriver.runHyperparameterTuning:643-674
(HyperparameterTuningMode RANDOM | BAYESIAN).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from photon_ml_tpu.core.regularization import Regularization
from photon_ml_tpu.evaluation.evaluator import EvaluationSuite
from photon_ml_tpu.game.config import FixedEffectConfig, GameConfig, RandomEffectConfig
from photon_ml_tpu.game.data import GameData
from photon_ml_tpu.game.descent import DescentHistory
from photon_ml_tpu.game.estimator import (GameEstimator, GameFitResult,
                                          GameTransformer)
from photon_ml_tpu.obs import get_registry
from photon_ml_tpu.obs.trace import span as obs_span
from photon_ml_tpu.tune.search import DomainDim, GaussianProcessSearch, RandomSearch, SearchDomain


def _with_l2(cfg, l2: float):
    reg = Regularization(l1=cfg.reg.l1, l2=l2)
    return dataclasses.replace(cfg, reg=reg)


class GameEstimatorEvaluationFunction:
    """params vector (one L2 weight per coordinate, log-tuned) -> validation
    metric via a full GAME retrain (the reference retrains per tuning
    iteration too, GameEstimatorEvaluationFunction.apply).

    A call is the span ``tune.trial`` (``l2`` per coordinate, ``primary``,
    ``iterations_kept``) and counts one ``tune.trials``; what the fit and
    the evaluation inside it take is in the spans they open themselves
    (``descent.fused_validated``, ``validate.evaluate``, ``validate.export``
    on the fully fused path)."""

    def __init__(self, estimator: GameEstimator, base_config: GameConfig,
                 data: GameData, validation_data: GameData, seed: int = 0,
                 initial_model=None, locked_coordinates=None):
        if estimator.validation_suite is None:
            raise ValueError("tuning needs an estimator with a validation suite")
        self.estimator = estimator
        self.base_config = base_config
        self.data = data
        self.validation_data = validation_data
        self.seed = seed
        self.initial_model = initial_model
        self.locked = set(locked_coordinates or ())
        # locked coordinates are never retrained, so their L2 is not a
        # tunable dimension (partial retraining, GameEstimator :106-112)
        self.coordinate_ids = [c for c in base_config.coordinates
                               if c not in self.locked]
        if not self.coordinate_ids:
            raise ValueError("all coordinates are locked; nothing to tune")
        self.results: List[GameFitResult] = []
        self._sweep = None  # None = not built; False = un-fusable

    def config_for(self, params: np.ndarray) -> GameConfig:
        # keep every coordinate (locked ones must stay in the config so the
        # descent can re-score them); override only the tuned L2s
        coords = dict(self.base_config.coordinates)
        for i, cid in enumerate(self.coordinate_ids):
            coords[cid] = _with_l2(coords[cid], float(params[i]))
        return dataclasses.replace(self.base_config, coordinates=coords)

    def _fused_sweep(self):
        """ONE FusedSweep shared by every tuning fit — reg weights are
        traced sweep inputs, so the whole tuning loop compiles exactly one
        descent program (the estimator's own sweep cache is local to each
        fit() call and would re-trace per tuning iteration)."""
        if self._sweep is False:
            return None
        if self._sweep is None:
            from photon_ml_tpu.game.fused import FusedSweep
            from photon_ml_tpu.types import VarianceComputationType

            needs_var = any(c.variance != VarianceComputationType.NONE
                            for c in self.base_config.coordinates.values())
            if self.base_config.num_outer_iterations > 1 and needs_var:
                # multi-iteration fused tuning runs via per-iteration
                # snapshots, which don't carry variances (FusedSweep
                # .run_snapshots) — host path keeps exact semantics
                self._sweep = False
                return None
            try:
                coords = {
                    cid: self.estimator.build_one_coordinate(
                        cid, self.data, ccfg, self.base_config.task, self.seed,
                        initial_model=self.initial_model)
                    for cid, ccfg in self.base_config.coordinates.items()}
                sweep = FusedSweep(
                    coords, order=list(self.base_config.coordinates),
                    num_iterations=self.base_config.num_outer_iterations)
                # the warm-start carry is constant for the life of this
                # evaluation function — score the initial model ONCE, not
                # once per tuning iteration
                carry0 = (sweep.init_carry(self.initial_model)
                          if self.initial_model is not None else None)
                # variance-free tuning runs FULLY fused: held-out scoring +
                # best-iteration selection ride the validated program
                # (run_validated); variance-computing single-iteration
                # configs keep the run() + host-evaluate path (plan=None)
                plan = (None if needs_var else sweep.validation_plan(
                    self.validation_data, self.estimator.validation_suite))
                self._sweep = (sweep, carry0, plan)
            except NotImplementedError:
                self._sweep = False  # un-fusable coordinate: host path
                return None
        return self._sweep

    def _select_and_record(self, config: GameConfig, snapshots) -> float:
        """Evaluate each snapshot on validation, keep the best (host-loop
        best-model retention semantics), record the fit."""
        suite = self.estimator.validation_suite
        best_model, best_ev = None, None
        with obs_span("validate.evaluate", rows=len(self.validation_data.y),
                      evaluators=[ev.name for ev in suite.evaluators],
                      snapshots=len(snapshots)):
            for m in snapshots:
                ev = GameTransformer(m, config.task).evaluate(
                    self.validation_data, suite)
                if best_ev is None or suite.better_than(ev, best_ev):
                    best_model, best_ev = m, ev
        self.results.append(GameFitResult(model=best_model, config=config,
                                          evaluation=best_ev,
                                          history=DescentHistory()))
        return best_ev.primary

    def __call__(self, params: np.ndarray) -> float:
        with obs_span("tune.trial", l2={
                cid: float(v) for cid, v
                in zip(self.coordinate_ids, params)}) as sp:
            primary = self._trial(params, sp)
            sp.set(primary=primary)
        get_registry().inc("tune.trials")
        return primary

    def _trial(self, params: np.ndarray, sp) -> float:
        config = self.config_for(params)
        # Fused fast path: train WITHOUT per-update validation (the whole
        # retrain is one jitted sweep, reused across every tuning fit).
        # Best-model retention (reference CoordinateDescent.scala:163-314)
        # compares FULL models at sweep boundaries only, so per-iteration
        # snapshots from the fused program (FusedSweep.run_snapshots) carry
        # exactly the candidates the host loop would compare — each is
        # evaluated on validation here and the best kept.  One outer
        # iteration degenerates to evaluating the final model via run().
        fused_ok = (not self.locked and self.estimator.fused is not False)
        sweep = self._fused_sweep() if fused_ok else None
        if sweep is not None:
            sweep_obj, carry0, plan = sweep
            regs = [config.coordinates[cid].reg for cid in config.coordinates]
            if plan is not None:
                # fully fused validated fit: training, held-out scoring,
                # per-update losses and the suite at every sweep boundary
                # in ONE compiled program
                model, evals, best_ev, _losses = sweep_obj.run_validated(
                    plan, initial=self.initial_model, carry0=carry0,
                    regs=regs, seed=self.seed)
                sp.set(iterations_kept=1 + evals.index(best_ev))
                self.results.append(GameFitResult(
                    model=model, config=config, evaluation=best_ev,
                    history=DescentHistory()))
                return best_ev.primary
            if config.num_outer_iterations == 1:
                model, _scores = sweep_obj.run(initial=self.initial_model,
                                               carry0=carry0, regs=regs,
                                               seed=self.seed)
                snapshots = [model]
            else:
                snapshots = sweep_obj.run_snapshots(
                    initial=self.initial_model, carry0=carry0, regs=regs,
                    seed=self.seed)
            return self._select_and_record(config, snapshots)
        res = self.estimator.fit(self.data, [config],
                                 validation_data=self.validation_data, seed=self.seed,
                                 initial_model=self.initial_model,
                                 locked_coordinates=self.locked or None)[0]
        self.results.append(res)
        return res.evaluation.primary

    def evaluate_batch(self, params_batch) -> List[float]:
        """Evaluate several parameter vectors in ONE vmapped grid fit
        (FusedSweep.run_grid/_snapshots): all grid lanes share the same
        design-matrix streams, so q tuning fits cost far less than q
        sequential retrains — the batched half of batch Bayesian
        optimization (the search picks the q candidates).  Order of
        ``results`` matches sequential evaluation.  Falls back to
        sequential calls when the fused path is unavailable."""
        params_batch = [np.asarray(p, float) for p in params_batch]
        if not params_batch:
            return []
        fused_ok = (not self.locked and self.estimator.fused is not False)
        sweep = self._fused_sweep() if fused_ok else None
        if sweep is None or len(params_batch) == 1:
            return [self(p) for p in params_batch]
        sweep_obj, carry0, _plan = sweep  # grid fits host-evaluate snapshots
        configs = [self.config_for(p) for p in params_batch]
        regs_grid = [[c.coordinates[cid].reg for cid in c.coordinates]
                     for c in configs]
        # key off the per-candidate configs like __call__ does (advisor r4);
        # a batched fused grid shares ONE program, so candidates that
        # disagree on iteration count cannot ride it — fall back to
        # sequential evaluation, which honors each candidate's own count
        iters = {c.num_outer_iterations for c in configs}
        if len(iters) > 1:
            return [self(p) for p in params_batch]
        if configs[0].num_outer_iterations == 1:
            snap_lists = [[m] for m, _scores in sweep_obj.run_grid(
                regs_grid, initial=self.initial_model, carry0=carry0,
                seed=self.seed)]
        else:
            snap_lists = sweep_obj.run_grid_snapshots(
                regs_grid, initial=self.initial_model, carry0=carry0,
                seed=self.seed)
        get_registry().inc("tune.trials", len(configs))
        return [self._select_and_record(config, snaps)
                for config, snaps in zip(configs, snap_lists)]

    def vectorize(self, config: GameConfig) -> np.ndarray:
        """Config -> params vector (reference configurationToVector)."""
        return np.asarray([config.coordinates[cid].reg.l2 for cid in self.coordinate_ids])

    def warmup(self, grid_sizes: Sequence[int] = ()) -> None:
        """Compile the shared fused tuning program (one throwaway fit at the
        base config's weights, not recorded).  Benchmarks call this so the
        timed window measures tuning-fit throughput, not XLA compilation —
        the same convention as the sweep benches' warm-up run.

        ``grid_sizes``: additionally pre-compile the batched grid program
        for each q (run_grid traces one program per distinct grid size) —
        callers that tune with ``batch_size=q`` warm q here so no compile
        lands inside their measured window."""
        n = len(self.results)
        base = self.vectorize(self.base_config)
        try:
            self(base)
            fused_ok = (not self.locked and self.estimator.fused is not False
                        and self._fused_sweep() is not None)
            if fused_ok:  # without a fused sweep there is no grid program
                for q in grid_sizes:  # to compile — evaluate_batch would
                    if q > 1:  # just run q discarded sequential retrains
                        self.evaluate_batch([base] * q)
        finally:
            # a warm-up records nothing, whether it ended or raised: a
            # reused evaluation function keeps the fits it had before
            del self.results[n:]


DEFAULT_L2_RANGE = (1e-4, 1e4)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_searching = 0  # searches in flight: the listener below counts for them
_listening = False  # the listener is registered (once a process)


def _count_compile(event, *_a, **_k) -> None:
    if _searching and event == _COMPILE_EVENT:
        get_registry().inc("tune.compiles_in_search")


@contextlib.contextmanager
def _counting_compiles():
    """Counter ``tune.compiles_in_search``: programs built (or loaded from
    the persistent cache) while a search runs.  The regularisation weights
    are traced inputs of ONE program (``Coordinate.sweep_key``), so past a
    warm-up it stays where it was whatever the search proposes."""
    global _searching, _listening
    import jax

    if not _listening:
        _listening = True
        jax.monitoring.register_event_duration_secs_listener(_count_compile)
    _searching += 1
    try:
        yield
    finally:
        _searching -= 1



def default_l2_domain(coordinate_ids, l2_range=DEFAULT_L2_RANGE) -> SearchDomain:
    """The standard per-coordinate log-scale L2 search domain (shared by
    tune_game_model and the driver's shrink branch)."""
    return SearchDomain([
        DomainDim(name=f"l2:{cid}", low=l2_range[0], high=l2_range[1],
                  log_scale=True)
        for cid in coordinate_ids
    ])


def tune_game_model(
    estimator: GameEstimator,
    base_config: GameConfig,
    data: GameData,
    validation_data: GameData,
    n_iterations: int = 10,
    mode: str = "bayesian",  # reference HyperparameterTuningMode {RANDOM, BAYESIAN}
    l2_range: Tuple[float, float] = DEFAULT_L2_RANGE,
    seed: int = 0,
    initial_model=None,
    locked_coordinates=None,
    search_domain: Optional[SearchDomain] = None,
    prior_observations: Optional[List[Tuple[np.ndarray, float]]] = None,
    evaluation_function: Optional[GameEstimatorEvaluationFunction] = None,
    batch_size: int = 1,
) -> Tuple[GameFitResult, "RandomSearch", List[GameFitResult]]:
    """Search per-coordinate L2 weights; returns (best fit, search object,
    all tuned fits in evaluation order — the driver's TUNED/ALL output modes
    save these, reference GameTrainingDriver.selectModels:683-701).

    ``initial_model``/``locked_coordinates``: forwarded to every tuning fit
    (warm start + partial retraining); locked coordinates are excluded from
    the search space.

    ``search_domain``: override the per-coordinate L2 domain (e.g. parsed
    from a reference-format JSON config, tune/serialization.py); dim order
    must match the unlocked-coordinate order.  ``prior_observations``:
    (params, value) pairs seeded into the search
    (HyperparameterSerialization.priorFromJson)."""
    if evaluation_function is not None:
        # caller pre-built (and possibly warmup()-compiled) the evaluation
        # function — it must wrap the SAME estimator/config, and the
        # per-fit knobs must not be double-specified (they live on fn)
        fn = evaluation_function
        if fn.estimator is not estimator or fn.base_config is not base_config:
            raise ValueError(
                "evaluation_function was built for a different estimator or "
                "base_config than the ones passed to tune_game_model")
        if fn.data is not data or fn.validation_data is not validation_data:
            raise ValueError(
                "evaluation_function was built for different data or "
                "validation_data than the ones passed to tune_game_model")
        if initial_model is not None or locked_coordinates is not None:
            raise ValueError(
                "pass initial_model/locked_coordinates to the "
                "GameEstimatorEvaluationFunction constructor, not to "
                "tune_game_model, when supplying evaluation_function")
        if seed != fn.seed:
            raise ValueError(
                f"seed {seed} != evaluation_function's seed {fn.seed}")
    else:
        fn = GameEstimatorEvaluationFunction(estimator, base_config, data, validation_data,
                                             seed, initial_model=initial_model,
                                             locked_coordinates=locked_coordinates)
    if search_domain is not None:
        if search_domain.d != len(fn.coordinate_ids):
            raise ValueError(
                f"search domain has {search_domain.d} dims but there are "
                f"{len(fn.coordinate_ids)} tunable coordinates")
        domain = search_domain
    else:
        domain = default_l2_domain(fn.coordinate_ids, l2_range)
    minimize = not estimator.validation_suite.primary.larger_is_better
    cls = GaussianProcessSearch if mode == "bayesian" else RandomSearch
    # batch_size > 1: each search round evaluates its candidates as ONE
    # vmapped grid fit (fn.evaluate_batch -> FusedSweep.run_grid) — batch
    # Bayesian optimization, total fit count unchanged
    search = cls(domain, minimize=minimize, seed=seed, batch_size=batch_size)
    # a reused evaluation_function may carry fits from a previous search —
    # this run's results are everything appended from here on
    start = len(fn.results)
    # prior: supplied observations (values already in the primary metric's
    # raw orientation), then the base config's own weights, evaluated first
    # (warm prior, reference ShrinkSearchRange / prior JSON defaults)
    priors = list(prior_observations or [])
    prior_params = fn.vectorize(base_config)
    with _counting_compiles():
        if np.all(prior_params > 0):
            priors.append((prior_params, fn(prior_params)))
        search.find(fn, n=n_iterations, priors=priors or None,
                    evaluate_batch=fn.evaluate_batch)

    results = list(fn.results[start:])
    best = estimator.best(results)
    return best, search, results
