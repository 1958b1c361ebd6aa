"""Traffic kind ``tune_jobs``: hyper-parameter tuning jobs back to back for
the window, closed loop, one trial in flight.

A JOB is one ``tune.tune_game_model`` over the configuration's search: the
prior trial at the base L2 weights, then ``tuning_iterations`` proposals
(``mode`` ``bayesian``: the Gaussian process refits before EVERY trial,
``batch_size`` 1; ``random``: the Sobol sequence alone).  A TRIAL is one call
of the program's ``GameEstimatorEvaluationFunction``: a full validated fit
from zero coefficients at the proposed weights (``FusedSweep.run_validated``:
training, held-out scoring, the metric suite, best-iteration retention, the
retained model exported) whose primary metric goes back to the search.  The
search loop, the fits and the scoring are the program's; this file builds
ONE evaluation function at set-up (one sweep, one plan, ``warmup()``), hands
it to every job, and wraps its call only to stamp each trial's start and
end and to stop the search at the deadline (``WindowClosed``, raised here
and caught here).  A job's search seed comes from ``truth_seed`` and the
job's number, NOT from ``--seed``: every run proposes from the same Sobol
points, and what differs between runs is the sample.

``train_examples_per_s`` = training rows x sweeps x trials FINISHED inside
the window, over first trial's start to last counted trial's end.  A
trial's interval runs from the search handing over the candidate to its
primary metric coming back; the proposal before it lies between intervals
and inside the window.  ``fit_s`` is the median interval.

After the window, outside it, FOUR trials (the first = a job's prior, the
last, their job's best, one drawn from ``--seed``) are held to the plain
references from the model each EXPORTED: the program's held-out totals of
``score_rows`` sampled held-out rows (every row of an unseen entity among
them) against ``reference/tuned_validation.py``; the recorded metrics
against the reference's on ALL held-out rows; the returned trial against the
recorded metrics of its job; the retained iteration of the last trial
against the reference's metric of both boundaries; the last trial's last
coordinate against the Newton reference at the trial's own L2; the prior
trial's training AUC and loss against the cell's band and gate.
``reference_dtype`` is the control: the reference on features rounded to
that type has to come out as not ``correct``.
"""

from __future__ import annotations

import statistics
import time

import numpy as np

SLICE_TRIALS = 3  # trials inside the traced slice (with their proposals)
CHECKED = ("prior", "last", "best", "drawn")


class WindowClosed(Exception):
    """The next trial could not end inside the window: the search stops."""


def search_seed(cfg: dict, job: int) -> int:
    return int(np.random.default_rng(
        [int(cfg["truth_seed"]), 11, job]).integers(1, 2 ** 31 - 1))


def game_config(cfg: dict):
    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.game import FixedEffectConfig, RandomEffectConfig
    from photon_ml_tpu.game.config import GameConfig
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.types import TaskType

    reg = Regularization(l2=float(cfg["l2"]))
    coords = {}
    for c in cfg["coordinates"]:
        spec = c.get("solver", cfg["solver"])
        solver = SolverConfig(max_iters=int(spec["max_iters"]),
                              tolerance=float(spec["tolerance"]))
        if c["kind"] == "fixed":
            coords[c["id"]] = FixedEffectConfig(
                feature_shard=c["feature_shard"], solver=solver, reg=reg)
        else:
            coords[c["id"]] = RandomEffectConfig(
                random_effect_type=c["entity"],
                feature_shard=c["feature_shard"], solver=solver, reg=reg,
                active_cap=c.get("active_cap"))
    return GameConfig(task=TaskType.LOGISTIC_REGRESSION, coordinates=coords,
                      num_outer_iterations=int(cfg["sweeps"]))


def sampled_rows(ctx, heldout: dict, trained: dict) -> np.ndarray:
    """``score_rows`` held-out rows, sorted: every row of an entity the
    training set never saw first (as many as fit), the rest drawn."""
    n = len(heldout["y"])
    want = min(int(ctx.traffic["score_rows"]), n)
    unseen = np.zeros(n, bool)
    for tag, ids in heldout["id_tags"].items():
        unseen |= ~np.isin(ids, trained["id_tags"][tag])
    rng = np.random.default_rng([ctx.seed, 6])
    first = np.flatnonzero(unseen)
    if len(first) > want // 2:
        first = rng.choice(first, size=want // 2, replace=False)
    rest = rng.choice(np.flatnonzero(~unseen), size=want - len(first),
                      replace=False)
    return np.sort(np.concatenate([first, rest]))


def reference_features(ctx, x):
    """The features as the reference reads them: as they are, or rounded to
    the mix's ``reference_dtype`` (the control)."""
    dtype = ctx.traffic.get("reference_dtype", "float32")
    if dtype == "float32":
        return x
    import jax.numpy as jnp

    return jnp.asarray(x).astype(dtype).astype(jnp.float32)


def reference_effects(ctx, cfg, model, heldout) -> list:
    """``tuned_validation.heldout_scores``' random effects of an exported
    model: (features, table, its entities sorted, the rows' ids)."""
    out = []
    for c in cfg["coordinates"]:
        if c["kind"] != "random":
            continue
        out.append((reference_features(ctx, heldout["features"]
                                       [c["feature_shard"]]),
                    *_table(model[c["id"]]),
                    heldout["id_tags"][c["entity"]]))
    return out


def newton_parity(ctx, cfg, result, coords, train) -> dict:
    """The coordinate updated last, at the trial's OWN L2, on a seeded
    sample of its entities: exported coefficients vs the plain Newton
    reference on the same active rows and weights; the offsets are the
    exported model's other coordinates on those rows (the trial solved it
    last, against their published scores).  {"err", "entities"}."""
    spec = cfg["coordinates"][-1]
    if spec["kind"] != "random":
        return {}
    ref = ctx.catalog.module("reference", "tuned_validation")
    solve = ctx.catalog.module("reference", "newton_solve").solve
    coord, model = coords[spec["id"]], result.model[spec["id"]]
    ids = train["id_tags"][spec["entity"]]
    counts = np.bincount(ids)
    kept = np.asarray(sorted(model.slot_of), np.int64)
    rng = np.random.default_rng([ctx.seed, 3])
    sample = rng.choice(kept, size=min(int(ctx.traffic["parity_entities"]),
                                       len(kept)), replace=False)
    active = []
    for e in sample:
        bi, lane = coord.buckets.lane_of[int(e)]
        rows = coord.buckets.buckets[bi].rows[lane]
        active.append(rows[rows >= 0])
    rows_all = np.concatenate(active)
    fixed = next(c for c in cfg["coordinates"] if c["kind"] == "fixed")
    others = [(train["features"][c["feature_shard"]][rows_all],
               *_table(result.model[c["id"]]),
               train["id_tags"][c["entity"]][rows_all])
              for c in cfg["coordinates"][:-1] if c["kind"] == "random"]
    off_all = ref.heldout_scores(
        train["features"][fixed["feature_shard"]][rows_all],
        np.asarray(result.model[fixed["id"]].coefficients.means, np.float32),
        others)
    x_host = train["features"][spec["feature_shard"]]
    s_max = max(len(r) for r in active)
    x = np.zeros((len(sample), s_max, x_host.shape[1]), np.float32)
    y = np.zeros((len(sample), s_max), np.float32)
    off, wt = np.zeros_like(y), np.zeros_like(y)
    at = 0
    for k, (e, rows) in enumerate(zip(sample, active)):
        x[k, :len(rows)] = x_host[rows]
        y[k, :len(rows)] = train["y"][rows]
        off[k, :len(rows)] = off_all[at:at + len(rows)]
        wt[k, :len(rows)] = counts[e] / len(rows)
        at += len(rows)
    l2 = float(result.config.coordinates[spec["id"]].reg.l2)
    want = np.asarray(solve(x, y, off, wt, l2))
    got = model.w_stack[[model.slot_of[int(e)] for e in sample]]
    far = (np.linalg.norm(got - want, axis=1)
           / np.maximum(np.linalg.norm(want, axis=1), 1e-30))
    return {"err": float(np.max(np.abs(got - want))
                         / max(np.max(np.abs(want)), 1e-30)),
            "p50": float(np.median(far)), "p10": float(np.quantile(far, 0.1)),
            "entities": int(len(sample)), "l2": l2}


def _table(model) -> tuple:
    entities = np.asarray(sorted(model.slot_of), np.int64)
    return (np.asarray(model.w_stack)[[model.slot_of[int(e)]
                                       for e in entities]], entities)


def training_quality(ctx, cfg, result, coords, train) -> tuple:
    """(AUC, regularised loss per row) of a trial's exported model on the
    training rows, from the program's own scores of them, as ``train_fits``
    reads its last fit."""
    fits = ctx.catalog.module("traffic", "train_fits")
    total, published = 0.0, []
    for c in cfg["coordinates"]:
        m = result.model[c["id"]]
        total = total + np.asarray(coords[c["id"]].score(m), np.float32)
        published.append(m.coefficients.means if c["kind"] == "fixed"
                         else m.w_stack)
    l2 = [float(result.config.coordinates[c["id"]].reg.l2)
          for c in cfg["coordinates"]]
    if len(set(l2)) != 1:
        raise ValueError("the training loss gate is the base weights'")
    return (fits.rank_auc(train["y"], total),
            fits.training_loss(train["y"], total, published, l2[0]))


def run(ctx) -> dict:
    import jax

    from photon_ml_tpu import obs
    from photon_ml_tpu.evaluation import EvaluationSuite
    from photon_ml_tpu.game import GameData, GameEstimator
    from photon_ml_tpu.game.estimator import GameTransformer
    from photon_ml_tpu.tune import tune_game_model
    from photon_ml_tpu.tune.game_tuning import GameEstimatorEvaluationFunction

    cfg, mix, gates = ctx.config, ctx.traffic, ctx.workload.get("gates", {})
    recipe = ctx.catalog.module("recipes", cfg["recipe"])
    ref = ctx.catalog.module("reference", "tuned_validation")
    if ctx.mesh() is not None:
        raise ValueError("tune_jobs runs on one chip")
    with ctx.span("data_make"):
        train, heldout = recipe.make_sets(cfg, ctx.seed)

    def game_data(d):
        return GameData(y=d["y"], features=d["features"],
                        id_tags=d["id_tags"])

    train_gd, heldout_gd = game_data(train), game_data(heldout)
    n_train, sweeps = len(train["y"]), int(cfg["sweeps"])
    suite = EvaluationSuite.from_specs(cfg["evaluators"],
                                       primary=cfg["primary_evaluator"])
    estimator = GameEstimator(validation_suite=suite)
    base_config = game_config(cfg)
    rows = sampled_rows(ctx, heldout, train)
    rows_dev = jax.numpy.asarray(rows.astype(np.int32))
    take_rows = jax.jit(lambda totals, at: totals[:, at])

    class Stamped(GameEstimatorEvaluationFunction):
        """The program's evaluation function, its call stamped."""

        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            self.trials, self.kept, self.est, self.deadline = [], [], 0.0, None
            self.profiling, self.slice_trials = None, 0

        def plan(self):
            fused = self._fused_sweep()
            return fused[2] if fused else None

        def __call__(self, params):
            t0 = time.perf_counter()
            if self.deadline is None:  # set-up: the warm-up's trial
                return super().__call__(params)
            if self.trials and t0 + self.est > self.deadline:
                raise WindowClosed
            if ctx.trace and len(self.trials) == 1:
                self.profiling = ctx.profile_slice()
                self.profiling.__enter__()
            with ctx.span("trial"):
                value = super().__call__(params)
            t1 = time.perf_counter()
            self.est = t1 - t0
            self.trials.append((t0, t1, t1 <= self.deadline
                                or not self.trials))
            # the program's held-out totals of the sampled rows: a handful
            # of KB that stay on the device until the window is over
            totals = getattr(self.plan(), "totals", None)
            self.kept.append(None if totals is None
                             else take_rows(totals, rows_dev))
            if self.profiling is not None:
                self.slice_trials += 1
                if self.slice_trials >= SLICE_TRIALS:
                    self.profiling.__exit__(None, None, None)
                    self.profiling = None
            return value

    with ctx.span("coord_build"):  # both coordinates' build AND the plan's
        fn = Stamped(estimator, base_config, train_gd, heldout_gd,
                     seed=search_seed(cfg, 0))
        if fn.plan() is None:
            raise RuntimeError("the configuration did not take the fully "
                               "fused validated path")
    with ctx.span("warm_fit"):  # compiles (or loads) the one program
        fn.warmup()
        totals = getattr(fn.plan(), "totals", None)
        if totals is not None:
            jax.block_until_ready(take_rows(totals, rows_dev))
    compiles_before = obs.get_registry().counter("tune.compiles_in_search")

    t_win = ctx.window_start()
    fn.deadline = t_win + ctx.seconds
    jobs, raised = [], 0
    while time.perf_counter() < fn.deadline and raised < 3:
        job, first = len(jobs), len(fn.results)
        fn.seed = search_seed(cfg, job)
        done = None
        try:
            done = tune_game_model(
                estimator, base_config, train_gd, heldout_gd,
                n_iterations=int(mix["tuning_iterations"]), mode=mix["mode"],
                seed=fn.seed, evaluation_function=fn,
                batch_size=int(mix["batch_size"]))
        except WindowClosed:
            pass
        except Exception:  # a job that raises is a failed job, reported
            import traceback

            traceback.print_exc()
            raised += 1
        jobs.append({"first": first, "trials": len(fn.results) - first,
                     "returned": None if done is None
                     else fn.results.index(done[0])})
        if done is None and not raised:
            break
    if fn.profiling is not None:
        fn.profiling.__exit__(None, None, None)
    ctx.window_end()
    results, trials = fn.results, fn.trials
    if not results or len(results) != len(trials):
        raise RuntimeError("no trial finished, or a trial left no result")

    counted = [(a, b) for a, b, inside in trials if inside]
    rate = n_train * sweeps * len(counted) / (counted[-1][1] - counted[0][0])
    durations = [b - a for a, b in counted]
    primary = [float(r.evaluation.primary) for r in results]
    finite = all(np.isfinite(list(r.evaluation.values.values())).all()
                 for r in results)

    # -- outside the window: are the trials right? --------------------------
    coords = fn._fused_sweep()[0].coordinates
    fixed = next(c for c in cfg["coordinates"] if c["kind"] == "fixed")
    x_held = reference_features(ctx, heldout["features"]
                                [fixed["feature_shard"]])
    last_job = next(j for j in reversed(jobs) if j["trials"])
    in_job = range(last_job["first"], last_job["first"] + last_job["trials"])
    picked = dict(zip(CHECKED, (
        0, len(results) - 1, max(in_job, key=lambda t: primary[t]),
        int(np.random.default_rng([ctx.seed, 7]).integers(len(results))))))
    name_tag = {ev.name: ev.group_name for ev in suite.evaluators}
    seen, score_err, metric_err, one_class = {}, 0.0, 0.0, None
    kept_iteration = {}
    for which, t in picked.items():
        if t in seen:
            continue
        model = results[t].model
        scores = ref.heldout_scores(
            x_held, np.asarray(model[fixed["id"]].coefficients.means,
                               np.float32),
            reference_effects(ctx, cfg, model, heldout))
        seen[t] = scores
        # (1) the program's held-out totals of the sampled rows; where the
        # program keeps none (the parent of the PR that added them), its
        # own scoring of the exported model on those rows
        if fn.kept[t] is not None:
            got = np.asarray(fn.kept[t], np.float64)
        else:
            sub = GameData(
                y=heldout["y"][rows],
                features={k: np.asarray(v[rows]) for k, v
                          in heldout["features"].items()},
                id_tags={k: v[rows] for k, v in heldout["id_tags"].items()})
            got = GameTransformer(model, base_config.task).score(sub)[None]
        errs = np.max(np.abs(got - scores[rows][None]), axis=1) / max(
            float(np.max(np.abs(scores[rows]))), 1e-30)
        kept_iteration[t] = int(np.argmin(errs))
        score_err = max(score_err, float(np.min(errs)))
        # (2) the recorded metrics, on ALL held-out rows and ALL groups
        for name, value in results[t].evaluation.values.items():
            if name_tag[name] is None:
                want = ref.rank_auc(heldout["y"], scores)
            else:
                groups = ref.per_group_auc(
                    heldout["y"], scores, heldout["id_tags"][name_tag[name]])
                want, one_class = groups["half_for_one_class"], groups
            metric_err = max(metric_err, abs(float(value) - want))
    checks = {"trials_finished": len(counted) > 0 and raised == 0,
              "losses_finite": bool(finite),
              "one_program": obs.get_registry().counter(
                  "tune.compiles_in_search") == compiles_before}
    detail = {
        "setup_spans_s": ctx.span_seconds(("data_make", "coord_build",
                                           "warm_fit")),
        "trials_in_window": len(counted), "jobs": jobs,
        "rows": n_train, "heldout_rows": len(heldout["y"]), "sweeps": sweeps,
        "fit_s_min": min(durations), "fit_s_max": max(durations),
        "fit_s_each": [round(d, 6) for d in durations],
        "l2_each": [[float(r.config.coordinates[c["id"]].reg.l2)
                     for c in cfg["coordinates"]] for r in results],
        "primary_each": primary, "checked_trials": picked,
        "heldout_score_err": score_err, "metric_err": metric_err,
        "sampled_rows": int(len(rows)),
        "reference_dtype": mix.get("reference_dtype", "float32")}
    if one_class is not None:
        detail["groups"] = one_class["groups"]
        detail["one_class_groups"] = one_class["one_class"]
    if gates.get("score_tol") is not None:
        checks["heldout_rows_scored"] = score_err <= gates["score_tol"]
    if gates.get("auc_tol") is not None:
        checks["metrics_match"] = metric_err <= gates["auc_tol"]
    # (3) the job returned its best trial; the last trial retained the
    # better boundary by the reference's own metric of the program's totals
    best_ok = all(j["returned"] is None or primary[j["returned"]] == max(
        primary[j["first"]:j["first"] + j["trials"]]) for j in jobs)
    totals = getattr(fn.plan(), "totals", None)
    if totals is not None and suite.primary.group_name is None:
        t_last = len(results) - 1
        each = [ref.rank_auc(heldout["y"], np.asarray(row, np.float64))
                for row in np.asarray(totals)]
        detail["boundary_auc_by_reference"] = each
        detail["iteration_kept"] = kept_iteration[t_last]
        best_ok = best_ok and (each[kept_iteration[t_last]] >= max(each)
                               - float(gates.get("auc_tol", 0.0)))
    checks["best_is_best"] = bool(best_ok)
    # (4) the last trial's last coordinate at ITS weights; the prior
    # trial's fit of the training rows
    parity = newton_parity(ctx, cfg, results[-1], coords, train)
    if parity:
        detail["newton_parity_err"] = parity["err"]
        detail["newton_parity_p50"] = parity["p50"]
        detail["newton_parity_p10"] = parity["p10"]
        detail["newton_parity_l2"] = parity["l2"]
        if gates.get("newton_tol") is not None:
            # {"p50": the median entity's relative distance, "max": the
            # worst coefficient over the largest of the reference's}
            checks["newton_parity"] = (
                parity["p50"] <= gates["newton_tol"]["p50"]
                and parity["err"] <= gates["newton_tol"]["max"])
    auc, loss = training_quality(ctx, cfg, results[0], coords, train)
    detail.update(auc=auc, loss_per_row=loss)
    if gates.get("auc_band") and not ctx.dry_run:
        checks["auc_in_band"] = bool(gates["auc_band"][0] <= auc
                                     <= gates["auc_band"][1])
    if gates.get("loss_gate") is not None and not ctx.dry_run:
        checks["loss_under_gate"] = bool(loss <= gates["loss_gate"])

    if ctx.trace:
        # where a trial's time goes, by the program's own names: seconds
        # under each host span over the run, and device self seconds by
        # scope over the traced slice (PERF.md section 5)
        import layer_join

        by_name = {}
        for r in obs.get_tracer().records():
            if r["ph"] == "X":
                by_name[r["name"]] = by_name.get(r["name"], 0.0) \
                    + r["dur_ns"] * 1e-9
        detail["program_spans_s"] = by_name
        detail["setup_program_spans"] = [
            [r["name"], round(r["dur_ns"] * 1e-9, 4),
             {k: v for k, v in r["attrs"].items()
              if isinstance(v, (int, float, str, bool))}]
            for r in obs.get_tracer().records() if r["ph"] == "X"
            and r["name"].startswith(("coord.", "validate.plan",
                                      "descent.device_table"))]
        detail["slice_scopes_s"] = layer_join.seconds_by(
            {"profile": ctx.profile})
        detail["slice_coordinates_s"] = layer_join.seconds_by(
            {"profile": ctx.profile}, layer_join.coordinate_of)
    return {
        "attempted": len(trials) + raised, "failed": raised + (not finite),
        "checks": checks, "detail": detail,
        "end_to_end": {"train_examples_per_s": rate},
        "layer_values": {
            "fit_s": statistics.median(durations),
            "coord_build_s": detail["setup_spans_s"].get("coord_build"),
            "slice_fits": fn.slice_trials,
        },
    }
