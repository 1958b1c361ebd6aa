"""A GLMix that is tuned (ISSUE 32): the validated fit's held-out scoring and
metric suite inside its program, the grouped metrics over a layout built
once, the search's proposals and compiles, and the benchmark cell
``glmix_tune_ml20m.tune_jobs`` through its own generator, reference and
gates at dry-run sizes.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
sys.path[:0] = [BENCH, REPO]

import run as harness  # noqa: E402

from photon_ml_tpu import obs  # noqa: E402
from photon_ml_tpu.core.regularization import Regularization  # noqa: E402
from photon_ml_tpu.evaluation import (EvaluationSuite,  # noqa: E402
                                      make_evaluator)
from photon_ml_tpu.evaluation.evaluator import (GroupLayout,  # noqa: E402
                                                grouped_evaluate)
from photon_ml_tpu.game import (FixedEffectConfig, GameData,  # noqa: E402
                                GameEstimator, RandomEffectConfig)
from photon_ml_tpu.game.config import GameConfig  # noqa: E402
from photon_ml_tpu.game.coordinate import build_coordinate  # noqa: E402
from photon_ml_tpu.game.fused import FusedSweep  # noqa: E402
from photon_ml_tpu.obs.trace import Tracer, set_tracer  # noqa: E402
from photon_ml_tpu.opt.types import SolverConfig  # noqa: E402
from photon_ml_tpu.parallel import bucketing  # noqa: E402
from photon_ml_tpu.tune import tune_game_model  # noqa: E402
from photon_ml_tpu.tune.game_tuning import (  # noqa: E402
    GameEstimatorEvaluationFunction)
from photon_ml_tpu.tune.search import (DomainDim,  # noqa: E402
                                       GaussianProcessSearch, RandomSearch,
                                       SearchDomain)
from photon_ml_tpu.types import TaskType  # noqa: E402

CELL = "glmix_tune_ml20m.tune_jobs"
TASK = TaskType.LOGISTIC_REGRESSION
reference = harness.Catalog().module("reference", "tuned_validation")


# -- the grouped metrics: layout form == padded oracle == plain loop ----------

def _rows(rng, n=900, dtype=np.float64):
    """Ragged groups, tied scores, a one-class group, a weightless group,
    rows of weight 0."""
    gids = rng.integers(0, 40, size=n) * 3 + 5
    gids[:7] = 2                       # a small group of its own
    s = np.round(rng.normal(size=n), 1)
    y = (rng.random(n) > 0.5).astype(dtype)
    y[gids == 11] = 1.0
    w = (rng.random(n) * (rng.random(n) > 0.1)).astype(dtype)
    w[gids == 14] = 0.0
    return gids, s.astype(dtype), y, w


def _loop(spec, gids, s, y, w):
    """The plain loop: every group with weight through the single
    evaluator, averaged."""
    ev = make_evaluator(spec)
    vals = [float(ev.metric_fn()(jnp.asarray(s[gids == g]),
                                 jnp.asarray(y[gids == g]),
                                 jnp.asarray(w[gids == g])))
            for g in np.unique(gids) if w[gids == g].sum() > 0]
    return float(np.mean(vals))


@pytest.mark.parametrize("spec", [
    "auc", "aupr", "rmse", "logistic_loss", "poisson_loss", "squared_loss",
    "smoothed_hinge_loss", "precision@3"])
def test_grouped_layout_equals_padded_oracle_and_plain_loop(rng, spec):
    gids, s, y, w = _rows(rng)
    ev = make_evaluator(spec + ":g")
    layout = GroupLayout.build(gids)
    assert layout.num_groups == len(np.unique(gids))
    got = ev.evaluate(jnp.asarray(s), jnp.asarray(y), jnp.asarray(w), layout)
    assert got == ev.evaluate(jnp.asarray(s), jnp.asarray(y), jnp.asarray(w),
                              gids)
    oracle = grouped_evaluate(make_evaluator(spec).metric_fn(), gids,
                              jnp.asarray(s), jnp.asarray(y), jnp.asarray(w))
    np.testing.assert_allclose(got, oracle, rtol=1e-12)
    np.testing.assert_allclose(got, _loop(spec, gids, s, y, w), rtol=1e-12)
    # float32, as the chip computes it: the oracle's to rounding
    f32 = [jnp.asarray(a.astype(np.float32)) for a in (s, y, w)]
    np.testing.assert_allclose(
        ev.evaluate(*f32, layout),
        grouped_evaluate(make_evaluator(spec).metric_fn(), gids, *f32),
        rtol=2e-6)


def test_segmented_scan_restarts_and_keeps_its_segments_magnitude(rng):
    from photon_ml_tpu.evaluation.metrics import segmented_scan

    n = 128 * 37 + 5
    v = rng.random(n)
    start = rng.random(n) < 0.02
    start[0] = True
    want = np.empty(n)
    run = 0.0
    for i in range(n):
        run = v[i] if start[i] else run + v[i]
        want[i] = run
    np.testing.assert_allclose(
        segmented_scan(jnp.asarray(v), jnp.asarray(start)), want, rtol=1e-12)
    # a small segment behind a huge one: float32 keeps the small one's digits
    big = np.r_[np.full(4096, 3e7, np.float32), np.float32([0.25, 0.5])]
    cut = np.zeros(len(big), bool)
    cut[[0, 4096]] = True
    out = np.asarray(segmented_scan(jnp.asarray(big), jnp.asarray(cut)))
    assert out[-1] == np.float32(0.75)


# -- a validated trial against the plain reference ----------------------------

def _sets(rng, n_users=24, n_items=14, d_g=6, d_u=3):
    """(training, held-out): ragged users, users and items the training
    set never saw, users with one class, rows of weight 0."""
    per_user = rng.integers(3, 40, size=n_users)
    uid = np.repeat(np.arange(n_users) * 2 + 7, per_user)
    n = len(uid)
    iid = rng.integers(0, n_items, size=n) + 100
    xg, xu, xi = (rng.normal(size=(n, d)) for d in (d_g, d_u, d_u))
    wg = rng.normal(size=d_g) * 0.6
    wu = rng.normal(size=(n_users, d_u)) * 0.8
    z = xg @ wg + np.einsum("nd,nd->n", xu, wu[(uid - 7) // 2])
    y = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(float)
    held = rng.random(n) < 0.3
    held[uid == 7] = True              # a user and an item never trained on
    held[iid == 100] = True
    y[held & (uid == 9)] = 1.0         # a one-class user in the held-out set
    w = np.ones(n)
    w[held & (rng.random(n) < 0.1)] = 0.0

    def one(m):
        return GameData(y=y[m], weight=w[m],
                        features={"g": xg[m], "u": xu[m], "i": xi[m]},
                        id_tags={"userId": uid[m], "itemId": iid[m]})

    return one(~held), one(held)


def _coordinates(data, l2=1.0):
    solver = SolverConfig(max_iters=60, tolerance=1e-9)
    reg = Regularization(l2=l2)
    cfgs = {
        "fixed": FixedEffectConfig(feature_shard="g", solver=solver, reg=reg),
        "per-user": RandomEffectConfig(random_effect_type="userId",
                                       feature_shard="u", solver=solver,
                                       reg=reg),
        "per-item": RandomEffectConfig(random_effect_type="itemId",
                                       feature_shard="i", solver=solver,
                                       reg=reg)}
    return cfgs, {cid: build_coordinate(cid, data, c, TASK)
                  for cid, c in cfgs.items()}


def _table(model):
    entities = np.asarray(sorted(model.slot_of), np.int64)
    return (np.asarray(model.w_stack)[[model.slot_of[int(e)]
                                       for e in entities]], entities)


@pytest.mark.parametrize("layout", ["row_major", "entity_major",
                                    "transposed"])
def test_validated_trial_equals_the_plain_reference(rng, monkeypatch, layout):
    """Held-out totals, ``auc`` and ``auc:userId`` of a validated fit equal
    ``reference/tuned_validation.py``'s on the model the fit exported, in
    each layout a random effect's held-out design can take."""
    train, held = _sets(rng)
    if layout != "row_major":
        # every size is over the narrow layouts' line; no chunk length fits
        # where none may pad
        monkeypatch.setattr(bucketing, "NARROW_SCORE_PAD_BYTES_MIN", 0)
        monkeypatch.setattr(bucketing, "EM_PAD_MAX",
                            1.0 if layout == "transposed" else 16.0)
    set_tracer(Tracer(enabled=True))
    try:
        _, coords = _coordinates(train)
        sweep = FusedSweep(coords, num_iterations=2)
        suite = EvaluationSuite.from_specs(["auc", "auc:userId"],
                                           primary="auc")
        plan = sweep.validation_plan(held, suite)
        model, evals, best, losses = sweep.run_validated(plan)
        spans = [r for r in obs.get_tracer().records() if r["ph"] == "X"]
    finally:
        set_tracer(Tracer())
    chosen = {r["attrs"]["coordinate"]: r["attrs"]["layout"] for r in spans
              if r["name"] == "coord.external_layout"}
    assert chosen == {"per-user": layout, "per-item": layout}
    # ragged held-out rows grouped by user come back by the un-pad, an
    # item's rows lie anywhere and keep the position gather
    back = {r["attrs"]["coordinate"]: r["attrs"].get("back") for r in spans
            if r["name"] == "coord.external_layout"}
    assert back == ({"per-user": "unpad", "per-item": "gather"}
                    if layout == "entity_major"
                    else {"per-user": None, "per-item": None})
    effects = [(held.features[shard], *_table(model[cid]), held.id_tags[tag])
               for cid, shard, tag in (("per-user", "u", "userId"),
                                       ("per-item", "i", "itemId"))]
    assert 7 in held.id_tags["userId"] and 7 not in effects[0][2], \
        "the held-out set holds a user the training set never saw"
    want = reference.heldout_scores(
        held.features["g"], np.asarray(model["fixed"].coefficients.means),
        effects)
    kept = evals.index(best)
    np.testing.assert_allclose(np.asarray(plan.totals)[kept], want,
                               rtol=2e-5, atol=2e-6)  # float32 on both sides
    live = held.weight > 0  # the reference's rows carry no weight: cut them
    got = best.values
    np.testing.assert_allclose(
        got["auc"], reference.rank_auc(held.y[live], want[live]), rtol=1e-6)
    groups = reference.per_group_auc(held.y[live], want[live],
                                     held.id_tags["userId"][live])
    assert groups["one_class"] > 0
    np.testing.assert_allclose(got["auc:userId"],
                               groups["half_for_one_class"], rtol=1e-6)
    assert losses.shape == (2, 3) and np.isfinite(losses).all()
    assert {r["name"] for r in spans} >= {
        "descent.fused_validated", "validate.evaluate", "validate.export",
        "validate.plan"}


@pytest.mark.parametrize("back, per_user, shuffled, more", [
    ("identity", 16, False, {}),
    ("unpad", 14, False, dict(stages=4, slots=128)),  # 2 x 7 slots in front
    ("gather", 16, True, {}),
])
def test_external_layout_span_says_the_way_back(rng, monkeypatch, back,
                                                per_user, shuffled, more):
    """``coord.external_layout`` of an entity-major held-out design says how
    its scores come back to sample order, and ``trace_score_external``
    scores every row as the plain gather-and-dot does."""
    monkeypatch.setattr(bucketing, "NARROW_SCORE_PAD_BYTES_MIN", 0)
    train, _ = _sets(rng)
    _, coords = _coordinates(train)
    coord = coords["per-user"]
    uid = np.repeat(np.arange(8) * 2 + 7, per_user)  # user 7: never trained
    if shuffled:
        uid = rng.permutation(uid)
    n = len(uid)
    held = GameData(y=np.zeros(n), features={
        "g": rng.normal(size=(n, 6)), "u": rng.normal(size=(n, 3)),
        "i": rng.normal(size=(n, 3))},
        id_tags={"userId": uid, "itemId": np.zeros(n, np.int64)})
    set_tracer(Tracer(enabled=True))
    try:
        vdata = coord.external_data(held)
        (span,) = [r["attrs"] for r in obs.get_tracer().records()
                   if r["name"] == "coord.external_layout"]
    finally:
        set_tracer(Tracer())
    assert span == dict(coordinate="per-user", rows=n, layout="entity_major",
                        chunk=16, lanes=8, fill=per_user / 16, back=back,
                        **more)
    assert isinstance(vdata["way_back"], bucketing.Unpad) == (back == "unpad")
    w = jnp.asarray(rng.normal(size=(len(coord._slot_of), 3)), coord._dtype)
    got = np.asarray(coord.trace_score_external(w, vdata))
    slots = np.asarray([coord._slot_of.get(int(u), -1) for u in uid])
    want = np.where(slots >= 0, np.einsum(
        "nd,nd->n", held.features["u"], np.asarray(w)[slots]), 0.0)
    assert got.shape == (n,)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert (got[uid == 7] == 0).all()


def test_validated_program_records_its_table_and_scopes(rng):
    train, held = _sets(rng)
    set_tracer(Tracer(enabled=True))
    try:
        _, coords = _coordinates(train)
        sweep = FusedSweep(coords, num_iterations=2)
        suite = EvaluationSuite.from_specs(["auc", "auc:userId"])
        sweep.run_validated(sweep.validation_plan(held, suite))
        tables = obs.get_tracer().device_tables()
    finally:
        set_tracer(Tracer())
    assert set(tables) == {"jit_validated"}
    paths = "\n".join(tables["jit_validated"].values())
    for scope in ("photon.validate.score.fixed",
                  "photon.validate.score.per_user",
                  "photon.validate.score.per_item", "photon.validate.loss",
                  "photon.evaluate.auc", "photon.evaluate.auc_userId"):
        assert scope in paths, scope


def test_external_data_takes_a_device_design_as_it_is(rng):
    """A held-out design the caller made on the device is not fetched and
    not uploaded again: ``device_put_counted`` counts no byte for it."""
    train, held = _sets(rng)
    _, coords = _coordinates(train)
    on_device = jnp.asarray(held.features["g"], jnp.float32)
    held.features["g"] = on_device
    probe = obs.get_probe()
    before = probe.transfer_bytes("h2d", site="device_put")
    vdata = coords["fixed"].external_data(held)
    assert probe.transfer_bytes("h2d", site="device_put") == before
    assert vdata["x"] is on_device
    coords["per-user"].external_data(held)  # a host shard is counted
    assert probe.transfer_bytes("h2d", site="device_put") > before


# -- the search ---------------------------------------------------------------

PARENT_PROPOSALS = {  # sha256 of the parent commit's proposals (6999422)
    GaussianProcessSearch: "21d7e4cebfd2d8c6",
    RandomSearch: "f563b245fd9c146b"}


@pytest.mark.parametrize("cls", [GaussianProcessSearch, RandomSearch])
def test_a_seed_proposes_the_same_weights_bitwise(cls):
    """Two searches with one seed propose the same weights, bit for bit,
    and they are the weights the commit before ISSUE 32 proposed."""
    dom = SearchDomain([DomainDim(f"l2:{c}", 1e-4, 1e4, True) for c in "abc"])

    def bowl(p):
        return float(-np.sum((np.log10(p) - np.array([0.5, -1.0, 2.0])) ** 2))

    def proposals():
        s = cls(dom, minimize=False, seed=1234)
        s.find(bowl, n=8, priors=[(np.ones(3), bowl(np.ones(3)))])
        return [o.params.tobytes().hex() for o in s.observations]

    first, second = proposals(), proposals()
    assert first == second and len(first) == 9
    assert hashlib.sha256(str(first).encode()).hexdigest()[:16] \
        == PARENT_PROPOSALS[cls]


def _tuning(rng):
    train, held = _sets(rng)
    cfgs, _ = _coordinates(train)
    config = GameConfig(task=TASK, num_outer_iterations=2, coordinates=cfgs)
    est = GameEstimator(validation_suite=EvaluationSuite.from_specs(
        ["auc", "auc:userId"], primary="auc"))
    return est, config, train, held


def test_twenty_trials_at_twenty_weights_compile_once(rng):
    est, config, train, held = _tuning(rng)
    fn = GameEstimatorEvaluationFunction(est, config, train, held, seed=3)
    fn.warmup()
    built = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, *_a, **_k: built.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    registry = obs.get_registry()
    before = (registry.counter("tune.compiles_in_search"),
              registry.counter("tune.trials"),
              registry.counter("validate.pull_bytes"))
    set_tracer(Tracer(enabled=True))
    try:
        best, search, tuned = tune_game_model(
            est, config, train, held, n_iterations=19, mode="bayesian",
            seed=3, evaluation_function=fn)
        spans = [r for r in obs.get_tracer().records() if r["ph"] == "X"]
    finally:
        set_tracer(Tracer())
    assert len(tuned) == 20 and best in tuned
    assert len({tuple(r.config.coordinates[c].reg.l2 for c in
                      config.coordinates) for r in tuned}) == 20
    assert built == []
    assert registry.counter("tune.compiles_in_search") == before[0]
    assert registry.counter("tune.trials") == before[1] + 20
    assert registry.counter("validate.pull_bytes") > before[2]
    trials = [r for r in spans if r["name"] == "tune.trial"]
    proposals = [r for r in spans if r["name"] == "tune.propose"]
    assert len(trials) == 20 and len(proposals) == 19
    assert all(set(r["attrs"]) >= {"l2", "primary", "iterations_kept"}
               for r in trials)
    assert [r["attrs"]["candidates"] for r in proposals] == [1, 1] + [250] * 17
    assert {r["attrs"]["mode"] for r in proposals} == {"bayesian"}
    assert best.evaluation.primary == max(r.evaluation.primary for r in tuned)


def test_a_raising_warmup_leaves_the_function_as_it_found_it(rng,
                                                             monkeypatch):
    est, config, train, held = _tuning(rng)
    fn = GameEstimatorEvaluationFunction(est, config, train, held, seed=0)
    fn(np.ones(3))
    kept = list(fn.results)

    def broken(self, params_batch):
        self.results.append(kept[0])  # recorded, then the batch fails
        raise RuntimeError("no grid program today")

    monkeypatch.setattr(GameEstimatorEvaluationFunction, "evaluate_batch",
                        broken)
    with pytest.raises(RuntimeError, match="no grid program"):
        fn.warmup(grid_sizes=(2,))
    assert fn.results == kept


# -- the cell, through its own generator, reference and gates -----------------

def _dry_run(tmp_path, workload, manifest=None, trace=0):
    argv = [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
            workload, "--seed", "3200000019", "--seconds", "2", "--trace",
            str(trace), "--dry-run"]
    if manifest:
        argv += ["--manifest", manifest]
    done = subprocess.run(
        argv, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=900,
        env={**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla")})
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_dry_run_of_the_cell_is_correct(tmp_path):
    line = _dry_run(tmp_path, CELL)
    assert line["correct"], line["checks"]
    assert set(line["checks"]) >= {
        "trials_finished", "losses_finite", "one_program", "best_is_best",
        "heldout_rows_scored", "metrics_match", "newton_parity",
        "no_compile_in_window"}
    assert set(line["metrics"]) == {"train_examples_per_s", "setup_s"}
    d = line["detail"]
    assert d["trials_in_window"] >= 2 and d["heldout_score_err"] < 2e-5
    assert d["l2_each"][0] == [1.0, 1.0, 1.0]  # a job starts at the prior
    for job in d["jobs"]:  # every trial of a job at weights of its own
        if job["trials"] == 0:  # the window ended before its first trial
            continue
        tried = d["l2_each"][job["first"]:job["first"] + job["trials"]]
        assert len(tried) == job["trials"]
        assert tried[0] == [1.0, 1.0, 1.0]
        assert len({tuple(w) for w in tried}) == len(tried)
    assert d["one_class_groups"] > 0 and d["reference_dtype"] == "float32"


def test_dry_run_under_the_bfloat16_control_is_not_correct(tmp_path):
    """The reference on features rounded to bfloat16, the nearest precision
    below the configuration's, has to come out as not ``correct``: by the
    held-out rows' scores, and by nothing the precision does not touch."""
    catalog = harness.Catalog()
    more = tmp_path / "more"
    os.makedirs(more / "workloads")
    wl = catalog.json("workloads", CELL)
    wl["name"] = CELL + "_bf16"
    wl["traffic_params"] = dict(wl.get("traffic_params", {}),
                                reference_dtype="bfloat16")
    (more / "workloads" / (wl["name"] + ".json")).write_text(json.dumps(wl))
    manifest = tmp_path / "BENCHMARK.json"
    manifest.write_text(json.dumps(dict(catalog.manifest,
                                        paths=[BENCH, str(more)])))
    line = _dry_run(tmp_path, wl["name"], str(manifest))
    assert not line["correct"]
    assert not line["checks"]["heldout_rows_scored"]
    assert line["detail"]["heldout_score_err"] > 1e-4
    assert all(line["checks"][k] for k in (
        "trials_finished", "losses_finite", "one_program", "best_is_best",
        "no_compile_in_window"))


def test_recipe_splits_the_problem_not_the_sample():
    """``ml20m_holdout`` hands over the very features ``ml20m_counts`` draws
    from the same seed, split by a mask that is ``truth_seed``'s; how many
    rows of each user AND of each movie lie in each set is ``truth_seed``'s
    too: one program for every seed."""
    catalog = harness.Catalog()
    cfg = harness.sized(catalog.json("configs", "glmix_tune_ml20m"), True)
    whole = catalog.module("recipes", "ml20m_counts").make_training(cfg, 11)
    recipe = catalog.module("recipes", "ml20m_holdout")
    train, held = recipe.make_sets(cfg, 11)
    other_train, other_held = recipe.make_sets(cfg, 12)
    mask = recipe.held_out_rows(cfg)
    assert 0.15 < mask.mean() < 0.25
    for part, other, rows in ((train, other_train, ~mask),
                              (held, other_held, mask)):
        for shard in "gui":
            np.testing.assert_array_equal(
                np.asarray(part["features"][shard]),
                np.asarray(whole["features"][shard])[rows])
        assert np.array_equal(part["id_tags"]["userId"],
                              whole["id_tags"]["userId"][rows])
        assert not np.array_equal(part["y"], other["y"])
        for tag in ("userId", "itemId"):  # the seed moves no entity's count
            assert np.array_equal(np.bincount(part["id_tags"][tag]),
                                  np.bincount(other["id_tags"][tag]))
        assert not np.array_equal(part["id_tags"]["itemId"],
                                  other["id_tags"]["itemId"])
    sizes = recipe.sizes(cfg)
    assert sizes["n_train"] + sizes["n_heldout"] == sizes["n"] == len(mask)
    assert np.array_equal(
        np.bincount(np.r_[train["id_tags"]["itemId"],
                          held["id_tags"]["itemId"]]),
        np.bincount(whole["id_tags"]["itemId"]))
    unseen = ~np.isin(held["id_tags"]["itemId"], train["id_tags"]["itemId"])
    assert unseen.any(), "some movie has every row in the held-out set"
