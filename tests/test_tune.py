"""Hyperparameter-tuning tests: GP regression quality, slice sampler, search
convergence on closed-form objectives, GAME tuning end-to-end."""

import numpy as np
import pytest

from photon_ml_tpu.tune import (
    GaussianProcess,
    GaussianProcessSearch,
    Matern52,
    RBF,
    RandomSearch,
    SearchDomain,
    expected_improvement,
    slice_sample,
)
from photon_ml_tpu.tune.search import DomainDim


def test_kernels_psd_and_forms(rng):
    x = rng.normal(size=(20, 3))
    for kern in (RBF(), Matern52()):
        k = kern(x, x)
        np.testing.assert_allclose(k, k.T, atol=1e-12)
        w = np.linalg.eigvalsh(k)
        assert w.min() > -1e-9
        np.testing.assert_allclose(np.diagonal(k), kern.amplitude, rtol=1e-10)
    # RBF closed form on a simple pair
    k = RBF()(np.zeros((1, 1)), np.ones((1, 1)))
    np.testing.assert_allclose(k[0, 0], np.exp(-0.5), rtol=1e-12)


def test_gp_interpolates_smooth_function(rng):
    f = lambda x: np.sin(3 * x[:, 0]) + 0.5 * x[:, 0]
    x = rng.random((25, 1))
    y = f(x)
    gp = GaussianProcess().fit(x, y, seed=1)
    xt = rng.random((50, 1))
    mu, sigma = gp.predict(xt)
    err = np.abs(mu - f(xt))
    assert np.mean(err) < 0.1, np.mean(err)
    # posterior mean interpolates the observations
    mu0, _ = gp.predict(x)
    np.testing.assert_allclose(mu0, y, atol=0.05)


def test_slice_sampler_matches_gaussian(rng):
    logp = lambda x: float(-0.5 * ((x[0] - 2.0) / 1.5) ** 2)
    samples = slice_sample(logp, np.zeros(1), 2000, np.random.default_rng(0), burn_in=50)
    assert abs(samples.mean() - 2.0) < 0.15
    assert abs(samples.std() - 1.5) < 0.2


def test_expected_improvement_properties():
    # lower mean -> higher EI; zero sigma at worse point -> 0 EI
    ei = expected_improvement(np.asarray([0.0, 1.0]), np.asarray([0.5, 0.5]), best=0.5)
    assert ei[0] > ei[1]
    ei0 = expected_improvement(np.asarray([1.0]), np.asarray([1e-15]), best=0.5)
    assert ei0[0] < 1e-10


def test_domain_roundtrip_log_and_linear():
    dom = SearchDomain([
        DomainDim("a", 1e-3, 1e3, log_scale=True),
        DomainDim("b", -2.0, 5.0),
    ])
    u = np.asarray([[0.5, 0.5], [0.0, 0.0], [1.0, 1.0]])
    real = dom.to_real(u)
    np.testing.assert_allclose(real[0], [1.0, 1.5], rtol=1e-10)
    np.testing.assert_allclose(dom.to_unit(real), u, atol=1e-12)


@pytest.mark.parametrize("cls", [RandomSearch, GaussianProcessSearch])
def test_search_finds_minimum(cls):
    dom = SearchDomain([DomainDim("x", 0.0, 1.0), DomainDim("y", 0.0, 1.0)])
    f = lambda p: float((p[0] - 0.3) ** 2 + (p[1] - 0.7) ** 2)
    search = cls(dom, minimize=True, seed=0)
    best, val = search.find(f, n=25)
    assert val < 0.05, (best, val)
    # GP search should do at least as well as pure random with same budget
    if cls is GaussianProcessSearch:
        assert val < 0.02, (best, val)


def test_search_maximize_orientation():
    dom = SearchDomain([DomainDim("x", 0.0, 1.0)])
    f = lambda p: float(-((p[0] - 0.6) ** 2))  # max at 0.6
    search = RandomSearch(dom, minimize=False, seed=1)
    best, val = search.find(f, n=30)
    assert abs(best[0] - 0.6) < 0.1
    assert val <= 0.0


def test_game_tuning_end_to_end(rng):
    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.evaluation import EvaluationSuite
    from photon_ml_tpu.game import FixedEffectConfig, GameData, GameEstimator
    from photon_ml_tpu.game.config import GameConfig
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.tune import tune_game_model
    from photon_ml_tpu.types import TaskType

    n, d = 400, 8
    x = rng.normal(size=(n, d))
    w = rng.normal(size=d)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-x @ w))).astype(float)
    tr = GameData(y=y[:300], features={"g": x[:300]})
    va = GameData(y=y[300:], features={"g": x[300:]})

    config = GameConfig(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinates={"fixed": FixedEffectConfig(
            feature_shard="g", solver=SolverConfig(max_iters=50),
            reg=Regularization(l2=1.0))},
    )
    est = GameEstimator(validation_suite=EvaluationSuite.from_specs(["auc"]))
    best, search, tuned = tune_game_model(est, config, tr, va, n_iterations=4,
                                          mode="bayesian", seed=0)
    assert best.evaluation.values["auc"] > 0.7
    assert len(search.observations) == 5  # prior + 4 iterations
    assert len(tuned) == 5 and best in tuned
    tuned_l2 = best.config.coordinates["fixed"].reg.l2
    assert 1e-4 <= tuned_l2 <= 1e4


def test_multi_iteration_fused_tuning_matches_host(rng):
    """num_outer_iterations > 1 tuning fits run through ONE compiled fused
    program (FusedSweep.run_snapshots) whose per-iteration snapshots are
    exactly the full models host best-model retention compares
    (reference CoordinateDescent.scala:163-167) — fused and host paths must
    agree on the selected model's validation metric."""
    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.evaluation import EvaluationSuite
    from photon_ml_tpu.game import (FixedEffectConfig, GameData,
                                    GameEstimator, RandomEffectConfig)
    from photon_ml_tpu.game.config import GameConfig
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.tune.game_tuning import GameEstimatorEvaluationFunction
    from photon_ml_tpu.types import TaskType

    n, d_g, d_u, n_users = 512, 6, 3, 16
    xg = rng.normal(size=(n, d_g)).astype(np.float32)
    xu = rng.normal(size=(n, d_u)).astype(np.float32)
    uids = np.repeat(np.arange(n_users), n // n_users)
    wu = rng.normal(size=(n_users, d_u))
    logits = xg @ rng.normal(size=d_g) + np.einsum("nd,nd->n", xu, wu[uids])
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logits))).astype(np.float32)
    # shuffle so every user appears on BOTH sides of the split — otherwise
    # the validation rows belong to unseen entities, which score 0, and the
    # assertion would never see the random-effect snapshots at all
    perm = rng.permutation(n)
    xg, xu, uids, y = xg[perm], xu[perm], uids[perm], y[perm]
    cut = 384
    tr = GameData(y=y[:cut], features={"g": xg[:cut], "u": xu[:cut]},
                  id_tags={"userId": uids[:cut]})
    va = GameData(y=y[cut:], features={"g": xg[cut:], "u": xu[cut:]},
                  id_tags={"userId": uids[cut:]})
    solver = SolverConfig(max_iters=25, tolerance=1e-7)
    config = GameConfig(
        task=TaskType.LOGISTIC_REGRESSION, num_outer_iterations=2,
        coordinates={
            "fixed": FixedEffectConfig(feature_shard="g", solver=solver,
                                       reg=Regularization(l2=1.0)),
            "per-user": RandomEffectConfig(random_effect_type="userId",
                                           feature_shard="u", solver=solver,
                                           reg=Regularization(l2=1.0))})
    suite = EvaluationSuite.from_specs(["auc"])
    fn_fused = GameEstimatorEvaluationFunction(
        GameEstimator(validation_suite=suite), config, tr, va, seed=0)
    fn_host = GameEstimatorEvaluationFunction(
        GameEstimator(validation_suite=suite, fused=False), config, tr, va, seed=0)
    for params in ([1.0, 1.0], [10.0, 0.1]):
        v_fused = fn_fused(np.asarray(params))
        v_host = fn_host(np.asarray(params))
        # tolerance: the 128-example validation split quantizes AUC in
        # ~1/(n_pos*n_neg) ≈ 2.4e-4 steps, and fused/host are different
        # float32 XLA programs — allow a few flipped score pairs
        assert abs(v_fused - v_host) < 2e-3, params
    # the fused path really did share one sweep (not the host fallback)
    assert fn_fused._sweep not in (None, False)
    sweep, _, _plan = fn_fused._sweep
    snaps = sweep.run_snapshots()
    assert len(snaps) == 2  # one full model per outer iteration
    assert set(snaps[0].models) == {"fixed", "per-user"}


def test_hyperparameter_serialization_roundtrip():
    """Reference HyperparameterSerialization.configFromJson/priorFromJson:
    LOG variables are declared by base-10 exponent; prior records fill
    missing params from defaults."""
    import numpy as np

    from photon_ml_tpu.tune.serialization import (config_from_json,
                                                  config_to_json,
                                                  prior_from_json)

    # the reference's GameHyperparameterDefaults.configDefault shape
    cfg = """
    { "tuning_mode" : "BAYESIAN",
      "variables" : {
        "global_regularizer" : {"type": "FLOAT", "transform": "LOG",
                                "min": -3, "max": 3},
        "member_regularizer" : {"type": "FLOAT", "min": 0.5, "max": 2.0}
      }
    }"""
    mode, domain = config_from_json(cfg)
    assert mode == "BAYESIAN"
    assert domain.d == 2
    g, m = domain.dims
    assert g.log_scale and np.isclose(g.low, 1e-3) and np.isclose(g.high, 1e3)
    assert not m.log_scale and m.low == 0.5 and m.high == 2.0

    mode2, domain2 = config_from_json(config_to_json(mode, domain))
    assert mode2 == mode
    for a, b in zip(domain.dims, domain2.dims):
        assert a.name == b.name and np.isclose(a.low, b.low) and np.isclose(a.high, b.high)

    priors = prior_from_json(
        '{"records": [{"global_regularizer": "10", "evaluationValue": "0.8"},'
        ' {"member_regularizer": "1.5", "evaluationValue": "0.6"}]}',
        {"global_regularizer": "0.0", "member_regularizer": "1.0"},
        ["global_regularizer", "member_regularizer"])
    np.testing.assert_allclose(priors[0][0], [10.0, 1.0])
    assert priors[0][1] == 0.8
    np.testing.assert_allclose(priors[1][0], [0.0, 1.5])
    assert priors[1][1] == 0.6

    import pytest

    with pytest.raises(ValueError):
        config_from_json('{"tuning_mode": "GRID", "variables": {}}')


def test_tuning_with_json_config_and_priors(tmp_path, rng):
    """tune_game_model honors a serialized search domain + prior records."""
    import numpy as np

    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.evaluation import EvaluationSuite
    from photon_ml_tpu.game import FixedEffectConfig, GameData, GameEstimator
    from photon_ml_tpu.game.config import GameConfig
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.tune import tune_game_model
    from photon_ml_tpu.tune.serialization import config_from_json
    from photon_ml_tpu.types import TaskType

    n, d = 300, 5
    x = rng.normal(size=(n, d))
    w = rng.normal(size=d)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-x @ w))).astype(float)
    tr = GameData(y=y[:220], features={"g": x[:220]})
    va = GameData(y=y[220:], features={"g": x[220:]})
    config = GameConfig(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinates={"fixed": FixedEffectConfig(
            feature_shard="g", solver=SolverConfig(max_iters=40),
            reg=Regularization(l2=1.0))})

    mode, domain = config_from_json(
        '{"tuning_mode": "RANDOM", "variables": '
        '{"l2:fixed": {"type": "FLOAT", "transform": "LOG", "min": -2, "max": 2}}}')
    est = GameEstimator(validation_suite=EvaluationSuite.from_specs(["auc"]))
    best, search, tuned = tune_game_model(
        est, config, tr, va, n_iterations=3, mode=mode.lower(), seed=0,
        search_domain=domain,
        prior_observations=[(np.asarray([0.5]), 0.55)])
    # 3 evaluated + 1 prior record + 1 base-config warm prior
    assert len(search.observations) == 5
    assert len(tuned) == 4  # prior observations don't retrain
    for obs in search.observations[2:]:
        assert 1e-2 <= obs.params[0] <= 1e2  # respects the JSON domain
    assert best.evaluation.values["auc"] > 0.6


def test_shrink_search_range():
    """Reference ShrinkSearchRange.getBounds:40-100: GP on priors -> best
    Sobol candidate -> [best-r, best+r] box clamped to the original domain."""
    from photon_ml_tpu.tune.search import DomainDim, SearchDomain
    from photon_ml_tpu.tune.shrink import shrink_search_range

    dom = SearchDomain([DomainDim("l2", 1e-3, 1e3, log_scale=True),
                        DomainDim("b", 0.0, 10.0)])
    # quadratic bowl with minimum at l2=1.0 (unit 0.5), b=2.0 (unit 0.2)
    priors = []
    rng = np.random.default_rng(3)
    for _ in range(25):
        u = rng.random(2)
        p = dom.to_real(u)
        v = (np.log10(p[0])) ** 2 + 0.5 * (p[1] - 2.0) ** 2
        priors.append((p, float(v)))

    shrunk = shrink_search_range(dom, priors, radius=0.2, seed=0)
    l2d, bd = shrunk.dims
    assert l2d.log_scale
    # the shrunk box contains the optimum and is genuinely narrower
    assert l2d.low <= 1.0 <= l2d.high
    assert bd.low <= 2.0 <= bd.high
    assert np.log(l2d.high / l2d.low) < 0.5 * np.log(1e3 / 1e-3)
    assert (bd.high - bd.low) < 0.5 * 10.0
    # clamped inside the original domain
    assert l2d.low >= 1e-3 - 1e-12 and l2d.high <= 1e3 + 1e-9
    assert bd.low >= 0.0 and bd.high <= 10.0

    import pytest

    with pytest.raises(ValueError):
        shrink_search_range(dom, [], radius=0.2)


class _RecordingTuner:
    """Custom tuner for the reflection-loading test."""

    calls = []

    def tune(self, estimator, base_config, data, validation_data, **kwargs):
        _RecordingTuner.calls.append(kwargs)
        return None, None, []


def test_tuner_factory_dispatch():
    """Reference HyperparameterTunerFactory.scala:20-48: tuner by name —
    DUMMY no-op, BUILTIN in-tree, module:Class reflection-loaded."""
    import pytest

    from photon_ml_tpu.tune.factory import (BuiltinTuner, DummyTuner,
                                            tuner_factory)

    assert isinstance(tuner_factory("DUMMY"), DummyTuner)
    assert isinstance(tuner_factory("dummy"), DummyTuner)
    assert isinstance(tuner_factory("BUILTIN"), BuiltinTuner)
    assert isinstance(tuner_factory(""), BuiltinTuner)

    t = tuner_factory("test_tune:_RecordingTuner")
    assert isinstance(t, _RecordingTuner)
    assert t.tune(None, None, None, None, n_iterations=3) == (None, None, [])
    assert _RecordingTuner.calls[-1]["n_iterations"] == 3

    assert DummyTuner().tune(None, None, None, None) == (None, None, [])

    with pytest.raises(ValueError):
        tuner_factory("NOPE")
    with pytest.raises(ValueError):
        tuner_factory("no.such.module:Thing")
    with pytest.raises(ValueError):
        tuner_factory("collections:OrderedDict")  # loads but has no tune()


def test_tuning_warm_start_carries_prior_entities(rng):
    """Bayesian tuning with a warm-start model whose random effect covers an
    entity absent from (or under-bound in) the tuning data: every tuned fit
    — through the shared fused program — publishes that entity unchanged,
    and the carried contribution rides each fit's offsets."""
    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.evaluation import EvaluationSuite
    from photon_ml_tpu.game import (FixedEffectConfig, GameData,
                                    GameEstimator, RandomEffectConfig)
    from photon_ml_tpu.game.config import GameConfig
    from photon_ml_tpu.models.game import GameModel, RandomEffectModel
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.tune import tune_game_model
    from photon_ml_tpu.types import TaskType

    d_g, d_u = 4, 3
    # entity 7's TWO training rows are under the bound (4) and the prior
    # covers it -> the existing-model filter drops it from training and the
    # prior carries; entities 0/1 train normally (rows placed BEFORE the
    # validation cut so the lower-bound path actually fires)
    uids = np.concatenate([np.zeros(24), np.full(2, 7), np.ones(24)]).astype(np.int64)
    n = len(uids)
    xg = rng.normal(size=(n, d_g)).astype(np.float32)
    xu = rng.normal(size=(n, d_u)).astype(np.float32)
    y = (rng.random(n) > 0.5).astype(np.float32)
    cut = n - 10
    tr = GameData(y=y[:cut], features={"g": xg[:cut], "u": xu[:cut]},
                  id_tags={"userId": uids[:cut]})
    va = GameData(y=y[cut:], features={"g": xg[cut:], "u": xu[cut:]},
                  id_tags={"userId": uids[cut:]})
    solver = SolverConfig(max_iters=15)
    config = GameConfig(
        task=TaskType.LOGISTIC_REGRESSION, num_outer_iterations=2,
        coordinates={
            "fixed": FixedEffectConfig(feature_shard="g", solver=solver,
                                       reg=Regularization(l2=1.0)),
            "user": RandomEffectConfig(random_effect_type="userId",
                                       feature_shard="u", solver=solver,
                                       reg=Regularization(l2=1.0),
                                       min_active_samples=4)})
    prior_w = (rng.normal(size=(1, d_u)) * 1.5).astype(np.float32)
    prior = GameModel(models={"user": RandomEffectModel(
        w_stack=prior_w, slot_of={7: 0}, random_effect_type="userId",
        feature_shard="u", task=TaskType.LOGISTIC_REGRESSION)})
    est = GameEstimator(validation_suite=EvaluationSuite.from_specs(["auc"]))
    best, _search, tuned = tune_game_model(
        est, config, tr, va, n_iterations=3, mode="bayesian", seed=0,
        initial_model=prior)
    assert len(tuned) == 4 and best in tuned
    for r in tuned:
        m = r.model["user"]
        assert 7 in m.slot_of
        np.testing.assert_array_equal(m.w_stack[m.slot_of[7]], prior_w[0])


def test_batched_grid_tuning_matches_sequential(rng):
    """evaluate_batch (ONE vmapped FusedSweep.run_grid[_snapshots] over a
    reg grid) must reproduce sequential evaluation: same metrics, same
    recorded models, same order — for both the multi-iteration snapshot
    path and the single-iteration run_grid path; and tune_game_model with
    batch_size>1 keeps the total fit count."""
    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.evaluation import EvaluationSuite
    from photon_ml_tpu.game import (FixedEffectConfig, GameData,
                                    GameEstimator, RandomEffectConfig)
    from photon_ml_tpu.game.config import GameConfig
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.tune import tune_game_model
    from photon_ml_tpu.tune.game_tuning import GameEstimatorEvaluationFunction
    from photon_ml_tpu.types import TaskType

    n, d_g, d_u, n_users = 512, 6, 3, 16
    xg = rng.normal(size=(n, d_g)).astype(np.float32)
    xu = rng.normal(size=(n, d_u)).astype(np.float32)
    uids = np.repeat(np.arange(n_users), n // n_users)
    wu = rng.normal(size=(n_users, d_u))
    logits = xg @ rng.normal(size=d_g) + np.einsum("nd,nd->n", xu, wu[uids])
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logits))).astype(np.float32)
    perm = rng.permutation(n)
    xg, xu, uids, y = xg[perm], xu[perm], uids[perm], y[perm]
    cut = 384
    tr = GameData(y=y[:cut], features={"g": xg[:cut], "u": xu[:cut]},
                  id_tags={"userId": uids[:cut]})
    va = GameData(y=y[cut:], features={"g": xg[cut:], "u": xu[cut:]},
                  id_tags={"userId": uids[cut:]})
    solver = SolverConfig(max_iters=25, tolerance=1e-7)
    suite = EvaluationSuite.from_specs(["auc"])
    grid = [np.asarray([1.0, 1.0]), np.asarray([10.0, 0.1]),
            np.asarray([0.2, 5.0])]

    for outer in (2, 1):  # snapshots path and run_grid path
        config = GameConfig(
            task=TaskType.LOGISTIC_REGRESSION, num_outer_iterations=outer,
            coordinates={
                "fixed": FixedEffectConfig(feature_shard="g", solver=solver,
                                           reg=Regularization(l2=1.0)),
                "per-user": RandomEffectConfig(
                    random_effect_type="userId", feature_shard="u",
                    solver=solver, reg=Regularization(l2=1.0))})
        fn_seq = GameEstimatorEvaluationFunction(
            GameEstimator(validation_suite=suite), config, tr, va, seed=0)
        fn_bat = GameEstimatorEvaluationFunction(
            GameEstimator(validation_suite=suite), config, tr, va, seed=0)
        seq = [fn_seq(p) for p in grid]
        bat = fn_bat.evaluate_batch(grid)
        np.testing.assert_allclose(bat, seq, atol=2e-3)
        assert len(fn_bat.results) == len(grid)
        for rs, rb in zip(fn_seq.results, fn_bat.results):
            np.testing.assert_allclose(
                np.asarray(rb.model["fixed"].coefficients.means),
                np.asarray(rs.model["fixed"].coefficients.means), atol=2e-3)
            np.testing.assert_allclose(
                np.asarray(rb.model["per-user"].w_stack),
                np.asarray(rs.model["per-user"].w_stack), atol=2e-3)

    # end-to-end batched tuning: same fit count
    config = GameConfig(
        task=TaskType.LOGISTIC_REGRESSION, num_outer_iterations=2,
        coordinates={
            "fixed": FixedEffectConfig(feature_shard="g", solver=solver,
                                       reg=Regularization(l2=1.0)),
            "per-user": RandomEffectConfig(
                random_effect_type="userId", feature_shard="u",
                solver=solver, reg=Regularization(l2=1.0))})
    est = GameEstimator(validation_suite=suite)
    best, search, tuned = tune_game_model(est, config, tr, va,
                                          n_iterations=6, mode="bayesian",
                                          seed=0, batch_size=3)
    assert len(tuned) == 7  # prior + 6 tuning fits, batched 3 per round
    assert best in tuned


def test_warmup_precompiles_grid_sizes(rng):
    """warmup(grid_sizes=(q,)) must leave no recorded fits
    while having exercised both the single-fit and q-grid fused
    programs (the bench's batched gp_tune relies on this so no XLA compile
    lands inside its measured window)."""
    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.evaluation import EvaluationSuite
    from photon_ml_tpu.game import (FixedEffectConfig, GameData,
                                    GameEstimator, RandomEffectConfig)
    from photon_ml_tpu.game.config import GameConfig
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.tune import tune_game_model
    from photon_ml_tpu.tune.game_tuning import GameEstimatorEvaluationFunction
    from photon_ml_tpu.types import TaskType

    n, d_g, d_u, n_users = 256, 4, 2, 8
    xg = rng.normal(size=(n, d_g)).astype(np.float32)
    xu = rng.normal(size=(n, d_u)).astype(np.float32)
    uids = np.repeat(np.arange(n_users), n // n_users)
    y = (rng.random(n) < 0.5).astype(np.float32)
    cut = 192
    tr = GameData(y=y[:cut], features={"g": xg[:cut], "u": xu[:cut]},
                  id_tags={"userId": uids[:cut]})
    va = GameData(y=y[cut:], features={"g": xg[cut:], "u": xu[cut:]},
                  id_tags={"userId": uids[cut:]})
    solver = SolverConfig(max_iters=10, tolerance=1e-6)
    config = GameConfig(
        task=TaskType.LOGISTIC_REGRESSION, num_outer_iterations=2,
        coordinates={
            "fixed": FixedEffectConfig(feature_shard="g", solver=solver,
                                       reg=Regularization(l2=1.0)),
            "per-user": RandomEffectConfig(
                random_effect_type="userId", feature_shard="u",
                solver=solver, reg=Regularization(l2=1.0))})
    est = GameEstimator(validation_suite=EvaluationSuite.from_specs(["auc"]))
    fn = GameEstimatorEvaluationFunction(est, config, tr, va, seed=0)
    fn.warmup(grid_sizes=(2,))
    assert fn.results == []
    # the warmed function then drives a batched search normally
    best, search, tuned = tune_game_model(est, config, tr, va,
                                          n_iterations=4, mode="bayesian",
                                          seed=0, evaluation_function=fn,
                                          batch_size=2)
    assert len(tuned) == 5  # prior + 4 tuning fits
    assert best in tuned
