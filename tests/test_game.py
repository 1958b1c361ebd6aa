"""GAME layer tests: coordinate semantics, residual descent, estimator.

Reference analogs: FixedEffectCoordinateIntegTest, RandomEffectCoordinateIntegTest,
GameEstimatorIntegTest (SURVEY.md §4).
"""

import numpy as np
import pytest

from photon_ml_tpu.core.regularization import Regularization
from photon_ml_tpu.evaluation import EvaluationSuite
from photon_ml_tpu.game import (
    CoordinateDescent,
    FixedEffectConfig,
    GameData,
    GameEstimator,
    GameTransformer,
    RandomEffectConfig,
    build_coordinate,
)
from photon_ml_tpu.game.config import GameConfig
from photon_ml_tpu.models.game import GameModel
from photon_ml_tpu.opt.types import SolverConfig
from photon_ml_tpu.types import TaskType


def _glmix_data(rng, n_users=20, per_user=60, d_global=6, d_user=3):
    """Generative GLMix: logit = x_g·w_g + x_u·w_user(u)."""
    n = n_users * per_user
    xg = rng.normal(size=(n, d_global))
    xu = rng.normal(size=(n, d_user))
    uid = np.repeat(np.arange(n_users) * 3 + 11, per_user)
    wg = rng.normal(size=d_global) * 0.8
    wu = rng.normal(size=(n_users, d_user)) * 1.2
    logits = xg @ wg + np.einsum("nd,nd->n", xu, wu[np.repeat(np.arange(n_users), per_user)])
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logits))).astype(float)
    data = GameData(
        y=y,
        features={"global": xg, "per_user": xu},
        id_tags={"userId": uid},
    )
    return data, wg, wu, logits


def _configs(num_iters=3):
    solver = SolverConfig(max_iters=100, tolerance=1e-8)
    return GameConfig(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinates={
            "fixed": FixedEffectConfig(feature_shard="global", solver=solver,
                                       reg=Regularization(l2=1.0)),
            "per-user": RandomEffectConfig(random_effect_type="userId",
                                           feature_shard="per_user", solver=solver,
                                           reg=Regularization(l2=1.0)),
        },
        num_outer_iterations=num_iters,
    )


def test_fixed_coordinate_update_and_score(rng):
    data, wg, _, _ = _glmix_data(rng, n_users=4, per_user=50)
    cfg = _configs().coordinates["fixed"]
    coord = build_coordinate("fixed", data, cfg, TaskType.LOGISTIC_REGRESSION)
    model, res = coord.update(np.zeros(data.num_samples))
    s = coord.score(model)
    np.testing.assert_allclose(
        s, data.features["global"] @ model.coefficients.means, rtol=1e-5, atol=1e-6
    )



def test_residual_offsets_matter(rng):
    """A coordinate trained with the other coordinate's score as offset must
    differ from one trained without (the residual trick)."""
    data, *_ = _glmix_data(rng, n_users=4, per_user=50)
    cfg = _configs().coordinates["fixed"]
    coord = build_coordinate("fixed", data, cfg, TaskType.LOGISTIC_REGRESSION)
    m0, _ = coord.update(np.zeros(data.num_samples))
    m1, _ = coord.update(rng.normal(size=data.num_samples) * 2.0)
    assert not np.allclose(m0.coefficients.means, m1.coefficients.means)


def test_glmix_descent_beats_fixed_only(rng):
    data, wg, wu, logits = _glmix_data(rng)
    suite = EvaluationSuite.from_specs(["auc", "logistic_loss"], primary="auc")
    est = GameEstimator(validation_suite=suite)
    # fixed-only
    fixed_only = GameConfig(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinates={"fixed": _configs().coordinates["fixed"]},
    )
    r_fixed = est.fit(data, [fixed_only], validation_data=data)[0]
    # full GLMix
    r_full = est.fit(data, [_configs()], validation_data=data)[0]
    auc_fixed = r_fixed.evaluation.values["auc"]
    auc_full = r_full.evaluation.values["auc"]
    assert auc_full > auc_fixed + 0.05, (auc_fixed, auc_full)
    assert auc_full > 0.8


def test_glmix_recovers_fixed_coefficients(rng):
    """With random effects absorbing per-user structure, the fixed coordinate
    should approach the generative global coefficients."""
    data, wg, wu, _ = _glmix_data(rng, n_users=30, per_user=80)
    res = GameEstimator().fit(data, [_configs(num_iters=4)])[0]
    w_hat = res.model["fixed"].coefficients.means
    corr = np.corrcoef(w_hat, wg)[0, 1]
    assert corr > 0.95, corr


def test_descent_converges_training_loss(rng):
    """Each outer iteration must not worsen the training objective.
    fused=False: the per-update validation entries this asserts live in the
    HOST loop's history (the fused validated program tracks per-update
    losses in-program instead — tests/test_solve_path.py)."""
    data, *_ = _glmix_data(rng, n_users=8, per_user=40)
    suite = EvaluationSuite.from_specs(["logistic_loss"])
    est = GameEstimator(validation_suite=suite, fused=False)
    res = est.fit(data, [_configs(num_iters=3)], validation_data=data)[0]
    losses = [s["validation"].values["logistic_loss"] for s in res.history.steps]
    assert losses[-1] <= losses[0]
    # best-model tracking returned the minimum seen
    assert res.evaluation.values["logistic_loss"] <= min(losses) + 1e-9


def test_normalization_returns_original_space_model(rng):
    """A standardized solve must publish ORIGINAL-space coefficients: with
    negligible regularization the optimum is normalization-invariant, so the
    published models must agree (NormalizationContext.scala:73-124 parity)."""
    import jax.numpy as jnp

    from photon_ml_tpu.core.normalization import (build_normalization,
                                                  compute_feature_stats)
    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.game.config import FixedEffectConfig
    from photon_ml_tpu.types import NormalizationType

    n, d = 600, 4
    # badly scaled features (bad conditioning, margins still O(1))
    scales = np.asarray([100.0, 0.01, 5.0, 1.0])
    x = rng.normal(size=(n, d)) * scales + np.asarray([10.0, 0.0, 0.0, 2.0])
    x = np.concatenate([x, np.ones((n, 1))], axis=1)  # intercept col 4
    w_true = np.asarray([0.01, 60.0, -0.2, 0.8, 0.5])
    y = (rng.random(n) < 1 / (1 + np.exp(-(x @ w_true)))).astype(np.float64)
    data = GameData(features={"s": x}, y=y, offset=np.zeros(n), weight=np.ones(n),
                    id_tags={})

    def fit(norm):
        cfg = GameConfig(task=TaskType.LOGISTIC_REGRESSION, coordinates={
            "fixed": FixedEffectConfig(feature_shard="s",
                                       reg=Regularization(l2=1e-6),
                                       intercept_index=4)})
        est = GameEstimator(normalization=norm)
        return est.fit(data, [cfg])[0].model["fixed"].coefficients.means

    stats = compute_feature_stats(jnp.asarray(x), jnp.asarray(np.ones(n)),
                                  intercept_index=4)
    ctx = build_normalization(NormalizationType.STANDARDIZATION, stats)
    w_plain = fit(None)
    w_norm = fit({"s": ctx})
    # the published coefficients are ORIGINAL-space: they recover the
    # generative weights (including the tiny-scale feature's w=60 that the
    # unnormalized solve cannot move within its iteration budget)
    np.testing.assert_allclose(w_norm, w_true, rtol=0.25, atol=0.5)

    def logloss(w):
        z = np.clip(x @ w, -30, 30)
        return float(np.mean(np.log1p(np.exp(-np.abs(z))) + np.maximum(z, 0) - z * y))

    # conditioning win: the normalized solve reaches a better optimum
    assert logloss(w_norm) < logloss(w_plain) - 0.01, (logloss(w_norm), logloss(w_plain))


def test_checkpoint_resume_matches_uninterrupted(rng):
    """Preemption mid-descent: resuming from the captured (model, cursor)
    reproduces the uninterrupted run exactly (storage/checkpoint wiring)."""
    data, *_ = _glmix_data(rng, n_users=6, per_user=40)
    est = GameEstimator()
    cfg = _configs(num_iters=3)

    states = []
    full = est.fit(data, [cfg],
                   checkpoint_hook=lambda m, cur, **kw: states.append((m, cur)))[0]
    assert len(states) == 3 * len(cfg.coordinates)
    assert states[0][1] == {"config": 0, "iteration": 0, "coordinate": 1}

    # "crash" after the 3rd update; resume from that checkpoint
    model_ck, cursor_ck = states[2]
    resumed = est.fit(data, [cfg], initial_model=model_ck,
                      resume_cursor=cursor_ck)[0]
    # resume rebuilds `total` as a fresh sum while the uninterrupted run
    # accumulated it incrementally — f32 ordering noise only
    np.testing.assert_allclose(resumed.model["fixed"].coefficients.means,
                               full.model["fixed"].coefficients.means, atol=2e-3)
    for cid in cfg.coordinates:
        if cid != "fixed":
            np.testing.assert_allclose(np.asarray(resumed.model[cid].w_stack),
                                       np.asarray(full.model[cid].w_stack), atol=2e-3)


def test_checkpoint_preserves_best_model_across_resume(rng):
    """Best-by-primary-metric retention must survive preemption: the hook
    captures (best, best_changed) and resume seeds the tracker with it."""
    data, *_ = _glmix_data(rng, n_users=6, per_user=40)
    suite = EvaluationSuite.from_specs(["auc", "logistic_loss"], primary="auc")
    est = GameEstimator(validation_suite=suite)
    cfg = _configs(num_iters=3)

    snaps = []
    full = est.fit(data, [cfg], validation_data=data,
                   checkpoint_hook=lambda m, cur, **kw: snaps.append((m, cur, kw)))[0]
    # best-model retention compares FULL models only (reference
    # CoordinateDescent.scala:163-167): snapshots before the first complete
    # sweep carry no best; every one after the first sweep does
    n_coords = len(cfg.coordinates)
    assert all(kw["best"] is None for _, _, kw in snaps[: n_coords - 1])
    assert all(kw["best"] is not None for _, _, kw in snaps[n_coords - 1:])
    # first save of a config is a FULL snapshot (no stale hard-link baseline);
    # later saves are incremental with the updated coordinate named
    assert snaps[0][2]["updated"] is None
    assert snaps[1][2]["updated"] is not None
    m_ck, cur_ck, kw_ck = snaps[2]
    resumed = est.fit(data, [cfg], validation_data=data, initial_model=m_ck,
                      resume_cursor=cur_ck, resume_best=kw_ck["best"])[0]
    # the resumed run may only return something at least as good as the
    # checkpointed best (it can improve later, never regress below it)
    assert resumed.evaluation.values["auc"] >= kw_ck["best"][1].primary - 1e-9


def test_warm_start_and_locked_coordinates(rng):
    data, *_ = _glmix_data(rng, n_users=6, per_user=40)
    est = GameEstimator()
    first = est.fit(data, [_configs(num_iters=2)])[0]
    # partial retrain: lock the fixed effect, retrain only random effects
    res = est.fit(data, [_configs(num_iters=1)], initial_model=first.model,
                  locked_coordinates={"fixed"})[0]
    np.testing.assert_array_equal(
        res.model["fixed"].coefficients.means, first.model["fixed"].coefficients.means
    )
    # locked without initial model -> error
    with pytest.raises(ValueError, match="locked"):
        est.fit(data, [_configs(num_iters=1)], locked_coordinates={"fixed"})


def test_transformer_scores_new_data(rng):
    full, wg, wu, _ = _glmix_data(rng, per_user=80)
    n = full.num_samples
    idx = rng.permutation(n)
    tr, te = idx[: n // 2], idx[n // 2:]

    def take(i):
        return GameData(
            y=full.y[i],
            features={k: v[i] for k, v in full.features.items()},
            id_tags={k: v[i] for k, v in full.id_tags.items()},
        )

    data, new_data = take(tr), take(te)
    res = GameEstimator().fit(data, [_configs(num_iters=2)])[0]
    tf = GameTransformer(res.model, TaskType.LOGISTIC_REGRESSION)
    scores = tf.score(new_data)
    assert scores.shape == (new_data.num_samples,)
    preds = tf.predict(new_data)
    assert np.all((preds >= 0) & (preds <= 1))
    suite = EvaluationSuite.from_specs(["auc"])
    ev = tf.evaluate(new_data, suite)
    assert ev.values["auc"] > 0.6  # generalizes (same users, new samples)


def test_grouped_validation_metric(rng):
    data, *_ = _glmix_data(rng, n_users=6, per_user=50)
    suite = EvaluationSuite.from_specs(["auc", "auc:userId"], primary="auc")
    est = GameEstimator(validation_suite=suite)
    res = est.fit(data, [_configs(num_iters=1)], validation_data=data)[0]
    assert "auc:userId" in res.evaluation.values
    assert 0.0 <= res.evaluation.values["auc:userId"] <= 1.0


def test_multiple_configs_warm_start(rng):
    """Reg-path over two configs: second fit warm-starts from the first."""
    data, *_ = _glmix_data(rng, n_users=5, per_user=40)
    suite = EvaluationSuite.from_specs(["auc"])
    est = GameEstimator(validation_suite=suite)
    c1 = _configs(num_iters=1)
    results = est.fit(data, [c1, c1], validation_data=data)
    assert len(results) == 2
    best = est.best(results)
    assert best in results


def test_down_sampling_weights_semantics(rng):
    """Reference BinaryClassificationDownSampler.scala:32-55: keep every
    positive at weight 1, keep negatives with prob=rate at weight 1/rate,
    drop the rest (weight 0); deterministic per seed; rate>=1 is a no-op."""
    import dataclasses

    data, _, _, _ = _glmix_data(rng, n_users=8, per_user=40)
    cfg = FixedEffectConfig(feature_shard="global",
                            solver=SolverConfig(max_iters=20),
                            reg=Regularization(l2=1.0), down_sampling_rate=0.5)
    coord = build_coordinate("fixed", data, cfg, TaskType.LOGISTIC_REGRESSION)

    base = np.asarray(coord._base_weight)
    w = np.asarray(coord._down_sample_weights(seed=7))
    y = np.asarray(coord._batch.y)

    pos = y > 0.5
    np.testing.assert_allclose(w[pos], base[pos])  # positives untouched
    neg = ~pos & (base > 0)  # padded rows have base weight 0
    kept = neg & (w > 0)
    dropped = neg & (w == 0)
    assert kept.sum() > 0 and dropped.sum() > 0
    np.testing.assert_allclose(w[kept], base[kept] / 0.5)
    # survivor mass ~= original negative mass in expectation
    assert abs(w[neg].sum() - base[neg].sum()) / base[neg].sum() < 0.25
    # deterministic per seed, different across seeds
    np.testing.assert_array_equal(w, np.asarray(coord._down_sample_weights(seed=7)))
    assert not np.array_equal(w, np.asarray(coord._down_sample_weights(seed=8)))

    # rate >= 1 is the identity
    full = build_coordinate(
        "fixed", data,
        dataclasses.replace(cfg, down_sampling_rate=1.0),
        TaskType.LOGISTIC_REGRESSION)
    np.testing.assert_array_equal(np.asarray(full._down_sample_weights(seed=7)),
                                  np.asarray(full._base_weight))

    # and the down-sampled solve still lands near the full-data solution
    model_ds, _ = coord.update(np.zeros(data.num_samples))
    model_full, _ = full.update(np.zeros(data.num_samples))
    cos = (model_ds.coefficients.means @ model_full.coefficients.means) / (
        np.linalg.norm(model_ds.coefficients.means)
        * np.linalg.norm(model_full.coefficients.means))
    assert cos > 0.95


def test_fused_sweep_warm_start(rng):
    """initial= warm start feeds both coordinate types."""
    from photon_ml_tpu.game.fused import FusedSweep

    data, _, _, _ = _glmix_data(rng, n_users=8, per_user=40)
    cfg = _configs(num_iters=2)
    coords = {cid: build_coordinate(cid, data, c, cfg.task)
              for cid, c in cfg.coordinates.items()}
    sweep = FusedSweep(coords, num_iterations=2)
    m1, _ = sweep.run()
    # warm-started fused run must track the warm-started host descent
    m2, _ = sweep.run(initial=m1)
    h2, _, _ = CoordinateDescent(coords, num_iterations=2).run(initial=m1)
    np.testing.assert_allclose(m2["fixed"].coefficients.means,
                               h2["fixed"].coefficients.means,
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(m2["per-user"].w_stack,
                               h2["per-user"].w_stack, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("projector,extra", [
    ("INDEX_MAP", {}),
    ("RANDOM", {"projected_dim": 2}),
])
def test_fused_sweep_projected_space_matches_host(rng, projector, extra):
    """Projected random effects run INSIDE the fused sweep: each bucket
    solves in its compact space and trace_publish back-projects (traced twin
    of ProjectedBuckets.back_project) — published models must match the
    host-paced loop for both projector flavors."""
    import dataclasses

    from photon_ml_tpu.types import ProjectorType

    data, _, _, _ = _glmix_data(rng, n_users=6, per_user=40)
    base = _configs(num_iters=2)
    cfg = dataclasses.replace(base, coordinates={
        "fixed": base.coordinates["fixed"],
        "per-user": dataclasses.replace(base.coordinates["per-user"],
                                        projector=ProjectorType[projector],
                                        **extra)})
    f = GameEstimator(fused=True).fit(data, [cfg])[0].model
    h = GameEstimator(fused=False).fit(data, [cfg])[0].model
    assert f["per-user"].w_stack.shape == h["per-user"].w_stack.shape
    np.testing.assert_allclose(f["fixed"].coefficients.means,
                               h["fixed"].coefficients.means,
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(f["per-user"].w_stack, h["per-user"].w_stack,
                               rtol=2e-3, atol=2e-3)


def test_variance_computation_game_path(rng, tmp_path):
    """Coefficient variances through the GAME coordinate path (reference
    DistributedOptimizationProblem.scala:84-108): SIMPLE = 1/diag(H),
    FULL = diag(H^-1); persisted via BayesianLinearModelAvro.variances."""
    import dataclasses

    import scipy.special as spec

    from photon_ml_tpu.types import VarianceComputationType

    data, _, _, _ = _glmix_data(rng, n_users=6, per_user=40)
    l2 = 1.0
    base = _configs(num_iters=1)

    def closed_form_hessian(x, y_, w, off):
        z = x @ w + off
        q = spec.expit(z) * (1.0 - spec.expit(z))
        return (x * q[:, None]).T @ x + l2 * np.eye(x.shape[1])

    for kind in (VarianceComputationType.SIMPLE, VarianceComputationType.FULL):
        cfg = dataclasses.replace(base.coordinates["fixed"], variance=kind)
        coord = build_coordinate("fixed", data, cfg, base.task)
        model, res = coord.update(np.zeros(data.num_samples))
        v = model.coefficients.variances
        assert v is not None and v.shape == model.coefficients.means.shape
        x = np.asarray(data.features["global"])
        h = closed_form_hessian(x, np.asarray(data.y),
                                np.asarray(model.coefficients.means),
                                np.zeros(data.num_samples))
        expect = (1.0 / np.diag(h) if kind == VarianceComputationType.SIMPLE
                  else np.diag(np.linalg.inv(h)))
        np.testing.assert_allclose(v, expect, rtol=2e-3, atol=1e-5)

    # random effect: per-entity SIMPLE variances, entity 0 checked closed-form
    re_cfg = dataclasses.replace(base.coordinates["per-user"],
                                 variance=VarianceComputationType.SIMPLE)
    re = build_coordinate("per-user", data, re_cfg, base.task)
    re_model, _ = re.update(np.zeros(data.num_samples))
    assert re_model.variances is not None
    assert re_model.variances.shape == re_model.w_stack.shape
    eid = sorted(re_model.slot_of)[0]
    slot = re_model.slot_of[eid]
    mask = np.asarray(data.id_tags["userId"]) == eid
    xu = np.asarray(data.features["per_user"])[mask]
    h = closed_form_hessian(xu, None, re_model.w_stack[slot], np.zeros(mask.sum()))
    np.testing.assert_allclose(re_model.variances[slot], 1.0 / np.diag(h),
                               rtol=2e-3, atol=1e-5)

    # persistence roundtrip keeps variances
    from photon_ml_tpu.data.index_map import IndexMap
    from photon_ml_tpu.data.reader import EntityIndex
    from photon_ml_tpu.models.game import GameModel
    from photon_ml_tpu.storage.model_io import load_game_model, save_game_model

    imap = IndexMap.from_features([(f"f{i}", "") for i in range(xu.shape[1])],
                                  add_intercept=False)
    eidx = EntityIndex()
    for e in sorted(re_model.slot_of):
        eidx.get_or_add(str(e))
    # remap slot ids through the entity index space used at save/load
    gm = GameModel(models={"per-user": dataclasses.replace(
        re_model, slot_of={eidx.get(str(e)): s
                           for e, s in re_model.slot_of.items()})})
    out = str(tmp_path / "m")
    save_game_model(gm, out, {"per_user": imap}, {"userId": eidx},
                    base.task)
    loaded, _ = load_game_model(out, {"per_user": imap}, {"userId": eidx})
    lv = loaded["per-user"].variances
    assert lv is not None
    got = np.asarray(sorted(np.round(lv.sum(axis=1), 6)))
    want = np.asarray(sorted(np.round(re_model.variances.sum(axis=1), 6)))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_estimator_fused_auto_matches_host(rng):
    """fused="auto" (no validation) must produce the same models as the
    host-paced loop (fused=False)."""
    data, *_ = _glmix_data(rng, n_users=8, per_user=40)
    cfg = _configs(num_iters=2)
    m_auto = GameEstimator(fused="auto").fit(data, [cfg])[0].model
    m_host = GameEstimator(fused=False).fit(data, [cfg])[0].model
    np.testing.assert_allclose(m_auto["fixed"].coefficients.means,
                               m_host["fixed"].coefficients.means,
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(m_auto["per-user"].w_stack,
                               m_host["per-user"].w_stack, rtol=2e-3, atol=2e-3)

    # validation present -> the fused VALIDATED program (held-out scoring
    # in-program, suite evaluated per sweep boundary) — evaluation attached
    suite = EvaluationSuite.from_specs(["auc"])
    r = GameEstimator(validation_suite=suite, fused="auto").fit(
        data, [cfg], validation_data=data)[0]
    assert r.evaluation is not None
    r_true = GameEstimator(validation_suite=suite, fused=True).fit(
        data, [cfg], validation_data=data)[0]
    assert r_true.evaluation is not None

    # fused=True still raises on genuinely host-paced per-update work
    with pytest.raises(ValueError):
        GameEstimator(validation_suite=suite, fused=True).fit(
            data, [cfg], validation_data=data,
            checkpoint_hook=lambda m, cur, **kw: None)

    # every coordinate flavor is now fused-eligible; ineligibility is only
    # per-fit host work (checkpoint/locks/resume), asserted above


def test_reg_grid_reuses_compiled_programs(rng):
    """A reg-weight grid must re-enter the same compiled solvers/sweep:
    reg is a traced argument (reference updateRegularizationWeight:64-75
    mutates weights in place for the same reason)."""
    import dataclasses

    import jax

    data, *_ = _glmix_data(rng, n_users=6, per_user=40)
    cfg1 = _configs(num_iters=1)
    coords = {cid: build_coordinate(cid, data, c, cfg1.task)
              for cid, c in cfg1.coordinates.items()}

    # rebind with a different L2 keeps the SAME jitted callables
    f2 = coords["fixed"].rebind(dataclasses.replace(
        cfg1.coordinates["fixed"], reg=Regularization(l2=10.0)))
    assert f2._solve is coords["fixed"]._solve
    r2 = coords["per-user"].rebind(dataclasses.replace(
        cfg1.coordinates["per-user"], reg=Regularization(l2=10.0)))
    assert r2._vsolve is coords["per-user"]._vsolve
    # ...and the solutions actually differ (reg flows through the trace)
    m1, _ = coords["fixed"].update(np.zeros(data.num_samples))
    m2, _ = f2.update(np.zeros(data.num_samples))
    assert np.linalg.norm(m2.coefficients.means) < np.linalg.norm(
        m1.coefficients.means)

    # an L1-regime flip DOES rebuild (OWLQN vs L-BFGS dispatch is static)
    f3 = coords["fixed"].rebind(dataclasses.replace(
        cfg1.coordinates["fixed"], reg=Regularization(l1=0.5)))
    assert f3._solve is not coords["fixed"]._solve

    # estimator grid: one sweep program for the whole λ grid
    grid = []
    for l2 in (0.1, 1.0, 10.0):
        cs = {cid: dataclasses.replace(c, reg=Regularization(l2=l2))
              for cid, c in cfg1.coordinates.items()}
        grid.append(GameConfig(task=cfg1.task, coordinates=cs,
                               num_outer_iterations=1))
    est = GameEstimator(fused=True)
    with jax.log_compiles(False):
        results = est.fit(data, grid)
    # the three grid points must be genuinely different solutions
    w_grid = [r.model["fixed"].coefficients.means for r in results]
    assert not np.allclose(w_grid[0], w_grid[2], atol=1e-3)
    # host-paced loop agrees at each grid point
    host = GameEstimator(fused=False).fit(data, grid)
    for r, h in zip(results, host):
        np.testing.assert_allclose(r.model["fixed"].coefficients.means,
                                   h.model["fixed"].coefficients.means,
                                   rtol=2e-3, atol=2e-3)


def test_fused_grid_l1_regime_switch(rng):
    """A grid crossing the smooth/L1 boundary must NOT reuse the compiled
    sweep: the L1 point must come back sparsity-inducing and equal to the
    host loop's solution."""
    import dataclasses

    data, *_ = _glmix_data(rng, n_users=6, per_user=40)
    base = _configs(num_iters=1)
    fixed = base.coordinates["fixed"]
    grid = [
        GameConfig(task=base.task, coordinates={
            "fixed": dataclasses.replace(fixed, reg=Regularization(l2=1.0))}),
        GameConfig(task=base.task, coordinates={
            "fixed": dataclasses.replace(fixed, reg=Regularization(l1=2.0))}),
    ]
    fused = GameEstimator(fused=True).fit(data, grid)
    host = GameEstimator(fused=False).fit(data, grid)
    for f, h in zip(fused, host):
        np.testing.assert_allclose(f.model["fixed"].coefficients.means,
                                   h.model["fixed"].coefficients.means,
                                   rtol=2e-3, atol=2e-3)


def _golden_fit():
    rng = np.random.default_rng(20260729)
    data, *_ = _glmix_data(rng, n_users=5, per_user=40)
    return data, GameEstimator(fused=False).fit(
        data, [_configs(num_iters=2)])[0].model


def test_golden_coefficients_regression():
    """Pinned-value regression in the reference's style
    (GameEstimatorIntegTest.scala:105-107 asserts exact coefficient values
    captured from an assumed-correct run).  Guards the whole stack — data
    layout, solvers, residual descent — against silent numeric drift.
    Captured 2026-07-29 on the CPU x64 test surface, seed 20260729;
    re-captured 2026-07-30 after the batch-as-argument jit refactor (XLA
    fusion order shifted f32 rounding by ~8e-5) and 2026-07-31 after the
    approximate-Wolfe line-search slack (opt/linesearch.py: f32 solves stop
    at the working-precision plateau, shifting iterates by ~2e-5).
    The 2026-08-05 capture (PR 13) took the values of another image: on
    this one (jax 0.9.0) every commit from PR 6 to PR 26 gives the values
    below, which pass the 2026-07-31 golden and failed the 2026-08-05 one
    by 6e-4 on the per-user row at every PR since.  Re-captured 2026-10-01
    (PR 27) from the unchanged solver, after checking it against the plain
    float64 Newton descent of ``test_golden_fit_matches_plain_newton``: this
    fit is 2.1e-5 from it on that row, the 2026-08-05 values 1.7e-4.
    To regenerate after a LEGITIMATE numeric change: run ``_golden_fit``,
    see that the test below still passes, paste ``repr(float(x))`` of each
    coefficient, and record the cause here."""
    _, model = _golden_fit()

    golden_fixed = np.asarray([
        -0.3468096852302551, -1.50300931930542, -0.1629900336265564,
        1.1834657192230225, 0.5667847394943237, -0.41816797852516174])
    np.testing.assert_allclose(model["fixed"].coefficients.means,
                               golden_fixed, rtol=1e-4, atol=1e-5)

    re_model = model["per-user"]
    assert sorted(re_model.slot_of) == [11, 14, 17, 20, 23]
    golden_user0 = np.asarray([
        0.7988278269767761, 0.15703976154327393, -0.6275042295455933])
    np.testing.assert_allclose(re_model.w_stack[re_model.slot_of[11]],
                               golden_user0, rtol=1e-4, atol=1e-5)


def test_golden_fit_matches_plain_newton():
    """What makes the golden values right and not only pinned: the same
    two sweeps (fixed, then one GLM a user on the other's scores as
    offsets) by an undamped float64 Newton in numpy, run to a step under
    1e-14.  The float32 fit stops at its working-precision plateau, 2e-5
    to 4e-5 from it; 1e-4 is the golden test's own tolerance."""
    data, model = _golden_fit()

    def newton(x, y, offset, l2=1.0):
        w = np.zeros(x.shape[1])
        for _ in range(100):
            p = 1.0 / (1.0 + np.exp(-(x @ w + offset)))
            grad = x.T @ (p - y) + l2 * w
            hess = (x * (p * (1 - p))[:, None]).T @ x + l2 * np.eye(len(w))
            step = np.linalg.solve(hess, grad)
            w -= step
            if np.abs(step).max() < 1e-14:
                return w
        raise AssertionError("plain Newton did not converge")

    xg = np.asarray(data.features["global"], np.float64)
    xu = np.asarray(data.features["per_user"], np.float64)
    y, uid = np.asarray(data.y, np.float64), data.id_tags["userId"]
    user_scores = np.zeros(len(y))
    for _ in range(2):
        w_fixed = newton(xg, y, user_scores)
        w_user = {}
        for u in np.unique(uid):
            rows = uid == u
            w_user[u] = newton(xu[rows], y[rows], xg[rows] @ w_fixed)
            user_scores[rows] = xu[rows] @ w_user[u]

    np.testing.assert_allclose(model["fixed"].coefficients.means, w_fixed,
                               rtol=1e-4, atol=1e-4)
    re_model = model["per-user"]
    for u, w in w_user.items():
        np.testing.assert_allclose(re_model.w_stack[re_model.slot_of[u]], w,
                                   rtol=1e-4, atol=1e-4)


def test_per_entity_l2_multipliers(rng):
    """Per-entity regularization (beyond-reference: the reference only
    envisioned per-entity lambda, RandomEffectOptimizationProblem.scala:42):
    a heavily-multiplied entity's coefficients shrink, others are untouched;
    the fused sweep agrees with the host loop."""
    import dataclasses

    from photon_ml_tpu.game.fused import FusedSweep

    data, _, _, _ = _glmix_data(rng, n_users=8, per_user=50)
    base = _configs(num_iters=1)
    re_base = base.coordinates["per-user"]
    eids = sorted(set(int(e) for e in data.id_tags["userId"]))
    heavy = eids[2]

    def fit(cfg):
        coord = build_coordinate("u", data, cfg, base.task)
        model, _ = coord.update(np.zeros(data.num_samples))
        return coord, model

    _, plain = fit(re_base)
    cfg_mult = dataclasses.replace(
        re_base, per_entity_l2_multipliers={heavy: 1000.0})
    coord, mult = fit(cfg_mult)

    slot = plain.slot_of[heavy]
    assert (np.linalg.norm(mult.w_stack[slot])
            < 0.05 * np.linalg.norm(plain.w_stack[slot]))
    for e in eids:
        if e == heavy:
            continue
        np.testing.assert_allclose(mult.w_stack[plain.slot_of[e]],
                                   plain.w_stack[plain.slot_of[e]],
                                   rtol=1e-4, atol=1e-5)

    # config canonicalization: dict -> sorted tuple, hash/eq safe
    assert cfg_mult.per_entity_l2_multipliers == ((heavy, 1000.0),)

    # fused sweep applies the multipliers too (they're part of sweep_key)
    coords = {"u": coord}
    fused_model, _ = FusedSweep(coords, num_iterations=1).run()
    np.testing.assert_allclose(fused_model["u"].w_stack, mult.w_stack,
                               rtol=2e-3, atol=2e-3)


def test_per_entity_multipliers_cli(tmp_path):
    import json as _json
    import os

    from photon_ml_tpu.cli import train as train_cli
    from photon_ml_tpu.storage.model_io import load_game_model
    from photon_ml_tpu.data.index_map import load_index
    from photon_ml_tpu.data.reader import EntityIndex

    import sys
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    if tests_dir not in sys.path:
        sys.path.insert(0, tests_dir)
    from test_cli import _write_fixture

    train_path = str(tmp_path / "train.avro")
    _write_fixture(train_path, n=300, seed=11)
    mults = str(tmp_path / "mults.json")
    with open(mults, "w") as f:
        _json.dump({"user0": 500.0, "ghost_user": 2.0}, f)

    out = str(tmp_path / "out")
    rc = train_cli.run([
        "--train-data", train_path, "--feature-shards", "all",
        "--coordinate", "name=fixed,feature.shard=all,reg.weights=1",
        "--coordinate", f"name=u,random.effect.type=userId,feature.shard=all,"
                        f"reg.weights=1,per.entity.l2.multipliers={mults}",
        "--id-tags", "userId",
        "--output-dir", out,
    ])
    assert rc == 0
    eidx = EntityIndex.load(os.path.join(out, "userId.entities.json"))
    imap = load_index(os.path.join(out, "all.idx"))
    model, _ = load_game_model(os.path.join(out, "best"), {"all": imap},
                               {"userId": eidx})
    re_model = model["u"]
    heavy_slot = re_model.slot_of[eidx.get("user0")]
    other = [s for e, s in re_model.slot_of.items()
             if e != eidx.get("user0")]
    heavy_norm = np.linalg.norm(re_model.w_stack[heavy_slot])
    other_norms = [np.linalg.norm(re_model.w_stack[s]) for s in other]
    assert heavy_norm < 0.3 * np.median(other_norms)


# --- Reference-golden parity: the reference's own pinned scikit-learn values ---

# The reference's "trivial" dataset (photon-api/src/test/.../GameTestUtils.scala:
# trivialLabeledPoints, 68-79): 10 points, 2 features; an intercept column of
# ones is appended LAST, exactly as GameEstimatorIntegTest.simpleHardcodedTest
# does before training.
_TRIVIAL_X = np.asarray([
    [-0.7306653538519616, 0.0],
    [0.6750417712898752, -0.4232874171873786],
    [0.1863463229359709, -0.8163423997075965],
    [-0.6719842051493347, 0.0],
    [0.9699938346531928, 0.0],
    [0.22759406190283604, 0.0],
    [0.9688721028330911, 0.0],
    [0.5993795346650845, 0.0],
    [0.9219423508390701, -0.8972778242305388],
    [0.7006904841584055, -0.5607635619919824],
])
_TRIVIAL_Y = np.asarray([0.0, 1.0, 1.0, 0.0, 1.0, 1.0, 1.0, 0.0, 0.0, 0.0])


def _trivial_game_data():
    x = np.concatenate([_TRIVIAL_X, np.ones((len(_TRIVIAL_Y), 1))], axis=1)
    return GameData(y=_TRIVIAL_Y, features={"features": x}, id_tags={})


def test_reference_golden_trivial_linear_l2():
    """Cross-implementation golden parity: linear regression + L2(0.3) on the
    reference's trivial dataset must reproduce the scikit-learn-derived
    coefficients the reference pins at HIGH_PRECISION_TOLERANCE
    (GameEstimatorIntegTest.scala:105-107; loss = 1/2 Σ(z-y)², reg = λ/2‖w‖²
    including the intercept)."""
    cfg = GameConfig(task=TaskType.LINEAR_REGRESSION, coordinates={
        "global": FixedEffectConfig(
            feature_shard="features",
            solver=SolverConfig(max_iters=100, tolerance=1e-11),
            reg=Regularization(l2=0.3), intercept_index=2)})
    res = GameEstimator(dtype=np.float64).fit(_trivial_game_data(), [cfg])[0]
    np.testing.assert_allclose(
        res.model["global"].coefficients.means,
        [0.3215554473500486, 0.17904355431985355, 0.4122241763914806],
        rtol=0, atol=1e-9)


@pytest.mark.parametrize("kind", ["none", "scale_with_max_magnitude",
                                  "scale_with_standard_deviation",
                                  "standardization"])
def test_reference_golden_trivial_normalization(kind):
    """GameEstimatorIntegTest.testNormalization parity: the UNregularized
    solve is invariant under every normalization type because the published
    model is mapped back to original space — all four must reproduce the
    reference's pinned scikit-learn OLS coefficients at
    LOW_PRECISION_TOLERANCE (1e-8)."""
    import jax.numpy as jnp

    from photon_ml_tpu.core.normalization import (build_normalization,
                                                  compute_feature_stats)
    from photon_ml_tpu.types import NormalizationType

    data = _trivial_game_data()
    x = data.features["features"]
    stats = compute_feature_stats(jnp.asarray(x, jnp.float64),
                                  intercept_index=2)
    ctx = build_normalization(NormalizationType(kind), stats)
    cfg = GameConfig(task=TaskType.LINEAR_REGRESSION, coordinates={
        "global": FixedEffectConfig(
            feature_shard="features",
            solver=SolverConfig(max_iters=100, tolerance=1e-11),
            reg=Regularization(), intercept_index=2)})
    res = GameEstimator(normalization={"features": ctx},
                        dtype=np.float64).fit(data, [cfg])[0]
    np.testing.assert_allclose(
        res.model["global"].coefficients.means,
        [0.34945501725815586, 0.26339479490270173, 0.4366125400310442],
        rtol=0, atol=1e-8)


def test_down_sampling_default_sampler_regression_tasks(rng):
    """Reference DownSamplerHelper.scala:33-40: regression tasks down-sample
    with DefaultDownSampler — uniform sampling at rate, NO positive-keeping
    and NO 1/rate reweighting."""
    data, *_ = _glmix_data(rng, n_users=8, per_user=40)
    cfg = FixedEffectConfig(feature_shard="global",
                            solver=SolverConfig(max_iters=20),
                            reg=Regularization(l2=1.0), down_sampling_rate=0.5)
    coord = build_coordinate("fixed", data, cfg, TaskType.LINEAR_REGRESSION)
    base = np.asarray(coord._base_weight)
    w = np.asarray(coord._down_sample_weights(seed=7))
    live = base > 0
    kept = live & (w > 0)
    # sampled rows keep their ORIGINAL weight (no compensation), others drop
    np.testing.assert_allclose(w[kept], base[kept])
    frac = kept.sum() / live.sum()
    assert 0.35 < frac < 0.65  # ~rate of the live rows survive


def test_fused_down_sampling_matches_host_statistically(rng):
    """The fused sweep now runs per-update down-sampling inside the compiled
    program (traced PRNG fold per iteration).  Draws differ from the host
    path's numpy PRNG, so parity is statistical: both must land near the
    no-sampling solution at rate→1⁻ semantics scale, and the fused solution
    must track the host solution closely on a well-conditioned problem."""
    import dataclasses

    data, *_ = _glmix_data(rng, n_users=6, per_user=80)
    base_cfg = _configs(num_iters=2)
    fixed = dataclasses.replace(base_cfg.coordinates["fixed"],
                                down_sampling_rate=0.8)
    cfg = GameConfig(task=base_cfg.task, coordinates={
        "fixed": fixed, "per-user": base_cfg.coordinates["per-user"]},
        num_outer_iterations=2)

    w_fused = GameEstimator(fused=True).fit(data, [cfg])[0] \
        .model["fixed"].coefficients.means
    w_host = GameEstimator(fused=False).fit(data, [cfg])[0] \
        .model["fixed"].coefficients.means
    # different PRNG streams -> not identical...
    assert not np.allclose(w_fused, w_host, atol=1e-12)
    # ...but the same estimator up to sampling noise
    np.testing.assert_allclose(w_fused, w_host, rtol=0.35, atol=0.15)

    # seed is a traced input: same seed reproduces, different seed varies
    coords = {cid: build_coordinate(cid, data, c, cfg.task)
              for cid, c in cfg.coordinates.items()}
    from photon_ml_tpu.game.fused import FusedSweep
    sweep = FusedSweep(coords, num_iterations=2)
    m1, _ = sweep.run(seed=3)
    m2, _ = sweep.run(seed=3)
    m3, _ = sweep.run(seed=4)
    np.testing.assert_array_equal(m1["fixed"].coefficients.means,
                                  m2["fixed"].coefficients.means)
    assert not np.array_equal(m1["fixed"].coefficients.means,
                              m3["fixed"].coefficients.means)


def test_fused_variances_match_host(rng):
    """Fused sweep computes coefficient variances in the scan body on the
    final iteration, at each coordinate's last-update offsets/weights/reg —
    must equal the host-paced path's published variances on both coordinate
    types (only the final update's variances survive there too)."""
    import dataclasses

    from photon_ml_tpu.types import VarianceComputationType

    data, *_ = _glmix_data(rng, n_users=6, per_user=40)
    base = _configs(num_iters=2)
    cfg = GameConfig(task=base.task, coordinates={
        "fixed": dataclasses.replace(base.coordinates["fixed"],
                                     variance=VarianceComputationType.SIMPLE),
        "per-user": dataclasses.replace(base.coordinates["per-user"],
                                        variance=VarianceComputationType.FULL)},
        num_outer_iterations=2)

    fused = GameEstimator(fused=True).fit(data, [cfg])[0].model
    host = GameEstimator(fused=False).fit(data, [cfg])[0].model

    fv = fused["fixed"].coefficients.variances
    hv = host["fixed"].coefficients.variances
    assert fv is not None and hv is not None
    np.testing.assert_allclose(fv, hv, rtol=1e-4, atol=1e-7)

    fr, hr = fused["per-user"], host["per-user"]
    assert fr.variances is not None and hr.variances is not None
    assert fr.slot_of == hr.slot_of
    np.testing.assert_allclose(fr.variances, hr.variances, rtol=1e-4, atol=1e-7)


def test_fused_reg_grid_variances_use_each_lambda(rng):
    """Regression: a fused λ grid reuses ONE compiled sweep whose reg enters
    as a traced argument — the published variances must be computed with EACH
    grid point's λ (not the first config's), matching the host path at every
    grid point."""
    import dataclasses

    from photon_ml_tpu.types import VarianceComputationType

    data, *_ = _glmix_data(rng, n_users=6, per_user=40)
    base = _configs(num_iters=1)
    fixed = dataclasses.replace(base.coordinates["fixed"],
                                variance=VarianceComputationType.SIMPLE)
    ruser = dataclasses.replace(base.coordinates["per-user"],
                                variance=VarianceComputationType.SIMPLE)
    grid = []
    for l2 in (0.1, 10.0):
        grid.append(GameConfig(task=base.task, coordinates={
            "fixed": dataclasses.replace(fixed, reg=Regularization(l2=l2)),
            "per-user": dataclasses.replace(ruser, reg=Regularization(l2=l2))}))

    fused = GameEstimator(fused=True).fit(data, grid)
    host = GameEstimator(fused=False).fit(data, grid)
    for f, h in zip(fused, host):
        np.testing.assert_allclose(f.model["fixed"].coefficients.variances,
                                   h.model["fixed"].coefficients.variances,
                                   rtol=1e-4, atol=1e-7)
        np.testing.assert_allclose(f.model["per-user"].variances,
                                   h.model["per-user"].variances,
                                   rtol=1e-4, atol=1e-7)
    # the two grid points' variances genuinely differ (λ enters the Hessian)
    v0 = fused[0].model["fixed"].coefficients.variances
    v1 = fused[1].model["fixed"].coefficients.variances
    assert not np.allclose(v0, v1, rtol=1e-2)


def test_storage_dtype_mixed_precision_fit(rng):
    """storage_dtype="bfloat16": design matrices live at bf16 (half the HBM
    bytes per objective pass) while solver state stays f32 — published
    coefficients must track the all-f32 fit closely on both coordinate types,
    and the fused path must accept the config."""
    import dataclasses

    data, *_ = _glmix_data(rng, n_users=6, per_user=60)
    base = _configs(num_iters=2)
    mixed = GameConfig(task=base.task, coordinates={
        "fixed": dataclasses.replace(base.coordinates["fixed"],
                                     storage_dtype="bfloat16"),
        "per-user": dataclasses.replace(base.coordinates["per-user"],
                                        storage_dtype="bfloat16")},
        num_outer_iterations=2)

    w32 = GameEstimator(fused=False).fit(data, [base])[0].model
    wbf_host = GameEstimator(fused=False).fit(data, [mixed])[0].model
    wbf_fused = GameEstimator(fused=True).fit(data, [mixed])[0].model

    for m in (wbf_host, wbf_fused):
        assert m["fixed"].coefficients.means.dtype == np.float32
        np.testing.assert_allclose(m["fixed"].coefficients.means,
                                   w32["fixed"].coefficients.means,
                                   rtol=0.08, atol=0.08)
        np.testing.assert_allclose(m["per-user"].w_stack,
                                   w32["per-user"].w_stack,
                                   rtol=0.15, atol=0.15)


def test_fused_sweep_tron_matches_host(rng):
    """TRON (trust region + truncated CG) through the fused sweep: the
    make_solver dispatch is optimizer-agnostic, so the whole-descent program
    must reproduce the host-paced TRON descent on both coordinate types."""
    import dataclasses

    from photon_ml_tpu.types import OptimizerType

    data, *_ = _glmix_data(rng, n_users=6, per_user=40)
    base = _configs(num_iters=2)
    cfg = dataclasses.replace(base, coordinates={
        "fixed": dataclasses.replace(base.coordinates["fixed"],
                                     optimizer=OptimizerType.TRON),
        "per-user": dataclasses.replace(base.coordinates["per-user"],
                                        optimizer=OptimizerType.TRON)})
    f = GameEstimator(fused=True).fit(data, [cfg])[0].model
    h = GameEstimator(fused=False).fit(data, [cfg])[0].model
    np.testing.assert_allclose(f["fixed"].coefficients.means,
                               h["fixed"].coefficients.means,
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(f["per-user"].w_stack, h["per-user"].w_stack,
                               rtol=2e-3, atol=2e-3)


def test_fused_program_has_no_large_baked_constants(rng):
    """Compile-time guard: closed-over jax.Arrays lower to baked XLA
    constants and compile time grows linearly with constant bytes (118s -> 3s
    at bench scale when the design matrices moved to arguments).  The fused
    program's jaxpr consts must stay tiny — if a design matrix, score vector,
    or bucket array ever leaks back into a closure, this trips."""
    import jax

    data, *_ = _glmix_data(rng, n_users=8, per_user=50)
    cfg = _configs(num_iters=2)
    coords = {cid: build_coordinate(cid, data, c, cfg.task)
              for cid, c in cfg.coordinates.items()}
    from photon_ml_tpu.game.fused import FusedSweep

    sweep = FusedSweep(coords, num_iterations=2)
    regs = tuple(coords[cid].config.reg for cid in sweep.order)
    jaxpr = jax.make_jaxpr(sweep._program.__wrapped__)(
        *sweep._cold, sweep._vars0, regs, jax.random.PRNGKey(0),
        sweep._base, sweep._datas)
    const_bytes = sum(np.asarray(c).nbytes for c in jaxpr.consts)
    # n=400 samples: a single leaked score vector would be 3.2KB (f64) and a
    # leaked design matrix 9.6KB+ — anything over 1KB means a leak
    assert const_bytes <= 1024, f"{const_bytes} bytes of baked constants"


# --- box constraints through GAME configs (reference OptimizerConfig.scala:47,
# --- applied via OptimizationUtils.projectCoefficientsToSubspace) ---

def test_fixed_effect_constraints(rng):
    """A constrained GAME fit keeps coefficients inside bounds and matches
    scipy L-BFGS-B under the same box."""
    import scipy.optimize as sopt
    import scipy.special as sp

    n, d = 600, 6
    x = rng.normal(size=(n, d))
    w_true = rng.normal(size=d) * 2.0
    y = (rng.random(n) < 1 / (1 + np.exp(-x @ w_true))).astype(float)
    data = GameData(y=y, features={"g": x})
    l2 = 0.5
    lo, hi = -0.25, 0.25
    cfg = GameConfig(task=TaskType.LOGISTIC_REGRESSION, coordinates={
        "fixed": FixedEffectConfig(
            feature_shard="g", reg=Regularization(l2=l2),
            solver=SolverConfig(max_iters=200, tolerance=1e-9),
            constraints=tuple((j, lo, hi) for j in range(d)))})
    res = GameEstimator(dtype=np.float64).fit(data, [cfg])[0]
    w = np.asarray(res.model["fixed"].coefficients.means)
    assert np.all(w >= lo - 1e-9) and np.all(w <= hi + 1e-9)
    # some bounds must actually bind (w_true is far outside the box)
    assert np.any(np.isclose(np.abs(w), 0.25, atol=1e-6))

    def nll(wv):
        z = x @ wv
        return np.sum(np.logaddexp(0, z) - y * z) + 0.5 * l2 * wv @ wv

    def grad(wv):
        z = x @ wv
        return x.T @ (sp.expit(z) - y) + l2 * wv

    ref = sopt.minimize(nll, np.zeros(d), jac=grad, method="L-BFGS-B",
                        bounds=[(lo, hi)] * d)
    np.testing.assert_allclose(w, ref.x, atol=5e-5)


def test_random_effect_constraints(rng):
    """Constraints apply to EVERY entity's solve in the vmapped buckets."""
    n_users, per_user, d = 8, 40, 3
    n = n_users * per_user
    x = rng.normal(size=(n, d))
    uids = np.repeat(np.arange(n_users), per_user)
    wu = rng.normal(size=(n_users, d)) * 3.0
    y = (rng.random(n) < 1 / (1 + np.exp(-np.einsum(
        "nd,nd->n", x, wu[uids])))).astype(float)
    data = GameData(y=y, features={"u": x}, id_tags={"userId": uids})
    cfg = GameConfig(task=TaskType.LOGISTIC_REGRESSION, coordinates={
        "per-user": RandomEffectConfig(
            random_effect_type="userId", feature_shard="u",
            reg=Regularization(l2=0.1),
            constraints=((0, -0.5, 0.5), (2, 0.0, 1.0)))})
    res = GameEstimator().fit(data, [cfg])[0]
    m = res.model["per-user"]
    assert np.all(m.w_stack[:, 0] >= -0.5 - 1e-6)
    assert np.all(m.w_stack[:, 0] <= 0.5 + 1e-6)
    assert np.all(m.w_stack[:, 2] >= -1e-6)
    assert np.all(m.w_stack[:, 2] <= 1.0 + 1e-6)
    # feature 1 unconstrained: at least one entity escapes the [-0.5, 0.5] box
    assert np.any(np.abs(m.w_stack[:, 1]) > 0.5)


def test_constraint_validation():
    with pytest.raises(ValueError, match="lower bound"):
        FixedEffectConfig(feature_shard="g", constraints=((0, 1.0, -1.0),))
    with pytest.raises(ValueError, match="infinite"):
        FixedEffectConfig(
            feature_shard="g",
            constraints=((0, float("-inf"), float("inf")),))
    # dict form canonicalizes to sorted tuples
    c = FixedEffectConfig(feature_shard="g",
                          constraints={3: (0.0, 1.0), 1: (-1.0, 1.0)})
    assert c.constraints == ((1, -1.0, 1.0), (3, 0.0, 1.0))
    # TRON + constraints must refuse loudly at solver bind
    from photon_ml_tpu.types import OptimizerType

    data = GameData(y=np.ones(8), features={"g": np.ones((8, 2))})
    with pytest.raises(ValueError, match="box"):
        build_coordinate(
            "fixed", data,
            FixedEffectConfig(feature_shard="g", optimizer=OptimizerType.TRON,
                              constraints=((0, -1.0, 1.0),)),
            TaskType.LOGISTIC_REGRESSION)


# --- per-entity normalization for random effects (reference
# --- NormalizationContextRDD, RandomEffectOptimizationProblem.scala:154-178) ---

def _re_norm_data(rng, n_users=6, per_user=50, d=4):
    """Per-user logistic data with an intercept column and deliberately
    badly-scaled features (what normalization is for)."""
    n = n_users * per_user
    scales = np.resize(np.asarray([1.0, 0.03, 12.0, 1.0]), d)
    x = rng.normal(size=(n, d)) * scales
    x[:, 0] = 1.0  # intercept
    uids = np.repeat(np.arange(n_users), per_user)
    wu = rng.normal(size=(n_users, d))
    y = (rng.random(n) < 1 / (1 + np.exp(-np.einsum(
        "nd,nd->n", x, wu[uids])))).astype(float)
    return x, uids, y


def test_random_effect_shared_normalization_parity(rng):
    """IDENTITY projector: ONE standardization context for every entity
    (reference NormalizationContextBroadcast).  Each entity's published
    coefficients must match a direct per-entity normalized host solve."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.core.losses import logistic_loss
    from photon_ml_tpu.core.normalization import NormalizationContext
    from photon_ml_tpu.core.objective import GLMObjective
    from photon_ml_tpu.core.batch import dense_batch
    from photon_ml_tpu.opt.solve import make_solver

    x, uids, y = _re_norm_data(rng)
    factors = 1.0 / (np.std(x, axis=0) + 1e-12)
    shifts = np.mean(x, axis=0).copy()
    factors[0], shifts[0] = 1.0, 0.0  # intercept untouched
    norm = NormalizationContext(factors=jnp.asarray(factors, jnp.float32),
                                shifts=jnp.asarray(shifts, jnp.float32))

    data = GameData(y=y, features={"u": x}, id_tags={"userId": uids})
    cfg = RandomEffectConfig(
        random_effect_type="userId", feature_shard="u",
        reg=Regularization(l2=0.3), intercept_index=0,
        solver=SolverConfig(max_iters=100, tolerance=1e-9))
    coord = build_coordinate("u", data, cfg, TaskType.LOGISTIC_REGRESSION,
                             norm=norm)
    model, _ = coord.update(np.zeros(len(y)))

    obj = GLMObjective(loss=logistic_loss, reg=Regularization(l2=0.3), norm=norm)
    solve = jax.jit(make_solver(obj))
    for u in range(6):
        rows = uids == u
        res = solve(jnp.zeros(x.shape[1], jnp.float32),
                    dense_batch(x[rows].astype(np.float32),
                                y[rows].astype(np.float32)))
        w_ref = norm.model_to_original_space(res.w, 0)
        slot = model.slot_of[u]
        # f32 solves stop at slightly different iterates (vmapped vs single
        # reduction order); parity is semantic, not bitwise
        np.testing.assert_allclose(model.w_stack[slot], np.asarray(w_ref),
                                   rtol=1e-2, atol=1e-3)


def test_random_effect_projected_normalization_parity(rng):
    """INDEX_MAP projector: the context projected into each entity's compact
    space (reference NormalizationContextRDD case).  Compaction is exact, so
    the published model must match the IDENTITY fit with the same context."""
    import jax.numpy as jnp

    from photon_ml_tpu.core.normalization import NormalizationContext
    from photon_ml_tpu.types import ProjectorType

    x, uids, y = _re_norm_data(rng, d=5)
    # entity-disjoint sparsity so INDEX_MAP actually compacts
    for u in range(6):
        x[uids == u, 1 + (u % 3)] = 0.0
    factors = 1.0 / (np.std(x, axis=0) + 1e-12)
    factors[0] = 1.0
    norm = NormalizationContext(factors=jnp.asarray(factors, jnp.float32),
                                shifts=None)
    data = GameData(y=y, features={"u": x}, id_tags={"userId": uids})

    def fit(projector):
        cfg = RandomEffectConfig(
            random_effect_type="userId", feature_shard="u",
            reg=Regularization(l2=0.3), projector=projector,
            solver=SolverConfig(max_iters=100, tolerance=1e-9))
        coord = build_coordinate("u", data, cfg, TaskType.LOGISTIC_REGRESSION,
                                 norm=norm)
        model, _ = coord.update(np.zeros(len(y)))
        return model

    ident = fit(ProjectorType.IDENTITY)
    comp = fit(ProjectorType.INDEX_MAP)
    for u in range(6):
        np.testing.assert_allclose(comp.w_stack[comp.slot_of[u]],
                                   ident.w_stack[ident.slot_of[u]],
                                   rtol=1e-2, atol=1e-3)


def test_random_effect_standardization_under_compaction(rng):
    """STANDARDIZATION (factors + SHIFTS) under INDEX_MAP compaction: the
    context is projected per entity — factor/shift rows gathered through each
    lane's observed-column map, the margin shift folded into the lane's own
    compact intercept position (reference NormalizationContextRDD through
    IndexMapProjectorRDD.scala:34-262).  With every feature observed the
    compact solve IS the full-space solve, so INDEX_MAP must match IDENTITY
    exactly; warm-starting from the published optimum must be a fixed point
    (round-trips the per-lane modelToTransformedSpace)."""
    import jax.numpy as jnp

    from photon_ml_tpu.core.normalization import NormalizationContext
    from photon_ml_tpu.types import ProjectorType

    x, uids, y = _re_norm_data(rng, d=5)
    factors = 1.0 / (np.std(x, axis=0) + 1e-12)
    shifts = np.mean(x, axis=0).copy()
    factors[0], shifts[0] = 1.0, 0.0  # intercept untouched
    norm = NormalizationContext(factors=jnp.asarray(factors, jnp.float32),
                                shifts=jnp.asarray(shifts, jnp.float32))
    data = GameData(y=y, features={"u": x}, id_tags={"userId": uids})

    def coord(projector):
        cfg = RandomEffectConfig(
            random_effect_type="userId", feature_shard="u",
            reg=Regularization(l2=0.3), projector=projector,
            intercept_index=0,
            solver=SolverConfig(max_iters=100, tolerance=1e-9))
        return build_coordinate("u", data, cfg, TaskType.LOGISTIC_REGRESSION,
                                norm=norm)

    ci = coord(ProjectorType.IDENTITY)
    cc = coord(ProjectorType.INDEX_MAP)
    assert cc._norm_per_lane and cc._norm_shift_dev is not None
    mi, _ = ci.update(np.zeros(len(y)))
    mc, _ = cc.update(np.zeros(len(y)))
    for u in range(6):
        np.testing.assert_allclose(mc.w_stack[mc.slot_of[u]],
                                   mi.w_stack[mi.slot_of[u]],
                                   rtol=1e-2, atol=1e-3)
    # warm start from the optimum is a fixed point (inverse map round-trip)
    # up to the f32 working-precision plateau: the approximate-Wolfe slack
    # lets a re-solve wander within the plateau-flat region (~4e-3 along
    # ill-conditioned directions), so the TIGHT invariant is the training
    # objective — per-sample logistic loss of the two models' scores must
    # agree to working precision — while coefficients get plateau room
    mc2, _ = cc.update(np.zeros(len(y)), init=mc)
    np.testing.assert_allclose(mc2.w_stack, mc.w_stack, rtol=1e-2, atol=5e-3)
    s1 = np.asarray(cc.score(mc), np.float64)
    s2 = np.asarray(cc.score(mc2), np.float64)
    loss1 = float(np.mean(np.logaddexp(0, s1) - y * s1))
    loss2 = float(np.mean(np.logaddexp(0, s2) - y * s2))
    np.testing.assert_allclose(loss2, loss1, rtol=1e-5)
    # fused program publishes the same model
    state = cc.init_sweep_state()
    sdata = cc.sweep_data()
    state, _ = cc.trace_update(state, jnp.zeros(len(y), jnp.float32),
                               data=sdata)
    w_stack = np.asarray(cc.trace_publish(state, data=sdata))
    np.testing.assert_allclose(w_stack, mc.w_stack, rtol=1e-4, atol=1e-5)


def test_sparse_re_standardization_matches_densified_compaction(rng):
    """Shift normalization on a SPARSE random-effect shard (the round-3
    refusal at the old game/coordinate.py:674): row-sparse compaction with a
    per-row intercept slot must match the densified INDEX_MAP fit — the two
    compact paths project the context identically."""
    import jax.numpy as jnp

    from photon_ml_tpu.core.normalization import NormalizationContext
    from photon_ml_tpu.game.data import SparseShard
    from photon_ml_tpu.types import ProjectorType

    n_users, per_user, d, k = 8, 48, 32, 5
    n = n_users * per_user
    uids = np.repeat(np.arange(n_users), per_user)
    # k-sparse rows over features 1..d-1 plus an explicit intercept column 0
    idx = np.concatenate(
        [np.zeros((n, 1), np.int32),
         rng.integers(1, d, size=(n, k)).astype(np.int32)], axis=1)
    vals = np.concatenate(
        [np.ones((n, 1), np.float32),
         (rng.normal(size=(n, k)) * 3.0 + 1.0).astype(np.float32)], axis=1)
    wu = rng.normal(size=(n_users, d)).astype(np.float32) * 0.5
    margins = np.einsum("nk,nk->n", vals, np.take_along_axis(
        wu[uids], idx, axis=1))
    y = (rng.random(n) < 1 / (1 + np.exp(-margins))).astype(np.float32)
    dense = np.zeros((n, d), np.float32)
    np.add.at(dense, (np.repeat(np.arange(n), k + 1), idx.ravel()),
              vals.ravel())

    factors = np.ones(d, np.float32)
    factors[1:] = 0.4
    shifts = np.zeros(d, np.float32)
    shifts[1:] = 1.0  # nonzero shifts on every non-intercept feature
    norm = NormalizationContext(factors=jnp.asarray(factors),
                                shifts=jnp.asarray(shifts))

    def coord(features, projector):
        cfg = RandomEffectConfig(
            random_effect_type="userId", feature_shard="u",
            reg=Regularization(l2=0.5), projector=projector,
            intercept_index=0,
            solver=SolverConfig(max_iters=60, tolerance=1e-9))
        gd = GameData(y=y, features={"u": features}, id_tags={"userId": uids})
        return build_coordinate("u", gd, cfg, TaskType.LOGISTIC_REGRESSION,
                                norm=norm)

    cs = coord(SparseShard(indices=idx, values=vals, dim=d),
               ProjectorType.IDENTITY)
    cd = coord(dense, ProjectorType.INDEX_MAP)
    ms, _ = cs.update(np.zeros(n))
    md, _ = cd.update(np.zeros(n))
    assert ms.w_stack.shape == md.w_stack.shape == (n_users, d)
    for u in range(n_users):
        np.testing.assert_allclose(ms.w_stack[ms.slot_of[u]],
                                   md.w_stack[md.slot_of[u]],
                                   rtol=1e-2, atol=1e-3)


def test_random_effect_normalization_rejections(rng):
    import jax.numpy as jnp

    from photon_ml_tpu.core.normalization import NormalizationContext
    from photon_ml_tpu.types import ProjectorType

    x, uids, y = _re_norm_data(rng)
    data = GameData(y=y, features={"u": x}, id_tags={"userId": uids})
    norm_shift = NormalizationContext(factors=None,
                                      shifts=jnp.asarray(np.full(4, 0.5)))
    # INDEX_MAP + shifts is SUPPORTED (round 4: per-lane projected contexts)
    # but needs intercept_index so each lane's compact intercept position can
    # absorb the margin shift
    with pytest.raises(ValueError, match="intercept_index"):
        build_coordinate(
            "u", data,
            RandomEffectConfig(random_effect_type="userId", feature_shard="u",
                               projector=ProjectorType.INDEX_MAP),
            TaskType.LOGISTIC_REGRESSION, norm=norm_shift)
    shift0 = np.full(4, 0.5)
    shift0[0] = 0.0  # the intercept column itself is never shifted
    coord_im = build_coordinate(
        "u", data,
        RandomEffectConfig(random_effect_type="userId", feature_shard="u",
                           projector=ProjectorType.INDEX_MAP,
                           intercept_index=0),
        TaskType.LOGISTIC_REGRESSION,
        norm=NormalizationContext(factors=None, shifts=jnp.asarray(shift0)))
    assert coord_im._norm_shift_dev is not None
    # factor normalization under RANDOM projection is SUPPORTED (round 3):
    # the context is pushed through the Gaussian matrix and shared
    # (ProjectionMatrixBroadcast.projectNormalizationContext; full parity
    # coverage in tests/test_projection.py) — only shift normalization
    # WITHOUT an intercept_index still refuses
    coord = build_coordinate(
        "u", data,
        RandomEffectConfig(random_effect_type="userId", feature_shard="u",
                           projector=ProjectorType.RANDOM, projected_dim=2),
        TaskType.LOGISTIC_REGRESSION,
        norm=NormalizationContext(factors=jnp.ones(4) * 2.0, shifts=None))
    assert coord._norm_proj is not None
    with pytest.raises(ValueError, match="intercept_index"):
        build_coordinate(
            "u", data,
            RandomEffectConfig(random_effect_type="userId", feature_shard="u",
                               projector=ProjectorType.RANDOM, projected_dim=2),
            TaskType.LOGISTIC_REGRESSION, norm=norm_shift)


def test_lower_bound_existing_model_semantics(rng):
    """Reference RandomEffectDataset.scala:322-333 + RandomEffectCoordinate
    .updateModel:114-127: with a warm-start model, an under-bound entity
    ALREADY covered by it is not retrained (its model passes through
    unchanged), while an under-bound NEW entity still trains; without a
    warm start, under-bound entities are dropped outright."""
    from photon_ml_tpu.models.game import RandomEffectModel

    d = 4
    # entity 0: 16 samples; entity 1: 2 samples (under bound), IN the prior;
    # entity 2: 2 samples (under bound), NOT in the prior
    uids = np.concatenate([np.zeros(16), np.ones(2), np.full(2, 2)]).astype(np.int64)
    n = len(uids)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.random(n) > 0.5).astype(np.float32)
    data = GameData(y=y, features={"u": x}, id_tags={"userId": uids})
    cfg = RandomEffectConfig(random_effect_type="userId", feature_shard="u",
                             solver=SolverConfig(max_iters=20),
                             reg=Regularization(l2=1.0),
                             min_active_samples=4)
    prior_w = np.asarray([[1.0, 2.0, 3.0, 4.0], [5.0, 6.0, 7.0, 8.0]],
                         np.float32)
    prior = RandomEffectModel(w_stack=prior_w, slot_of={0: 0, 1: 1},
                              random_effect_type="userId", feature_shard="u",
                              task=TaskType.LOGISTIC_REGRESSION)

    # no warm start: under-bound entities dropped outright
    cold = build_coordinate("u", data, cfg, TaskType.LOGISTIC_REGRESSION)
    m_cold, _ = cold.update(np.zeros(n, np.float32))
    assert set(m_cold.slot_of) == {0}

    # warm start: entity 1 (under-bound, prior) NOT retrained — its prior
    # coefficients pass through; entity 2 (under-bound, new) IS trained
    warm = build_coordinate("u", data, cfg, TaskType.LOGISTIC_REGRESSION,
                            existing_model_keys=frozenset(prior.slot_of))
    assert set(warm.buckets.lane_of) == {0, 2}
    m_warm, _ = warm.update(np.zeros(n, np.float32), init=prior)
    assert set(m_warm.slot_of) == {0, 1, 2}
    np.testing.assert_array_equal(
        m_warm.w_stack[m_warm.slot_of[1]], prior_w[1])
    # retrained entities moved off the prior
    assert np.max(np.abs(m_warm.w_stack[m_warm.slot_of[0]] - prior_w[0])) > 1e-3
    # the carried entity's samples score with its carried model
    sc = warm.score(m_warm)
    expected = x[16:18] @ prior_w[1]
    np.testing.assert_allclose(sc[16:18], expected, rtol=1e-5)

    # estimator path (fused): same semantics end-to-end
    est = GameEstimator()
    config = GameConfig(task=TaskType.LOGISTIC_REGRESSION,
                        coordinates={"user": cfg})
    res = est.fit(data, [config],
                  initial_model=GameModel(models={"user": prior}), seed=0)[0]
    m_fused = res.model["user"]
    assert set(m_fused.slot_of) == {0, 1, 2}
    np.testing.assert_array_equal(
        m_fused.w_stack[m_fused.slot_of[1]], prior_w[1])


def test_warm_start_carry_through_fused_matches_host(rng):
    """Carried entities' samples contribute a CONSTANT score to every
    residual; the fused program folds it into the base offsets, so a
    2-coordinate warm-started fused fit must match the host loop (which
    re-scores the merged model each update) — and both must differ from a
    fit that ignores the carried prior."""
    d_g, d_u = 5, 3
    uids = np.concatenate([np.zeros(24), np.ones(2), np.full(24, 2)]).astype(np.int64)
    n = len(uids)
    xg = rng.normal(size=(n, d_g)).astype(np.float32)
    xu = rng.normal(size=(n, d_u)).astype(np.float32)
    y = (rng.random(n) > 0.5).astype(np.float32)
    data = GameData(y=y, features={"g": xg, "u": xu}, id_tags={"userId": uids})
    solver = SolverConfig(max_iters=30, tolerance=1e-8)
    config = GameConfig(
        task=TaskType.LOGISTIC_REGRESSION, num_outer_iterations=2,
        coordinates={
            "fixed": FixedEffectConfig(feature_shard="g", solver=solver,
                                       reg=Regularization(l2=1.0)),
            "user": RandomEffectConfig(random_effect_type="userId",
                                       feature_shard="u", solver=solver,
                                       reg=Regularization(l2=1.0),
                                       min_active_samples=4)})
    from photon_ml_tpu.models.game import RandomEffectModel

    prior_w = (rng.normal(size=(1, d_u)) * 2.0).astype(np.float32)
    prior = GameModel(models={"user": RandomEffectModel(
        w_stack=prior_w, slot_of={1: 0}, random_effect_type="userId",
        feature_shard="u", task=TaskType.LOGISTIC_REGRESSION)})

    m_fused = GameEstimator().fit(data, [config], initial_model=prior,
                                  seed=0)[0].model
    m_host = GameEstimator(fused=False).fit(data, [config],
                                            initial_model=prior,
                                            seed=0)[0].model
    # entity 1 (under-bound, in prior): carried identically by both paths
    for m in (m_fused, m_host):
        np.testing.assert_array_equal(
            m["user"].w_stack[m["user"].slot_of[1]], prior_w[0])
    # the FIXED coordinate saw the carried residual identically
    np.testing.assert_allclose(m_fused["fixed"].coefficients.means,
                               m_host["fixed"].coefficients.means, atol=2e-4)
    np.testing.assert_allclose(
        m_fused["user"].w_stack[m_fused["user"].slot_of[0]],
        m_host["user"].w_stack[m_host["user"].slot_of[0]], atol=2e-4)
    # and the carried prior is load-bearing: without it the fixed effect
    # trains against a different residual
    m_cold = GameEstimator().fit(data, [config], seed=0)[0].model
    assert np.max(np.abs(m_cold["fixed"].coefficients.means
                         - m_fused["fixed"].coefficients.means)) > 1e-3


def test_compact_random_effect_model(rng):
    """CompactRandomEffectModel (wide-vocabulary published container):
    round-trips with the dense stack, scores identically on BOTH shard
    kinds including missing entities, and its memory is O(entities x
    observed) rather than O(entities x vocabulary)."""
    from photon_ml_tpu.game.data import SparseShard
    from photon_ml_tpu.models.game import RandomEffectModel

    e, d, k_obs = 24, 512, 6
    w = np.zeros((e, d), np.float32)
    for i in range(e):
        cols = rng.choice(d, size=k_obs, replace=False)
        w[i, cols] = rng.normal(size=k_obs)
    w[3] = 0.0  # an all-zero entity must survive the round trip
    slot_of = {100 + i * 7: i for i in range(e)}
    dense = RandomEffectModel(w_stack=w, slot_of=slot_of,
                              random_effect_type="userId", feature_shard="u")
    compact = dense.to_compact()
    # memory claim + exact round trip
    assert compact.values.nbytes + compact.indices.nbytes < w.nbytes / 10
    np.testing.assert_array_equal(compact.to_dense().w_stack, w)
    assert compact.to_dense().slot_of == slot_of

    # scoring parity, dense shard (+ unknown entity ids -> 0)
    n = 200
    uids = rng.choice(list(slot_of) + [999999], size=n)
    x = rng.normal(size=(n, d)).astype(np.float32)
    data_dense = GameData(y=np.zeros(n), features={"u": x},
                          id_tags={"userId": uids})
    s_dense = np.asarray(dense.score(data_dense))
    s_compact = np.asarray(compact.score(data_dense))
    np.testing.assert_allclose(s_compact, s_dense, rtol=1e-6, atol=1e-6)
    assert np.all(s_compact[uids == 999999] == 0.0)

    # scoring parity, sparse shard (feature ids hit AND miss the model's
    # observed columns)
    ks = 5
    f_idx = rng.integers(0, d, size=(n, ks)).astype(np.int32)
    f_val = rng.normal(size=(n, ks)).astype(np.float32)
    data_sparse = GameData(
        y=np.zeros(n),
        features={"u": SparseShard(indices=f_idx, values=f_val, dim=d)},
        id_tags={"userId": uids})
    np.testing.assert_allclose(np.asarray(compact.score(data_sparse)),
                               np.asarray(dense.score(data_sparse)),
                               rtol=1e-6, atol=1e-6)

    # capacity guard: k below the densest entity refuses loudly
    with pytest.raises(ValueError, match="capacity"):
        dense.to_compact(k=k_obs - 1)
    # variance-carrying models refuse (variances' support differs)
    import dataclasses as _dc
    with pytest.raises(ValueError, match="variances"):
        _dc.replace(dense, variances=np.ones_like(w)).to_compact()
    # explicit roomier capacity still round-trips
    np.testing.assert_array_equal(dense.to_compact(k=k_obs + 3)
                                  .to_dense().w_stack, w)


def test_constraint_space_transformed_reference_compat(rng):
    """The reference applies constraintMap bounds RAW to the transformed-
    space iterate every TRON/LBFGS iteration (TRON.scala:228 ->
    OptimizationUtils.projectCoefficientsToSubspace, OptimizationUtils
    .scala:56-58) — even under normalization that rescales and shifts, so
    the PUBLISHED original-space coefficients can violate the written
    bounds.  constraint_space="transformed" reproduces that faithfully;
    this test pins BOTH the reference's numbers (scipy bounded solve on
    the transformed design) and the deviation the default space refuses
    to produce."""
    import scipy.optimize as sopt
    import scipy.special as sp

    import jax.numpy as jnp

    from photon_ml_tpu.core.normalization import NormalizationContext

    n, d = 800, 3
    x = np.empty((n, d))
    x[:, 0] = 1.0                               # intercept
    x[:, 1] = rng.normal(size=n) * 0.1 + 0.5    # tiny scale, shifted
    x[:, 2] = rng.normal(size=n)
    w_true = np.asarray([0.2, 8.0, -1.0])
    y = (rng.random(n) < 1 / (1 + np.exp(-x @ w_true))).astype(float)
    data = GameData(y=y, features={"g": x})
    l2 = 0.5
    bounds = (1, -0.3, 0.3)  # binds hard: unconstrained w_t[1] ~ 0.8

    mean = x.mean(axis=0)
    std = x.std(axis=0)
    factors = 1.0 / np.where(std == 0, 1.0, std)
    shifts = mean.copy()
    factors[0], shifts[0] = 1.0, 0.0            # intercept untouched
    norm = NormalizationContext(factors=jnp.asarray(factors),
                                shifts=jnp.asarray(shifts))

    def fit(space):
        cfg = GameConfig(task=TaskType.LOGISTIC_REGRESSION, coordinates={
            "fixed": FixedEffectConfig(
                feature_shard="g", reg=Regularization(l2=l2),
                solver=SolverConfig(max_iters=300, tolerance=1e-10),
                intercept_index=0, constraints=(bounds,),
                constraint_space=space)})
        est = GameEstimator(dtype=np.float64, normalization={"g": norm})
        return est.fit(data, [cfg])[0]

    # default space: honest refusal (the repo's documented deviation)
    with pytest.raises(ValueError, match="non-separable under shifts"):
        fit("original")

    res = fit("transformed")
    w_orig = np.asarray(res.model["fixed"].coefficients.means)
    # published ORIGINAL-space coefficient violates the written bound —
    # exactly what the reference ships (the questionable half of faithful)
    assert abs(w_orig[1]) > 0.3 + 0.5

    # pin the reference's numbers: bounded scipy solve on the TRANSFORMED
    # design (x_t = (x - mean) * factors) with raw bounds
    xt = (x - shifts) * factors

    def nll(wv):
        z = xt @ wv
        return np.sum(np.logaddexp(0, z) - y * z) + 0.5 * l2 * wv @ wv

    def grad(wv):
        z = xt @ wv
        return xt.T @ (sp.expit(z) - y) + l2 * wv

    ref = sopt.minimize(nll, np.zeros(d), jac=grad, method="L-BFGS-B",
                        bounds=[(None, None), (-0.3, 0.3), (None, None)])
    # map the repo's published model back to transformed space and compare
    w_t = np.asarray(norm.model_to_transformed_space(jnp.asarray(w_orig), 0))
    np.testing.assert_allclose(w_t, ref.x, atol=5e-5)
    assert abs(w_t[1]) <= 0.3 + 1e-9  # raw bound respected where applied


def test_constraint_space_validation():
    with pytest.raises(ValueError, match="constraint_space"):
        FixedEffectConfig(feature_shard="g", constraint_space="bogus")
    from photon_ml_tpu.cli.config_grammar import parse_coordinate_spec

    spec = parse_coordinate_spec(
        "name=f,feature.shard=g,constraint.space=transformed,reg.weights=1")
    assert spec.template.constraint_space == "transformed"


def test_constraint_space_transformed_compact_refusal(rng):
    """transformed + compact (sparse/INDEX_MAP) + normalization must refuse
    loudly: the per-lane compact solve applies bounds with ORIGINAL
    semantics, so silently accepting the compat flag would produce exactly
    the reference divergence it exists to prevent (MIGRATION.md)."""
    from photon_ml_tpu.core.normalization import NormalizationContext
    from photon_ml_tpu.types import ProjectorType

    n_users, per_user, d = 4, 12, 3
    n = n_users * per_user
    x = rng.normal(size=(n, d))
    uids = np.repeat(np.arange(n_users), per_user)
    y = (rng.random(n) < 0.5).astype(float)
    data = GameData(y=y, features={"u": x}, id_tags={"userId": uids})
    import jax.numpy as jnp
    norm = NormalizationContext(factors=jnp.ones(d) * 2.0, shifts=None)
    cfg = RandomEffectConfig(
        random_effect_type="userId", feature_shard="u",
        projector=ProjectorType.INDEX_MAP,
        constraints=((0, -0.5, 0.5),), constraint_space="transformed")
    with pytest.raises(ValueError, match="transformed.*compact|compact.*transformed"):
        build_coordinate("u", data, cfg, TaskType.LOGISTIC_REGRESSION,
                         norm=norm)
