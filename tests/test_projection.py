"""Projector tests (reference analog: IndexMapProjectorRDDIntegTest,
ProjectionMatrixTest, LocalDataset Pearson-filter tests — SURVEY.md §4)."""

import numpy as np
import pytest

from photon_ml_tpu.core.regularization import Regularization
from photon_ml_tpu.game.config import RandomEffectConfig
from photon_ml_tpu.game.coordinate import RandomEffectCoordinate
from photon_ml_tpu.game.data import GameData
from photon_ml_tpu.opt.types import SolverConfig
from photon_ml_tpu.parallel.bucketing import bucket_by_entity
from photon_ml_tpu.parallel.projection import (
    build_observed_indices,
    build_random_projection,
    pearson_scores,
    project_buckets,
)
from photon_ml_tpu.types import ProjectorType, TaskType


def _sparse_entity_data(rng, n_entities=12, per_entity=20, d=32):
    """Each entity observes only a small random subset of features."""
    n = n_entities * per_entity
    eids = np.repeat(np.arange(n_entities), per_entity).astype(np.int64)
    x = np.zeros((n, d), np.float32)
    for e in range(n_entities):
        cols = rng.choice(d - 1, size=5, replace=False)  # leave col d-1 = intercept
        rows = slice(e * per_entity, (e + 1) * per_entity)
        x[rows, cols] = rng.normal(size=(per_entity, 5)).astype(np.float32)
    x[:, d - 1] = 1.0  # intercept column observed everywhere
    w = rng.normal(size=d).astype(np.float32)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-(x @ w)))).astype(np.float32)
    return eids, x, y


def test_pearson_scores_match_numpy(rng):
    n, d = 200, 6
    x = rng.normal(size=(n, d))
    y = x[:, 0] * 2.0 + rng.normal(size=n) * 0.1
    w = np.ones(n)
    got = pearson_scores(x, y, w)
    for j in range(d):
        expect = abs(np.corrcoef(x[:, j], y)[0, 1])
        assert got[j] == pytest.approx(expect, abs=1e-6)
    # Constant columns carry no per-entity signal and score 0; the intercept's
    # survival is the caller's intercept_index pin (build_observed_indices),
    # so an entity-constant attribute feature can't hijack the carve-out.
    xc = np.concatenate([x, np.ones((n, 1)), np.full((n, 1), 2.0)], axis=1)
    s = pearson_scores(xc, y, w)
    assert s[-2] == 0.0 and s[-1] == 0.0


def test_observed_projection_margin_exact(rng):
    eids, x, y = _sparse_entity_data(rng)
    buckets = bucket_by_entity(eids, x, y)
    assert len(buckets.buckets) == 1
    b = buckets.buckets[0]
    proj = build_observed_indices(b, buckets.dim)
    assert proj.d_proj < buckets.dim  # actually compacted
    xp = proj.project_x(b.x)
    w_proj = rng.normal(size=(b.num_lanes, proj.d_proj)).astype(np.float32)
    w_full = proj.back_project(w_proj)
    # margins identical in both spaces for every lane/sample
    m_proj = np.einsum("esd,ed->es", xp, w_proj)
    m_full = np.einsum("esd,ed->es", b.x, w_full)
    np.testing.assert_allclose(m_proj, m_full, rtol=1e-5, atol=1e-5)


def test_random_projection_margin_exact(rng):
    d, dp = 32, 8
    proj = build_random_projection(d, dp, seed=3)
    x = rng.normal(size=(4, 10, d)).astype(np.float32)
    xp = proj.project_x(x)
    w_proj = rng.normal(size=(4, dp)).astype(np.float32)
    w_full = proj.back_project(w_proj)
    np.testing.assert_allclose(
        np.einsum("esd,ed->es", xp, w_proj),
        np.einsum("esd,ed->es", x, w_full), rtol=1e-4, atol=1e-4)


def test_pearson_ratio_caps_features_and_keeps_intercept(rng):
    eids, x, y = _sparse_entity_data(rng, per_entity=16)
    buckets = bucket_by_entity(eids, x, y)
    b = buckets.buckets[0]
    d = buckets.dim
    proj = build_observed_indices(b, d, features_to_samples_ratio=0.25,
                                  intercept_index=d - 1)
    for lane in range(b.num_lanes):
        k = int(b.counts[lane])
        kept = proj.indices[lane][proj.indices[lane] >= 0]
        assert len(kept) <= max(1, int(np.ceil(0.25 * k)))
        assert (d - 1) in kept  # intercept survives the cut


def test_re_coordinate_index_map_matches_identity(rng):
    eids, x, y = _sparse_entity_data(rng)
    data = GameData(y=y, features={"s": x}, id_tags={"e": eids})
    solver = SolverConfig(max_iters=60, tolerance=1e-9)
    kw = dict(random_effect_type="e", feature_shard="s", solver=solver,
              reg=Regularization(l2=0.5))
    base = RandomEffectCoordinate(
        "re", data, RandomEffectConfig(**kw), TaskType.LOGISTIC_REGRESSION)
    projected = RandomEffectCoordinate(
        "re", data, RandomEffectConfig(projector=ProjectorType.INDEX_MAP, **kw),
        TaskType.LOGISTIC_REGRESSION)
    offs = np.zeros(len(y), np.float32)
    m0, _ = base.update(offs)
    m1, _ = projected.update(offs)
    # zero-init + L2 ==> unobserved coords stay 0; optima coincide
    np.testing.assert_allclose(np.asarray(m1.w_stack), np.asarray(m0.w_stack),
                               rtol=1e-3, atol=2e-3)
    np.testing.assert_allclose(projected.score(m1), base.score(m0),
                               rtol=1e-3, atol=2e-3)
    # warm start from the projected model converges immediately to itself
    m2, _ = projected.update(offs, init=m1)
    np.testing.assert_allclose(np.asarray(m2.w_stack), np.asarray(m1.w_stack),
                               rtol=1e-3, atol=2e-3)


def test_re_coordinate_random_projection_runs(rng):
    eids, x, y = _sparse_entity_data(rng)
    data = GameData(y=y, features={"s": x}, id_tags={"e": eids})
    coord = RandomEffectCoordinate(
        "re", data,
        RandomEffectConfig(random_effect_type="e", feature_shard="s",
                           solver=SolverConfig(max_iters=20),
                           reg=Regularization(l2=0.5),
                           projector=ProjectorType.RANDOM, projected_dim=8),
        TaskType.LOGISTIC_REGRESSION)
    model, _ = coord.update(np.zeros(len(y), np.float32))
    assert np.asarray(model.w_stack).shape[1] == x.shape[1]  # full-dim model
    assert np.all(np.isfinite(np.asarray(model.w_stack)))
    scores = coord.score(model)
    assert np.all(np.isfinite(scores))


def test_project_buckets_requires_dim_for_random(rng):
    eids, x, y = _sparse_entity_data(rng, n_entities=3, per_entity=4)
    buckets = bucket_by_entity(eids, x, y)
    with pytest.raises(ValueError):
        project_buckets(buckets, ProjectorType.RANDOM)
    with pytest.raises(ValueError):
        project_buckets(buckets, ProjectorType.IDENTITY)
    # Pearson/intercept knobs are INDEX_MAP-only: rejected, not ignored
    with pytest.raises(ValueError, match="INDEX_MAP"):
        project_buckets(buckets, ProjectorType.RANDOM, projected_dim=4,
                        features_to_samples_ratio=0.5)


def test_random_projection_normalization_parity():
    """Normalization under RANDOM projection: the coordinate context is
    pushed through the Gaussian matrix and shared by every entity
    (reference ProjectionMatrixBroadcast.projectNormalizationContext:102-112,
    intercept pass-through ProjectionMatrix.scala:112-120).  Must equal the
    reference-order manual computation: project design + context by hand,
    solve per-entity in the projected space (IDENTITY path), back-project."""
    import jax.numpy as jnp

    from photon_ml_tpu.core.normalization import NormalizationContext
    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.game import GameData
    from photon_ml_tpu.game.config import RandomEffectConfig
    from photon_ml_tpu.game.coordinate import build_coordinate
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.parallel.projection import build_random_projection
    from photon_ml_tpu.types import ProjectorType, TaskType

    rng = np.random.default_rng(9)
    n, d, n_users, d_proj = 512, 48, 8, 12
    x = rng.normal(size=(n, d)).astype(np.float32) * np.linspace(
        0.5, 3.0, d).astype(np.float32)
    x[:, -1] = 1.0  # intercept column
    uids = np.repeat(np.arange(n_users), n // n_users)
    rng.shuffle(uids)
    wu = (rng.normal(size=(n_users, d)) * 0.4).astype(np.float32)
    margins = np.einsum("nd,nd->n", x, wu[uids])
    y = (rng.random(n) < 1 / (1 + np.exp(-margins))).astype(np.float32)

    fac = (1.0 / np.maximum(x.std(axis=0), 1e-6)).astype(np.float32)
    fac[-1] = 1.0
    shifts = x.mean(axis=0).astype(np.float32)
    shifts[-1] = 0.0
    norm = NormalizationContext(factors=fac, shifts=shifts)

    solver = SolverConfig(max_iters=40, tolerance=1e-8)
    cfg = RandomEffectConfig(random_effect_type="userId", feature_shard="u",
                             solver=solver, reg=Regularization(l2=1.0),
                             projector=ProjectorType.RANDOM,
                             projected_dim=d_proj, intercept_index=d - 1)
    gd = GameData(y=y, features={"u": x}, id_tags={"userId": uids})
    c = build_coordinate("u", gd, cfg, TaskType.LOGISTIC_REGRESSION,
                         norm=norm, seed=3)
    m, _ = c.update(np.zeros(n, np.float32))

    rp = build_random_projection(d, d_proj, seed=3, dtype=np.float32,
                                 intercept_index=d - 1)
    ctx, p_ii = rp.project_normalization(norm)
    x_p = rp.project_x(x)
    np.testing.assert_allclose(x_p[:, -1], x[:, -1])  # intercept exact
    cfg_id = RandomEffectConfig(random_effect_type="userId",
                                feature_shard="u", solver=solver,
                                reg=Regularization(l2=1.0),
                                intercept_index=p_ii)
    gd_p = GameData(y=y, features={"u": x_p}, id_tags={"userId": uids})
    c2 = build_coordinate("u", gd_p, cfg_id, TaskType.LOGISTIC_REGRESSION,
                          norm=NormalizationContext(factors=ctx.factors,
                                                    shifts=ctx.shifts),
                          seed=3)
    m2, _ = c2.update(np.zeros(n, np.float32))
    w_manual = rp.back_project(m2.w_stack)
    np.testing.assert_allclose(m.w_stack, w_manual, atol=1e-4)

    # the context is load-bearing: dropping it changes the solution
    c_raw = build_coordinate("u", gd, cfg, TaskType.LOGISTIC_REGRESSION,
                             seed=3)
    m_raw, _ = c_raw.update(np.zeros(n, np.float32))
    assert np.max(np.abs(m_raw.w_stack - m.w_stack)) > 1e-3

    # fused sweep path publishes the same model (trace_publish order:
    # transformed->original projected space, then back-projection)
    state = c.init_sweep_state()
    state, _score = c.trace_update(state, jnp.zeros(n, jnp.float32))
    w_fused = np.asarray(c.trace_publish(state))
    np.testing.assert_allclose(w_fused, m.w_stack, atol=1e-4)


def test_random_projection_shift_requires_intercept():
    from photon_ml_tpu.core.normalization import NormalizationContext
    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.game import GameData
    from photon_ml_tpu.game.config import RandomEffectConfig
    from photon_ml_tpu.game.coordinate import build_coordinate
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.types import ProjectorType, TaskType

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    uids = np.repeat(np.arange(4), 16)
    y = (rng.random(64) < 0.5).astype(np.float32)
    norm = NormalizationContext(factors=None,
                                shifts=x.mean(axis=0).astype(np.float32))
    cfg = RandomEffectConfig(random_effect_type="userId", feature_shard="u",
                             solver=SolverConfig(max_iters=5),
                             reg=Regularization(l2=1.0),
                             projector=ProjectorType.RANDOM, projected_dim=4)
    gd = GameData(y=y, features={"u": x}, id_tags={"userId": uids})
    with pytest.raises(ValueError, match="intercept_index"):
        build_coordinate("u", gd, cfg, TaskType.LOGISTIC_REGRESSION,
                         norm=norm)


def test_index_map_simple_variances_match_identity():
    """SIMPLE variances under INDEX_MAP compaction equal the IDENTITY
    computation: diag(H) is per-feature and margin-invariant; unobserved
    features carry prior-only 1/λ2."""
    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.game import GameData
    from photon_ml_tpu.game.config import RandomEffectConfig
    from photon_ml_tpu.game.coordinate import build_coordinate
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.types import (ProjectorType, TaskType,
                                     VarianceComputationType)

    rng = np.random.default_rng(4)
    n, d, n_users = 256, 24, 8
    x = rng.normal(size=(n, d)).astype(np.float32)
    # per-entity sparsity so INDEX_MAP actually compacts: zero half the
    # columns per user
    uids = np.repeat(np.arange(n_users), n // n_users)
    mask = np.ones((n, d), bool)
    for u in range(n_users):
        cols = rng.choice(d, size=d // 2, replace=False)
        mask[np.ix_(uids == u, cols)] = False
    x = np.where(mask, x, 0.0).astype(np.float32)
    y = (rng.random(n) > 0.5).astype(np.float32)
    gd = GameData(y=y, features={"u": x}, id_tags={"userId": uids})
    l2 = 3.0

    def fit(projector):
        cfg = RandomEffectConfig(random_effect_type="userId",
                                 feature_shard="u",
                                 solver=SolverConfig(max_iters=25),
                                 reg=Regularization(l2=l2),
                                 projector=projector,
                                 variance=VarianceComputationType.SIMPLE)
        c = build_coordinate("u", gd, cfg, TaskType.LOGISTIC_REGRESSION)
        m, _ = c.update(np.zeros(n, np.float32))
        return m

    m_id = fit(ProjectorType.IDENTITY)
    m_im = fit(ProjectorType.INDEX_MAP)
    np.testing.assert_allclose(m_im.w_stack, m_id.w_stack, atol=5e-4)
    np.testing.assert_allclose(m_im.variances, m_id.variances, rtol=2e-3)


def test_index_map_soa_newton_matches_vmapped(rng, monkeypatch):
    """Narrow INDEX_MAP-projected buckets gate onto the SoA Newton solver
    (the gate keys on projected solve-space shapes); the published
    full-dim model matches the generic vmapped path."""
    eids, x, y = _sparse_entity_data(rng)
    data = GameData(y=y, features={"s": x}, id_tags={"e": eids})
    kw = dict(random_effect_type="e", feature_shard="s",
              solver=SolverConfig(max_iters=60, tolerance=1e-9),
              reg=Regularization(l2=0.5), projector=ProjectorType.INDEX_MAP)
    cs = RandomEffectCoordinate("re", data, RandomEffectConfig(**kw),
                                TaskType.LOGISTIC_REGRESSION)
    if not cs._use_soa:
        pytest.skip("fixture shapes exceed the SoA gate: "
                    + str([b.x.shape for b in cs._proj.buckets]))
    offs = np.zeros(len(y), np.float32)
    ms, _ = cs.update(offs)

    monkeypatch.setattr("photon_ml_tpu.opt.newton_soa.soa_eligible",
                        lambda dim, loss_name: False)
    cv = RandomEffectCoordinate("re", data, RandomEffectConfig(**kw),
                                TaskType.LOGISTIC_REGRESSION)
    assert not cv._use_soa
    mv, _ = cv.update(offs)
    np.testing.assert_allclose(np.asarray(ms.w_stack),
                               np.asarray(mv.w_stack), rtol=1e-3, atol=2e-3)
