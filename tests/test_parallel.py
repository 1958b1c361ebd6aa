"""Distributed-layer tests on the 8-device virtual CPU mesh.

Reference analog (SURVEY.md §4): distributed-vs-local parity tests
(DistributedOptimizationProblemIntegTest) — here: chip-count invariance
(1-device vs 8-device mesh gives identical solutions) and bucketed-vmap
random effects vs per-entity serial solves.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.core import GLMObjective, Regularization, losses
from photon_ml_tpu.core.batch import dense_batch, sparse_batch
from photon_ml_tpu.opt import SolverConfig, make_solver
from photon_ml_tpu.parallel import (
    bucket_by_entity,
    fit_fixed_effect,
    fit_random_effects,
    make_mesh,
    score_random_effects,
)
from photon_ml_tpu.parallel.bucketing import (
    gather_entity_coefficients,
    score_samples,
    stacked_coefficients,
)
from photon_ml_tpu.types import OptimizerType

D = 5


def _problem(rng, n=333):  # deliberately not divisible by 8
    x = rng.normal(size=(n, D))
    w = rng.normal(size=D)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-x @ w))).astype(float)
    wt = rng.random(n) + 0.5
    return dense_batch(x, y, weight=wt), GLMObjective(
        loss=losses.logistic_loss, reg=Regularization(l2=0.2)
    )


def test_fixed_effect_chip_count_invariance(rng, devices):
    batch, obj = _problem(rng)
    mesh1 = make_mesh(n_data=1, devices=devices[:1])
    mesh8 = make_mesh(n_data=8, devices=devices)
    r1 = fit_fixed_effect(obj, batch, jnp.zeros(D), mesh1)
    r8 = fit_fixed_effect(obj, batch, jnp.zeros(D), mesh8)
    np.testing.assert_allclose(r1.value, r8.value, rtol=1e-9)
    np.testing.assert_allclose(r1.w, r8.w, rtol=1e-6, atol=1e-9)
    # and both match the plain single-device solver
    plain = jax.jit(make_solver(obj, OptimizerType.LBFGS))(jnp.zeros(D), batch)
    np.testing.assert_allclose(r8.value, plain.value, rtol=1e-9)


def test_fixed_effect_feature_sharded(rng, devices):
    """Feature-axis (model-parallel) sharding: w lives P('feature'); result
    must match the replicated-w solve bit-for-bit up to reduction order, and
    padding (D=5 over 4 feature shards -> pad to 8) must trim cleanly."""
    batch, obj = _problem(rng, n=160)
    mesh = make_mesh(n_data=2, n_feature=4, devices=devices)
    r = fit_fixed_effect(obj, batch, jnp.zeros(D), mesh, feature_sharded=True)
    assert r.w.shape == (D,)
    plain = jax.jit(make_solver(obj, OptimizerType.LBFGS))(jnp.zeros(D), batch)
    np.testing.assert_allclose(r.value, plain.value, rtol=1e-8)
    np.testing.assert_allclose(r.w, plain.w, rtol=1e-5, atol=1e-8)


def test_fixed_effect_feature_sharded_box_and_norm(rng, devices):
    """Padding must extend box bounds and normalization factors/shifts so
    padded slots stay pinned at zero and real slots keep their semantics."""
    from photon_ml_tpu.core.normalization import NormalizationContext

    batch, _ = _problem(rng, n=96)
    factors = rng.random(D) + 0.5
    shifts = rng.normal(size=D) * 0.1
    obj = GLMObjective(
        loss=losses.logistic_loss, reg=Regularization(l2=0.2),
        norm=NormalizationContext(factors=jnp.asarray(factors), shifts=jnp.asarray(shifts)),
    )
    lo, hi = -jnp.ones(D) * 0.5, jnp.ones(D) * 0.5
    mesh = make_mesh(n_data=2, n_feature=4, devices=devices)  # D=5 pads to 8
    r = fit_fixed_effect(obj, batch, jnp.zeros(D), mesh, box=(lo, hi),
                         feature_sharded=True)
    plain = jax.jit(make_solver(obj, OptimizerType.LBFGS, box=(lo, hi)))(
        jnp.zeros(D), batch)
    assert r.w.shape == (D,)
    np.testing.assert_allclose(r.value, plain.value, rtol=1e-8)
    np.testing.assert_allclose(r.w, plain.w, rtol=1e-5, atol=1e-8)


def _sparse_problem(rng, n=120, d=11, k=3, l2=0.1):
    idx = np.stack([rng.choice(d, size=k, replace=False) for _ in range(n)])
    val = rng.normal(size=(n, k))
    w = rng.normal(size=d)
    y = (rng.random(n) < 1 / (1 + np.exp(-np.einsum(
        "nk,nk->n", val, w[idx])))).astype(float)
    sb = sparse_batch(idx, val, y, dim=d)
    obj = GLMObjective(loss=losses.logistic_loss, reg=Regularization(l2=l2))
    return sb, obj, d


@pytest.mark.parametrize("opt", [OptimizerType.LBFGS, OptimizerType.TRON])
def test_fixed_effect_feature_sharded_sparse(rng, devices, opt):
    """Sparse + feature-axis sharding (the 1M-feature scale path): global-id
    rows against blocked w must match the replicated-w solve, including the
    d=11-over-4-shards padding trim, for both LBFGS and TRON (hvp path)."""
    sb, obj, d = _sparse_problem(rng)
    mesh = make_mesh(n_data=2, n_feature=4, devices=devices)
    r = fit_fixed_effect(obj, sb, jnp.zeros(d), mesh, optimizer=opt,
                         feature_sharded=True)
    assert r.w.shape == (d,)
    plain = jax.jit(make_solver(obj, opt))(jnp.zeros(d), sb)
    np.testing.assert_allclose(r.value, plain.value, rtol=1e-8)
    np.testing.assert_allclose(r.w, plain.w, rtol=1e-5, atol=1e-8)


def test_fixed_effect_sparse_sharded_chip_count_invariance(rng, devices):
    """Same optimum for sparse x (data, feature) meshes of any shape."""
    sb, obj, d = _sparse_problem(rng, n=96)
    r1 = fit_fixed_effect(obj, sb, jnp.zeros(d),
                          make_mesh(n_data=1, devices=devices[:1]),
                          feature_sharded=True)
    for n_data, n_feature in [(1, 8), (4, 2), (2, 2)]:
        mesh = make_mesh(n_data=n_data, n_feature=n_feature,
                         devices=devices[: n_data * n_feature])
        r = fit_fixed_effect(obj, sb, jnp.zeros(d), mesh, feature_sharded=True)
        np.testing.assert_allclose(r.value, r1.value, rtol=1e-9)
        np.testing.assert_allclose(r.w, r1.w, rtol=1e-6, atol=1e-9)


def test_fixed_effect_feature_sharded_sparse_norm_and_variance(rng, devices):
    """Scaling-only normalization flows through the blocked objective
    (effective coefficients + chain rule at GSPMD level) and SIMPLE
    variances (hessian_diag) match the unsharded computation; shift
    normalization must refuse (it would densify sparse margins)."""
    from photon_ml_tpu.core.normalization import NormalizationContext
    from photon_ml_tpu.opt.solve import compute_variances
    from photon_ml_tpu.parallel.fixed import ShardSparseObjective
    from photon_ml_tpu.parallel.mesh import padded_dim, shard_batch, shard_coefficients
    from photon_ml_tpu.types import VarianceComputationType

    sb, _, d = _sparse_problem(rng)
    factors = jnp.asarray(rng.random(d) + 0.5)
    obj = GLMObjective(loss=losses.logistic_loss, reg=Regularization(l2=0.2),
                       norm=NormalizationContext(factors=factors, shifts=None))
    mesh = make_mesh(n_data=2, n_feature=4, devices=devices)
    r = fit_fixed_effect(obj, sb, jnp.zeros(d), mesh, feature_sharded=True)
    plain = jax.jit(make_solver(obj, OptimizerType.LBFGS))(jnp.zeros(d), sb)
    np.testing.assert_allclose(r.value, plain.value, rtol=1e-8)
    np.testing.assert_allclose(r.w, plain.w, rtol=1e-5, atol=1e-8)

    # SIMPLE variances through the blocked hessian_diag
    d_pad = padded_dim(d, mesh)
    padded_obj = obj.replace(norm=obj.norm.replace(
        factors=jnp.pad(factors, (0, d_pad - d), constant_values=1.0)))
    sm = ShardSparseObjective(padded_obj, mesh, d_pad // mesh.shape["feature"])
    w_sh = shard_coefficients(jnp.asarray(plain.w), mesh)
    b_sh = shard_batch(sb, mesh)
    var = jax.jit(lambda w, b: compute_variances(
        sm, w, b, VarianceComputationType.SIMPLE))(w_sh, b_sh)
    var_plain = compute_variances(obj, plain.w, sb, VarianceComputationType.SIMPLE)
    np.testing.assert_allclose(np.asarray(var)[:d], var_plain, rtol=1e-6)

    # shift normalization refuses loudly
    shifted = GLMObjective(
        loss=losses.logistic_loss,
        norm=NormalizationContext(factors=None, shifts=jnp.zeros(d) + 0.1))
    with pytest.raises(ValueError, match="scaling-only"):
        fit_fixed_effect(shifted, sb, jnp.zeros(d), mesh, feature_sharded=True)


def test_fixed_effect_sparse_sharded(rng, devices):
    n, k = 100, 3
    idx = np.stack([rng.choice(D, size=k, replace=False) for _ in range(n)])
    val = rng.normal(size=(n, k))
    y = (rng.random(n) > 0.5).astype(float)
    sb = sparse_batch(idx, val, y, dim=D)
    obj = GLMObjective(loss=losses.logistic_loss, reg=Regularization(l2=0.1))
    mesh8 = make_mesh(n_data=8, devices=devices)
    r8 = fit_fixed_effect(obj, sb, jnp.zeros(D), mesh8)
    plain = jax.jit(make_solver(obj, OptimizerType.LBFGS))(jnp.zeros(D), sb)
    np.testing.assert_allclose(r8.value, plain.value, rtol=1e-9)
    np.testing.assert_allclose(r8.w, plain.w, rtol=1e-6, atol=1e-9)


def _entity_data(rng, n_entities=13, dim=3):
    sizes = rng.integers(2, 40, size=n_entities)
    rows = []
    eids = []
    for e in range(n_entities):
        xe = rng.normal(size=(sizes[e], dim))
        we = rng.normal(size=dim)
        ye = (rng.random(sizes[e]) < 1.0 / (1.0 + np.exp(-xe @ we))).astype(float)
        rows.append((xe, ye))
        eids.extend([e * 7 + 100] * sizes[e])  # non-contiguous ids
    x = np.concatenate([r[0] for r in rows])
    y = np.concatenate([r[1] for r in rows])
    return np.asarray(eids), x, y


def test_bucketing_layout(rng):
    eids, x, y = _entity_data(rng)
    b = bucket_by_entity(eids, x, y, dtype=np.float64)
    # every real sample appears exactly once across buckets
    all_rows = np.concatenate([bk.rows.ravel() for bk in b.buckets])
    real = all_rows[all_rows >= 0]
    assert sorted(real.tolist()) == list(range(len(eids)))
    # capacities are powers of two and counts fit
    for bk in b.buckets:
        assert bk.capacity & (bk.capacity - 1) == 0
        assert np.all(bk.counts <= bk.capacity)
        # padding slots have weight 0
        pad = bk.rows < 0
        assert np.all(bk.weight[pad] == 0.0)


def test_random_effects_match_serial(rng, devices):
    """Bucketed vmapped solves == per-entity serial solves (reference
    RandomEffectCoordinate semantics)."""
    eids, x, y = _entity_data(rng)
    obj = GLMObjective(loss=losses.logistic_loss, reg=Regularization(l2=0.4))
    cfg = SolverConfig(max_iters=100, tolerance=1e-9)
    mesh = make_mesh(n_data=8, devices=devices)
    b = bucket_by_entity(eids, x, y, lane_multiple=8, dtype=np.float64)
    coeffs, results = fit_random_effects(obj, b, mesh=mesh, config=cfg)
    per_entity = gather_entity_coefficients(coeffs, b)

    solve = make_solver(obj, OptimizerType.LBFGS, cfg)
    dim = x.shape[1]
    for eid in np.unique(eids):
        m = eids == eid
        ref = solve(jnp.zeros(dim), dense_batch(x[m], y[m]))
        np.testing.assert_allclose(per_entity[int(eid)], ref.w, rtol=1e-5, atol=1e-7)


def test_reservoir_cap_deterministic_and_rescaled(rng):
    eids = np.zeros(100, np.int64)
    x = rng.normal(size=(100, 2))
    y = (rng.random(100) > 0.5).astype(float)
    b1 = bucket_by_entity(eids, x, y, active_cap=16, seed=3)
    b2 = bucket_by_entity(eids, x, y, active_cap=16, seed=3)
    np.testing.assert_array_equal(b1.buckets[0].rows, b2.buckets[0].rows)
    bk = b1.buckets[0]
    assert int(bk.counts[0]) == 16
    # weight rescale count/cap = 100/16 (reference RandomEffectDataset.scala:408-417)
    np.testing.assert_allclose(bk.weight[0, :16], 100.0 / 16.0)
    # different seed -> different sample (overwhelmingly likely)
    b3 = bucket_by_entity(eids, x, y, active_cap=16, seed=4)
    assert not np.array_equal(b1.buckets[0].rows, b3.buckets[0].rows)


def test_min_active_samples_filter(rng):
    eids = np.asarray([1, 1, 1, 2, 3, 3], np.int64)
    x = rng.normal(size=(6, 2))
    y = np.ones(6)
    b = bucket_by_entity(eids, x, y, min_active_samples=2)
    assert set(b.lane_of) == {1, 3}
    assert b.num_entities == 2


def test_scoring_roundtrip(rng):
    eids, x, y = _entity_data(rng)
    obj = GLMObjective(loss=losses.logistic_loss, reg=Regularization(l2=0.4))
    b = bucket_by_entity(eids, x, y, dtype=np.float64)
    coeffs, _ = fit_random_effects(obj, b, config=SolverConfig(max_iters=50))
    # bucket-layout scoring == gather-based scoring == manual dot
    s_active = np.asarray(score_random_effects(coeffs, b))
    w_stack, slot_of = stacked_coefficients(coeffs, b)
    slots = np.asarray([slot_of.get(int(e), -1) for e in eids], np.int32)
    s_gather = np.asarray(score_samples(w_stack, jnp.asarray(slots), jnp.asarray(x)))
    np.testing.assert_allclose(s_active, s_gather, rtol=1e-9, atol=1e-12)
    per_entity = gather_entity_coefficients(coeffs, b)
    manual = np.asarray([x[i] @ per_entity[int(eids[i])] for i in range(len(eids))])
    np.testing.assert_allclose(s_gather, manual, rtol=1e-9, atol=1e-12)


def test_score_samples_t_matches_row_layout(rng):
    """[d, n] samples-on-lanes scoring (score_samples_t — the narrow-shard
    HBM-padding fix, 32x at d=4 on TPU tiling) agrees with the [n, d]
    gather layout, including -1 slots and bf16 storage against f32
    coefficients."""
    from photon_ml_tpu.parallel.bucketing import score_samples_t

    n, ne, d = 257, 19, 4
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=(ne, d)).astype(np.float32)
    slots = jnp.asarray(rng.integers(-1, ne, size=n).astype(np.int32))
    a = np.asarray(score_samples(jnp.asarray(w), slots, jnp.asarray(x)))
    b = np.asarray(score_samples_t(jnp.asarray(w), slots, jnp.asarray(x.T)))
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    assert (np.asarray(slots) < 0).any() and (b[np.asarray(slots) < 0] == 0).all()
    xb = jnp.asarray(x).astype(jnp.bfloat16)
    a16 = np.asarray(score_samples(jnp.asarray(w), slots, xb))
    b16 = np.asarray(score_samples_t(jnp.asarray(w), slots, xb.T))
    np.testing.assert_allclose(a16, b16, rtol=1e-3, atol=1e-3)


def test_transposed_scoring_gate_is_padded_bytes():
    """The [d, n] layout gate keys on the padded-HBM footprint
    (n x 128 lanes x itemsize), not width alone: glmix2-shaped shards
    (524k x 16 f32, 268 MB padded) measured 1.56x FASTER row-major on the
    v5e while glmix_chip's (8.39M x 4 bf16, 2.1 GB padded) OOMs without
    the transpose (builders' v5e runs of 2026-08, before the ledger)."""
    from photon_ml_tpu.parallel.bucketing import (
        NARROW_SCORE_PAD_BYTES_MIN, use_transposed_scoring)

    assert not use_transposed_scoring(524_288, 16, 4)   # glmix2: row-major
    assert use_transposed_scoring(8_388_608, 4, 2)      # glmix_chip: [d, n]
    assert not use_transposed_scoring(8_388_608, 64, 2)  # wide: never
    n_edge = NARROW_SCORE_PAD_BYTES_MIN // (128 * 4)
    assert use_transposed_scoring(n_edge, 4, 4)
    assert not use_transposed_scoring(n_edge - 1, 4, 4)


def _capped_glmix(rng, shuffled):
    """A small two-coordinate GLMix whose per-user effect has an active cap
    (half of each user's rows are passive: scored, not trained on)."""
    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.game import (FixedEffectConfig, GameData,
                                    RandomEffectConfig)
    from photon_ml_tpu.opt.types import SolverConfig

    n_users, per_user, dg, du = 12, 32, 6, 3
    n = n_users * per_user
    uids = np.repeat(np.arange(n_users) * 3 + 1, per_user)
    if shuffled:
        uids = rng.permutation(uids)
    data = GameData(
        y=(rng.random(n) < 0.5).astype(float),
        features={"g": rng.normal(size=(n, dg)), "u": rng.normal(size=(n, du))},
        id_tags={"userId": uids})
    solver = SolverConfig(max_iters=40, tolerance=1e-9)
    cfgs = {
        "fixed": FixedEffectConfig(feature_shard="g", solver=solver,
                                   reg=Regularization(l2=1.0)),
        "user": RandomEffectConfig(random_effect_type="userId",
                                   feature_shard="u", solver=solver,
                                   reg=Regularization(l2=1.0), active_cap=16),
    }
    return data, cfgs


@pytest.mark.parametrize("shuffled", [False, True],
                         ids=["entity_major_rows", "rows_anywhere"])
def test_entity_major_rescore_matches_host_descent(rng, monkeypatch,
                                                   shuffled):
    """The entity-major full-sample layout (ISSUE 24) is a pure layout
    change: with the padded-footprint line lowered so that a small shard
    takes it, the fused sweep equals the host loop (which scores through
    ``score(model)``), equals the row-major build of the same data, scores
    the capped-out rows too, and serves a FOREIGN slot map and the
    carry-through from per-chunk slots."""
    import dataclasses

    from photon_ml_tpu.game.coordinate import build_coordinate
    from photon_ml_tpu.game.descent import CoordinateDescent
    from photon_ml_tpu.game.fused import FusedSweep
    from photon_ml_tpu.parallel import bucketing
    from photon_ml_tpu.types import TaskType

    data, cfgs = _capped_glmix(rng, shuffled)
    task = TaskType.LOGISTIC_REGRESSION

    def build():
        return {cid: build_coordinate(cid, data, c, task)
                for cid, c in cfgs.items()}

    row_major = build()
    assert row_major["user"]._em is None
    monkeypatch.setattr(bucketing, "NARROW_SCORE_PAD_BYTES_MIN", 1)
    coords = build()
    user = coords["user"]
    assert user._em is not None and user._em.chunk == 32
    assert (user._em.pos is None) == (not shuffled)
    # in the place of the sample-order design and slots, not beside them
    assert sorted(user._full) == ["lane_slot", "way_back", "x_em"]
    assert sorted(row_major["user"]._full) == ["slots", "x_full"]

    fused, fused_scores = FusedSweep(coords, num_iterations=2).run()
    host, _, _ = CoordinateDescent(coords, num_iterations=2).run()
    plain, plain_scores = FusedSweep(row_major, num_iterations=2).run()
    for other in (host, plain):
        assert other["user"].slot_of == fused["user"].slot_of
        np.testing.assert_allclose(fused["user"].w_stack,
                                   other["user"].w_stack,
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(fused["fixed"].coefficients.means,
                                   other["fixed"].coefficients.means,
                                   rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(fused_scores["user"], plain_scores["user"],
                               rtol=2e-3, atol=2e-3)

    # every row is scored, the passive half of each user's included
    model = fused["user"]
    x, uids = data.features["u"], data.id_tags["userId"]
    want = np.einsum("nd,nd->n", x, model.w_stack[
        [model.slot_of[int(u)] for u in uids]])
    np.testing.assert_allclose(fused_scores["user"], want, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(user.score(model), want, rtol=1e-5, atol=1e-5)

    # a model trained elsewhere: other slot order, one user of ours missing
    # (scores exactly 0), one we never saw
    ids = sorted(model.slot_of)
    gone = ids[2]
    foreign_of = {e: i for i, e in enumerate(reversed(ids + [10_000]))
                  if e != gone}
    foreign_w = rng.normal(size=(len(ids) + 1, x.shape[1]))
    foreign = dataclasses.replace(model, w_stack=foreign_w,
                                  slot_of=foreign_of)
    want = np.where(uids == gone, 0.0, np.einsum(
        "nd,nd->n", x, foreign_w[[foreign_of.get(int(u), 0) for u in uids]]))
    got = user.score(foreign)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert (got[uids == gone] == 0).all()
    np.testing.assert_allclose(got, row_major["user"].score(foreign),
                               rtol=1e-5, atol=1e-5)


def test_entity_major_carry_through_scores(rng, monkeypatch):
    """A prior model's entity that this run does not retrain (under the
    lower bound, covered by the prior model) keeps scoring its rows: the
    entity-major layout serves the carry-through from per-chunk slots, and
    only the carried entity's rows score."""
    import dataclasses

    from photon_ml_tpu.game.coordinate import build_coordinate
    from photon_ml_tpu.models.game import RandomEffectModel
    from photon_ml_tpu.parallel import bucketing
    from photon_ml_tpu.types import TaskType

    data, cfgs = _capped_glmix(rng, shuffled=True)
    uids = data.id_tags["userId"].copy()
    rare = int(uids[0])
    keep = np.flatnonzero(uids == rare)[:4]  # 4 rows stay the rare user's
    uids[np.setdiff1d(np.flatnonzero(uids == rare), keep)] = 4  # another's
    data = dataclasses.replace(data, id_tags={"userId": uids})
    cfg = dataclasses.replace(cfgs["user"], min_active_samples=8)
    x = data.features["u"]
    prior = RandomEffectModel(
        w_stack=rng.normal(size=(2, x.shape[1])), slot_of={rare: 1, 777: 0},
        random_effect_type="userId", feature_shard="u",
        task=TaskType.LOGISTIC_REGRESSION)
    want = np.where(uids == rare, x @ prior.w_stack[1], 0.0)

    def carried():
        coord = build_coordinate("user", data, cfg,
                                 TaskType.LOGISTIC_REGRESSION,
                                 existing_model_keys=frozenset(prior.slot_of))
        assert rare not in coord._slot_of
        return coord, coord.carry_through_scores(prior)

    coord, plain = carried()
    assert coord._em is None
    monkeypatch.setattr(bucketing, "NARROW_SCORE_PAD_BYTES_MIN", 1)
    coord, got = carried()
    assert coord._em is not None
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got, plain, rtol=1e-5, atol=1e-6)
    assert (got[uids != rare] == 0).all()


@pytest.mark.parametrize("line, per_user, shuffled, layout", [
    (1, 32, False, dict(layout="entity_major", chunk=32, lanes=12, fill=1.0,
                        coef_indices=12, back="identity")),
    # 30 rows a user in chunks of 32: grouped rows, two slots of padding
    # behind each user, 22 in front of the last: five stages
    (1, 30, False, dict(layout="entity_major", chunk=32, lanes=12,
                        fill=0.9375, coef_indices=12, back="unpad", stages=5,
                        slots=384)),
    (1, 32, True, dict(layout="entity_major", chunk=32, lanes=12, fill=1.0,
                       coef_indices=12, back="gather")),
    (1, 10, False, dict(layout="transposed")),  # 10 rows a user: no chunk
    (None, 32, False, dict(layout="row_major")),
], ids=["identity", "unpad", "gather", "transposed", "row_major"])
def test_rescore_layout_span_says_what_engaged(rng, monkeypatch, line,
                                               per_user, shuffled, layout):
    """``coord.rescore_layout`` (inside ``coord.bucket``): which of the
    three dense layouts the coordinate took, and the entity-major one's
    chunk, lanes, fill, the coefficient indices its scorer gathers a call
    (``coef_indices``: one row a chunk, so the lanes), and the way BACK to
    the sample order (``back``; un-padded, the compaction's stages and
    slots)."""
    from photon_ml_tpu import obs
    from photon_ml_tpu.game import GameData, RandomEffectConfig
    from photon_ml_tpu.game.coordinate import build_coordinate
    from photon_ml_tpu.obs.trace import Tracer, set_tracer
    from photon_ml_tpu.parallel import bucketing
    from photon_ml_tpu.types import TaskType

    if line is not None:
        monkeypatch.setattr(bucketing, "NARROW_SCORE_PAD_BYTES_MIN", line)
    n = 12 * per_user
    uids = np.repeat(np.arange(12), per_user)
    data = GameData(y=(rng.random(n) < 0.5).astype(float),
                    features={"u": rng.normal(size=(n, 3))},
                    id_tags={"userId": rng.permutation(uids) if shuffled
                             else uids})
    prev = set_tracer(Tracer(capacity=256, enabled=True))
    try:
        coord = build_coordinate(
            "user", data, RandomEffectConfig(random_effect_type="userId",
                                             feature_shard="u"),
            TaskType.LOGISTIC_REGRESSION)
        records = obs.get_tracer().records()
    finally:
        set_tracer(prev)
    spans = [r for r in records if r["name"] == "coord.rescore_layout"]
    assert [r["attrs"] for r in spans] == [dict(coordinate="user", **layout)]
    assert spans[0]["parent"] in {r["id"] for r in records
                                  if r["name"] == "coord.bucket"}
    assert (coord._em is not None) == (layout["layout"] == "entity_major")
    assert coord._x_full_is_t == (layout["layout"] == "transposed")
    if coord._em is not None:
        assert coord._em.back == layout["back"]
        assert isinstance(coord._full["way_back"], bucketing.Unpad) == (
            layout["back"] == "unpad")


def _parents_score_samples_em(w_stack, lane_slot, x_em):
    """The column form ``score_samples_em`` replaced: d gathers of one
    coefficient a chunk, each out of a column of the table."""
    from photon_ml_tpu.parallel.bucketing import EM_ROW

    k = lane_slot.shape[0]
    has = lane_slot >= 0
    safe = jnp.where(has, lane_slot, 0)
    w_t = w_stack.T
    chunk_of_lane = jax.lax.broadcasted_iota(
        jnp.int32, (1, EM_ROW), 1) // (EM_ROW // k)

    def along_chunks(v):
        out = v[0][:, None]
        for i in range(1, k):
            out = jnp.where(chunk_of_lane == i, v[i][:, None], out)
        return out

    acc = jnp.zeros(x_em.shape[1:],
                    jnp.promote_types(x_em.dtype, w_stack.dtype))
    for j in range(x_em.shape[0]):
        acc = acc + x_em[j] * along_chunks(w_t[j][safe])
    return jnp.where(along_chunks(has), acc, 0.0).reshape(-1)


def _gathers(jaxpr):
    """Every ``gather`` equation of a closed jaxpr, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _gathers(sub)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("chunk", [16, 64, 128])
@pytest.mark.parametrize("d", [1, 4, 16])
def test_score_samples_em_gathers_one_row_a_chunk(d, chunk, dtype):
    """``score_samples_em`` issues ONE gather of the coefficient table a
    call, a ROW of d a chunk (k x R indices), not d gathers of a column;
    the scores are bitwise the column form's."""
    from photon_ml_tpu.parallel.bucketing import EM_ROW, score_samples_em

    k, r, entities = EM_ROW // chunk, 3, 5
    rng = np.random.default_rng(d * chunk)
    w = jnp.asarray(rng.normal(size=(entities, d)), dtype)
    lane_slot = jnp.asarray(rng.integers(-1, entities, size=(k, r)),
                            jnp.int32)
    x_em = jnp.asarray(rng.normal(size=(d, r, EM_ROW)), dtype)
    closed = jax.make_jaxpr(score_samples_em)(w, lane_slot, x_em)
    (gather,) = _gathers(closed.jaxpr)
    assert gather.invars[0] is closed.jaxpr.invars[0]  # the table itself
    assert gather.outvars[0].aval.shape == (k, r, d)
    if d == 4 and chunk == 64:  # glmix_chip's shape
        got, want = jax.jit(lambda *a: (score_samples_em(*a),
                                        _parents_score_samples_em(*a)))(
            w, lane_slot, x_em)
        np.testing.assert_array_equal(np.asarray(got).view(np.uint8),
                                      np.asarray(want).view(np.uint8))


def test_scoring_unknown_entity_is_zero(rng):
    eids, x, y = _entity_data(rng, n_entities=3)
    obj = GLMObjective(loss=losses.logistic_loss)
    b = bucket_by_entity(eids, x, y, dtype=np.float64)
    coeffs, _ = fit_random_effects(obj, b, config=SolverConfig(max_iters=20))
    w_stack, slot_of = stacked_coefficients(coeffs, b)
    slots = jnp.asarray([-1, 0], jnp.int32)
    s = score_samples(w_stack, slots, jnp.asarray(np.ones((2, x.shape[1]))))
    assert float(s[0]) == 0.0


def test_fused_sweep_on_mesh_matches_single_device(devices, rng):
    """FusedSweep under an 8-device mesh == FusedSweep single-device
    (chip-count invariance for the fully-jitted descent program)."""
    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.game import FixedEffectConfig, GameData, RandomEffectConfig
    from photon_ml_tpu.game.coordinate import build_coordinate
    from photon_ml_tpu.game.fused import FusedSweep
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.types import TaskType

    n_users, per_user, dg, du = 16, 32, 6, 3
    n = n_users * per_user
    xg = rng.normal(size=(n, dg))
    xu = rng.normal(size=(n, du))
    uids = np.repeat(np.arange(n_users), per_user)
    y = (rng.random(n) < 0.5).astype(float)
    data = GameData(y=y, features={"g": xg, "u": xu}, id_tags={"userId": uids})
    solver = SolverConfig(max_iters=40, tolerance=1e-9)
    task = TaskType.LOGISTIC_REGRESSION
    cfgs = {
        "fixed": FixedEffectConfig(feature_shard="g", solver=solver,
                                   reg=Regularization(l2=1.0)),
        "user": RandomEffectConfig(random_effect_type="userId",
                                   feature_shard="u", solver=solver,
                                   reg=Regularization(l2=1.0)),
    }

    models = {}
    for label, mesh in (("one", make_mesh(n_data=1, devices=devices[:1])),
                        ("eight", make_mesh(n_data=8, devices=devices))):
        coords = {cid: build_coordinate(cid, data, c, task, mesh=mesh)
                  for cid, c in cfgs.items()}
        m, _ = FusedSweep(coords, num_iterations=2).run()
        models[label] = m

    # psum/reduction order differs across device counts: f32 noise only
    np.testing.assert_allclose(models["one"]["fixed"].coefficients.means,
                               models["eight"]["fixed"].coefficients.means,
                               rtol=2e-3, atol=2e-4)
    assert models["one"]["user"].slot_of == models["eight"]["user"].slot_of
    np.testing.assert_allclose(models["one"]["user"].w_stack,
                               models["eight"]["user"].w_stack,
                               rtol=2e-3, atol=2e-4)


def test_variance_on_mesh_matches_single_device(devices, rng):
    """ShardMapObjective hessian_diag/hessian: variances computed under an
    8-device mesh equal the single-device ones (the L2 term must be added
    once, not once per shard)."""
    import dataclasses

    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.game import FixedEffectConfig, GameData
    from photon_ml_tpu.game.coordinate import build_coordinate
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.types import TaskType, VarianceComputationType

    n, d = 512, 6
    x = rng.normal(size=(n, d))
    y = (rng.random(n) < 0.5).astype(float)
    data = GameData(y=y, features={"g": x})
    for kind in (VarianceComputationType.SIMPLE, VarianceComputationType.FULL):
        cfg = FixedEffectConfig(feature_shard="g",
                                solver=SolverConfig(max_iters=40),
                                reg=Regularization(l2=2.0), variance=kind)
        got = {}
        for label, mesh in (("one", make_mesh(n_data=1, devices=devices[:1])),
                            ("eight", make_mesh(n_data=8, devices=devices))):
            coord = build_coordinate("fixed", data, cfg, TaskType.LOGISTIC_REGRESSION,
                                     mesh=mesh)
            model, _ = coord.update(np.zeros(n))
            assert model.coefficients.variances is not None
            got[label] = model.coefficients.variances
        np.testing.assert_allclose(got["one"], got["eight"], rtol=1e-3, atol=1e-6)


def test_multihost_helpers_single_process(devices):
    """Multi-host helpers in the 1-process degenerate case: row ranges
    tile the dataset, the global mesh covers all devices, and
    host-local -> global assembly yields correctly sharded arrays whose
    psum matches the local computation."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from photon_ml_tpu.parallel.multihost import (global_batch_from_local,
                                                  global_mesh,
                                                  initialize,
                                                  pad_local_rows,
                                                  padded_per_host_rows,
                                                  process_row_range)

    initialize(num_processes=1)  # explicit single-process no-op
    initialize()  # auto-detect falls back to single-process, never raises

    # row split math for a hypothetical 3-host job (ceil split: 35/35/33)
    n = 103
    ranges = [process_row_range(n, pid, 3) for pid in range(3)]
    assert ranges == [(0, 35), (35, 70), (70, 103)]
    assert all(b - a <= 35 for a, b in ranges)
    with pytest.raises(ValueError):
        process_row_range(n, 5, 3)

    mesh = global_mesh(n_feature=2)
    assert mesh.shape["data"] * mesh.shape["feature"] == len(jax.devices())
    with pytest.raises(ValueError):
        global_mesh(n_entity=3)  # 8 not divisible

    # this process owns ALL rows in a 1-process job
    start, stop = process_row_range(n)
    assert (start, stop) == (0, n)

    # balanced padded rows: 103 rows over the 4-device data axis -> 104
    rows = padded_per_host_rows(n, mesh)
    assert rows == 104 and rows % mesh.shape["data"] == 0

    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 6))
    y = rng.normal(size=n)
    w = np.ones(n)
    block = pad_local_rows({"x": x, "y": y, "weight": w}, rows)
    assert block["x"].shape == (rows, 6)
    assert block["weight"][n:].sum() == 0  # padding rows are weight-0
    with pytest.raises(ValueError):
        pad_local_rows({"x": x}, n - 1)

    g = global_batch_from_local(block, mesh,
                                specs={"x": P("data", "feature")})
    assert g["x"].shape == (rows, 6) and g["y"].shape == (rows,)
    assert g["x"].sharding.spec == P("data", "feature")
    assert g["y"].sharding.spec == P("data")
    x, y = block["x"], block["y"]

    # a jitted global reduction over the sharded arrays matches numpy
    total = jax.jit(lambda xx, yy: (xx.sum(), (xx.T @ yy)))(g["x"], g["y"])
    np.testing.assert_allclose(np.asarray(total[0]), x.sum(), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(total[1]), x.T @ y, rtol=1e-6)


@pytest.mark.parametrize("variant", ["bf16_storage", "projected"])
def test_fused_sweep_mesh_invariance_new_features(devices, rng, variant):
    """Chip-count invariance extends to the newer fused features: bf16
    design-matrix storage (mixed precision) and projected random effects —
    1-device vs 8-device meshes must agree up to reduction-order noise."""
    import dataclasses

    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.game import FixedEffectConfig, GameData, RandomEffectConfig
    from photon_ml_tpu.game.coordinate import build_coordinate
    from photon_ml_tpu.game.fused import FusedSweep
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.types import ProjectorType, TaskType

    n_users, per_user, dg, du = 16, 32, 6, 3
    n = n_users * per_user
    xg = rng.normal(size=(n, dg))
    xu = rng.normal(size=(n, du))
    uids = np.repeat(np.arange(n_users), per_user)
    y = (rng.random(n) < 0.5).astype(float)
    data = GameData(y=y, features={"g": xg, "u": xu}, id_tags={"userId": uids})
    solver = SolverConfig(max_iters=30, tolerance=1e-8)
    task = TaskType.LOGISTIC_REGRESSION
    fixed = FixedEffectConfig(feature_shard="g", solver=solver,
                              reg=Regularization(l2=1.0))
    user = RandomEffectConfig(random_effect_type="userId", feature_shard="u",
                              solver=solver, reg=Regularization(l2=1.0))
    if variant == "bf16_storage":
        fixed = dataclasses.replace(fixed, storage_dtype="bfloat16")
        user = dataclasses.replace(user, storage_dtype="bfloat16")
        tol = dict(rtol=3e-2, atol=3e-2)  # bf16 input resolution
    else:
        user = dataclasses.replace(user, projector=ProjectorType.INDEX_MAP)
        tol = dict(rtol=2e-3, atol=2e-4)
    cfgs = {"fixed": fixed, "user": user}

    models = {}
    for label, mesh in (("one", make_mesh(n_data=1, devices=devices[:1])),
                        ("eight", make_mesh(n_data=8, devices=devices))):
        coords = {cid: build_coordinate(cid, data, c, task, mesh=mesh)
                  for cid, c in cfgs.items()}
        m, _ = FusedSweep(coords, num_iterations=2).run()
        models[label] = m

    np.testing.assert_allclose(models["one"]["fixed"].coefficients.means,
                               models["eight"]["fixed"].coefficients.means,
                               **tol)
    assert models["one"]["user"].slot_of == models["eight"]["user"].slot_of
    np.testing.assert_allclose(models["one"]["user"].w_stack,
                               models["eight"]["user"].w_stack, **tol)


def test_multihost_two_processes(tmp_path):
    """TRUE multi-process jax.distributed: 2 processes x 2 CPU devices form a
    4-device global mesh; each host reads only its row range, assembles the
    global batch, and runs the SAME shard_map fixed-effect solve.  Both
    processes must publish the identical replicated optimum, matching a
    single-process solve of the full data (the reference's Spark-cluster
    execution model, SURVEY §5, with no driver process)."""
    import json
    import os

    port = _free_port()
    worker = tmp_path / "worker.py"
    worker.write_text(f"""
import sys
sys.path.insert(0, {repr(os.getcwd())})
import os, json
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")

pid = int(sys.argv[1]); nproc = int(sys.argv[2]); out = sys.argv[3]
from photon_ml_tpu.parallel import multihost as mh
mh.initialize(coordinator_address="127.0.0.1:{port}",
              num_processes=nproc, process_id=pid,
              expected_processes=nproc)
assert jax.process_count() == nproc
mesh = mh.global_mesh(n_feature=2)
# ICI/DCN contract: entity/feature axes never cross a process boundary —
# every (entity, feature) cell of the mesh lives inside ONE process
for row in mesh.devices.reshape(mesh.devices.shape[0], -1):
    assert len({{d.process_index for d in row}}) == 1, "feature axis crossed DCN"
# and the data axis DOES span processes (it is the only DCN axis)
assert len({{d.process_index for d in mesh.devices.reshape(-1)}}) == nproc

n, d = 64, 3
rng = np.random.default_rng(0)           # same data on every host
x = rng.normal(size=(n, d)).astype(np.float32)
w_true = np.asarray([0.5, -1.0, 0.25], np.float32)
y = (rng.random(n) < 1 / (1 + np.exp(-x @ w_true))).astype(np.float32)

start, stop = mh.process_row_range(n)
rows = mh.padded_per_host_rows(n, mesh)
block = mh.pad_local_rows(
    dict(x=x[start:stop], y=y[start:stop],
         offset=np.zeros(stop - start, np.float32),
         weight=np.ones(stop - start, np.float32)), rows)
g = mh.global_batch_from_local(block, mesh)

from photon_ml_tpu.core.batch import DenseBatch
from photon_ml_tpu.core.losses import logistic_loss
from photon_ml_tpu.core.objective import GLMObjective
from photon_ml_tpu.core.regularization import Regularization
from photon_ml_tpu.opt.solve import make_solver
from photon_ml_tpu.opt.types import SolverConfig
from photon_ml_tpu.parallel.fixed import ShardMapObjective
from photon_ml_tpu.parallel.mesh import replicate

batch = DenseBatch(x=g["x"], y=g["y"], offset=g["offset"], weight=g["weight"])
obj = ShardMapObjective(
    GLMObjective(loss=logistic_loss, reg=Regularization(l2=0.1)), mesh)
solve = jax.jit(make_solver(obj, config=SolverConfig(max_iters=50)),
                out_shardings=replicate(mesh))
res = solve(jax.numpy.zeros(d, jax.numpy.float32), batch)
w = np.asarray(res.w)

# part 2 (VERDICT r3 #8): a FEATURE-SHARDED sparse solve on the same
# global mesh — w blocked over the within-process feature axis (ICI
# collectives), data striding processes (the one DCN all-reduce).  The
# ICI/DCN tiering is thereby EXECUTED cross-process, not just asserted
# on the mesh layout above.
d2, k2 = 9, 3   # d2 odd: the feature axis pads to 10 and trims on exit
rng3 = np.random.default_rng(1)
idx2 = rng3.integers(0, d2, size=(n, k2)).astype(np.int32)
vals2 = rng3.normal(size=(n, k2)).astype(np.float32)
w2_true = rng3.normal(size=d2).astype(np.float32)
z2 = np.einsum("nk,nk->n", vals2, w2_true[idx2])
y2 = (rng3.random(n) < 1 / (1 + np.exp(-z2))).astype(np.float32)
block2 = mh.pad_local_rows(
    dict(indices=idx2[start:stop], values=vals2[start:stop],
         y=y2[start:stop], offset=np.zeros(stop - start, np.float32),
         weight=np.ones(stop - start, np.float32)), rows)
g2 = mh.global_batch_from_local(block2, mesh)
from photon_ml_tpu.core.batch import SparseBatch
from photon_ml_tpu.parallel.fixed import fit_fixed_effect

sb = SparseBatch(indices=g2["indices"], values=g2["values"], y=g2["y"],
                 offset=g2["offset"], weight=g2["weight"], dim=d2)
res2 = fit_fixed_effect(
    GLMObjective(loss=logistic_loss, reg=Regularization(l2=0.1)), sb,
    np.zeros(d2, np.float32), mesh, config=SolverConfig(max_iters=50),
    feature_sharded=True, batch_presharded=True)
w2 = np.asarray(res2.w)
assert w2.shape == (d2,)

with open(os.path.join(out, f"w{{pid}}.json"), "w") as f:
    json.dump({{"w": [float(v) for v in w],
               "w2": [float(v) for v in w2]}}, f)
""")

    _launch_workers(worker, 2, tmp_path, timeout=240)

    out0 = json.load(open(tmp_path / "w0.json"))
    out1 = json.load(open(tmp_path / "w1.json"))
    np.testing.assert_allclose(out0["w"], out1["w"], rtol=0, atol=0)
    np.testing.assert_allclose(out0["w2"], out1["w2"], rtol=0, atol=0)
    w0, w2 = out0["w"], out0["w2"]

    # reference: the same solves single-process on the full data
    from photon_ml_tpu.core.batch import dense_batch, sparse_batch
    from photon_ml_tpu.core.losses import logistic_loss
    from photon_ml_tpu.core.objective import GLMObjective
    from photon_ml_tpu.opt.solve import make_solver
    from photon_ml_tpu.opt.types import SolverConfig

    n, d = 64, 3
    rng2 = np.random.default_rng(0)
    x = rng2.normal(size=(n, d)).astype(np.float32)
    w_true = np.asarray([0.5, -1.0, 0.25], np.float32)
    y = (rng2.random(n) < 1 / (1 + np.exp(-x @ w_true))).astype(np.float32)
    obj = GLMObjective(loss=losses.logistic_loss,
                       reg=Regularization(l2=0.1))
    res = jax.jit(make_solver(obj, config=SolverConfig(max_iters=50)))(
        jnp.zeros(d), dense_batch(x.astype(np.float64), y.astype(np.float64)))
    np.testing.assert_allclose(w0, np.asarray(res.w), rtol=2e-3, atol=2e-4)

    # the cross-process feature-sharded sparse solve matches single-process
    d2, k2 = 9, 3
    rng3 = np.random.default_rng(1)
    idx2 = rng3.integers(0, d2, size=(n, k2)).astype(np.int32)
    vals2 = rng3.normal(size=(n, k2)).astype(np.float32)
    w2_true = rng3.normal(size=d2).astype(np.float32)
    z2 = np.einsum("nk,nk->n", vals2, w2_true[idx2])
    y2 = (rng3.random(n) < 1 / (1 + np.exp(-z2))).astype(np.float32)
    res2 = jax.jit(make_solver(obj, config=SolverConfig(max_iters=50)))(
        jnp.zeros(d2), sparse_batch(idx2, vals2, y2, dim=d2))
    np.testing.assert_allclose(w2, np.asarray(res2.w), rtol=2e-3, atol=2e-3)


def test_global_feature_stats_on_sharded_rows(devices, rng):
    """The multihost normalization recipe: each host pads its local rows
    (weight 0) and assembles a globally data-sharded array; a jitted
    compute_feature_stats over it equals the stats of the raw unpadded rows
    on every host — GSPMD inserts the cross-host moment reductions (the
    'sharded variant psums the moments' contract in core/normalization)."""
    from photon_ml_tpu.core.normalization import compute_feature_stats
    from photon_ml_tpu.parallel.mesh import make_mesh
    from photon_ml_tpu.parallel.multihost import (global_batch_from_local,
                                                  pad_local_rows)

    n, d = 100, 6  # deliberately NOT divisible by the mesh
    x = rng.normal(size=(n, d)).astype(np.float64) * np.linspace(0.5, 3, d)
    w = rng.random(n).astype(np.float64) + 0.5
    mesh = make_mesh(n_data=8, devices=devices[:8])
    rows = -(-n // 8) * 8
    local = pad_local_rows({"x": x, "weight": w}, rows)
    g = global_batch_from_local(local, mesh)

    stats_sharded = jax.jit(compute_feature_stats)(g["x"], g["weight"])
    stats_host = compute_feature_stats(jnp.asarray(x), jnp.asarray(w))
    for f in ("mean", "variance", "abs_max"):
        np.testing.assert_allclose(np.asarray(getattr(stats_sharded, f)),
                                   np.asarray(getattr(stats_host, f)),
                                   rtol=1e-10, err_msg=f)


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch_workers(worker, nproc, tmp_path, local_devices=2, timeout=420):
    """Run ``worker`` as nproc jax.distributed processes (argv: pid nproc
    tmp_path) and assert they all exit 0 — the ONE definition of the
    multi-process launch contract (env, device count, failure reporting).
    Skips (not fails) on backends that cannot execute cross-process
    computations at all (conftest capability probe)."""
    import os
    import subprocess
    import sys

    from conftest import require_multiprocess_backend

    require_multiprocess_backend()

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={local_devices}")
    env.pop("PYTEST_CURRENT_TEST", None)
    procs = [subprocess.Popen(
        [sys.executable, str(worker), str(pid), str(nproc), str(tmp_path)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for pid in range(nproc)]
    outs = [p.communicate(timeout=timeout) for p in procs]
    for p, (_, se) in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{se[-3000:]}"


# --- multihost GLMix (fixed + random effects across processes) -------------

_GLMIX_DATAGEN = """
rng = np.random.default_rng(42)
n, n_users, dg, du = {n}, 16, 4, 2
uids = rng.integers(0, n_users, size=n)
xg = rng.normal(size=(n, dg)).astype(np.float32)
xu = rng.normal(size=(n, du)).astype(np.float32)
uw = (rng.normal(size=(n_users, du)) * 1.2).astype(np.float32)
gw = rng.normal(size=dg).astype(np.float32)
z = xg @ gw + np.einsum("nd,nd->n", xu, uw[uids])
y = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(np.float32)
"""

_GLMIX_WORKER = """
import sys
sys.path.insert(0, {repo!r})
import os, json
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")

pid = int(sys.argv[1]); nproc = int(sys.argv[2]); out = sys.argv[3]
from photon_ml_tpu.parallel import multihost as mh
mh.initialize(coordinator_address="127.0.0.1:{port}", num_processes=nproc,
              process_id=pid, expected_processes=nproc)
mesh = mh.global_mesh(n_entity={n_entity})
# entity/feature cells never cross a process (ICI); data strides DCN
for row in mesh.devices.reshape(mesh.devices.shape[0], -1):
    assert len({{d.process_index for d in row}}) == 1, "entity axis crossed DCN"
{datagen}
from photon_ml_tpu.core.batch import DenseBatch
from photon_ml_tpu.core.losses import logistic_loss
from photon_ml_tpu.core.objective import GLMObjective
from photon_ml_tpu.core.regularization import Regularization
from photon_ml_tpu.opt.types import SolverConfig
from photon_ml_tpu.parallel.bucketing import bucket_by_entity

# fixed side: row-range read (last host short; padding rows weight 0)
start, stop = mh.process_row_range(n)
rows_per = mh.padded_per_host_rows(n, mesh)
blk = mh.pad_local_rows(dict(x=xg[start:stop], y=y[start:stop],
                             offset=np.zeros(stop - start, np.float32),
                             weight=np.ones(stop - start, np.float32)),
                        rows_per)
g = mh.global_batch_from_local(blk, mesh)
fixed_batch = DenseBatch(x=g["x"], y=g["y"], offset=g["offset"],
                         weight=g["weight"])

# random-effect side: entity-hash ownership, host-local bucketing with
# GLOBAL row ids, global lane assembly
rid = mh.local_entity_rows(uids)
assert len(rid) > 0, "hash split starved a host of entities"
n_glob = rows_per * nproc
w1 = np.ones(len(rid), np.float32)
local = bucket_by_entity(uids[rid], xu[rid], y[rid], weight=w1,
                         active_cap=16, seed=5, row_ids=rid,
                         num_samples=n_glob)
gb = mh.global_entity_buckets(local, mesh)
ls = bucket_by_entity(uids[rid], xu[rid], y[rid], weight=w1, seed=5,
                      row_ids=rid, num_samples=n_glob)
scoring = mh.build_re_scoring(gb, ls, mesh)

cfg = SolverConfig(max_iters=60, tolerance=1e-9)
wf, rec, _ = mh.multihost_glmix_sweep(
    mesh, fixed_batch, gb,
    GLMObjective(loss=logistic_loss, reg=Regularization(l2=0.1)),
    GLMObjective(loss=logistic_loss, reg=Regularization(l2=1.0)),
    num_iterations=2, config=cfg, re_scoring=scoring, num_samples=n)
exported = mh.export_local_random_effects(rec, gb, mesh)
with open(os.path.join(out, f"glmix{{pid}}.json"), "w") as f:
    json.dump({{"wf": [float(v) for v in np.asarray(wf)],
               "re": {{str(k): [float(v) for v in w]
                      for k, w in exported.items()}},
               "n_owned_rows": int(len(rid)),
               "row_space_misaligned": bool(rows_per != -(-n // nproc))}}, f)
"""


def _glmix_reference(n=503, active_cap=16):
    """Single-process framework solve of the same problem (same kept rows:
    reservoir keys mix global row ids, so topology cannot change them)."""
    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.game import FixedEffectConfig, GameData, RandomEffectConfig
    from photon_ml_tpu.game.coordinate import build_coordinate
    from photon_ml_tpu.game.descent import CoordinateDescent
    from photon_ml_tpu.types import TaskType

    ns = {"np": np}
    exec(_GLMIX_DATAGEN.format(n=n), ns)
    data = GameData(y=ns["y"], features={"g": ns["xg"], "u": ns["xu"]},
                    id_tags={"userId": ns["uids"]})
    cfg = SolverConfig(max_iters=60, tolerance=1e-9)
    coords = {
        "fixed": build_coordinate(
            "fixed", data,
            FixedEffectConfig(feature_shard="g", solver=cfg,
                              reg=Regularization(l2=0.1)),
            TaskType.LOGISTIC_REGRESSION, seed=5),
        "user": build_coordinate(
            "user", data,
            RandomEffectConfig(random_effect_type="userId", feature_shard="u",
                               solver=cfg, reg=Regularization(l2=1.0),
                               active_cap=active_cap),
            TaskType.LOGISTIC_REGRESSION, seed=5),
    }
    model, _, _ = CoordinateDescent(coords, order=["fixed", "user"],
                                    num_iterations=2).run(seed=5)
    return model


def _run_glmix_workers(tmp_path, nproc, local_devices, n_entity, n=503):
    import json
    import os

    worker = tmp_path / "glmix_worker.py"
    worker.write_text(_GLMIX_WORKER.format(
        repo=os.getcwd(), port=_free_port(), n_entity=n_entity,
        datagen=_GLMIX_DATAGEN.format(n=n)))
    _launch_workers(worker, nproc, tmp_path, local_devices=local_devices)
    return [json.load(open(tmp_path / f"glmix{pid}.json"))
            for pid in range(nproc)]


def _check_glmix_outputs(outs, nproc, n=503):
    """Replicated fixed coefficients agree bitwise across hosts; the union
    of per-host published random effects matches the single-process
    framework solve to solver tolerance."""
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0]["wf"], o["wf"], rtol=0, atol=0)
    # every entity published by exactly one host
    owners = [set(o["re"]) for o in outs]
    for i in range(nproc):
        for j in range(i + 1, nproc):
            assert not owners[i] & owners[j], "entity published twice"
    merged = {int(k): np.asarray(v) for o in outs for k, v in o["re"].items()}

    model = _glmix_reference(n=n)
    wf_ref = np.asarray(model["fixed"].coefficients.means)
    np.testing.assert_allclose(outs[0]["wf"], wf_ref, atol=5e-4, rtol=1e-3)
    re_ref = model["user"]
    assert set(merged) == set(re_ref.slot_of)
    for eid, w in merged.items():
        np.testing.assert_allclose(
            w, np.asarray(re_ref.w_stack[re_ref.slot_of[eid]]),
            atol=5e-4, rtol=1e-3)


def test_multihost_glmix_two_processes(tmp_path):
    """TRUE 2-process GLMix: entity-sharded random effects + row-sharded
    fixed effect, residual descent with global score vectors; published
    model matches the single-process CoordinateDescent solve.  n=503 leaves
    the last host a SHORT row range — the weight-0 padding contract is
    exercised, not just asserted."""
    outs = _run_glmix_workers(tmp_path, nproc=2, local_devices=2, n_entity=1)
    assert sum(o["n_owned_rows"] for o in outs) == 503
    _check_glmix_outputs(outs, 2)


def test_multihost_glmix_four_processes(tmp_path):
    """4-process GLMix sweep on a (data=4, entity=2) global mesh: the data
    axis strides DCN (4 processes), the entity axis stays on ICI (within
    each process's 2 devices) — the 2x2 interconnect tiering of SURVEY §5
    executed, with the same single-process parity gate."""
    outs = _run_glmix_workers(tmp_path, nproc=4, local_devices=2, n_entity=2)
    assert sum(o["n_owned_rows"] for o in outs) == 503
    _check_glmix_outputs(outs, 4)


def test_multihost_glmix_padded_row_space(tmp_path):
    """Original-vs-padded row-space translation: n=57 over 2 hosts gives
    per-host stride 29 but a padded stride of 30 (2 data devices per host),
    so every bucket-row gather/scatter must translate ids — the silent
    misalignment a size-aligned test can never catch."""
    outs = _run_glmix_workers(tmp_path, nproc=2, local_devices=2, n_entity=1,
                              n=57)
    assert outs[0]["row_space_misaligned"], (
        "test sizes drifted back into alignment; pick n so that "
        "ceil(n/nproc) is not a multiple of the per-host data-device count")
    assert sum(o["n_owned_rows"] for o in outs) == 57
    _check_glmix_outputs(outs, 2, n=57)


def test_multihost_glmix_sparse_compact_two_processes(tmp_path):
    """Wide-vocabulary multihost random effects: sparse (compact,
    observed-column) buckets built per host, compact widths aligned by the
    metadata all-gather, solved in the global sweep, back-projected
    host-locally on export — the multihost twin of the single-process
    sparse coordinate.  Parity vs the single-process framework solve."""
    import json
    import os

    port = _free_port()
    worker = tmp_path / "glmix_sparse_worker.py"
    worker.write_text(f"""
import sys
sys.path.insert(0, {os.getcwd()!r})
import os, json
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")

pid = int(sys.argv[1]); nproc = int(sys.argv[2]); out = sys.argv[3]
from photon_ml_tpu.parallel import multihost as mh
from photon_ml_tpu.parallel.bucketing import bucket_by_entity_sparse
mh.initialize(coordinator_address="127.0.0.1:{port}", num_processes=nproc,
              process_id=pid, expected_processes=nproc)
mesh = mh.global_mesh()

rng = np.random.default_rng(77)
n, n_users, dg, du, ku = 480, 12, 4, 64, 3
uids = rng.integers(0, n_users, size=n)
xg = rng.normal(size=(n, dg)).astype(np.float32)
idx_u = rng.integers(0, du, size=(n, ku)).astype(np.int32)
vals_u = rng.normal(size=(n, ku)).astype(np.float32)
uw = rng.normal(size=(n_users, du)).astype(np.float32)
gw = rng.normal(size=dg).astype(np.float32)
z = xg @ gw + np.einsum("nk,nk->n", vals_u, uw[uids[:, None], idx_u])
y = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(np.float32)

from photon_ml_tpu.core.batch import DenseBatch
from photon_ml_tpu.core.losses import logistic_loss
from photon_ml_tpu.core.objective import GLMObjective
from photon_ml_tpu.core.regularization import Regularization
from photon_ml_tpu.opt.types import SolverConfig

start, stop = mh.process_row_range(n)
rows_per = mh.padded_per_host_rows(n, mesh)
blk = mh.pad_local_rows(dict(x=xg[start:stop], y=y[start:stop],
                             offset=np.zeros(stop - start, np.float32),
                             weight=np.ones(stop - start, np.float32)),
                        rows_per)
g = mh.global_batch_from_local(blk, mesh)
fixed_batch = DenseBatch(x=g["x"], y=g["y"], offset=g["offset"],
                         weight=g["weight"])

rid = mh.local_entity_rows(uids)
assert len(rid) > 0
local, projs = bucket_by_entity_sparse(
    uids[rid], idx_u[rid], vals_u[rid], du, y[rid],
    weight=np.ones(len(rid), np.float32), seed=5,
    row_ids=rid, num_samples=rows_per * nproc)
gb, pp = mh.global_entity_buckets(local, mesh, projections=projs)

cfg = SolverConfig(max_iters=60, tolerance=1e-9)
wf, rec, _ = mh.multihost_glmix_sweep(
    mesh, fixed_batch, gb,
    GLMObjective(loss=logistic_loss, reg=Regularization(l2=0.1)),
    GLMObjective(loss=logistic_loss, reg=Regularization(l2=1.0)),
    num_iterations=2, config=cfg, num_samples=n)
exported = mh.export_local_random_effects(rec, gb, mesh, projections=pp)
with open(os.path.join(out, f"sp{{pid}}.json"), "w") as f:
    json.dump({{"wf": [float(v) for v in np.asarray(wf)],
               "re": {{str(k): [float(v) for v in w]
                      for k, w in exported.items()}}}}, f)
""")
    _launch_workers(worker, 2, tmp_path)
    res = [json.load(open(tmp_path / f"sp{pid}.json")) for pid in range(2)]
    np.testing.assert_allclose(res[0]["wf"], res[1]["wf"], rtol=0, atol=0)
    merged = {int(k): np.asarray(v) for o in res for k, v in o["re"].items()}

    # single-process framework reference (sparse shard -> compact coordinate)
    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.game import FixedEffectConfig, GameData, RandomEffectConfig
    from photon_ml_tpu.game.coordinate import build_coordinate
    from photon_ml_tpu.game.data import SparseShard
    from photon_ml_tpu.game.descent import CoordinateDescent
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(77)
    n, n_users, dg, du, ku = 480, 12, 4, 64, 3
    uids = rng.integers(0, n_users, size=n)
    xg = rng.normal(size=(n, dg)).astype(np.float32)
    idx_u = rng.integers(0, du, size=(n, ku)).astype(np.int32)
    vals_u = rng.normal(size=(n, ku)).astype(np.float32)
    uw = rng.normal(size=(n_users, du)).astype(np.float32)
    gw = rng.normal(size=dg).astype(np.float32)
    z = xg @ gw + np.einsum("nk,nk->n", vals_u, uw[uids[:, None], idx_u])
    y = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(np.float32)
    data = GameData(y=y, features={
        "g": xg, "u": SparseShard(indices=idx_u, values=vals_u, dim=du)},
        id_tags={"userId": uids})
    cfg = SolverConfig(max_iters=60, tolerance=1e-9)
    coords = {
        "fixed": build_coordinate("fixed", data, FixedEffectConfig(
            feature_shard="g", solver=cfg, reg=Regularization(l2=0.1)),
            TaskType.LOGISTIC_REGRESSION, seed=5),
        "user": build_coordinate("user", data, RandomEffectConfig(
            random_effect_type="userId", feature_shard="u",
            solver=cfg, reg=Regularization(l2=1.0)),
            TaskType.LOGISTIC_REGRESSION, seed=5),
    }
    model, _, _ = CoordinateDescent(coords, order=["fixed", "user"],
                                    num_iterations=2).run(seed=5)
    np.testing.assert_allclose(
        res[0]["wf"], np.asarray(model["fixed"].coefficients.means),
        atol=5e-4, rtol=1e-3)
    re_ref = model["user"]
    assert set(merged) == set(re_ref.slot_of)
    for eid, w in merged.items():
        np.testing.assert_allclose(
            w, np.asarray(re_ref.w_stack[re_ref.slot_of[eid]]),
            atol=5e-4, rtol=1e-3)


def test_multihost_glmix3_two_processes(tmp_path):
    """Three-coordinate multihost GLMix (fixed + per-user + per-item — the
    reference's flagship shape): each RE coordinate has its OWN entity-hash
    ownership and buckets; the residual schedule runs fixed then each RE
    against the residual of all others.  Parity vs the single-process
    3-coordinate CoordinateDescent solve."""
    import json
    import os

    port = _free_port()
    worker = tmp_path / "glmix3_worker.py"
    worker.write_text(f"""
import sys
sys.path.insert(0, {os.getcwd()!r})
import os, json
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")

pid = int(sys.argv[1]); nproc = int(sys.argv[2]); out = sys.argv[3]
from photon_ml_tpu.parallel import multihost as mh
from photon_ml_tpu.parallel.bucketing import bucket_by_entity
mh.initialize(coordinator_address="127.0.0.1:{port}", num_processes=nproc,
              process_id=pid, expected_processes=nproc)
mesh = mh.global_mesh()

rng = np.random.default_rng(91)
n, n_users, n_items, dg, du, di = 600, 12, 9, 4, 2, 2
uids = rng.integers(0, n_users, size=n)
iids = rng.integers(0, n_items, size=n)
xg = rng.normal(size=(n, dg)).astype(np.float32)
xu = rng.normal(size=(n, du)).astype(np.float32)
xi = rng.normal(size=(n, di)).astype(np.float32)
uw = rng.normal(size=(n_users, du)).astype(np.float32)
iw = rng.normal(size=(n_items, di)).astype(np.float32)
gw = rng.normal(size=dg).astype(np.float32)
z = (xg @ gw + np.einsum("nd,nd->n", xu, uw[uids])
     + np.einsum("nd,nd->n", xi, iw[iids]))
y = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(np.float32)

from photon_ml_tpu.core.batch import DenseBatch
from photon_ml_tpu.core.losses import logistic_loss
from photon_ml_tpu.core.objective import GLMObjective
from photon_ml_tpu.core.regularization import Regularization
from photon_ml_tpu.opt.types import SolverConfig

start, stop = mh.process_row_range(n)
rows_per = mh.padded_per_host_rows(n, mesh)
blk = mh.pad_local_rows(dict(x=xg[start:stop], y=y[start:stop],
                             offset=np.zeros(stop - start, np.float32),
                             weight=np.ones(stop - start, np.float32)),
                        rows_per)
g = mh.global_batch_from_local(blk, mesh)
fb = DenseBatch(x=g["x"], y=g["y"], offset=g["offset"], weight=g["weight"])
n_glob = rows_per * nproc

def make_buckets(ids, x):
    rid = mh.local_entity_rows(ids)
    local = bucket_by_entity(ids[rid], x[rid], y[rid],
                             weight=np.ones(len(rid), np.float32),
                             seed=5, row_ids=rid, num_samples=n_glob)
    return mh.global_entity_buckets(local, mesh)

gb = {{"user": make_buckets(uids, xu), "item": make_buckets(iids, xi)}}
cfg = SolverConfig(max_iters=60, tolerance=1e-9)
objs = {{"user": GLMObjective(loss=logistic_loss, reg=Regularization(l2=1.0)),
        "item": GLMObjective(loss=logistic_loss, reg=Regularization(l2=0.7))}}
wf, rec, _ = mh.multihost_glmix_sweep(
    mesh, fb, gb,
    GLMObjective(loss=logistic_loss, reg=Regularization(l2=0.1)),
    objs, num_iterations=2, config=cfg, num_samples=n)
ex = {{cid: mh.export_local_random_effects(rec[cid], gb[cid], mesh)
      for cid in gb}}
with open(os.path.join(out, f"g3_{{pid}}.json"), "w") as f:
    json.dump({{"wf": [float(v) for v in np.asarray(wf)],
               "re": {{cid: {{str(k): [float(v) for v in w]
                            for k, w in d.items()}}
                      for cid, d in ex.items()}}}}, f)
""")
    _launch_workers(worker, 2, tmp_path)
    res = [json.load(open(tmp_path / f"g3_{pid}.json")) for pid in range(2)]
    np.testing.assert_allclose(res[0]["wf"], res[1]["wf"], rtol=0, atol=0)
    merged = {cid: {int(k): np.asarray(v)
                    for o in res for k, v in o["re"][cid].items()}
              for cid in ("user", "item")}

    # single-process 3-coordinate reference
    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.game import FixedEffectConfig, GameData, RandomEffectConfig
    from photon_ml_tpu.game.coordinate import build_coordinate
    from photon_ml_tpu.game.descent import CoordinateDescent
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(91)
    n, n_users, n_items, dg, du, di = 600, 12, 9, 4, 2, 2
    uids = rng.integers(0, n_users, size=n)
    iids = rng.integers(0, n_items, size=n)
    xg = rng.normal(size=(n, dg)).astype(np.float32)
    xu = rng.normal(size=(n, du)).astype(np.float32)
    xi = rng.normal(size=(n, di)).astype(np.float32)
    uw = rng.normal(size=(n_users, du)).astype(np.float32)
    iw = rng.normal(size=(n_items, di)).astype(np.float32)
    gw = rng.normal(size=dg).astype(np.float32)
    z = (xg @ gw + np.einsum("nd,nd->n", xu, uw[uids])
         + np.einsum("nd,nd->n", xi, iw[iids]))
    y = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(np.float32)
    data = GameData(y=y, features={"g": xg, "u": xu, "i": xi},
                    id_tags={"userId": uids, "itemId": iids})
    cfg = SolverConfig(max_iters=60, tolerance=1e-9)
    coords = {
        "fixed": build_coordinate("fixed", data, FixedEffectConfig(
            feature_shard="g", solver=cfg, reg=Regularization(l2=0.1)),
            TaskType.LOGISTIC_REGRESSION, seed=5),
        "user": build_coordinate("user", data, RandomEffectConfig(
            random_effect_type="userId", feature_shard="u", solver=cfg,
            reg=Regularization(l2=1.0)), TaskType.LOGISTIC_REGRESSION,
            seed=5),
        "item": build_coordinate("item", data, RandomEffectConfig(
            random_effect_type="itemId", feature_shard="i", solver=cfg,
            reg=Regularization(l2=0.7)), TaskType.LOGISTIC_REGRESSION,
            seed=5),
    }
    model, _, _ = CoordinateDescent(coords, order=["fixed", "user", "item"],
                                    num_iterations=2).run(seed=5)
    np.testing.assert_allclose(
        res[0]["wf"], np.asarray(model["fixed"].coefficients.means),
        atol=5e-4, rtol=1e-3)
    for cid in ("user", "item"):
        ref = model[cid]
        assert set(merged[cid]) == set(ref.slot_of)
        for e, w in merged[cid].items():
            np.testing.assert_allclose(
                w, np.asarray(ref.w_stack[ref.slot_of[e]]),
                atol=5e-4, rtol=1e-3)


class TestMultihostGuards:
    """The loud-failure contracts of the multihost path (single-process
    degenerates — the errors fire before any cross-process work)."""

    def _mesh(self, devices):
        from photon_ml_tpu.parallel.multihost import global_mesh

        return global_mesh()

    def test_compact_buckets_require_projections(self, devices, rng):
        from photon_ml_tpu.parallel.bucketing import bucket_by_entity_sparse
        from photon_ml_tpu.parallel.multihost import global_entity_buckets

        n, d, k = 40, 16, 3
        uids = np.repeat(np.arange(8), 5)
        local, _projs = bucket_by_entity_sparse(
            uids, rng.integers(0, d, size=(n, k)).astype(np.int32),
            rng.normal(size=(n, k)).astype(np.float32), d,
            (rng.random(n) < 0.5).astype(np.float32))
        assert local.compact
        with pytest.raises(ValueError, match="projections"):
            global_entity_buckets(local, self._mesh(devices))

    def test_sweep_requires_num_samples(self, devices, rng):
        from photon_ml_tpu.core import GLMObjective, Regularization, losses
        from photon_ml_tpu.core.batch import dense_batch
        from photon_ml_tpu.parallel.bucketing import bucket_by_entity
        from photon_ml_tpu.parallel.multihost import (global_entity_buckets,
                                                      multihost_glmix_sweep)

        mesh = self._mesh(devices)
        n = 16
        uids = np.repeat(np.arange(4), 4)
        x = rng.normal(size=(n, 2)).astype(np.float32)
        y = (rng.random(n) < 0.5).astype(np.float32)
        gb = global_entity_buckets(
            bucket_by_entity(uids, x, y, row_ids=np.arange(n),
                             num_samples=n), mesh)
        obj = GLMObjective(loss=losses.logistic_loss, reg=Regularization(l2=1))
        with pytest.raises(ValueError, match="num_samples"):
            multihost_glmix_sweep(mesh, dense_batch(x, y), gb, obj, obj)

    def test_single_process_allgather_has_no_process_axis(self, devices,
                                                          rng):
        """Regression: with one process, ``process_allgather`` has returned
        the INPUT shape unchanged (no leading process axis) in the jax this
        was written against, and stacks an axis of one in jax 0.9.  The
        agreement pass in ``global_entity_buckets`` used to index
        ``all_vec[:, log, 0]`` as if the axis were always there and died
        with ``IndexError: too many indices for array``; it reshapes to
        ``[n_proc, ...]`` first, which holds either way."""
        from jax.experimental import multihost_utils

        from photon_ml_tpu.parallel.bucketing import bucket_by_entity
        from photon_ml_tpu.parallel.multihost import global_entity_buckets

        # the (MAXLOG, 2) metadata vector comes back (33, 2) or (1, 33, 2)
        # by jax version: the same 66 numbers, which is what the guard's
        # reshape needs
        vec = np.arange(66, dtype=np.int64).reshape(33, 2)
        out = np.asarray(multihost_utils.process_allgather(vec))
        assert out.shape in (vec.shape, (1,) + vec.shape)
        np.testing.assert_array_equal(out.reshape((1,) + vec.shape)[0], vec)

        # and the end-to-end single-process assembly works on top of it
        mesh = self._mesh(devices)
        n = 16
        uids = np.repeat(np.arange(4), 4)
        x = rng.normal(size=(n, 2)).astype(np.float32)
        y = (rng.random(n) < 0.5).astype(np.float32)
        gb = global_entity_buckets(
            bucket_by_entity(uids, x, y, row_ids=np.arange(n),
                             num_samples=n), mesh)
        assert gb.num_entities == 4

    def test_unknown_re_scoring_key_fails(self, devices, rng):
        from photon_ml_tpu.core import GLMObjective, Regularization, losses
        from photon_ml_tpu.core.batch import dense_batch
        from photon_ml_tpu.parallel.bucketing import bucket_by_entity
        from photon_ml_tpu.parallel.multihost import (global_entity_buckets,
                                                      multihost_glmix_sweep)

        mesh = self._mesh(devices)
        n = 16
        uids = np.repeat(np.arange(4), 4)
        x = rng.normal(size=(n, 2)).astype(np.float32)
        y = (rng.random(n) < 0.5).astype(np.float32)
        gb = global_entity_buckets(
            bucket_by_entity(uids, x, y, row_ids=np.arange(n),
                             num_samples=n), mesh)
        obj = GLMObjective(loss=losses.logistic_loss, reg=Regularization(l2=1))
        with pytest.raises(ValueError, match="re_scoring keys"):
            multihost_glmix_sweep(mesh, dense_batch(x, y), {"user": gb},
                                  obj, obj, num_samples=n,
                                  re_scoring={"users": None})


class TestKernelsInsideShardMap:
    """Every kernel's ``eligible()`` is False off-TPU, so no CPU test ever
    traced a ``pallas_call`` where a multi-chip run does: inside the repo's
    ``shard_map`` call sites.  There ``jax.shard_map``'s ``check_vma``
    rejects a pallas_call outright ("vma on jax.ShapeDtypeStruct must not
    be None"), and under plain GSPMD Mosaic refuses to be partitioned.
    These trace the kernels (interpret mode) inside the two sites that host
    one — ``ShardMapObjective`` (fused_glm) and the random-effect
    coordinate's per-lane solve (soa_newton).  ``compact_score`` runs under
    no shard_map: its one caller is single-device batch scoring."""

    def test_fused_glm_inside_shard_map_objective(self, devices, rng,
                                                  monkeypatch):
        import functools

        from photon_ml_tpu.core.batch import DenseBatch
        from photon_ml_tpu.ops import fused_glm
        from photon_ml_tpu.parallel.fixed import ShardMapObjective
        from photon_ml_tpu.parallel.mesh import shard_batch

        monkeypatch.setattr(fused_glm, "eligible",
                            lambda b, interpret=False: isinstance(b, DenseBatch))
        for name in ("fused_value_and_grad", "fused_hvp"):
            monkeypatch.setattr(fused_glm, name, functools.partial(
                getattr(fused_glm, name), interpret=True))

        n, d = 512, 128
        batch = dense_batch(rng.normal(size=(n, d)) * 0.3,
                            (rng.random(n) < 0.5).astype(float),
                            offset=rng.normal(size=n) * 0.1,
                            weight=rng.uniform(0.5, 2.0, size=n))
        w = jnp.asarray(rng.normal(size=d) * 0.2)
        v = jnp.asarray(rng.normal(size=d))
        plain = GLMObjective(loss=losses.logistic_loss,
                             reg=Regularization(l2=0.1))
        mesh = make_mesh(n_data=4, devices=devices[:4])
        sm = ShardMapObjective(plain.replace(fused=True), mesh)
        sharded = shard_batch(batch, mesh)

        assert "pallas_call" in str(jax.make_jaxpr(sm.value_and_grad)(
            w, sharded))
        val, grad = jax.jit(sm.value_and_grad)(w, sharded)
        ref_val, ref_grad = plain.value_and_grad(w, batch)
        np.testing.assert_allclose(val, ref_val, rtol=1e-12)
        np.testing.assert_allclose(grad, ref_grad, rtol=1e-10, atol=1e-12)
        assert "pallas_call" in str(jax.make_jaxpr(sm.hvp)(w, sharded, v))
        np.testing.assert_allclose(jax.jit(sm.hvp)(w, sharded, v),
                                   plain.hvp(w, batch, v),
                                   rtol=1e-10, atol=1e-12)

    def test_soa_newton_inside_the_coordinate_solve(self, devices, rng,
                                                    monkeypatch):
        import functools

        from photon_ml_tpu.game import GameData, RandomEffectConfig
        from photon_ml_tpu.game.coordinate import build_coordinate
        from photon_ml_tpu.opt import newton_soa
        from photon_ml_tpu.types import TaskType

        n_users, per_user, d = 512, 6, 3  # 128 lanes on each of 4 devices
        n = n_users * per_user
        data = GameData(y=(rng.random(n) < 0.5).astype(float),
                        features={"u": rng.normal(size=(n, d))},
                        id_tags={"userId": np.repeat(np.arange(n_users),
                                                     per_user)})
        cfg = RandomEffectConfig(
            random_effect_type="userId", feature_shard="u",
            solver=SolverConfig(max_iters=6, tolerance=1e-9),
            reg=Regularization(l2=1.0))
        task = TaskType.LOGISTIC_REGRESSION
        ref, _ = build_coordinate("user", data, cfg, task).update(np.zeros(n))

        monkeypatch.setattr(newton_soa, "solve_newton_soa", functools.partial(
            newton_soa.solve_newton_soa, interpret=True))
        mesh = make_mesh(n_data=4, devices=devices[:4])
        coord = build_coordinate("user", data, cfg, task, mesh=mesh)
        assert coord._use_soa
        dev, lanes = coord._dev[0], coord._dev[0]["x"].shape[0]
        jaxpr = str(jax.make_jaxpr(coord._vsolve)(
            jnp.zeros((lanes, d)), dev["x"], dev["y"],
            jnp.zeros_like(dev["y"]), dev["w"],
            coord._lane_regs(cfg.reg)[0]))
        assert "shard_map" in jaxpr and "pallas_call" in jaxpr
        got, _ = coord.update(np.zeros(n))
        assert got.slot_of == ref.slot_of
        np.testing.assert_allclose(got.w_stack, ref.w_stack,
                                   rtol=1e-8, atol=1e-10)
