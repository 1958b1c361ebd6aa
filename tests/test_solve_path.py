"""Per-entity solve-path tests: fused validated sweeps, pallas kernels
(interpret mode), and native sparse/compact serving.

Three contracts from the raw-speed pass:
  - ``FusedSweep.run_validated`` reproduces the host-paced
    ``CoordinateDescent`` + validation suite exactly: same best-model
    selection, tolerance-equal metrics, same per-update held-out losses.
  - The pallas kernels (ops/soa_newton, ops/compact_score) are the same
    math as their XLA references — verified in interpret mode on CPU,
    including padded/weightless lanes and line-search-rejection lanes.
  - A ``CompactRandomEffectModel`` serves end-to-end (resolve -> AOT
    execute -> delta -> rebalance -> swap) without any ``.to_dense()``:
    scores BITWISE-equal to the compact batch path (the engine contract),
    tolerance-equal to ``.to_dense()`` dense serving (different summation
    order: k observed columns vs the d-wide einsum).
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.core.losses import logistic_loss, poisson_loss
from photon_ml_tpu.core.regularization import Regularization
from photon_ml_tpu.data.index_map import IndexMap, feature_key
from photon_ml_tpu.data.reader import EntityIndex
from photon_ml_tpu.evaluation.evaluator import EvaluationSuite
from photon_ml_tpu.game.config import FixedEffectConfig, RandomEffectConfig
from photon_ml_tpu.game.coordinate import build_coordinate
from photon_ml_tpu.game.data import GameData
from photon_ml_tpu.game.descent import CoordinateDescent
from photon_ml_tpu.game.fused import FusedSweep
from photon_ml_tpu.models.game import (CompactRandomEffectModel,
                                       FixedEffectModel, GameModel,
                                       RandomEffectModel)
from photon_ml_tpu.models.glm import Coefficients
from photon_ml_tpu.opt.newton_soa import (_cholesky_solve_soa, _hess,
                                          _value_grad, solve_newton_soa)
from photon_ml_tpu.opt.types import SolverConfig
from photon_ml_tpu.ops import compact_score, soa_newton
from photon_ml_tpu.serving.batcher import (BucketedBatcher, Request,
                                           densify_features)
from photon_ml_tpu.serving.coefficient_store import (CoefficientStore,
                                                     CompactRandomCoordinate,
                                                     StoreConfig)
from photon_ml_tpu.serving.engine import ScoringEngine
from photon_ml_tpu.serving.metrics import ServingMetrics
from photon_ml_tpu.types import TaskType

TASK = TaskType.LOGISTIC_REGRESSION


# ---------------------------------------------------------------------------
# fused validated sweeps
# ---------------------------------------------------------------------------

def _glmix(rng, n_users=16, per_user=40, d_global=5, d_user=3):
    n = n_users * per_user
    xg = rng.normal(size=(n, d_global))
    xu = rng.normal(size=(n, d_user))
    uid = np.repeat(np.arange(n_users) * 2 + 7, per_user)
    wg = rng.normal(size=d_global) * 0.8
    wu = rng.normal(size=(n_users, d_user))
    logits = xg @ wg + np.einsum(
        "nd,nd->n", xu, wu[np.repeat(np.arange(n_users), per_user)])
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logits))).astype(float)
    return GameData(y=y, features={"global": xg, "per_user": xu},
                    id_tags={"userId": uid})


def _coords(data, num_l2=1.0):
    solver = SolverConfig(max_iters=80, tolerance=1e-8)
    cfgs = {
        "fixed": FixedEffectConfig(feature_shard="global", solver=solver,
                                   reg=Regularization(l2=num_l2)),
        "per-user": RandomEffectConfig(random_effect_type="userId",
                                       feature_shard="per_user",
                                       solver=solver,
                                       reg=Regularization(l2=num_l2)),
    }
    return {cid: build_coordinate(cid, data, c, TASK)
            for cid, c in cfgs.items()}


class TestFusedValidated:
    def test_matches_host_descent(self, rng):
        """Best-model selection + metrics parity vs the host loop with a
        validation suite, over multiple outer iterations."""
        data = _glmix(rng)
        val = _glmix(rng, per_user=15)
        coords = _coords(data)
        suite = EvaluationSuite.from_specs(["auc", "logistic_loss"])

        host_model, hist, host_ev = CoordinateDescent(
            coords, num_iterations=3, validation=(val, suite)).run()
        sweep = FusedSweep(coords, num_iterations=3)
        plan = sweep.validation_plan(val, suite)
        fmodel, evals, best_ev, losses = sweep.run_validated(plan)

        assert len(evals) == 3
        for k, v in host_ev.values.items():
            np.testing.assert_allclose(best_ev.values[k], v, rtol=1e-6)
        np.testing.assert_allclose(fmodel["fixed"].coefficients.means,
                                   host_model["fixed"].coefficients.means,
                                   rtol=1e-6, atol=1e-9)
        np.testing.assert_allclose(fmodel["per-user"].w_stack,
                                   host_model["per-user"].w_stack,
                                   rtol=1e-6, atol=1e-9)

    def test_per_update_losses_match_host_validation(self, rng):
        """The in-program per-(iteration, coordinate) held-out losses equal
        the host loop's per-update logistic_loss evaluations (the host
        metric is the weighted SUM; the program emits the weighted MEAN)."""
        data = _glmix(rng, n_users=10)
        val = _glmix(rng, n_users=10, per_user=12)
        coords = _coords(data)
        suite = EvaluationSuite.from_specs(["logistic_loss"])
        _, hist, _ = CoordinateDescent(
            coords, num_iterations=2, validation=(val, suite)).run()
        sweep = FusedSweep(coords, num_iterations=2)
        _, _, _, losses = sweep.run_validated(
            sweep.validation_plan(val, suite))
        assert losses.shape == (2, 2)
        host = np.asarray([s["validation"].values["logistic_loss"]
                           for s in hist.steps]).reshape(2, 2)
        wt_sum = float(np.sum(val.weight))
        np.testing.assert_allclose(losses * wt_sum, host, rtol=1e-5)

    def test_warm_start_with_carried_entities(self, rng):
        """A warm-start model with entities the training data never sees:
        the carried rows must ride the held-out base (constant) and the
        best model must merge them — host-loop parity end to end."""
        data = _glmix(rng, n_users=8)
        val = _glmix(rng, n_users=8, per_user=10)
        coords = _coords(data)
        d_user = 3
        # initial model: every trained entity + one carried stranger (id 999
        # appears in NEITHER data nor val — plus id 9 which is in val only
        # via... keep it simple: 999 carried, contributes where it appears)
        re0 = coords["per-user"]
        slot_of = dict(re0._slot_of)
        w0 = rng.normal(size=(len(slot_of), d_user)) * 0.1
        slot_of[999] = len(slot_of)
        w0 = np.vstack([w0, rng.normal(size=(1, d_user))])
        init = GameModel(models={
            "fixed": FixedEffectModel(
                coefficients=Coefficients(
                    means=rng.normal(size=5) * 0.1),
                feature_shard="global", task=TASK),
            "per-user": RandomEffectModel(
                w_stack=w0.astype(np.float32), slot_of=slot_of,
                random_effect_type="userId", feature_shard="per_user",
                task=TASK),
        })
        suite = EvaluationSuite.from_specs(["auc", "logistic_loss"])
        host_model, _, host_ev = CoordinateDescent(
            coords, num_iterations=2, validation=(val, suite)).run(
                initial=init)
        sweep = FusedSweep(coords, num_iterations=2)
        fmodel, _, best_ev, _ = sweep.run_validated(
            sweep.validation_plan(val, suite), initial=init)
        for k, v in host_ev.values.items():
            np.testing.assert_allclose(best_ev.values[k], v, rtol=1e-5)
        # the carried stranger survives into the published model
        assert 999 in fmodel["per-user"].slot_of
        np.testing.assert_allclose(
            fmodel["per-user"].w_stack[fmodel["per-user"].slot_of[999]],
            w0[-1], rtol=1e-6)

    def test_estimator_routes_validated_fused(self, rng):
        """GameEstimator.fit with a validation suite runs the validated
        program (empty per-update history) and returns host-equal metrics."""
        from photon_ml_tpu.game.config import GameConfig
        from photon_ml_tpu.game.estimator import GameEstimator

        data = _glmix(rng, n_users=8)
        suite = EvaluationSuite.from_specs(["auc"])
        solver = SolverConfig(max_iters=60, tolerance=1e-8)
        cfg = GameConfig(task=TASK, coordinates={
            "fixed": FixedEffectConfig(feature_shard="global", solver=solver,
                                       reg=Regularization(l2=1.0)),
            "per-user": RandomEffectConfig(
                random_effect_type="userId", feature_shard="per_user",
                solver=solver, reg=Regularization(l2=1.0)),
        }, num_outer_iterations=2)
        r_fused = GameEstimator(validation_suite=suite, fused=True).fit(
            data, [cfg], validation_data=data)[0]
        r_host = GameEstimator(validation_suite=suite, fused=False).fit(
            data, [cfg], validation_data=data)[0]
        assert r_fused.history.steps == []      # one program, no host steps
        assert len(r_host.history.steps) == 4   # 2 coords x 2 iterations
        np.testing.assert_allclose(r_fused.evaluation.primary,
                                   r_host.evaluation.primary, rtol=1e-6)

    def test_variances_fall_back_to_host(self, rng):
        """run_validated refuses variance-computing sweeps; the estimator
        falls back to the host loop (which still attaches variances)."""
        import dataclasses

        from photon_ml_tpu.game.config import GameConfig
        from photon_ml_tpu.game.estimator import GameEstimator
        from photon_ml_tpu.types import VarianceComputationType

        data = _glmix(rng, n_users=6)
        coords = _coords(data)
        coords["fixed"] = coords["fixed"].rebind(dataclasses.replace(
            coords["fixed"].config,
            variance=VarianceComputationType.SIMPLE))
        sweep = FusedSweep(coords, num_iterations=1)
        suite = EvaluationSuite.from_specs(["auc"])
        with pytest.raises(NotImplementedError):
            sweep.run_validated(sweep.validation_plan(data, suite))
        solver = SolverConfig(max_iters=40, tolerance=1e-7)
        cfg = GameConfig(task=TASK, coordinates={
            "fixed": FixedEffectConfig(
                feature_shard="global", solver=solver,
                reg=Regularization(l2=1.0),
                variance=VarianceComputationType.SIMPLE),
            "per-user": RandomEffectConfig(
                random_effect_type="userId", feature_shard="per_user",
                solver=solver, reg=Regularization(l2=1.0)),
        }, num_outer_iterations=1)
        r = GameEstimator(validation_suite=suite).fit(
            data, [cfg], validation_data=data)[0]
        assert r.evaluation is not None
        assert r.model["fixed"].coefficients.variances is not None
        assert len(r.history.steps) == 2  # host loop ran


# ---------------------------------------------------------------------------
# pallas kernels (interpret mode) vs XLA references
# ---------------------------------------------------------------------------

def _soa_problem(rng, loss, d=5, cap=12, lanes=256, dtype=np.float64):
    w = jnp.asarray(rng.normal(size=(d, lanes)) * 0.1, dtype)
    x = jnp.asarray(rng.normal(size=(cap, d, lanes)), dtype)
    if loss is poisson_loss:
        y = jnp.asarray(rng.poisson(2.0, size=(cap, lanes)).astype(dtype))
    else:
        y = jnp.asarray((rng.random((cap, lanes)) < 0.5).astype(dtype))
    off = jnp.asarray(rng.normal(size=(cap, lanes)) * 0.1, dtype)
    wt = jnp.asarray(rng.uniform(0.5, 2.0, size=(cap, lanes)), dtype)
    # weightless / padded lanes: whole lanes with zero weight (H = l2 I)
    wt = wt.at[:, :37].set(0.0)
    # padded SAMPLE slots inside real lanes
    wt = wt.at[cap - 2:, 40:90].set(0.0)
    l2 = jnp.asarray(rng.uniform(0.1, 2.0, size=lanes), dtype)
    return w, x, y, off, wt, l2


class TestSoaNewtonKernel:
    @pytest.mark.parametrize("loss", [logistic_loss, poisson_loss],
                             ids=lambda l: l.name)
    def test_newton_step_parity(self, rng, loss):
        """Kernel step == _hess + _cholesky_solve_soa chain (incl. the
        jitter rule), with weightless lanes and padded sample slots."""
        w, x, y, off, wt, l2 = _soa_problem(rng, loss)
        d = w.shape[0]
        _, g = _value_grad(loss, w, x, y, off, wt, l2)
        hh = _hess(loss, w, x, y, off, wt, l2)
        eps = jnp.asarray(np.finfo(np.float64).eps)
        jit_vec = eps * (jnp.abs(jnp.stack(
            [hh[i][i] for i in range(d)])).max(0) + 1.0)
        ref = _cholesky_solve_soa(hh, g, jit_vec)
        got = soa_newton.newton_step(loss, w, g, x, y, off, wt, l2,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-11, atol=1e-12)

    def test_full_solver_parity_including_rejection_lanes(self, rng):
        """solve_newton_soa with the kernel (interpret=True) == pure XLA,
        end to end — convergence reasons, iterates and line-search
        REJECTION lanes included (max_linesearch=1 + an aggressive
        objective makes some lanes reject and stall)."""
        w, x, y, off, wt, l2 = _soa_problem(rng, logistic_loss, lanes=128)
        # blow up some lanes' curvature scale so a full Newton step
        # overshoots and the single backtracking trial rejects
        off = off.at[:, 100:].add(25.0)
        cfg = SolverConfig(max_iters=8, tolerance=1e-10, max_linesearch=1)
        ref = solve_newton_soa(logistic_loss, w, x, y, off, wt, l2, cfg)
        from photon_ml_tpu.types import ConvergenceReason

        assert (np.asarray(ref.reason)
                == int(ConvergenceReason.OBJECTIVE_NOT_IMPROVING)).any(), \
            "fixture no longer produces line-search-rejection lanes"
        assert soa_newton.eligible(w.shape[0], w.shape[1], x.shape[0],
                                   x.dtype.itemsize, interpret=True)
        got = solve_newton_soa(logistic_loss, w, x, y, off, wt, l2, cfg,
                               interpret=True)
        np.testing.assert_array_equal(np.asarray(got.reason),
                                      np.asarray(ref.reason))
        np.testing.assert_array_equal(np.asarray(got.iterations),
                                      np.asarray(ref.iterations))
        np.testing.assert_allclose(np.asarray(got.w), np.asarray(ref.w),
                                   rtol=1e-9, atol=1e-11)

    def test_gating(self):
        ok = functools.partial(soa_newton.eligible, interpret=True)
        assert ok(4, 256, 32)
        assert not ok(4, 100, 32)  # not lane-aligned
        assert not ok(17, 256, 4)  # past the static Cholesky unroll
        # VMEM shape rule: a 128-lane block of the (cap, d) design must fit
        assert ok(16, 128, 427) and not ok(16, 128, 428)
        # off-TPU the production gate says no
        assert not soa_newton.eligible(4, 256, 32)
        with pytest.raises(ValueError, match="eligible"):
            soa_newton.newton_step(
                logistic_loss, jnp.zeros((4, 100)), jnp.zeros((4, 100)),
                jnp.zeros((3, 4, 100)), jnp.zeros((3, 100)),
                jnp.zeros((3, 100)), jnp.ones((3, 100)), jnp.ones(100))

    @pytest.mark.parametrize("lanes,want", [(1152, 1152), (16384, 2048),
                                            (131072, 2048), (128, 128)])
    def test_block_lanes_divide_and_fit(self, lanes, want):
        """The picker's block always divides the lane count (1152 used to
        pick a non-dividing 1024 and raise) and is the same at the smoke's
        16k lanes as at glmix_chip's 131k."""
        bl = soa_newton._pick_block_lanes(32, 4, lanes, 4)
        assert bl == want and lanes % bl == 0
        assert bl * soa_newton._bytes_per_lane(32, 4, 4) \
            <= soa_newton.VMEM_BLOCK_BUDGET_BYTES


class TestCompactScoreKernel:
    def _compact_model_arrays(self, rng, E=40, k_m=6, dim=60, dtype=np.float64):
        w_idx = np.full((E, k_m), dim, np.int32)
        w_val = np.zeros((E, k_m), dtype)
        for e in range(E):
            nn = int(rng.integers(1, k_m + 1))
            cols = np.sort(rng.choice(dim, size=nn, replace=False))
            w_idx[e, :nn] = cols
            w_val[e, :nn] = rng.normal(size=nn)
        return w_idx, w_val

    def test_match_dot_parity(self, rng):
        """Kernel == the searchsorted/take_along_axis chain: missing
        entities, dim-padded model rows, zero-valued padded feature slots
        and DUPLICATE feature ids (which accumulate) all covered."""
        from photon_ml_tpu.models.game import score_compact_sparse_xla

        dim, k_f, n = 60, 9, 300
        w_idx, w_val = self._compact_model_arrays(rng, dim=dim)
        slots = rng.integers(-1, 40, size=n).astype(np.int32)
        f_idx = rng.integers(0, dim, size=(n, k_f))
        f_idx[:, 3] = f_idx[:, 2]  # duplicates accumulate
        f_val = rng.normal(size=(n, k_f))
        f_val[:, -2:] = 0.0        # padded COO slots carry value 0
        ref = score_compact_sparse_xla(
            jnp.asarray(w_idx), jnp.asarray(w_val), jnp.asarray(slots),
            jnp.asarray(np.asarray(f_idx, np.int32)), jnp.asarray(f_val))
        got = compact_score.score_sparse_compact(
            jnp.asarray(w_idx), jnp.asarray(w_val), jnp.asarray(slots),
            jnp.asarray(np.asarray(f_idx, np.int32)), jnp.asarray(f_val),
            interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-10, atol=1e-12)

    def test_gating(self):
        ok = functools.partial(compact_score.eligible, interpret=True)
        assert ok(64, 64) and ok(8, 512) and ok(2048, 2)
        assert not ok(128, 128)   # match work too big
        assert not ok(1, 1024)    # past the static feature unroll
        assert not ok(4096, 1)    # a 128-lane block would not fit VMEM


# ---------------------------------------------------------------------------
# native sparse/compact serving
# ---------------------------------------------------------------------------

def _compact_fixture(rng, d=24, E=60, density=0.2):
    names = [f"f{j}" for j in range(d)]
    imap = IndexMap({feature_key(n): j for j, n in enumerate(names)})
    eidx = EntityIndex()
    for i in range(E):
        eidx.get_or_add(f"user{i}")
    w = rng.normal(size=(E, d)) * (rng.random((E, d)) < density)
    dense_re = RandomEffectModel(
        w_stack=w.astype(np.float32), slot_of={i: i for i in range(E)},
        random_effect_type="userId", feature_shard="all", task=TASK)
    fixed = FixedEffectModel(
        coefficients=Coefficients(means=rng.normal(size=d).astype(np.float32)),
        feature_shard="all", task=TASK)
    return names, imap, eidx, fixed, dense_re, dense_re.to_compact()


def _requests(rng, names, E, n):
    out = []
    for i in range(n):
        feats = [{"name": nm, "term": "", "value": float(v)}
                 for nm, v in zip(names, rng.normal(size=len(names)))]
        u = int(rng.integers(0, E + 5))  # some unknown entities
        out.append(Request(uid=i, features=feats, ids={"userId": f"user{u}"}))
    return out


def _engine_for(model, eidx, imap, cap=None, metrics=None):
    store = CoefficientStore.from_model(
        model, TASK, {"userId": eidx}, {"all": imap},
        config=StoreConfig(device_capacity=cap), metrics=metrics)
    engine = ScoringEngine(store, BucketedBatcher(16), metrics=metrics)
    n = engine.warm()
    return store, engine, n


class TestCompactServing:
    def test_serving_parity_and_lifecycle(self, rng):
        """resolve -> AOT execute -> delta -> rebalance, dense vs compact on
        the same request stream: compact serving is BITWISE the compact
        batch score (the engine<->batch contract) and tolerance-equal to
        .to_dense() dense serving (k-column vs d-wide summation order)."""
        names, imap, eidx, fixed, dense_re, compact_re = _compact_fixture(rng)
        E, d = dense_re.w_stack.shape
        dense_m = GameModel(models={"fixed": fixed, "per_user": dense_re})
        compact_m = GameModel(models={"fixed": fixed, "per_user": compact_re})
        st_d, eng_d, _ = _engine_for(dense_m, eidx, imap)
        # capacity 20/60: hot, cold (LRU) and unknown paths all exercised
        st_c, eng_c, n_warm = _engine_for(compact_m, eidx, imap, cap=20)
        assert isinstance(st_c.coordinates["per_user"],
                          CompactRandomCoordinate)

        reqs = _requests(rng, names, E, 50)
        reqs[0].ids["userId"] = "user3"  # the delta target must be scored
        s_dense = eng_d.score_requests(reqs)
        s_compact = eng_c.score_requests(reqs)
        np.testing.assert_allclose(s_compact, s_dense, rtol=2e-5, atol=1e-6)

        # bitwise vs the compact BATCH path on the same densified features,
        # on a bucket-aligned stream (one chunk, engine shapes == batch
        # shapes; at other chunkings the FIXED effect's [b, d] @ [d] matvec
        # rounds shape-sensitively on XLA CPU — a pre-existing property of
        # the engine<->batch contract, not of the compact path)
        bs = reqs[:16]
        xs = densify_features(bs, {"all": imap}, len(bs))
        ids = np.asarray([eidx.get(r.ids["userId"]) for r in bs], np.int64)
        gd = GameData(y=np.zeros(len(bs)), features={"all": xs["all"]},
                      id_tags={"userId": ids})
        np.testing.assert_array_equal(
            eng_c.score_requests(bs),
            np.asarray(compact_m.score(gd), s_compact.dtype))

        # per-coordinate: the engine's compact margins (resolve + the shared
        # gather kernel, hot + cold tiers) are BITWISE the compact batch
        # score at ANY chunk shape
        from photon_ml_tpu.models.game import score_compact_dense

        # compiled, as the engine's kernels and the batch wrapper both hold
        # this definition: op by op (eager) the k products are rounded and
        # then summed by a standalone reduce, another order than the fused
        # one, and 18 of 50 scores differ by an ulp (4.8e-7)
        score_compact_dense = jax.jit(score_compact_dense)

        allx = densify_features(reqs, {"all": imap}, len(reqs))["all"]
        allids = np.asarray([eidx.get(r.ids["userId"]) for r in reqs],
                            np.int64)
        hs, sl, (ov_i, ov_v) = st_c.resolve(
            "per_user", [r.ids.get("userId") for r in reqs])
        got = np.asarray(
            score_compact_dense(hs.indices, hs.values, jnp.asarray(sl),
                                jnp.asarray(allx))
            + score_compact_dense(jnp.asarray(ov_i), jnp.asarray(ov_v),
                                  jnp.arange(len(reqs), dtype=jnp.int32),
                                  jnp.asarray(allx)))
        gd_all = GameData(y=np.zeros(len(reqs)), features={"all": allx},
                          id_tags={"userId": allids})
        np.testing.assert_array_equal(
            got, np.asarray(compact_re.score(gd_all), got.dtype))

        # streaming delta: dense row on the wire, compacted in the store;
        # both stores patched -> still equal, and the score actually moved
        row = (rng.normal(size=d) * (rng.random(d) < 0.2)).astype(np.float32)
        row[0] = 1.5  # guarantee a visible, capacity-respecting change
        assert st_c.apply_delta("per_user", "user3", row)
        assert st_d.apply_delta("per_user", "user3", row)
        s_d2, s_c2 = eng_d.score_requests(reqs), eng_c.score_requests(reqs)
        np.testing.assert_allclose(s_c2, s_d2, rtol=2e-5, atol=1e-6)
        assert not np.array_equal(s_dense, s_d2)

        # frequency rebalance: residency moves, scores don't
        st_c.rebalance()
        np.testing.assert_array_equal(eng_c.score_requests(reqs), s_c2)

        # zero recompiles through the whole lifecycle
        assert eng_c.compile_count == n_warm

        # an over-capacity delta is refused loudly (k would have to grow)
        with pytest.raises(ValueError, match="capacity"):
            st_c.apply_delta("per_user", "user3", np.ones(d, np.float32))

    def test_compact_swap_end_to_end(self, rng, tmp_path):
        """Hot swap a compact model directory in: load -> warm -> flip,
        (generation, delta_version) identity reset — no .to_dense()."""
        from photon_ml_tpu.serving.swap import HotSwapper
        from photon_ml_tpu.storage.model_io import save_game_model

        names, imap, eidx, fixed, dense_re, compact_re = _compact_fixture(rng)
        E, d = dense_re.w_stack.shape

        def _save(m, sub):
            out = str(tmp_path / sub)
            save_game_model(m, out, {"all": imap}, {"userId": eidx}, TASK,
                            fmt="columnar")
            imap.save(os.path.join(out, "all.idx"))
            eidx.save(os.path.join(out, "userId.entities.json"))
            return out

        m1 = GameModel(models={"fixed": fixed, "per_user": compact_re})
        w2 = rng.normal(size=(E, d)) * (rng.random((E, d)) < 0.2)
        re2 = RandomEffectModel(
            w_stack=w2.astype(np.float32), slot_of=dict(dense_re.slot_of),
            random_effect_type="userId", feature_shard="all",
            task=TASK).to_compact(k=compact_re.indices.shape[1])
        m2 = GameModel(models={"fixed": fixed, "per_user": re2})
        dir2 = _save(m2, "gen2")

        metrics = ServingMetrics()
        st1, engine, n_warm = _engine_for(m1, eidx, imap, cap=20,
                                          metrics=metrics)
        swapper = HotSwapper(engine)
        reqs = _requests(rng, names, E, 30)
        s1 = engine.score_requests(reqs)
        row = (rng.normal(size=d) * (rng.random(d) < 0.1)).astype(np.float32)
        assert swapper.apply_delta("per_user", "user1", row)
        assert swapper.delta_version == 1

        assert swapper.swap(dir2) is True
        assert swapper.delta_version == 0  # fresh generation
        assert isinstance(engine.store.coordinates["per_user"],
                          CompactRandomCoordinate)
        s2 = engine.score_requests(reqs)
        assert not np.array_equal(s1, s2)
        # the new generation serves EXACTLY what a fresh engine built from
        # the in-memory m2 serves (disk roundtrip + swap changed nothing)
        _, eng_ref, _ = _engine_for(m2, eidx, imap, cap=20)
        np.testing.assert_array_equal(s2, eng_ref.score_requests(reqs))
        # ... and the batch scores to float tolerance
        xs = densify_features(reqs, {"all": imap}, len(reqs))
        ids = np.asarray([eidx.get(r.ids["userId"]) for r in reqs], np.int64)
        gd = GameData(y=np.zeros(len(reqs)), features={"all": xs["all"]},
                      id_tags={"userId": ids})
        np.testing.assert_allclose(s2, np.asarray(m2.score(gd), s2.dtype),
                                   rtol=2e-5, atol=1e-6)
        # same-shape swap reused the warm executables: zero new compiles
        assert engine.compile_count == n_warm

    def test_compact_compile_accounting_parity(self, rng):
        """Every compact AOT executable is counted by the runtime probe
        under the serving.engine site (jax_compiles_total parity)."""
        from photon_ml_tpu import obs
        from photon_ml_tpu.obs.registry import MetricsRegistry

        names, imap, eidx, fixed, dense_re, compact_re = _compact_fixture(rng)
        reg = MetricsRegistry()
        prev = obs.set_registry(reg)
        try:
            m = GameModel(models={"fixed": fixed, "per_user": compact_re})
            _, engine, n_warm = _engine_for(m, eidx, imap)
            total = sum(reg.counter_series("jax_compiles_total").values())
            assert n_warm > 0 and total == engine.compile_count == n_warm
        finally:
            obs.set_registry(prev)

    def test_device_copy_cache(self, rng):
        """Model score() uploads the coefficient arrays once per instance;
        dataclasses.replace (the mutation idiom) invalidates naturally."""
        import dataclasses

        names, imap, eidx, fixed, dense_re, compact_re = _compact_fixture(rng)
        n, d = 20, dense_re.w_stack.shape[1]
        gd = GameData(y=np.zeros(n),
                      features={"all": rng.normal(size=(n, d))},
                      id_tags={"userId": rng.integers(0, 10, size=n)})
        s1 = np.asarray(compact_re.score(gd))
        cache1 = compact_re._dev_cache
        s2 = np.asarray(compact_re.score(gd))
        assert compact_re._dev_cache is cache1  # reused, not rebuilt
        np.testing.assert_array_equal(s1, s2)
        patched = dataclasses.replace(
            compact_re, values=compact_re.values * 2.0)
        assert getattr(patched, "_dev_cache", None) is None
        s3 = np.asarray(patched.score(gd))
        assert not np.array_equal(s1, s3)
        # dense twin caches too
        dense_re.score(gd)
        c = dense_re._dev_cache
        dense_re.score(gd)
        assert dense_re._dev_cache is c


# ---------------------------------------------------------------------------
# obs wiring: solve-latency histogram family bounds
# ---------------------------------------------------------------------------

class TestSolveObs:
    def test_solve_bucket_histogram_uses_family_bounds(self, rng):
        from photon_ml_tpu import obs
        from photon_ml_tpu.obs.registry import (MetricsRegistry,
                                                family_bounds)

        reg = MetricsRegistry()
        prev = obs.set_registry(reg)
        try:
            data = _glmix(rng, n_users=6, per_user=20)
            coords = _coords(data)
            coords["per-user"].update(np.zeros(data.num_samples))
            series = reg.histogram_series("solve_bucket_seconds")
            assert series, "no solve_bucket_seconds histogram recorded"
            # the registered family ladder (100µs..~7min), not the default
            assert family_bounds("solve_bucket_seconds")[0] == 1e-4
            snap = reg.snapshot()["histograms"]
            assert any(k.startswith("solve_bucket_seconds") for k in snap)
        finally:
            obs.set_registry(prev)

    def test_family_bounds_applied_to_new_series(self):
        from photon_ml_tpu.obs.registry import (MetricsRegistry,
                                                set_family_bounds)

        set_family_bounds("solve_path_test_seconds", [0.1, 1.0, 10.0])
        reg = MetricsRegistry()
        reg.observe("solve_path_test_seconds", 0.5)
        h = reg._histograms[("solve_path_test_seconds", ())]
        assert h.bounds == (0.1, 1.0, 10.0)
        assert h.counts == [0, 1, 0, 0]
        # prometheus exposition uses the per-family ladder
        text = reg.to_prometheus()
        assert 'solve_path_test_seconds_bucket{le="0.1"} 0' in text
        assert 'solve_path_test_seconds_bucket{le="1.0"} 1' in text
