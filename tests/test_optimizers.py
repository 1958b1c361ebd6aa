"""Optimizer-kernel tests.

Reference analog: OptimizerIntegTest with a known-minimum objective
(photon-lib integTest) — here scipy.optimize is the golden reference, plus
vmap (batched-entity) semantics that the reference has no analog for.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.optimize as sopt

from photon_ml_tpu.core import GLMObjective, Regularization, losses
from photon_ml_tpu.core.batch import dense_batch
from photon_ml_tpu.opt import (
    SolverConfig,
    box_arrays,
    make_solver,
    minimize_lbfgs,
    minimize_owlqn,
    minimize_tron,
)
from photon_ml_tpu.opt.lbfgs import push_pair, two_loop_direction
from photon_ml_tpu.opt.solve import compute_variances
from photon_ml_tpu.types import ConvergenceReason, OptimizerType, VarianceComputationType

D = 6


def _logistic_problem(rng, n=200, d=D, l2=0.1, seed_shift=0.0):
    x = rng.normal(size=(n, d)) + seed_shift
    w_true = rng.normal(size=d)
    p = 1.0 / (1.0 + np.exp(-(x @ w_true)))
    y = (rng.random(n) < p).astype(float)
    batch = dense_batch(x, y)
    obj = GLMObjective(loss=losses.logistic_loss, reg=Regularization(l2=l2))
    return obj, batch


def _scipy_min(obj, batch, d=D):
    f = lambda w: np.asarray(obj.value(jnp.asarray(w), batch))
    g = lambda w: np.asarray(obj.gradient(jnp.asarray(w), batch))
    res = sopt.minimize(f, np.zeros(d), jac=g, method="L-BFGS-B",
                        options={"maxiter": 500, "ftol": 1e-15, "gtol": 1e-12})
    return res


def test_lbfgs_matches_scipy(rng):
    obj, batch = _logistic_problem(rng)
    solve = make_solver(obj, OptimizerType.LBFGS)
    res = jax.jit(solve)(jnp.zeros(D), batch)
    ref = _scipy_min(obj, batch)
    np.testing.assert_allclose(res.value, ref.fun, rtol=1e-8)
    np.testing.assert_allclose(res.w, ref.x, rtol=1e-4, atol=1e-6)
    assert res.convergence_reason() in (
        ConvergenceReason.FUNCTION_VALUES_CONVERGED,
        ConvergenceReason.GRADIENT_CONVERGED,
    )


def test_lbfgs_quadratic_exact(rng):
    """On a quadratic, L-BFGS must hit the known minimum fast."""
    a = rng.normal(size=(D, D))
    h = a @ a.T + np.eye(D)
    b = rng.normal(size=D)
    w_star = np.linalg.solve(h, b)
    hj, bj = jnp.asarray(h), jnp.asarray(b)

    def vg(w):
        return 0.5 * w @ hj @ w - bj @ w, hj @ w - bj

    res = minimize_lbfgs(vg, jnp.zeros(D), SolverConfig(max_iters=100, tolerance=1e-12))
    np.testing.assert_allclose(res.w, w_star, rtol=1e-6, atol=1e-8)
    assert int(res.iterations) < 30


def test_tron_matches_scipy(rng):
    obj, batch = _logistic_problem(rng)
    solve = make_solver(obj, OptimizerType.TRON,
                        SolverConfig(max_iters=50, tolerance=1e-10, max_cg=20))
    res = jax.jit(solve)(jnp.zeros(D), batch)
    ref = _scipy_min(obj, batch)
    np.testing.assert_allclose(res.value, ref.fun, rtol=1e-9)
    np.testing.assert_allclose(res.w, ref.x, rtol=1e-4, atol=1e-6)


def test_tron_poisson(rng):
    x = rng.normal(size=(150, D)) * 0.3
    y = rng.poisson(1.5, size=150).astype(float)
    batch = dense_batch(x, y)
    obj = GLMObjective(loss=losses.poisson_loss, reg=Regularization(l2=0.5))
    res = jax.jit(make_solver(obj, OptimizerType.TRON,
                              SolverConfig(max_iters=50, tolerance=1e-10)))(jnp.zeros(D), batch)
    ref = _scipy_min(obj, batch)
    np.testing.assert_allclose(res.value, ref.fun, rtol=1e-8)


def test_owlqn_l1_sparsity_and_value(rng):
    obj, batch = _logistic_problem(rng, l2=0.0)
    l1 = 12.0
    obj = obj.replace(reg=Regularization(l1=l1))
    solve = make_solver(obj, OptimizerType.LBFGS)  # auto-routes to OWLQN
    res = jax.jit(solve)(jnp.zeros(D), batch)

    # scipy reference: smooth + l1 via double-variable trick w = p - n, p,n >= 0
    def f(z):
        w = z[:D] - z[D:]
        return float(obj.raw_value(jnp.asarray(w), batch)) + l1 * z.sum()

    def g(z):
        w = jnp.asarray(z[:D] - z[D:])
        gs = np.asarray(obj.gradient(w, batch)) - 0.0  # no l2
        return np.concatenate([gs + l1, -gs + l1])

    ref = sopt.minimize(f, np.zeros(2 * D), jac=g, method="L-BFGS-B",
                        bounds=[(0, None)] * (2 * D), options={"maxiter": 1000, "ftol": 1e-15})
    np.testing.assert_allclose(res.value, ref.fun, rtol=1e-6)
    # strong L1 must produce some exact zeros
    assert int(jnp.sum(res.w == 0.0)) > 0


def test_box_constraints(rng):
    obj, batch = _logistic_problem(rng)
    box = box_arrays({0: (-0.05, 0.05), 3: (0.0, np.inf)}, D, np.float64)
    solve = make_solver(obj, OptimizerType.LBFGS, box=(jnp.asarray(box[0]), jnp.asarray(box[1])))
    res = jax.jit(solve)(jnp.zeros(D), batch)
    assert -0.05 <= float(res.w[0]) <= 0.05
    assert float(res.w[3]) >= 0.0
    ref = sopt.minimize(
        lambda w: np.asarray(obj.value(jnp.asarray(w), batch)),
        np.zeros(D),
        jac=lambda w: np.asarray(obj.gradient(jnp.asarray(w), batch)),
        method="L-BFGS-B",
        bounds=[(-0.05, 0.05), (None, None), (None, None), (0.0, None), (None, None), (None, None)],
        options={"maxiter": 500, "ftol": 1e-15},
    )
    np.testing.assert_allclose(res.value, ref.fun, rtol=1e-5)


def test_vmap_batched_entities(rng):
    """The random-effect shape: vmap the SAME solver over many entity problems
    with different data; each lane must match its own scipy solve."""
    n_entities, n, d = 5, 40, 4
    xs = rng.normal(size=(n_entities, n, d))
    ws = rng.normal(size=(n_entities, d))
    ys = (rng.random((n_entities, n)) < 1.0 / (1.0 + np.exp(-np.einsum("end,ed->en", xs, ws)))).astype(float)
    obj = GLMObjective(loss=losses.logistic_loss, reg=Regularization(l2=0.3))
    solve = make_solver(obj, OptimizerType.LBFGS, SolverConfig(max_iters=200, tolerance=1e-9))

    def solve_one(x, y):
        return solve(jnp.zeros(d), dense_batch(x, y))

    res = jax.jit(jax.vmap(solve_one))(jnp.asarray(xs), jnp.asarray(ys))
    for e in range(n_entities):
        batch_e = dense_batch(xs[e], ys[e])
        ref = sopt.minimize(
            lambda w: np.asarray(obj.value(jnp.asarray(w), batch_e)),
            np.zeros(d),
            jac=lambda w: np.asarray(obj.gradient(jnp.asarray(w), batch_e)),
            method="L-BFGS-B", options={"maxiter": 500, "ftol": 1e-15},
        )
        np.testing.assert_allclose(res.value[e], ref.fun, rtol=1e-8)
        np.testing.assert_allclose(res.w[e], ref.x, rtol=1e-3, atol=1e-5)


def test_convergence_reasons_and_tracker(rng):
    obj, batch = _logistic_problem(rng)
    # max-iterations: cap at 2
    res = minimize_lbfgs(lambda w: obj.value_and_grad(w, batch), jnp.zeros(D),
                         SolverConfig(max_iters=2, tolerance=1e-16))
    assert res.convergence_reason() == ConvergenceReason.MAX_ITERATIONS
    assert int(res.iterations) == 2
    # tracker recorded initial + 2 states, monotone decreasing
    vals = np.asarray(res.tracker.values[: int(res.tracker.num_states)])
    assert len(vals) == 3 and vals[1] <= vals[0] and vals[2] <= vals[1]
    # stationary start: zero gradient at optimum of trivial problem
    res2 = minimize_lbfgs(lambda w: (jnp.vdot(w, w), 2 * w), jnp.zeros(D))
    assert res2.convergence_reason() == ConvergenceReason.GRADIENT_CONVERGED
    assert int(res2.iterations) == 0


def test_variances(rng):
    obj, batch = _logistic_problem(rng)
    res = jax.jit(make_solver(obj, OptimizerType.LBFGS))(jnp.zeros(D), batch)
    h = np.asarray(obj.hessian(res.w, batch))
    v_simple = compute_variances(obj, res.w, batch, VarianceComputationType.SIMPLE)
    np.testing.assert_allclose(v_simple, 1.0 / np.diagonal(h), rtol=1e-8)
    v_full = compute_variances(obj, res.w, batch, VarianceComputationType.FULL)
    np.testing.assert_allclose(v_full, np.diagonal(np.linalg.inv(h)), rtol=1e-7)
    assert compute_variances(obj, res.w, batch, VarianceComputationType.NONE) is None


def test_warm_start_fewer_iterations(rng):
    """Warm start (reference GameEstimator warm-start between configs) must
    converge in fewer iterations than cold start."""
    obj, batch = _logistic_problem(rng)
    solve = make_solver(obj, OptimizerType.LBFGS)
    cold = solve(jnp.zeros(D), batch)
    warm = solve(cold.w, batch)
    assert int(warm.iterations) <= 2
    np.testing.assert_allclose(warm.value, cold.value, rtol=1e-9)


# ---------------------------------------------------------------------------
# Legacy reg-path training API (reference ModelTraining.scala:106-228)
# ---------------------------------------------------------------------------

def test_train_glm_reg_path(rng):
    import scipy.optimize as sopt
    import scipy.special as spec

    from photon_ml_tpu.models.training import train_glm_reg_path
    from photon_ml_tpu.types import OptimizerType, TaskType

    n, d = 500, 6
    x = rng.normal(size=(n, d))
    w_true = rng.normal(size=d)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-x @ w_true))).astype(float)

    lams = [0.1, 10.0, 1.0]
    path, trackers = train_glm_reg_path(x, y, TaskType.LOGISTIC_REGRESSION,
                                        lams, dtype=np.float64)

    # trained (and returned) in descending-λ order
    assert [lam for lam, _ in path] == [10.0, 1.0, 0.1]
    assert set(trackers) == {0.1, 1.0, 10.0}

    # each path point matches an independent scipy fit of the same objective
    for lam, model in path:
        def nll(w):
            z = x @ w
            return np.sum(np.logaddexp(0, z) - y * z) + 0.5 * lam * w @ w

        def grad(w):
            return x.T @ (spec.expit(x @ w) - y) + lam * w

        ref = sopt.minimize(nll, np.zeros(d), jac=grad, method="L-BFGS-B",
                            options={"maxiter": 200, "gtol": 1e-10})
        np.testing.assert_allclose(model.coefficients.means, ref.x,
                                   rtol=2e-4, atol=2e-4)

    # heavier regularization -> smaller coefficients
    norms = {lam: np.linalg.norm(m.coefficients.means) for lam, m in path}
    assert norms[10.0] < norms[1.0] < norms[0.1]


def test_train_glm_reg_path_warm_start_model(rng):
    from photon_ml_tpu.models.glm import Coefficients, GLMModel
    from photon_ml_tpu.models.training import train_glm_reg_path
    from photon_ml_tpu.types import TaskType

    n, d = 200, 4
    x = rng.normal(size=(n, d))
    y = (rng.random(n) < 0.5).astype(float)

    warm = {5.0: GLMModel(Coefficients(means=np.full(d, 0.3)),
                          TaskType.LOGISTIC_REGRESSION)}
    path, _ = train_glm_reg_path(x, y, TaskType.LOGISTIC_REGRESSION, [1.0],
                                 warm_start_models=warm, dtype=np.float64)
    path0, _ = train_glm_reg_path(x, y, TaskType.LOGISTIC_REGRESSION, [1.0],
                                  dtype=np.float64)
    # both converge to the same optimum; warm start just changes the route
    np.testing.assert_allclose(path[0][1].coefficients.means,
                               path0[0][1].coefficients.means, atol=1e-4)


def test_summarize_solver_results(rng):
    """Reference RandomEffectOptimizationTracker summary: reason counts +
    iteration/value stats over many (vmapped) solves, masked lanes excluded."""
    import jax.numpy as jnp

    from photon_ml_tpu.opt.types import SolverResult, summarize_solver_results
    from photon_ml_tpu.types import ConvergenceReason

    batched = SolverResult(
        w=jnp.zeros((4, 3)),
        value=jnp.asarray([1.0, 2.0, 3.0, 99.0]),
        grad_norm=jnp.zeros(4),
        iterations=jnp.asarray([5, 7, 9, 100], jnp.int32),
        reason=jnp.asarray([ConvergenceReason.GRADIENT_CONVERGED,
                            ConvergenceReason.GRADIENT_CONVERGED,
                            ConvergenceReason.MAX_ITERATIONS,
                            ConvergenceReason.MAX_ITERATIONS], jnp.int32),
    )
    # last lane is padding -> excluded
    s = summarize_solver_results([batched],
                                 valid_masks=[np.asarray([1, 1, 1, 0], bool)])
    assert s["count"] == 3
    assert s["convergence_reasons"] == {"GRADIENT_CONVERGED": 2,
                                        "MAX_ITERATIONS": 1}
    assert s["iterations"]["max"] == 9
    np.testing.assert_allclose(s["iterations"]["mean"], 7.0)
    np.testing.assert_allclose(s["final_value"]["mean"], 2.0)

    assert summarize_solver_results([])["count"] == 0


def test_re_coordinate_tracker_summary(rng):
    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.game import GameData, RandomEffectConfig
    from photon_ml_tpu.game.coordinate import build_coordinate
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.types import TaskType

    n_users, per = 7, 30
    n = n_users * per
    x = rng.normal(size=(n, 3))
    y = (rng.random(n) < 0.5).astype(float)
    uids = np.repeat(np.arange(n_users), per)
    data = GameData(y=y, features={"u": x}, id_tags={"uid": uids})
    coord = build_coordinate(
        "re", data,
        RandomEffectConfig(random_effect_type="uid", feature_shard="u",
                           solver=SolverConfig(max_iters=50),
                           reg=Regularization(l2=1.0)),
        TaskType.LOGISTIC_REGRESSION)
    _, trackers = coord.update(np.zeros(n))
    s = coord.tracker_summary(trackers)
    assert s["count"] == n_users  # padded lanes excluded
    assert sum(s["convergence_reasons"].values()) == n_users


def test_select_best_glm(rng):
    """Reference ModelSelection.scala: best λ on validation by the
    task-default metric (AUC for classifiers)."""
    from photon_ml_tpu.models.training import select_best_glm, train_glm_reg_path
    from photon_ml_tpu.types import TaskType

    x = rng.normal(size=(600, 5))
    w = rng.normal(size=5) * 2
    y = (rng.random(600) < 1.0 / (1.0 + np.exp(-x @ w))).astype(float)
    path, _ = train_glm_reg_path(x[:400], y[:400], TaskType.LOGISTIC_REGRESSION,
                                 [0.01, 1.0, 1000.0], dtype=np.float64)
    lam, model = select_best_glm(path, x[400:], y[400:])
    assert lam != 1000.0  # the crushed model can't win on AUC
    # metric override: logistic loss picks a (possibly different) minimum
    lam2, _ = select_best_glm(path, x[400:], y[400:], metric="logistic_loss")
    assert lam2 in (0.01, 1.0)
    with pytest.raises(ValueError):
        select_best_glm([], x, y)


def test_f32_plateau_exits_without_thrashing():
    """Regression for the working-precision plateau pathology
    (opt/linesearch.py approximate-Wolfe slack + opt/types.PLATEAU_ULPS):
    when tolerance*|f0| sits BELOW one ulp of f (a large constant offset
    makes ulp(f) huge), the solver must still exit via the value-based
    reasons in a handful of iterations — before the fix it burned
    max_iters x max_linesearch objective passes failing exact-Armijo at
    the rounding floor."""
    import jax.numpy as jnp

    from photon_ml_tpu.opt.lbfgs import minimize_lbfgs
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.types import ConvergenceReason

    big = jnp.float32(1e8)  # ulp(1e8) = 8.0 in f32

    def vg(w):
        f = big + 0.5 * jnp.sum((w - 1.0) ** 2)
        return f.astype(jnp.float32), (w - 1.0).astype(jnp.float32)

    w0 = jnp.zeros(4, jnp.float32)
    # tolerance*|f0| = 1e-9 * 1e8 = 0.1 << ulp(f) = 8 -> the floor must act
    res = minimize_lbfgs(vg, w0, SolverConfig(max_iters=50, tolerance=1e-9,
                                              max_linesearch=25))
    # the solve must exit via the VALUE-based reasons in a couple of steps;
    # before the fix the exact-Armijo test failed every trial at the
    # rounding floor and the exit reason was OBJECTIVE_NOT_IMPROVING after
    # a full max_linesearch of wasted evaluations
    assert int(res.reason) in (int(ConvergenceReason.FUNCTION_VALUES_CONVERGED),
                               int(ConvergenceReason.GRADIENT_CONVERGED)), \
        int(res.reason)
    assert int(res.iterations) <= 5, int(res.iterations)
    # NOTE deliberately no optimum assertion: at this offset the WHOLE
    # remaining descent (<= 2.0) sits below one ulp of f (8.0) — the
    # objective cannot resolve it, and stopping promptly is the point


class TestNewtonSoa:
    """The narrow-lane structure-of-arrays Newton solver (opt/newton_soa.py)
    must reach the SAME optimum as the vmapped generic path — it replaces
    it on the flagship GLMix random-effect shapes (dense, d<=16, smooth
    l2), so parity here is what licenses the swap."""

    def _bucket(self, rng, L=7, cap=12, d=5, loss_name="logistic"):
        import numpy as np

        x = rng.normal(size=(L, cap, d)).astype(np.float64)
        off = (rng.normal(size=(L, cap)) * 0.2).astype(np.float64)
        wt = (rng.random(size=(L, cap)) + 0.5).astype(np.float64)
        wt[:, cap - 3:] = 0.0          # padded rows
        x[:, cap - 3:, :] = 0.0
        off[:, cap - 3:] = 0.0
        wt[L - 1] = 0.0                # an entirely-padded lane
        x[L - 1] = 0.0
        logits = np.einsum("lcd,d->lc", x, rng.normal(size=d))
        if loss_name == "poisson":
            y = rng.poisson(np.exp(np.clip(logits * 0.3, -3, 3)))
        elif loss_name == "squared":
            y = logits + rng.normal(size=logits.shape) * 0.1
        else:
            y = (rng.random(size=logits.shape) < 1 / (1 + np.exp(-logits)))
        y = np.where(wt > 0, y, 0.0).astype(np.float64)
        l2 = np.where(np.arange(L) % 2 == 0, 0.5, 2.0).astype(np.float64)
        return x, y, off, wt, l2

    @pytest.mark.parametrize("loss_name", ["logistic", "squared", "poisson"])
    def test_matches_vmapped_lbfgs(self, rng, loss_name):
        import numpy as np

        from photon_ml_tpu.core.batch import DenseBatch
        from photon_ml_tpu.core.losses import loss_by_name
        from photon_ml_tpu.core.objective import GLMObjective
        from photon_ml_tpu.core.regularization import Regularization
        from photon_ml_tpu.opt.newton_soa import solve_newton_soa
        from photon_ml_tpu.opt.solve import make_solver

        x, y, off, wt, l2 = self._bucket(rng, loss_name=loss_name)
        L, cap, d = x.shape
        loss = loss_by_name(loss_name)
        cfg = SolverConfig(max_iters=200, tolerance=1e-10)

        solve = make_solver(GLMObjective(loss=loss), config=cfg)

        def one(lam, xx, yy, oo, ww):
            return solve(jnp.zeros(d, jnp.float64),
                         DenseBatch(x=xx, y=yy, offset=oo, weight=ww),
                         objective=GLMObjective(
                             loss=loss, reg=Regularization(l2=lam)))

        res_v = jax.vmap(one)(jnp.asarray(l2), jnp.asarray(x),
                              jnp.asarray(y), jnp.asarray(off),
                              jnp.asarray(wt))

        res_s = solve_newton_soa(
            loss, jnp.zeros((d, L), jnp.float64),
            jnp.asarray(x.transpose(1, 2, 0)), jnp.asarray(y.T),
            jnp.asarray(off.T), jnp.asarray(wt.T), jnp.asarray(l2), cfg)

        # same optimum to SOLVER tolerance: the SoA side lands at machine-
        # precision gradients (verified vs scipy in development); the vmapped
        # L-BFGS side may exit a few ulps earlier via its value-plateau
        # check, so the band is solver-scale, not machine-scale
        np.testing.assert_allclose(np.asarray(res_s.w.T),
                                   np.asarray(res_v.w),
                                   rtol=1e-3, atol=2e-4)
        # the weightless lane's optimum is exactly 0 under pure l2
        np.testing.assert_allclose(np.asarray(res_s.w.T)[L - 1], 0.0,
                                   atol=1e-12)
        assert int(jnp.max(res_s.iterations)) <= 25  # Newton, not LBFGS

    def test_cholesky_solve_matches_numpy(self, rng):
        import numpy as np

        from photon_ml_tpu.opt.newton_soa import _cholesky_solve_soa

        L, d = 11, 6
        a = rng.normal(size=(L, d, d))
        H = np.einsum("lij,lkj->lik", a, a) + np.eye(d) * 0.1
        g = rng.normal(size=(L, d))
        hh = [[jnp.asarray(H[:, i, j]) for j in range(d)] for i in range(d)]
        x = _cholesky_solve_soa(hh, jnp.asarray(g.T),
                                jnp.asarray(1e-300))
        ref = np.stack([np.linalg.solve(H[i], g[i]) for i in range(L)])
        np.testing.assert_allclose(np.asarray(x.T), ref, rtol=1e-8,
                                   atol=1e-10)

    def test_line_search_failure_keeps_iterate(self):
        """A non-finite Newton step (Hessian overflow -> NaN Cholesky) must
        not poison the lane: the fully rejected line search KEEPS the
        iterate (the pre-fix code computed w - 0*NaN = NaN) and reports
        OBJECTIVE_NOT_IMPROVING like the generic solvers, while healthy
        lanes in the same bucket still solve."""
        import numpy as np

        from photon_ml_tpu.core.losses import loss_by_name
        from photon_ml_tpu.opt.newton_soa import solve_newton_soa
        from photon_ml_tpu.types import ConvergenceReason

        L, cap, d = 2, 4, 3
        x = np.zeros((cap, d, L))
        x[:, :, 0] = 1e160          # H entries overflow -> inf/inf = NaN
        rng = np.random.default_rng(3)
        x[:, :, 1] = rng.normal(size=(cap, d))
        y = np.zeros((cap, L))
        off = np.zeros((cap, L))
        wt = np.ones((cap, L))
        l2 = np.full(L, 0.5)
        res = solve_newton_soa(
            loss_by_name("poisson"), jnp.zeros((d, L), jnp.float64),
            jnp.asarray(x), jnp.asarray(y), jnp.asarray(off),
            jnp.asarray(wt), jnp.asarray(l2),
            SolverConfig(max_iters=50, tolerance=1e-9))
        w = np.asarray(res.w)
        assert np.isfinite(w).all(), w
        np.testing.assert_array_equal(w[:, 0], 0.0)   # iterate preserved
        assert int(res.reason[0]) == int(
            ConvergenceReason.OBJECTIVE_NOT_IMPROVING)
        assert int(res.reason[1]) != int(
            ConvergenceReason.OBJECTIVE_NOT_IMPROVING)
        assert np.abs(w[:, 1]).max() > 0               # healthy lane solved


# ---------------------------------------------------------------------------
# The L-BFGS history, newest-first (PR 26).  The plain references live here:
# the circular-buffer recursion the solver had before (every read through
# ``pos``, a per-lane gather under vmap), to be matched EXACTLY, and a
# textbook recursion over a Python list of pairs, to rounding.
# ---------------------------------------------------------------------------


def _circular_two_loop(g, s_hist, y_hist, rho, count, pos):
    m = rho.shape[0]

    def newest_first(j):
        return (pos - 1 - j) % m

    def loop1(j, carry):
        q, alphas = carry
        i = newest_first(j)
        a = rho[i] * jnp.vdot(s_hist[i], q)
        a = jnp.where(j < count, a, 0.0)
        q = q - a * y_hist[i]
        return q, alphas.at[i].set(a)

    q, alphas = jax.lax.fori_loop(0, m, loop1, (g, jnp.zeros_like(rho)))
    newest = newest_first(0)
    sy = jnp.vdot(s_hist[newest], y_hist[newest])
    yy = jnp.vdot(y_hist[newest], y_hist[newest])
    gamma = jnp.where((count > 0) & (yy > 0), sy / jnp.where(yy == 0, 1.0, yy), 1.0)

    def loop2(j, r):
        jj = m - 1 - j
        i = newest_first(jj)
        b = rho[i] * jnp.vdot(y_hist[i], r)
        return r + jnp.where(jj < count, 1.0, 0.0) * ((alphas[i] - b) * s_hist[i])

    return -jax.lax.fori_loop(0, m, loop2, gamma * q)


def _circular_push(hist, s, y, ok):
    s_hist, y_hist, rho, count, pos = hist
    m = rho.shape[0]
    sy = jnp.vdot(s, y)
    admit = ok & (sy > 1e-12 * jnp.maximum(jnp.vdot(y, y), 1e-30))
    return (jnp.where(admit, s_hist.at[pos].set(s), s_hist),
            jnp.where(admit, y_hist.at[pos].set(y), y_hist),
            jnp.where(admit, rho.at[pos].set(1.0 / jnp.where(sy == 0, 1.0, sy)), rho),
            jnp.where(admit, jnp.minimum(count + 1, m), count),
            jnp.where(admit, (pos + 1) % m, pos))


def _textbook_direction(g, pairs, m):
    """Nocedal & Wright, algorithm 7.4, in float64 over the last ``m`` pairs
    (oldest first)."""
    pairs = [(np.asarray(s, np.float64), np.asarray(y, np.float64))
             for s, y in pairs[-m:]]
    q, alphas = np.asarray(g, np.float64), []
    for s, y in reversed(pairs):
        alphas.append((s @ q) / (s @ y))
        q = q - alphas[-1] * y
    r = q * ((pairs[-1][0] @ pairs[-1][1]) / (pairs[-1][1] @ pairs[-1][1])
             if pairs else 1.0)
    for (s, y), a in zip(pairs, reversed(alphas)):
        r = r + (a - (y @ r) / (s @ y)) * s
    return -r


# steps offered to the history: "a" a pair with s.y > 0 from an accepted
# step, "n" one with s.y < 0 (refused by the cautious rule), "f" a failed
# line search (ok = False)
_HISTORY_PATTERNS = {
    "empty": "",
    "partly_filled": "aaaa",
    "full": "aaaaaa",
    "wrapped": "aaaaaaaaa",
    "wrapped_twice": "aaaaaaaaaaaaaaa",
    "refused_in_between": "anaafaanaanfaa",
    "refused_first_and_last": "nfaaaaaaanf",
}
_STEPS = max(len(p) for p in _HISTORY_PATTERNS.values())


def _history_steps(pattern, d, dtype, seed):
    """[T, d] s, [T, d] y, [T] ok, padded to _STEPS with failed steps."""
    rng = np.random.default_rng(seed)
    a = rng.normal(size=(d, d)) * 0.2
    hess = a @ a.T + np.eye(d)
    s = rng.normal(size=(_STEPS, d))
    y = s @ hess
    ok = np.zeros(_STEPS, bool)
    for t, kind in enumerate(pattern):
        ok[t] = kind != "f"
        if kind == "n":
            y[t] = -y[t]
    return s.astype(dtype), y.astype(dtype), ok


def _run_histories(s, y, ok, g, m):
    """Offer the steps one by one; the direction from the newest-first
    history, the direction from the circular one, and both final states."""
    d = s.shape[-1]
    zero = (jnp.zeros((m, d), s.dtype), jnp.zeros((m, d), s.dtype),
            jnp.zeros((m,), s.dtype), jnp.int32(0))

    def step(carry, step_t):
        new, circ = carry
        return (push_pair(*new, *step_t), _circular_push(circ, *step_t)), None

    (new, circ), _ = jax.lax.scan(step, (zero, zero + (jnp.int32(0),)),
                                  (s, y, ok))
    return two_loop_direction(g, *new), _circular_two_loop(g, *circ), new, circ


_run_histories_jit = jax.jit(_run_histories, static_argnums=4)


def _admitted(pattern, s, y):
    """The pairs the history must hold, oldest first."""
    return [(s[t], y[t]) for t, kind in enumerate(pattern) if kind == "a"]


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("pattern", sorted(_HISTORY_PATTERNS))
def test_newest_first_history_matches_circular_and_textbook(pattern, dtype):
    m, d = 6, 5
    s, y, ok = _history_steps(_HISTORY_PATTERNS[pattern], d, dtype, seed=len(pattern))
    g = np.random.default_rng(7).normal(size=d).astype(dtype)
    got, circ_dir, new, circ = _run_histories_jit(s, y, ok, g, m)
    # same operations in the same order on the same values: bit for bit
    np.testing.assert_array_equal(np.asarray(got), np.asarray(circ_dir))
    admitted = _admitted(_HISTORY_PATTERNS[pattern], s, y)
    count, pos = int(circ[3]), int(circ[4])
    assert int(new[3]) == count == min(len(admitted), m)
    # slot j is the j-th newest admitted pair, where the circular buffer
    # holds it at (pos - 1 - j) % m
    for j in range(count):
        np.testing.assert_array_equal(new[0][j], admitted[-1 - j][0])
        np.testing.assert_array_equal(new[1][j], admitted[-1 - j][1])
        np.testing.assert_array_equal(new[0][j], circ[0][(pos - 1 - j) % m])
        assert new[2][j] == circ[2][(pos - 1 - j) % m]
    np.testing.assert_allclose(
        got, _textbook_direction(g, admitted, m),
        rtol=2e-4 if dtype == "float32" else 1e-11,
        atol=2e-5 if dtype == "float32" else 1e-12)


@pytest.mark.parametrize("m", [3, 6, 10])
def test_newest_first_history_under_vmap_lanes_at_unlike_fill(m):
    """One vmapped program, every pattern a lane: lanes hold 0 to m pairs,
    wrap or not, and refuse pairs at different steps, so a circular buffer's
    ``pos`` differs from lane to lane while the newest-first slot does not."""
    d, dtype = 5, "float32"
    names = sorted(_HISTORY_PATTERNS)
    steps = [_history_steps(_HISTORY_PATTERNS[p], d, dtype, seed=10 + k)
             for k, p in enumerate(names)]
    s, y, ok = (np.stack(a) for a in zip(*steps))
    g = np.random.default_rng(8).normal(size=(len(names), d)).astype(dtype)
    batched = jax.jit(jax.vmap(lambda *a: _run_histories(*a, m)))
    got, circ_dir, new, circ = batched(s, y, ok, g)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(circ_dir))
    assert len(set(np.asarray(circ[4]).tolist())) > 1  # pos differs by lane
    for k, name in enumerate(names):
        plain = _run_histories_jit(s[k], y[k], ok[k], g[k], m)[0]
        # a batched dot may sum in another order than a plain one
        np.testing.assert_allclose(got[k], plain, rtol=1e-5, atol=1e-6)
        admitted = _admitted(_HISTORY_PATTERNS[name], s[k], y[k])
        assert int(new[3][k]) == min(len(admitted), m)
        np.testing.assert_allclose(got[k], _textbook_direction(g[k], admitted, m),
                                   rtol=2e-4, atol=2e-5)
