"""The L-BFGS's line search on the margins ``z + alpha u`` (PR 28).

One strong-Wolfe state machine (opt/linesearch.py), two evaluators of a
trial step (opt/lbfgs.py): by passes, a trial is one value+grad evaluation;
on the margins (GLMObjective.along) it is elementwise work over [rows] and a
solver trip reads the design twice whatever its trials.  opt/solve.py's rule
chooses.  Held here: the two evaluators agree, the accepted step satisfies
strong Wolfe on the true objective, both paths reach the same optimum, the
carried margins stay at X w, the bound on the reads (in the jaxpr), the rule
at each edge, the frozen traces of the pass evaluator, and the trials
counter.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.core import GLMObjective
from photon_ml_tpu.core.batch import DenseBatch, SparseBatch, dense_batch
from photon_ml_tpu.core.losses import loss_for_task
from photon_ml_tpu.core.normalization import NormalizationContext
from photon_ml_tpu.core.regularization import Regularization
from photon_ml_tpu.opt import lbfgs as lbfgs_module
from photon_ml_tpu.opt.lbfgs import minimize_lbfgs
from photon_ml_tpu.opt.linesearch import strong_wolfe
from photon_ml_tpu.opt.solve import (lbfgs_trials, line_search_kind,
                                     make_solver)
from photon_ml_tpu.opt.types import SolverConfig
from photon_ml_tpu.types import OptimizerType, TaskType

LOSSES = {
    "linear": TaskType.LINEAR_REGRESSION,
    "logistic": TaskType.LOGISTIC_REGRESSION,
    "poisson": TaskType.POISSON_REGRESSION,
    "smoothed_hinge": TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
}
NORMS = ("plain", "factors", "factors_shifts")
N, D, GARBAGE = 96, 6, 24  # the last GARBAGE rows: weight 0, wild values


def problem(loss_name, norm_name, seed=0, dtype=jnp.float64, padding=False):
    """(objective, batch, w, p): offsets, row weights, and rows of weight 0
    that hold garbage (``padding``: every row is one)."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(N, D))
    x[:, 0] = 1.0  # intercept
    w_true = rng.normal(size=D) * 0.5
    z = x @ w_true
    if loss_name == "linear":
        y = z + 0.3 * rng.normal(size=N)
    elif loss_name == "poisson":
        y = rng.poisson(np.exp(np.clip(z, -3, 2))).astype(float)
    else:
        y = (rng.uniform(size=N) < 1 / (1 + np.exp(-z))).astype(float)
    offset = 0.2 * rng.normal(size=N)
    weight = rng.uniform(0.5, 2.0, size=N)
    weight[-GARBAGE:] = 0.0
    # finite in x (0 * inf in X^T r is outside the masking contract), wild
    # in the margins: exp(z) overflows, in float32 z itself does
    x[-GARBAGE:] = 1e30 * rng.normal(size=(GARBAGE, D))
    offset[-GARBAGE:] = 1e6
    if padding:
        weight[:] = 0.0
    norm = NormalizationContext(
        factors=None if norm_name == "plain"
        else jnp.asarray(np.r_[1.0, rng.uniform(0.5, 2.0, D - 1)], dtype),
        shifts=jnp.asarray(np.r_[0.0, rng.normal(size=D - 1)], dtype)
        if norm_name == "factors_shifts" else None)
    objective = GLMObjective(loss=loss_for_task(LOSSES[loss_name]),
                             reg=Regularization(l2=0.7), norm=norm)
    batch = dense_batch(x, y, offset=offset, weight=weight, dtype=dtype)
    w = jnp.asarray(0.3 * rng.normal(size=D), dtype)
    p = jnp.asarray(rng.normal(size=D), dtype)
    return objective, batch, w, p


CASES = [(l, n) for l in LOSSES for n in NORMS]


# -- the two evaluators agree --------------------------------------------------

@pytest.mark.parametrize("loss_name,norm_name", CASES)
def test_margin_and_pass_evaluators_agree(loss_name, norm_name):
    """phi and phi' from z + alpha u against one value_and_grad at
    w + alpha p, to rounding; so does the gradient at a step."""
    objective, batch, w, p = problem(loss_name, norm_name)
    f, g, z = objective.value_grad_margins(w, batch)
    want = objective.value_and_grad(w, batch)
    assert f == want[0] and (g == want[1]).all()  # the same XLA tail
    phi, grad_at = objective.along(w, z, p, batch)
    for alpha in (0.0, 1e-3, 0.25, 1.0):
        f, g = objective.value_and_grad(w + alpha * p, batch)
        got_f, got_d = phi(jnp.asarray(alpha))
        assert np.isfinite(got_f) and np.isfinite(got_d)
        np.testing.assert_allclose(got_f, f, rtol=1e-11)
        np.testing.assert_allclose(got_d, jnp.vdot(g, p), rtol=1e-9,
                                   atol=1e-9 * float(jnp.linalg.norm(g)))
        f_at, g_at, z_at = grad_at(jnp.asarray(alpha))
        np.testing.assert_allclose(f_at, f, rtol=1e-11)
        np.testing.assert_allclose(g_at, g, rtol=1e-9,
                                   atol=1e-11 * float(jnp.abs(g).max()))
        live = np.asarray(batch.weight) > 0
        np.testing.assert_allclose(
            z_at[live], objective.margins(w + alpha * p, batch)[live],
            rtol=1e-11, atol=1e-12)
        assert (np.asarray(z_at)[~live] == 0).all()


@pytest.mark.parametrize("loss_name", LOSSES)
def test_a_lane_that_is_all_padding_searches_the_l2_term_alone(loss_name):
    """Every row of weight 0 and full of garbage: nothing of it reaches a
    reduction, phi is the L2 term."""
    objective, batch, w, p = problem(loss_name, "factors_shifts",
                                     padding=True)
    _, _, z = objective.value_grad_margins(w, batch)
    phi, grad_at = objective.along(w, z, p, batch)
    for alpha in (0.0, 0.5):
        w_at = w + alpha * p
        f, d = phi(jnp.asarray(alpha))
        np.testing.assert_allclose(f, 0.35 * jnp.vdot(w_at, w_at), rtol=1e-12)
        np.testing.assert_allclose(d, 0.7 * jnp.vdot(w_at, p), rtol=1e-12)
        np.testing.assert_allclose(grad_at(jnp.asarray(alpha))[1],
                                   0.7 * w_at, rtol=1e-12)


# -- the accepted step ---------------------------------------------------------

@pytest.mark.parametrize("loss_name,norm_name", CASES)
def test_accepted_step_satisfies_strong_wolfe_on_the_true_objective(
        loss_name, norm_name):
    objective, batch, w, _ = problem(loss_name, norm_name, seed=1)
    f0, g0, z = objective.value_grad_margins(w, batch)
    c1, c2 = 1e-4, 0.9
    # a steepest-descent step too long by far, and one too short
    for scale in (50.0, 1e-3):
        p = -g0
        phi, _ = objective.along(w, z, p, batch)
        dphi0 = jnp.vdot(g0, p)
        ls = strong_wolfe(lambda a: (*phi(a), ()), f0, dphi0, (),
                          jnp.asarray(scale / float(jnp.linalg.norm(g0))),
                          c1=c1, c2=c2)
        assert bool(ls.success) and bool(ls.wolfe)
        f, g = objective.value_and_grad(w + ls.alpha * p, batch)
        assert f <= f0 + c1 * ls.alpha * dphi0 + 1e-12 * abs(f0)
        assert abs(jnp.vdot(g, p)) <= -c2 * dphi0 * (1 + 1e-9)
        np.testing.assert_allclose(ls.phi, f, rtol=1e-11)


# -- make_solver's two paths ---------------------------------------------------

def by_passes(objective, batch, w0, config):
    return minimize_lbfgs(lambda w: objective.value_and_grad(w, batch), w0,
                          config)


@pytest.mark.parametrize("loss_name,norm_name", CASES)
def test_both_paths_reach_the_same_optimum(loss_name, norm_name):
    objective, batch, _, _ = problem(loss_name, norm_name, seed=2)
    config = SolverConfig(max_iters=60, tolerance=1e-9)
    w0 = jnp.zeros(D)
    assert lbfgs_trials(objective, batch, w0) == "margins"
    got = jax.jit(make_solver(objective, config=config))(w0, batch)
    want = jax.jit(lambda w, b: by_passes(objective, b, w, config))(w0, batch)
    assert int(got.reason) == int(want.reason) != 0
    assert abs(int(got.iterations) - int(want.iterations)) <= 1
    np.testing.assert_allclose(got.value, want.value, rtol=1e-9)
    np.testing.assert_allclose(got.w, want.w,
                               atol=1e-5 * float(jnp.abs(want.w).max()))
    # the search itself took the same steps
    assert abs(int(got.trials) - int(want.trials)) <= 2


@pytest.mark.parametrize("loss_name", LOSSES)
def test_both_paths_agree_lane_by_lane_under_vmap(loss_name):
    """A vmapped solve over lanes of differing difficulty, one of them all
    padding: float32, as the chip runs it."""
    lanes = []
    for seed in range(5):
        objective, batch, _, _ = problem(loss_name, "plain", seed=10 + seed,
                                         dtype=jnp.float32,
                                         padding=seed == 4)
        lanes.append(batch)
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *lanes)
    config = SolverConfig(max_iters=40, tolerance=1e-6)
    w0 = jnp.zeros((5, D), jnp.float32)
    got = jax.jit(jax.vmap(make_solver(objective, config=config)))(
        w0, stacked)
    want = jax.jit(jax.vmap(
        lambda w, b: by_passes(objective, b, w, config)))(w0, stacked)
    assert (np.asarray(got.reason) != 0).all()
    assert np.abs(np.asarray(got.iterations)
                  - np.asarray(want.iterations)).max() <= 1
    np.testing.assert_allclose(got.w, want.w, atol=2e-3)
    np.testing.assert_allclose(got.value, want.value, rtol=1e-5)
    assert int(got.iterations[4]) == 0 and int(got.trials[4]) == 0


# -- the margins carried from step to step -------------------------------------

@pytest.mark.parametrize("loss_name", LOSSES)
def test_carried_margins_stay_at_the_design_times_the_coefficients(loss_name):
    """float32, 30 steps of ``z <- z + alpha u`` (a solve's most in the
    benchmark's cells): what the carried margins drift from ``X w`` by is a
    rounding a step, under 1e-6 of the largest margin, so nothing has to
    refresh them."""
    objective, batch, w, _ = problem(loss_name, "factors_shifts", seed=4,
                                     dtype=jnp.float32)
    rng = np.random.default_rng(4)
    _, _, z = objective.value_grad_margins(w, batch)
    for _ in range(30):
        p = jnp.asarray(rng.normal(size=D), jnp.float32)
        alpha = jnp.float32(rng.uniform(0.05, 1.0))
        _, grad_at = objective.along(w, z, p, batch)
        _, _, z = grad_at(alpha)
        w = w + alpha * p
    live = np.asarray(batch.weight) > 0
    exact = np.asarray(objective.margins(
        w.astype(jnp.float64),
        jax.tree.map(lambda a: a.astype(jnp.float64), batch)))[live]
    drift = np.abs(np.asarray(z, np.float64)[live] - exact).max()
    assert 0 < drift < 1e-6 * np.abs(exact).max()


# -- the bound, in the jaxpr ---------------------------------------------------

def sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (list, tuple)) else [v]):
            if hasattr(j, "jaxpr"):  # ClosedJaxpr
                yield j.jaxpr
            elif hasattr(j, "eqns"):
                yield j


def walk(jaxpr):
    """Every equation, those of nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in sub_jaxprs(eqn):
            yield from walk(sub)


def takes(eqn, shape):
    return any(getattr(v.aval, "shape", None) == shape for v in eqn.invars)


def design_reads(jaxpr, shape):
    """Equations that read an array of the design's shape and are not
    control flow passing it along."""
    return [e for e in walk(jaxpr)
            if takes(e, shape) and not list(sub_jaxprs(e))]


@pytest.mark.parametrize("path", ["margins", "passes"])
def test_a_solver_trip_reads_the_design_twice(path):
    """In a vmapped solve on the margins the line search's while_loop takes
    no array of the design's shape, and the outer body contracts the design
    exactly twice: for u = X p and for the gradient at the accepted step.
    By passes the line search's loop holds both contractions."""
    objective, batch, _, _ = problem("logistic", "factors_shifts",
                                     dtype=jnp.float32)
    lanes = 3
    stacked = jax.tree.map(lambda a: jnp.stack([a] * lanes), batch)
    design = stacked.x.shape
    config = SolverConfig(max_iters=5)
    solve = (make_solver(objective, config=config) if path == "margins"
             else lambda w, b: by_passes(objective, b, w, config))
    jaxpr = jax.make_jaxpr(jax.vmap(solve))(
        jnp.zeros((lanes, D), jnp.float32), stacked).jaxpr
    outer = [e for e in jaxpr.eqns if e.primitive.name == "while"]
    assert len(outer) == 1
    body = outer[0].params["body_jaxpr"].jaxpr
    searches = [e for e in walk(body) if e.primitive.name == "while"]
    assert len(searches) == 1  # the recursion's fori_loops are scans
    reads = design_reads(body, design)
    assert all(e.primitive.name == "dot_general" for e in reads)
    if path == "margins":
        assert not takes(searches[0], design)
        assert not design_reads(searches[0].params["body_jaxpr"].jaxpr,
                                design)
        assert len(reads) == 2
    else:
        assert takes(searches[0], design)
        assert len(design_reads(searches[0].params["body_jaxpr"].jaxpr,
                                design)) == 2 == len(reads)


# -- the rule, at each edge ----------------------------------------------------

def shapes(n=256, d=128, x_dtype=jnp.float32, w_dtype=jnp.float32):
    rows = jax.ShapeDtypeStruct((n,), w_dtype)
    return (DenseBatch(x=jax.ShapeDtypeStruct((n, d), x_dtype), y=rows,
                       offset=rows, weight=rows),
            jax.ShapeDtypeStruct((d,), w_dtype))


def shard_map_objective(objective):
    from photon_ml_tpu.parallel.fixed import ShardMapObjective
    from photon_ml_tpu.parallel.mesh import make_mesh

    return ShardMapObjective(objective, make_mesh())


EDGES = {
    "plain": (dict(), "margins"),
    "fused_not_eligible": (dict(fused=True), "margins"),  # no TPU here
    "fused_and_eligible": (dict(fused=True, tpu=True), "passes"),
    "fused_eligible_dim_unaligned": (dict(fused=True, tpu=True, d=96),
                                     "margins"),
    "box": (dict(box=True), "passes"),
    "narrower_storage": (dict(x_dtype=jnp.bfloat16), "passes"),
    "shard_map": (dict(wrap=shard_map_objective), "passes"),
    "sparse": (dict(sparse=True), "margins"),
}


@pytest.mark.parametrize("edge", EDGES)
def test_the_rule_at_each_edge(edge, monkeypatch):
    spec, want = EDGES[edge]
    if spec.get("tpu"):
        from photon_ml_tpu.ops import fused_glm

        monkeypatch.setattr(fused_glm, "has_tpu", lambda: True)
    objective = GLMObjective(loss=loss_for_task(TaskType.LOGISTIC_REGRESSION),
                             reg=Regularization(l2=1.0),
                             fused=spec.get("fused", False))
    batch, w0 = shapes(d=spec.get("d", 128),
                       x_dtype=spec.get("x_dtype", jnp.float32))
    if spec.get("sparse"):
        batch = SparseBatch(
            indices=jax.ShapeDtypeStruct((256, 4), jnp.int32),
            values=jax.ShapeDtypeStruct((256, 4), jnp.float32), y=batch.y,
            offset=batch.offset, weight=batch.weight, dim=128)
    box = (w0, w0) if spec.get("box") else None
    objective = spec.get("wrap", lambda o: o)(objective)
    assert lbfgs_trials(objective, batch, w0, box) == want
    assert line_search_kind(objective, OptimizerType.LBFGS, batch, w0,
                            box) == want


@pytest.mark.parametrize("optimizer,l1", [(OptimizerType.TRON, 0.0),
                                          (OptimizerType.OWLQN, 0.5),
                                          (OptimizerType.LBFGS, 0.5)])
def test_solvers_without_a_strong_wolfe_search_say_none(optimizer, l1):
    objective = GLMObjective(loss=loss_for_task(TaskType.LOGISTIC_REGRESSION),
                             reg=Regularization(l2=1.0, l1=l1))
    batch, w0 = shapes()
    assert line_search_kind(objective, optimizer, batch, w0) == "none"
    _, batch, _, _ = problem("logistic", "plain")
    res = jax.jit(make_solver(objective, optimizer))(jnp.zeros(D), batch)
    assert res.trials is None and int(res.iterations) > 0


@pytest.mark.parametrize("edge", ["box", "narrower_storage"])
def test_make_solver_keeps_the_passes_where_the_rule_says(edge, monkeypatch):
    """The solver make_solver builds asks for no margin search there."""
    asked = []
    real = lbfgs_module.minimize_lbfgs

    def spy(vg, w0, config, box=None, margins=None):
        asked.append(margins is not None)
        return real(vg, w0, config, box=box, margins=margins)

    monkeypatch.setattr("photon_ml_tpu.opt.solve.minimize_lbfgs", spy)
    objective, batch, _, _ = problem("logistic", "plain", dtype=jnp.float32)
    w0 = jnp.zeros(D, jnp.float32)
    box = None
    if edge == "box":
        box = (jnp.full(D, -0.1, jnp.float32), jnp.full(D, 0.1, jnp.float32))
    else:
        batch = batch.replace(x=batch.x[:-GARBAGE].astype(jnp.bfloat16),
                              **{k: getattr(batch, k)[:-GARBAGE]
                                 for k in ("y", "offset", "weight")})
    config = SolverConfig(max_iters=5)
    make_solver(objective, config=config, box=box)(w0, batch)
    make_solver(objective, config=config)(w0, dense_batch(
        np.ones((4, D)), np.ones(4), dtype=jnp.float32))
    assert asked == [False, True]
    with pytest.raises(ValueError, match="not affine"):
        minimize_lbfgs(lambda w: (w.sum(), w), w0, config,
                       box=(w0, w0), margins=lbfgs_module.MarginSearch(
                           None, None))


# -- the pass evaluator's frozen traces ----------------------------------------

def quadratic(center, scale):
    """f(w) = scale/2 |w - center|^2 with small whole numbers: every value
    the search computes along -grad is exact in floating point whatever the
    machine fuses, so the parent's results are the same bits anywhere."""
    c = jnp.asarray(center)
    return lambda w: (0.5 * scale * jnp.vdot(w - c, w - c), scale * (w - c))


# (center, scale, alpha0, max_evals) -> what the parent of PR 28 (7c2fd2d)
# returned through strong_wolfe(phi_fn, phi0, g0, d, alpha0): alpha, phi,
# the gradient carried, success, wolfe, num_evals
FROZEN = {
    "first_trial_accepted": ((3.0, -4.0), 1.0, 1.0, 25,
                             (1.0, 0.0, (0.0, 0.0), True, True, 1)),
    "expands_twice": ((3.0, -4.0), 1.0, 1 / 32, 25,
                      (0.125, 9.5703125, (-2.625, 3.5), True, True, 3)),
    "brackets_then_zooms": ((3.0, -4.0), 2.0, 4.0, 25,
                            (0.5, 0.0, (0.0, 0.0), True, True, 2)),
    "zooms_twice": ((3.0, -4.0), 4.0, 8.0, 25,
                    (0.25, 0.0, (0.0, 0.0), True, True, 3)),
    "out_of_trials": ((3.0, -4.0), 2.0, 4.0, 1,
                      (0.0, 25.0, (-6.0, 8.0), False, False, 1)),
}


@pytest.mark.parametrize("case", FROZEN)
def test_pass_evaluator_returns_what_the_parent_returned(case):
    center, scale, alpha0, max_evals, want = FROZEN[case]
    vg = quadratic(center, scale)
    w = jnp.zeros(2)
    f0, g0 = vg(w)
    d = -g0

    def by_pass(alpha):
        f, g = vg(w + alpha * d)
        return f, jnp.vdot(g, d), g

    ls = jax.jit(lambda: strong_wolfe(by_pass, f0, jnp.vdot(g0, d), g0,
                                      jnp.asarray(alpha0),
                                      max_evals=max_evals))()
    alpha, phi, g, success, wolfe, evals = want
    assert float(ls.alpha) == alpha and float(ls.phi) == phi
    assert tuple(np.asarray(ls.payload).tolist()) == g
    assert (bool(ls.success), bool(ls.wolfe), int(ls.num_evals)) == (
        success, wolfe, evals)


def test_a_direction_of_ascent_fails_at_once():
    vg = quadratic((3.0, -4.0), 1.0)
    w = jnp.zeros(2)
    f0, g0 = vg(w)
    ls = strong_wolfe(lambda a: (vg(w + a * g0)[0], jnp.vdot(g0, g0), ()),
                      f0, jnp.vdot(g0, g0), (), jnp.asarray(1.0))
    assert (float(ls.alpha), bool(ls.success), int(ls.num_evals)) == (
        0.0, False, 0)


# -- the counter ---------------------------------------------------------------

@pytest.mark.parametrize("path", ["margins", "passes"])
def test_trials_are_the_sum_of_the_searches_evaluations(path, monkeypatch):
    """SolverResult.trials = LineSearchResult.num_evals summed over the
    solve's trips (the search run unjitted, its results collected)."""
    seen = []
    real = lbfgs_module.strong_wolfe

    def spy(*a, **kw):
        ls = real(*a, **kw)
        jax.debug.callback(lambda n: seen.append(int(n)), ls.num_evals)
        return ls

    monkeypatch.setattr(lbfgs_module, "strong_wolfe", spy)
    objective, batch, _, _ = problem("poisson", "factors", seed=3)
    config = SolverConfig(max_iters=25, tolerance=1e-9)
    w0 = jnp.zeros(D)
    res = (make_solver(objective, config=config)(w0, batch)
           if path == "margins" else by_passes(objective, batch, w0, config))
    jax.effects_barrier()
    assert len(seen) == int(res.iterations) > 3
    assert int(res.trials) == sum(seen) >= len(seen)
    assert res.trials.dtype == jnp.int32
