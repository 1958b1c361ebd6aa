"""Property tests: L-BFGS/OWLQN on random convex quadratics.

A strongly-convex quadratic has a closed-form optimum, so the solver core
(two-loop recursion, strong-Wolfe line search, box projection) can be
checked against exact answers on randomly-conditioned problems — breadth
the scipy-parity tests in test_optimizers (fixed problems) don't give.
One fixed shape keeps a single jit compile across all hypothesis examples.
"""

import os
import sys

import numpy as np
import pytest

pytest.importorskip("hypothesis")  # not in the image; skip, don't error at collection
from hypothesis import given, settings
from hypothesis import strategies as st

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from photon_ml_tpu.opt.lbfgs import minimize_lbfgs  # noqa: E402
from photon_ml_tpu.opt.types import PLATEAU_ULPS, SolverConfig  # noqa: E402

_D = 5


def _quad_vg(A, b):
    def vg(w):
        g = A @ w - b
        return 0.5 * jnp.vdot(w, A @ w) - jnp.vdot(b, w), g
    return vg


@jax.jit
def _solve_quad(A, b, w0):
    return minimize_lbfgs(_quad_vg(A, b), w0,
                          SolverConfig(max_iters=100, tolerance=1e-12))


@jax.jit
def _solve_quad_box(A, b, w0, lo, hi):
    return minimize_lbfgs(_quad_vg(A, b), w0,
                          SolverConfig(max_iters=200, tolerance=1e-12),
                          box=(lo, hi))


def _spd(draw_mat, jitter):
    M = np.asarray(draw_mat, np.float64).reshape(_D, _D)
    return M @ M.T + jitter * np.eye(_D)


_mat = st.lists(st.floats(-2, 2, allow_nan=False),
                min_size=_D * _D, max_size=_D * _D)
_vec = st.lists(st.floats(-3, 3, allow_nan=False),
                min_size=_D, max_size=_D).map(
                    lambda v: np.asarray(v, np.float64))


@settings(max_examples=50, deadline=None)
@given(m=_mat, b=_vec, w0=_vec, jitter=st.floats(0.1, 5.0))
def test_lbfgs_reaches_analytic_optimum(m, b, w0, jitter):
    """The solver stops on FUNCTION VALUES (opt/types.convergence_check:
    |f_k - f_{k-1}| <= tolerance x |f_0|, floored at PLATEAU_ULPS ulps of f),
    so that is the scale it is held to: the objective gap it leaves, and
    the distance from the optimum that gap allows on a strongly convex
    quadratic (gap = e'Ae / 2 >= lambda_min |e|^2 / 2).  A fixed 1e-6 on w
    was never promised: at |f_0| = 16 and lambda_min = 1 the criterion
    stops 1.8e-6 away, as asked.  The gap left after a step that gained
    f_tol is that gain x r / (1 - r) for a step contracting by r: 10 x
    allows r = 0.91 (6,000 random draws of these problems peak at 2.1 x)."""
    A = _spd(m, jitter)
    res = _solve_quad(jnp.asarray(A), jnp.asarray(b), jnp.asarray(w0))
    want = np.linalg.solve(A, b)

    def f(w):
        return 0.5 * w @ A @ w - b @ w

    f_tol = max(1e-12 * abs(f(w0)),
                PLATEAU_ULPS * np.finfo(np.float64).eps * abs(f(want)))
    e = np.asarray(res.w) - want
    assert 0.5 * e @ A @ e <= 10 * f_tol
    assert np.linalg.norm(e) <= np.sqrt(
        2 * 10 * f_tol / np.linalg.eigvalsh(A)[0])


@settings(max_examples=40, deadline=None)
@given(m=_mat, b=_vec, w0=_vec, jitter=st.floats(0.5, 5.0))
def test_box_constrained_satisfies_kkt(m, b, w0, jitter):
    """Projected L-BFGS on a box: the result must (a) lie inside the box and
    (b) satisfy the projected-gradient stationarity condition
    ||w - P(w - g)|| ~ 0 — the exact KKT certificate the solver's own
    convergence test uses, verified here from scratch in numpy."""
    A = _spd(m, jitter)
    lo, hi = np.full(_D, -0.5), np.full(_D, 0.5)
    res = _solve_quad_box(jnp.asarray(A), jnp.asarray(b),
                          jnp.asarray(np.clip(w0, lo, hi)),
                          jnp.asarray(lo), jnp.asarray(hi))
    w = np.asarray(res.w)
    assert np.all(w >= lo - 1e-9) and np.all(w <= hi + 1e-9)
    g = A @ w - b
    proj_g = w - np.clip(w - g, lo, hi)
    np.testing.assert_allclose(proj_g, 0.0, atol=5e-5)


@jax.jit
def _solve_quad_tron(A, b, w0):
    from photon_ml_tpu.opt.tron import minimize_tron

    return minimize_tron(_quad_vg(A, b), lambda w, v: A @ v, w0,
                         SolverConfig(max_iters=30, tolerance=1e-12))


@settings(max_examples=40, deadline=None)
@given(m=_mat, b=_vec, w0=_vec, jitter=st.floats(0.1, 5.0))
def test_tron_reaches_analytic_optimum(m, b, w0, jitter):
    """TRON (trust region + truncated CG) on the same random quadratics:
    with an exact quadratic model the solver must land on the closed-form
    optimum — any trust-region/CG bookkeeping slip shows up immediately."""
    A = _spd(m, jitter)
    res = _solve_quad_tron(jnp.asarray(A), jnp.asarray(b), jnp.asarray(w0))
    want = np.linalg.solve(A, b)
    np.testing.assert_allclose(np.asarray(res.w), want, rtol=1e-5, atol=1e-5)
