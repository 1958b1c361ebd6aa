"""L-BFGS and OWLQN as fully-jitted ``lax.while_loop`` solvers.

Reference: photon-lib .../optimization/LBFGS.scala:39-157 (Breeze LBFGS adapter,
m=10, tol=1e-7, maxIter=100) and OWLQN.scala:36-86 (L1 via Breeze OWLQN with a
mutable l1 weight for reg-path sweeps — here the l1 weight is a traced argument,
so sweeps don't recompile).

TPU-first design decisions:
- ONE solver shape for both deployment modes (SURVEY.md §1: the reference runs
  the same Breeze code cluster-wide and executor-local).  Here the closure
  passed as ``value_and_grad`` either psums internally (fixed effect, see
  photon_ml_tpu.parallel) or is vmapped over entity lanes (random effects) —
  ``lax.while_loop`` is vmappable, lanes that converge early mask out.
- Newest-first [m, d] history buffers (a shift register: slot j is the j-th
  newest pair) with slot masking instead of Breeze's deque-of-vectors; the
  two-loop recursion is a masked ``lax.fori_loop`` whose counter IS the slot:
  under ``vmap`` all lanes read one slot, a slice (through a circular
  buffer's per-lane insert position it is a gather: 7-9 ns an index, v5e).
- One strong-Wolfe state machine (opt/linesearch.py), two evaluators of a
  trial step.  BY PASSES a trial is one value+grad evaluation at
  ``w + alpha p`` whose gradient the search carries, so an iteration costs
  its trials in evaluations, the reference's per-iteration treeAggregate
  count: right where an evaluation reads the design once (the fused kernel)
  and wherever the step is not affine (a box) or the sums are psum'd.  ON
  THE MARGINS (``MarginSearch``; a GLM's margins are affine in the step) a
  trial is elementwise work over ``z + alpha u`` and an iteration reads the
  design TWICE whatever its trials: once for ``u = X p``, once for the
  gradient at the accepted step; the margins ``z`` ride in the carry.
  opt/solve.py's rule chooses.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.opt.constraints import project_to_box
from photon_ml_tpu.opt.linesearch import strong_wolfe
from photon_ml_tpu.opt.types import SolverConfig, SolverResult, StateTracker, convergence_check
from photon_ml_tpu.types import ConvergenceReason

Array = jax.Array
ValueAndGrad = Callable[[Array], Tuple[Array, Array]]


class MarginSearch(NamedTuple):
    """An objective whose margins are affine in the coefficients, as the
    line search on the margins needs it (GLMObjective's methods of the same
    names, bound to a batch)."""
    # w0 -> (value, gradient, margins z at w0)
    value_grad_margins: Callable[[Array], Tuple[Array, Array, Array]]
    # (w, z, p) -> (alpha -> (phi, phi'),
    #               alpha -> (value, gradient, margins) at w + alpha p)
    along: Callable[[Array, Array, Array], Tuple[Callable, Callable]]


class _LbfgsCarry(NamedTuple):
    w: Array
    f: Array
    g: Array
    z: Any  # the margins at w where the search runs on them, else ()
    s_hist: Array  # [m, d], newest pair in slot 0
    y_hist: Array  # [m, d]
    rho: Array  # [m]
    count: Array  # int32 valid pairs
    it: Array  # int32
    trials: Array  # int32: line-search trials over all iterations
    reason: Array  # int32
    tracker: StateTracker


def two_loop_direction(g, s_hist, y_hist, rho, count):
    """Masked L-BFGS two-loop recursion over a newest-first history.

    Slot ``j`` holds the ``j``-th newest pair (``push_pair``), so each step
    reads the slot of the loop's own counter: under ``vmap`` one slice for
    all lanes, where a per-lane insert position makes every read a gather.
    Unfilled slots (j >= count) are masked to no-ops so the compiled program
    has static shape regardless of how much history exists yet.
    """
    m = rho.shape[0]

    def loop1(j, carry):
        q, alphas = carry
        a = jnp.where(j < count, rho[j] * jnp.vdot(s_hist[j], q), 0.0)
        q = q - a * y_hist[j]
        return q, alphas.at[j].set(a)

    q, alphas = lax.fori_loop(0, m, loop1, (g, jnp.zeros_like(rho)))

    # Initial Hessian scaling gamma = s·y / y·y of the newest pair.
    sy = jnp.vdot(s_hist[0], y_hist[0])
    yy = jnp.vdot(y_hist[0], y_hist[0])
    gamma = jnp.where((count > 0) & (yy > 0), sy / jnp.where(yy == 0, 1.0, yy), 1.0)
    r = gamma * q

    def loop2(j, r):
        i = m - 1 - j  # oldest first
        b = rho[i] * jnp.vdot(y_hist[i], r)
        upd = (alphas[i] - b) * s_hist[i]
        return r + jnp.where(i < count, 1.0, 0.0) * upd

    r = lax.fori_loop(0, m, loop2, r)
    return -r


def push_pair(s_hist, y_hist, rho, count, s, y, ok):
    """The history after a step ``s`` that moved the gradient by ``y``.  The
    pair is admitted where the step was accepted (``ok``) and s·y > 0
    (cautious update): every slot then moves down by one, the oldest falls
    off and the pair takes slot 0; a refused pair leaves the history as it
    is.  Whole-array selects, no index."""
    sy = jnp.vdot(s, y)
    admit = ok & (sy > 1e-12 * jnp.maximum(jnp.vdot(y, y), 1e-30))

    def pushed(hist, new):
        return jnp.where(admit, jnp.concatenate([new[None], hist[:-1]]), hist)

    return (pushed(s_hist, s), pushed(y_hist, y),
            pushed(rho, 1.0 / jnp.where(sy == 0, 1.0, sy)),
            jnp.where(admit, jnp.minimum(count + 1, rho.shape[0]), count))


def minimize_lbfgs(
    value_and_grad: ValueAndGrad,
    w0: Array,
    config: SolverConfig = SolverConfig(),
    box: Optional[Tuple[Array, Array]] = None,
    margins: Optional[MarginSearch] = None,
) -> SolverResult:
    """Minimize a smooth objective with L-BFGS + strong Wolfe line search.

    ``margins``: the same objective as a ``MarginSearch``: the line search
    then evaluates its trials on the margins and the gradient is computed
    once, at the accepted step (the module docstring).  Without it every
    trial is one ``value_and_grad``.  Not with a ``box``: a projected step
    is not affine.

    ``box`` = (lower[d], upper[d]) enables a gradient-projection variant
    (the reference's constrained path, OptimizationUtils.
    projectCoefficientsToSubspace, and the LBFGSB use-case — LBFGSB.scala:30-95):
    iterates are clipped into the box, coordinates active at a bound (with the
    gradient pushing outward) are frozen out of the quasi-Newton direction, and
    convergence is measured on the projected gradient ||w - P(w - g)||.
    Projected steps break the Wolfe guarantee, so curvature pairs are admitted
    only when s·y > 0 (cautious update).
    """
    dtype = w0.dtype
    m, d = config.history, w0.shape[-1]
    if box is not None and margins is not None:
        raise ValueError("a box-constrained line search is not affine in the "
                         "step: it evaluates by passes")

    if box is not None:
        lower, upper = box
        project = project_to_box(lower, upper)

        def opt_gradient(w, g):
            # projected-gradient residual: zero iff w is KKT-stationary
            return w - jnp.clip(w - g, lower, upper)

        def free_mask(w, g):
            active = ((w <= lower) & (g > 0)) | ((w >= upper) & (g < 0))
            return ~active
    else:
        project = None
        opt_gradient = lambda w, g: g
        free_mask = None

    w0 = project(w0) if project is not None else w0
    if margins is None:
        (f0, g0), z0 = value_and_grad(w0), ()
    else:
        f0, g0, z0 = margins.value_grad_margins(w0)
    g0norm = jnp.linalg.norm(opt_gradient(w0, g0))

    tracker = StateTracker.init(config.max_iters, dtype).record(f0, g0norm)

    init = _LbfgsCarry(
        w=w0, f=f0, g=g0, z=z0,
        s_hist=jnp.zeros((m, d), dtype),
        y_hist=jnp.zeros((m, d), dtype),
        rho=jnp.zeros((m,), dtype),
        count=jnp.int32(0), it=jnp.int32(0), trials=jnp.int32(0),
        reason=jnp.int32(ConvergenceReason.NOT_CONVERGED),
        tracker=tracker,
    )
    # Degenerate start: already at a stationary point.
    init = init._replace(
        reason=jnp.where(g0norm == 0.0,
                         jnp.int32(ConvergenceReason.GRADIENT_CONVERGED), init.reason)
    )

    def body(c: _LbfgsCarry) -> _LbfgsCarry:
        if free_mask is None:
            g_dir = c.g
        else:
            # Freeze bound-active coordinates out of the direction.
            g_dir = jnp.where(free_mask(c.w, c.g), c.g, 0.0)
        dvec = two_loop_direction(g_dir, c.s_hist, c.y_hist, c.rho, c.count)
        if free_mask is not None:
            dvec = jnp.where(free_mask(c.w, c.g), dvec, 0.0)
        dphi0 = jnp.vdot(c.g, dvec)
        # Fall back to steepest descent if the direction lost descent (can
        # happen after projection or a skipped curvature pair).
        bad = dphi0 >= 0
        dvec = jnp.where(bad, -g_dir, dvec)

        gnorm = jnp.linalg.norm(opt_gradient(c.w, c.g))
        alpha0 = jnp.where(c.count == 0,
                           jnp.minimum(1.0, 1.0 / jnp.maximum(gnorm, 1e-12)),
                           jnp.ones((), dtype))

        if margins is None:
            def by_pass(alpha):
                wt = c.w + alpha * dvec
                wt = project(wt) if project is not None else wt
                f, g = value_and_grad(wt)
                return f, jnp.vdot(g, dvec), g

            evaluate, payload0 = by_pass, c.g
        else:
            phi, grad_at = margins.along(c.w, c.z, dvec)
            evaluate, payload0 = (lambda alpha: (*phi(alpha), ())), ()

        ls = strong_wolfe(evaluate, c.f, jnp.vdot(c.g, dvec), payload0, alpha0,
                          c1=config.c1, c2=config.c2, max_evals=config.max_linesearch)

        w_new = c.w + ls.alpha * dvec
        w_new = project(w_new) if project is not None else w_new
        f_new = ls.phi
        if margins is None:
            g_new, z_new = ls.payload, ()
        else:
            _, g_new, z_new = grad_at(ls.alpha)

        s_hist, y_hist, rho, count = push_pair(
            c.s_hist, c.y_hist, c.rho, c.count, w_new - c.w, g_new - c.g, ls.success)

        it = c.it + 1
        g_new_norm = jnp.linalg.norm(opt_gradient(w_new, g_new))
        reason = convergence_check(
            f_new, c.f, f0, g_new_norm, g0norm, it, config.max_iters, config.tolerance
        )
        # Line search found no Armijo point: objective can't improve along any
        # direction we can build -> ObjectiveNotImproving (Optimizer.scala's
        # fourth reason; Breeze throws a LineSearchFailed here instead).
        reason = jnp.where(~ls.success, jnp.int32(ConvergenceReason.OBJECTIVE_NOT_IMPROVING), reason)

        keep = ls.success
        return _LbfgsCarry(
            w=jnp.where(keep, w_new, c.w),
            f=jnp.where(keep, f_new, c.f),
            g=jnp.where(keep, g_new, c.g),
            z=jax.tree.map(lambda new, old: jnp.where(keep, new, old),
                           z_new, c.z),
            s_hist=s_hist, y_hist=y_hist, rho=rho, count=count,
            it=it, trials=c.trials + ls.num_evals, reason=reason,
            tracker=c.tracker.record(jnp.where(keep, f_new, c.f),
                                     jnp.where(keep, g_new_norm, gnorm)),
        )

    def cond(c: _LbfgsCarry) -> Array:
        return c.reason == ConvergenceReason.NOT_CONVERGED

    final = lax.while_loop(cond, body, init)
    return SolverResult(
        w=final.w, value=final.f,
        grad_norm=jnp.linalg.norm(opt_gradient(final.w, final.g)),
        iterations=final.it, reason=final.reason,
        tracker=final.tracker if config.track_states else None,
        trials=final.trials,
    )


# ---------------------------------------------------------------------------
# OWLQN — orthant-wise L-BFGS for L1 (reference OWLQN.scala:36-86)
# ---------------------------------------------------------------------------


def _pseudo_gradient(w: Array, g: Array, l1: Array) -> Array:
    """Sub-gradient of f(w) + l1*|w|_1 choosing the steepest orthant at 0."""
    right = g + l1
    left = g - l1
    at_zero = jnp.where(right < 0, right, jnp.where(left > 0, left, 0.0))
    return jnp.where(w > 0, right, jnp.where(w < 0, left, at_zero))


class _OwlqnCarry(NamedTuple):
    w: Array
    f: Array  # smooth part
    g: Array  # smooth gradient
    full_f: Array  # f + l1 term
    s_hist: Array
    y_hist: Array
    rho: Array
    count: Array
    it: Array
    reason: Array
    tracker: StateTracker


def minimize_owlqn(
    value_and_grad: ValueAndGrad,
    w0: Array,
    l1: Array,
    config: SolverConfig = SolverConfig(),
) -> SolverResult:
    """Minimize smooth(w) + l1*||w||_1 orthant-wise.

    ``l1`` is a traced scalar (or [d] vector with 0 for unpenalized entries,
    e.g. the intercept) — regularization-path sweeps reuse the compiled solver,
    unlike the reference's mutable ``l1RegularizationWeight`` (OWLQN.scala:43).

    Line search: projected backtracking Armijo on the composite objective
    (Breeze OWLQN does the same); curvature history uses smooth gradients.
    """
    dtype = w0.dtype
    m, d = config.history, w0.shape[-1]
    l1 = jnp.asarray(l1, dtype)

    def composite(w, f_smooth):
        return f_smooth + jnp.sum(l1 * jnp.abs(w))

    f0, g0 = value_and_grad(w0)
    pg0 = _pseudo_gradient(w0, g0, l1)
    pg0norm = jnp.linalg.norm(pg0)
    ff0 = composite(w0, f0)
    tracker = StateTracker.init(config.max_iters, dtype).record(ff0, pg0norm)

    init = _OwlqnCarry(
        w=w0, f=f0, g=g0, full_f=ff0,
        s_hist=jnp.zeros((m, d), dtype), y_hist=jnp.zeros((m, d), dtype),
        rho=jnp.zeros((m,), dtype),
        count=jnp.int32(0), it=jnp.int32(0),
        reason=jnp.where(pg0norm == 0.0,
                         jnp.int32(ConvergenceReason.GRADIENT_CONVERGED),
                         jnp.int32(ConvergenceReason.NOT_CONVERGED)),
        tracker=tracker,
    )

    def body(c: _OwlqnCarry) -> _OwlqnCarry:
        pg = _pseudo_gradient(c.w, c.g, l1)
        dvec = two_loop_direction(pg, c.s_hist, c.y_hist, c.rho, c.count)
        # Align: zero direction components that leave the pseudo-gradient's
        # descent orthant.
        dvec = jnp.where(dvec * -pg > 0, dvec, 0.0)
        dphi0 = jnp.vdot(pg, dvec)
        bad = dphi0 >= 0
        dvec = jnp.where(bad, -pg, dvec)
        dphi0 = jnp.where(bad, -jnp.vdot(pg, pg), dphi0)

        # Orthant of the trial region: sign(w), or steepest-orthant at 0.
        xi = jnp.where(c.w != 0, jnp.sign(c.w), jnp.sign(-pg))

        pgnorm = jnp.linalg.norm(pg)
        alpha0 = jnp.where(c.count == 0,
                           jnp.minimum(1.0, 1.0 / jnp.maximum(pgnorm, 1e-12)),
                           jnp.ones((), dtype))

        def ls_body(carry):
            alpha, _, _, _, _, k = carry
            wt = c.w + alpha * dvec
            wt = jnp.where(wt * xi >= 0, wt, 0.0)  # orthant projection
            ft, gt = value_and_grad(wt)
            fft = composite(wt, ft)
            ok = fft <= c.full_f + config.c1 * alpha * dphi0
            return (alpha * 0.5, wt, ft, gt, ok, k + 1)

        def ls_cond(carry):
            _, _, _, _, ok, k = carry
            return (~ok) & (k < config.max_linesearch)

        zero_w = jnp.zeros_like(c.w)
        a, w_new, f_new, g_new, ok, _ = lax.while_loop(
            ls_cond, ls_body, (alpha0, zero_w, c.f, c.g, jnp.bool_(False), jnp.int32(0))
        )

        s_hist, y_hist, rho, count = push_pair(
            c.s_hist, c.y_hist, c.rho, c.count, w_new - c.w, g_new - c.g, ok)

        ff_new = composite(w_new, f_new)
        it = c.it + 1
        pg_new = _pseudo_gradient(w_new, g_new, l1)
        pg_new_norm = jnp.linalg.norm(pg_new)
        reason = convergence_check(
            ff_new, c.full_f, ff0, pg_new_norm, pg0norm, it, config.max_iters, config.tolerance
        )
        reason = jnp.where(~ok, jnp.int32(ConvergenceReason.OBJECTIVE_NOT_IMPROVING), reason)

        return _OwlqnCarry(
            w=jnp.where(ok, w_new, c.w),
            f=jnp.where(ok, f_new, c.f),
            g=jnp.where(ok, g_new, c.g),
            full_f=jnp.where(ok, ff_new, c.full_f),
            s_hist=s_hist, y_hist=y_hist, rho=rho, count=count,
            it=it, reason=reason,
            tracker=c.tracker.record(jnp.where(ok, ff_new, c.full_f),
                                     jnp.where(ok, pg_new_norm, pgnorm)),
        )

    def cond(c: _OwlqnCarry) -> Array:
        return c.reason == ConvergenceReason.NOT_CONVERGED

    final = lax.while_loop(cond, body, init)
    pg_fin = _pseudo_gradient(final.w, final.g, l1)
    return SolverResult(
        w=final.w, value=final.full_f, grad_norm=jnp.linalg.norm(pg_fin),
        iterations=final.it, reason=final.reason,
        tracker=final.tracker if config.track_states else None,
    )
