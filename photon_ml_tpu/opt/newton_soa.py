"""Batched exact-Newton solver for NARROW random-effect lanes, in
structure-of-arrays ([d, L]) layout.

Why this exists (TPU layout): the generic random-effect path is
``jax.vmap(solve)`` over entity lanes, whose solver state is [L, d] (and
[L, m, d] L-BFGS history).  TPU tiling pads an array's trailing axis to
128 lanes, so at d=4 every state array occupies 32x its logical HBM bytes
and the vmapped while-loop becomes a padded-state bandwidth burn: profiled
on a real v5e, the RE solve loop was 3.06s of a 4.84s glmix_chip sweep at
13% HBM utilization (TPU_PROFILE/, round 5).  Samples-on-lanes [d, L]
arrays pad d only up to the 8-sublane tile (2x at d=4, 1x at d>=8), and
every per-lane reduction is a sublane sum — no dot_general, no transposes,
no padded intermediates.

Why NEWTON: at d <= 16 the exact per-lane Hessian is d(d+1)/2 weighted
column products (cheap, one fused pass over the bucket) and its Cholesky
factorization unrolls into elementwise-over-[L] ops that XLA fuses into a
single kernel.  Newton with Armijo backtracking reaches the reference
tolerance in ~5-10 iterations where L-BFGS takes tens — fewer iterations
x less traffic per iteration.  The OPTIMUM is the same: these per-entity
objectives (pointwise loss + l2/2 ||w||^2, l2 > 0 on every real config)
are strictly convex, so LBFGS / TRON / Newton agree to solver tolerance
(property-tested against the vmapped path in tests/test_optimizers.py).

Reference parity: solves the same per-entity problem as the reference's
SingleNodeOptimizationProblem (photon-api .../optimization/
SingleNodeOptimizationProblem.scala) under the same convergence contract
(opt/types.convergence_check — function values, then gradient, then max
iterations, rel->abs tolerances).  The reference never specializes for
narrow entities; this module is the TPU-native answer to its per-entity
solve loop.

Gating (game/coordinate.py::_bind_solver): decided on SOLVE-space shapes
— plain dense buckets, compact sparse buckets, and INDEX_MAP/RANDOM-
projected buckets (their compact/projected width is exactly where narrow
dims live) all qualify when there are no per-lane normalization/box
extras, l1 == 0, solve dim <= _MAX_SOA_DIM, cap*d^2/2 is small enough,
and the loss is smooth.  Everything else keeps the general vmapped path.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.core.losses import PointwiseLoss
from photon_ml_tpu.opt.types import SolverConfig, SolverResult, convergence_check
from photon_ml_tpu.types import ConvergenceReason

Array = jax.Array

_MAX_SOA_DIM = 16   # Cholesky unroll is O(d^3) fused ops; 16 covers every
# GLMix random-effect shard of the benchmark's cells (d = 16 and d = 4).
# d=32 was tried and reverted: the unroll compiles ~35s (measured, XLA
# CPU) and under the cap*d^2/2 traffic guard only cap<=2 buckets would
# ever qualify at that width — compile cost without a measurable win
# (the 1M-entity cap4xd32 demo shape sits just past the guard, and an
# end-to-end A/B there showed no speedup worth the compile).


def soa_eligible(dim: int, loss_name: str) -> bool:
    """Static part of the gate (the caller adds its own layout conditions)."""
    return dim <= _MAX_SOA_DIM and loss_name != "smoothed_hinge"


def _cholesky_solve_soa(hh: List[List[Array]], g: Array, jitter: Array) -> Array:
    """x = (H + jitter*I)^-1 g, unrolled over the static d.

    ``hh[i][j]`` (j <= i) are the lower-triangle Hessian entries, each an
    [L] array; ``g`` is [d, L].  Every operation below is elementwise over
    lanes — XLA fuses the whole factorization + two triangular solves into
    one kernel with no [L, d, d] array ever materialized.
    """
    d = g.shape[0]
    lo = [[None] * d for _ in range(d)]
    for i in range(d):
        s = hh[i][i] + jitter
        for k in range(i):
            s = s - lo[i][k] * lo[i][k]
        lii = jnp.sqrt(jnp.maximum(s, jitter))
        lo[i][i] = lii
        for j in range(i + 1, d):
            s2 = hh[j][i]
            for k in range(i):
                s2 = s2 - lo[j][k] * lo[i][k]
            lo[j][i] = s2 / lii
    z = [None] * d
    for i in range(d):
        s = g[i]
        for k in range(i):
            s = s - lo[i][k] * z[k]
        z[i] = s / lo[i][i]
    x = [None] * d
    for i in reversed(range(d)):
        s = z[i]
        for k in range(i + 1, d):
            s = s - lo[k][i] * x[k]
        x[i] = s / lo[i][i]
    return jnp.stack(x)


def _margins(w: Array, x_t: Array, off_t: Array) -> Array:
    """[cap, L] margins: sum over the d sublane axis, no dot_general."""
    acc = jnp.promote_types(x_t.dtype, w.dtype)
    return (x_t.astype(acc) * w[None].astype(acc)).sum(axis=1) + off_t


def _value(loss: PointwiseLoss, w, x_t, y_t, off_t, wt_t, l2) -> Array:
    z = _margins(w, x_t, off_t)
    return (wt_t * loss.loss(z, y_t)).sum(0) + 0.5 * l2 * (w * w).sum(0)


def _value_grad(loss: PointwiseLoss, w, x_t, y_t, off_t, wt_t, l2):
    z = _margins(w, x_t, off_t)
    l, d1 = loss.loss_and_d1(z, y_t)
    f = (wt_t * l).sum(0) + 0.5 * l2 * (w * w).sum(0)
    r = wt_t * d1                                     # [cap, L]
    acc = r.dtype
    g = (x_t.astype(acc) * r[:, None, :]).sum(0) + l2 * w   # [d, L]
    return f, g


def _hess(loss: PointwiseLoss, w, x_t, y_t, off_t, wt_t, l2):
    """Lower-triangle Hessian entries hh[i][j] as [L] arrays — the dominant
    per-iteration cost (d(d+1)/2 weighted column products), computed exactly
    once per Newton iteration."""
    z = _margins(w, x_t, off_t)
    q = wt_t * loss.d2(z, y_t)                        # [cap, L]
    acc = q.dtype
    d = w.shape[0]
    xq = x_t.astype(acc) * q[:, None, :]              # [cap, d, L]
    hh = [[None] * d for _ in range(d)]
    for i in range(d):
        for j in range(i + 1):
            hij = (xq[:, i, :] * x_t[:, j, :].astype(acc)).sum(0)
            if i == j:
                hij = hij + l2
            hh[i][j] = hij
            hh[j][i] = hij
    return hh


def solve_newton_soa(loss: PointwiseLoss, w0_t: Array, x_t: Array,
                     y_t: Array, off_t: Array, wt_t: Array, l2: Array,
                     config: SolverConfig,
                     interpret: bool = False) -> SolverResult:
    """Per-lane Newton descent; all arrays lanes-last.

    w0_t: [d, L] start; x_t: [cap, d, L]; y/off/wt_t: [cap, L]; l2: [L]
    (per-lane traced regularization — lambda sweeps reuse the compile).
    Returns SolverResult with lanes-last ``w`` ([d, L]); the caller
    transposes at its boundary.  ``interpret``: run the pallas step in
    interpret mode on any backend (tests only).
    """
    d, num_l = w0_t.shape
    dtype = w0_t.dtype
    c1 = jnp.asarray(config.c1, dtype)
    tol = jnp.asarray(config.tolerance, dtype)
    # Pallas fast path for the step (TPU, lane-aligned buckets): margins ->
    # curvature -> Hessian triangle -> Cholesky solve in ONE kernel, the
    # design streamed through VMEM once per iteration and the [cap, d, L]
    # xq intermediate never materialized (ops/soa_newton.py; same algorithm,
    # parity-tested in interpret mode).
    from photon_ml_tpu.ops import soa_newton

    use_pallas = soa_newton.eligible(d, num_l, x_t.shape[0],
                                     x_t.dtype.itemsize, interpret)

    def gnorm(g):
        # L2 norm, matching the vmapped L-BFGS/TRON convergence inputs
        return jnp.sqrt((g * g).sum(axis=0))

    f0, g0 = _value_grad(loss, w0_t, x_t, y_t, off_t, wt_t, l2)
    gn0 = gnorm(g0)
    # the scale-relative Cholesky floor: keeps padded / weightless lanes
    # (H = l2 I, possibly l2 = 0) factorizable without biasing real steps
    jitter = jnp.asarray(jnp.finfo(dtype).eps, dtype)
    not_improving = jnp.asarray(
        int(ConvergenceReason.OBJECTIVE_NOT_IMPROVING), jnp.int32)

    def cond(state):
        _, _, _, reason, _, k = state
        return jnp.logical_and(k < config.max_iters,
                               jnp.any(reason == 0))

    def body(state):
        # (f, g) ride the carry so the gradient pass runs once per
        # iteration and the Hessian assembly — the dominant cost — exactly
        # once too
        w, f, g, reason, iters, k = state
        active = reason == 0
        if use_pallas:
            step = soa_newton.newton_step(loss, w, g, x_t, y_t, off_t,
                                          wt_t, l2, interpret=interpret)
        else:
            hh = _hess(loss, w, x_t, y_t, off_t, wt_t, l2)
            step = _cholesky_solve_soa(
                hh, g, jitter * (jnp.abs(jnp.stack([hh[i][i]
                                                    for i in range(d)])).max(0)
                                 + jnp.asarray(1.0, dtype)))
        gd = (g * step).sum(0)                     # descent rate, [L] >= 0

        def ls_cond(ls):
            alpha, accepted, t = ls
            return jnp.logical_and(t < config.max_linesearch,
                                   jnp.any(jnp.logical_and(active,
                                                           ~accepted)))

        def ls_body(ls):
            alpha, accepted, t = ls
            f_try = _value(loss, w - alpha[None] * step,
                           x_t, y_t, off_t, wt_t, l2)
            ok = f_try <= f - c1 * alpha * gd      # False for NaN f_try
            newly = jnp.logical_and(~accepted, ok)
            accepted = jnp.logical_or(accepted, newly)
            alpha = jnp.where(accepted, alpha, alpha * 0.5)
            return alpha, accepted, t + 1

        alpha0 = jnp.ones((num_l,), dtype)
        alpha, accepted, _ = lax.while_loop(
            ls_cond, ls_body,
            (alpha0, jnp.zeros((num_l,), bool), jnp.asarray(0, jnp.int32)))
        # a rejected line search KEEPS the iterate (never w - 0*step: with a
        # non-finite step that is 0*inf = NaN and would poison the lane —
        # the generic solvers keep w on line-search failure too)
        stepped = jnp.logical_and(active, accepted)
        w_new = jnp.where(stepped[None], w - alpha[None] * step, w)
        f_new, g_new = _value_grad(loss, w_new, x_t, y_t, off_t, wt_t, l2)
        r_new = convergence_check(f_new, f, f0, gnorm(g_new), gn0,
                                  k + 1, config.max_iters, tol)
        # line-search exhaustion is a stall, not convergence — the same
        # OBJECTIVE_NOT_IMPROVING the vmapped L-BFGS/TRON paths report
        r_new = jnp.where(jnp.logical_and(active, ~accepted),
                          not_improving, r_new)
        reason = jnp.where(active, r_new, reason)
        w = jnp.where(active[None], w_new, w)
        f_out = jnp.where(active, f_new, f)
        g_out = jnp.where(active[None], g_new, g)
        iters = jnp.where(active, iters + 1, iters)
        return w, f_out, g_out, reason, iters, k + 1

    init = (w0_t, f0, g0,
            jnp.zeros((num_l,), jnp.int32), jnp.zeros((num_l,), jnp.int32),
            jnp.asarray(0, jnp.int32))
    w, f, g, reason, iters, _ = lax.while_loop(cond, body, init)
    return SolverResult(w=w, value=f, grad_norm=gnorm(g),
                        iterations=iters, reason=reason, tracker=None)
