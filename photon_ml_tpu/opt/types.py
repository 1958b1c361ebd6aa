"""Solver configuration / result / state-tracking containers.

Reference: photon-lib .../optimization/Optimizer.scala:36-249 (iteration loop,
convergence reasons, rel->abs tolerance derived from the FIRST state) and
OptimizationStatesTracker.scala (per-iteration value/gradient-norm history).

TPU-first: everything is a statically-shaped pytree so solvers run inside
``lax.while_loop`` and under ``vmap`` (per-entity random-effect solves with
per-lane convergence masks).  The tracker is a pre-allocated [max_iters] array
written with ``.at[iter].set`` — the device-side analog of the reference's
mutable state list.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from flax import struct

from photon_ml_tpu.types import ConvergenceReason

# Working-precision plateau width, in ulps of the objective value.  Shared
# INVARIANT with opt/linesearch.py's approximate-Wolfe slack: the line
# search may accept a step whose objective is up to PLATEAU_ULPS ulps worse
# than phi0, and convergence_check's function tolerance is floored at the
# same width — so any slack-accepted step is immediately recognized as
# converged and the solver can never creep uphill across iterations.
# Raising the slack without raising the floor would reintroduce the
# plateau-thrashing pathology both exist to prevent.
PLATEAU_ULPS = 4.0

Array = jax.Array


@struct.dataclass
class SolverConfig:
    """Solver hyperparameters.  Static fields shape the compiled program.

    Defaults follow the reference: LBFGS m=10, tol=1e-7, maxIter=100
    (LBFGS.scala:152-157); TRON tol=1e-5, maxIter=15, CG<=20 (TRON.scala:256-262).
    """

    max_iters: int = struct.field(pytree_node=False, default=100)
    tolerance: float = struct.field(pytree_node=False, default=1e-7)
    history: int = struct.field(pytree_node=False, default=10)  # L-BFGS m
    max_linesearch: int = struct.field(pytree_node=False, default=25)
    c1: float = struct.field(pytree_node=False, default=1e-4)  # Armijo
    c2: float = struct.field(pytree_node=False, default=0.9)  # Wolfe curvature
    # TRON (reference TRON.scala:80-253):
    max_cg: int = struct.field(pytree_node=False, default=20)
    track_states: bool = struct.field(pytree_node=False, default=True)

    @classmethod
    def lbfgs_default(cls) -> "SolverConfig":
        return cls(max_iters=100, tolerance=1e-7)

    @classmethod
    def tron_default(cls) -> "SolverConfig":
        return cls(max_iters=15, tolerance=1e-5, max_cg=20)


@struct.dataclass
class StateTracker:
    """Stacked per-iteration history (reference OptimizationStatesTracker).

    ``values[i]`` / ``grad_norms[i]`` are valid for i < num_states; unused
    slots stay at their init sentinel (nan).  Shape [max_iters + 1]: slot 0 is
    the initial state, matching the reference which records the state at the
    initial coefficients before iterating (Optimizer.scala:181).
    """

    values: Array
    grad_norms: Array
    num_states: Array  # int32 scalar

    @classmethod
    def init(cls, max_iters: int, dtype) -> "StateTracker":
        n = max_iters + 1
        return cls(
            values=jnp.full((n,), jnp.nan, dtype),
            grad_norms=jnp.full((n,), jnp.nan, dtype),
            num_states=jnp.zeros((), jnp.int32),
        )

    def record(self, value: Array, grad_norm: Array) -> "StateTracker":
        i = self.num_states
        return StateTracker(
            values=self.values.at[i].set(value),
            grad_norms=self.grad_norms.at[i].set(grad_norm),
            num_states=i + 1,
        )


@struct.dataclass
class SolverResult:
    """Final solver output.

    ``reason`` encodes ConvergenceReason as int32 (device-friendly); use
    ``convergence_reason()`` host-side.
    """

    w: Array
    value: Array
    grad_norm: Array
    iterations: Array  # int32
    reason: Array  # int32 ConvergenceReason
    tracker: Optional[StateTracker] = None
    # int32: trial steps of the strong-Wolfe line search, summed over the
    # iterations (LineSearchResult.num_evals); None where the solver has no
    # such search (TRON, OWLQN, SoA Newton)
    trials: Optional[Array] = None

    def convergence_reason(self) -> ConvergenceReason:
        return ConvergenceReason(int(self.reason))


def convergence_check(value, prev_value, init_value, grad_norm, init_grad_norm,
                      iteration, max_iters, tolerance):
    """The reference's convergence logic (Optimizer.scala:135-149), vectorized.

    Tolerances are RELATIVE to the initial state (rel->abs conversion at
    iteration 0, Optimizer.scala:181):
      - FunctionValuesConverged: |f_k - f_{k-1}| <= tol * max(|f_0|, eps)
      - GradientConverged:       ||g_k|| <= tol * max(||g_0||, eps)
      - MaxIterations:           k >= max_iters
    Returns int32 reason (0 = not converged).  Priority order matches the
    reference's check order: function values, gradient, max-iterations.
    """
    eps = jnp.asarray(jnp.finfo(value.dtype).tiny, value.dtype)
    # Working-precision floor: |f_k - f_{k-1}| cannot be resolved below a
    # few ulps of f, so a relative tolerance tighter than that (easy at f32
    # with large n: tol*|f0| ~ 1 ulp of f) makes convergence ulp-LUCK — the
    # unlucky path burns a full max_linesearch of objective passes in a
    # doomed final line search before exiting via OBJECTIVE_NOT_IMPROVING
    # (measured 5x on full-scale glmix2).  The reference runs f64 where
    # tol*|f0| is always far above this floor, so clamping preserves its
    # semantics while making f32 exit deterministically at the plateau.
    ulp = jnp.asarray(jnp.finfo(value.dtype).eps, value.dtype) * jnp.maximum(
        jnp.abs(value), jnp.abs(prev_value))
    f_tol = jnp.maximum(tolerance * jnp.maximum(jnp.abs(init_value), eps),
                        PLATEAU_ULPS * ulp)
    g_tol = tolerance * jnp.maximum(init_grad_norm, eps)
    func_conv = jnp.abs(value - prev_value) <= f_tol
    grad_conv = grad_norm <= g_tol
    max_iter = iteration >= max_iters
    reason = jnp.where(
        func_conv,
        ConvergenceReason.FUNCTION_VALUES_CONVERGED,
        jnp.where(
            grad_conv,
            ConvergenceReason.GRADIENT_CONVERGED,
            jnp.where(max_iter, ConvergenceReason.MAX_ITERATIONS, ConvergenceReason.NOT_CONVERGED),
        ),
    )
    return reason.astype(jnp.int32)


def summarize_solver_results(results, valid_masks=None) -> dict:
    """Aggregate statistics over many (possibly vmapped) solver results.

    Reference: RandomEffectOptimizationTracker.scala:158 — thousands of
    per-entity solves reduce to convergence-reason counts + iteration/loss
    summary stats for the job log.  ``results``: SolverResult or list of
    them (each scalar or batched over lanes); ``valid_masks``: per-result
    boolean lane masks (padded bucket lanes are excluded).
    """
    import numpy as np

    if not isinstance(results, (list, tuple)):
        results = [results]
    its, reasons, values = [], [], []
    for k, res in enumerate(results):
        it = np.atleast_1d(np.asarray(res.iterations))
        rs = np.atleast_1d(np.asarray(res.reason))
        va = np.atleast_1d(np.asarray(res.value))
        mask = np.ones(it.shape, bool)
        if valid_masks is not None and valid_masks[k] is not None:
            mask = np.atleast_1d(np.asarray(valid_masks[k])).astype(bool)
        its.append(it[mask])
        reasons.append(rs[mask])
        values.append(va[mask])
    its = np.concatenate(its) if its else np.zeros(0, np.int32)
    reasons = np.concatenate(reasons) if reasons else np.zeros(0, np.int32)
    values = np.concatenate(values) if values else np.zeros(0)
    if len(its) == 0:
        return {"count": 0}
    return {
        "count": int(len(its)),
        "convergence_reasons": {
            ConvergenceReason(int(r)).name: int((reasons == r).sum())
            for r in np.unique(reasons)
        },
        "iterations": {
            "mean": float(its.mean()), "max": int(its.max()),
            "p50": float(np.percentile(its, 50)),
            "p90": float(np.percentile(its, 90)),
        },
        "final_value": {
            "mean": float(values.mean()),
            "max": float(values.max()), "min": float(values.min()),
        },
    }
