"""Solver factory binding a GLMObjective + optimizer choice into a jittable
``solve(w0, batch) -> SolverResult`` function, plus coefficient-variance
computation.

Reference: OptimizerFactory.scala:80, GeneralizedLinearOptimizationProblem.scala:173,
DistributedOptimizationProblem.scala:46-217 (variance: 84-108 — SIMPLE is
1/diag(H), FULL is diag(H^-1) via Cholesky, Linalg.choleskyInverse:104).

The returned ``solve`` is the SINGLE kernel reused in both deployment shapes
(SURVEY.md §1): jit it plainly (or shard_map its objective) for the fixed
effect; ``jax.vmap(solve)`` over padded entity buckets for random effects.
The reference selects OWLQN automatically when L1 regularization is present
(LBFGS.scala init) — same rule here.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from photon_ml_tpu.core.batch import Batch, DenseBatch
from photon_ml_tpu.core.objective import GLMObjective
from photon_ml_tpu.opt.lbfgs import (MarginSearch, minimize_lbfgs,
                                     minimize_owlqn)
from photon_ml_tpu.opt.tron import minimize_tron
from photon_ml_tpu.opt.types import SolverConfig, SolverResult
from photon_ml_tpu.types import OptimizerType, VarianceComputationType
from photon_ml_tpu.utils.linalg import cholesky_inverse

Array = jax.Array


def check_box_support(optimizer: OptimizerType, has_l1: bool) -> None:
    """Box constraints are a projected-gradient L-BFGS feature (reference
    OptimizationUtils.projectCoefficientsToSubspace applies them in LBFGSB
    only); TRON and the L1/OWLQN regime refuse.  Shared by make_solver and
    callers that pass per-call boxes to an unboxed-at-build solver."""
    if optimizer == OptimizerType.TRON:
        raise ValueError("TRON does not support box constraints")
    if optimizer == OptimizerType.OWLQN or has_l1:
        raise ValueError("OWLQN does not support box constraints")


def line_search_kind(objective, optimizer: OptimizerType, batch: Batch,
                     w0: Array, box=None) -> str:
    """How the solver that ``make_solver`` builds for ``objective`` evaluates
    a trial step of its line search: ``"margins"`` or ``"passes"``
    (``lbfgs_trials``), or ``"none"`` where it has no strong-Wolfe search
    (TRON; OWLQN, which an L1 weight selects).  For the build-time
    objective, whose weights are concrete."""
    if optimizer != OptimizerType.LBFGS or objective.reg.l1 > 0.0:
        return "none"
    return lbfgs_trials(objective, batch, w0, box)


def lbfgs_trials(objective, batch: Batch, w0: Array, box=None) -> str:
    """THE rule for the L-BFGS's line search, from what the code can observe
    of the problem (types, shapes and dtypes only: arrays, tracers or
    ``jax.ShapeDtypeStruct``s serve).  One algorithm, two costs of an
    evaluation:

    - ``"margins"`` where an evaluation costs TWO reads of the design and
      the step is affine, so that searching along ``z + alpha u``
      (GLMObjective.along) makes an iteration two reads whatever its
      trials: a plain ``GLMObjective`` on its XLA path, no box, storage as
      wide as the solver's state (narrower storage rounds
      ``X bf16(w + alpha p)`` and ``X bf16(w) + alpha X bf16(p)``
      differently);
    - ``"passes"`` everywhere else, a trial one evaluation: the fused Mosaic
      kernel (ONE read an evaluation, so an iteration of few trials costs
      less than two reads and a gradient tail), a box (``project`` is not
      affine), objectives whose sums are psum'd (``ShardMapObjective``,
      ``ShardSparseObjective``)."""
    storage = batch.x if isinstance(batch, DenseBatch) else batch.values
    affine = (isinstance(objective, GLMObjective) and box is None
              and storage.dtype == w0.dtype
              and not (objective.fused
                       and objective._fused_eligible(batch, w0)))
    return "margins" if affine else "passes"


def make_solver(
    objective: GLMObjective,
    optimizer: OptimizerType = OptimizerType.LBFGS,
    config: Optional[SolverConfig] = None,
    box: Optional[Tuple[Array, Array]] = None,
) -> Callable[[Array, Batch], SolverResult]:
    """Build solve(w0, batch) for one GLM coordinate.

    ``box``: optional (lower[d], upper[d]) constraint arrays
    (reference constrained-coefficients path, OptimizationUtils.scala).

    The returned callable accepts an optional ``objective=`` override with the
    SAME static structure (loss, fused) but different reg/norm leaves — under
    one ``jax.jit`` this makes regularization-path sweeps recompile-free
    (the reference mutates ``l1RegularizationWeight``/L2 mixins in place for
    the same reason, DistributedOptimizationProblem.updateRegularizationWeight
    :64-75).  The optimizer/L1 dispatch below stays keyed to the λ=build-time
    reg, so an override must not move between the smooth and L1 regimes.
    """
    if config is None:
        config = SolverConfig.tron_default() if optimizer == OptimizerType.TRON else SolverConfig.lbfgs_default()
    has_l1 = objective.reg.l1 > 0.0

    if optimizer == OptimizerType.TRON and has_l1:
        raise ValueError("TRON does not support L1 regularization (reference parity)")
    if box is not None:
        check_box_support(optimizer, has_l1)
    if optimizer == OptimizerType.OWLQN or (optimizer == OptimizerType.LBFGS and has_l1):

        def solve_owlqn(w0: Array, batch: Batch,
                        objective: GLMObjective = objective) -> SolverResult:
            vg = lambda w: objective.value_and_grad(w, batch)
            return minimize_owlqn(vg, w0, objective.reg.l1, config)

        return solve_owlqn

    if optimizer == OptimizerType.LBFGS:

        def solve_lbfgs(w0: Array, batch: Batch,
                        objective: GLMObjective = objective,
                        box: Optional[Tuple[Array, Array]] = box) -> SolverResult:
            # ``box`` is per-call overridable like ``objective`` (same static
            # presence rule): the random-effect coordinate passes per-lane
            # bound arrays through vmap for compact-space constrained solves.
            vg = lambda w: objective.value_and_grad(w, batch)
            margins = None
            if lbfgs_trials(objective, batch, w0, box) == "margins":
                margins = MarginSearch(
                    lambda w: objective.value_grad_margins(w, batch),
                    lambda w, z, p: objective.along(w, z, p, batch))
            return minimize_lbfgs(vg, w0, config, box=box, margins=margins)

        return solve_lbfgs

    if optimizer == OptimizerType.TRON:

        def solve_tron(w0: Array, batch: Batch,
                       objective: GLMObjective = objective) -> SolverResult:
            vg = lambda w: objective.value_and_grad(w, batch)
            hvp_at = lambda w, v: objective.hvp(w, batch, v)
            return minimize_tron(vg, hvp_at, w0, config)

        return solve_tron

    raise ValueError(f"unknown optimizer {optimizer!r}")


def compute_variances(
    objective: GLMObjective,
    w: Array,
    batch: Batch,
    kind: VarianceComputationType,
) -> Optional[Array]:
    """Coefficient variances (reference DistributedOptimizationProblem.scala:84-108).

    SIMPLE: 1 / diag(H)  (NOT the inverse-Hessian diagonal — reference parity).
    FULL:   diag(H^-1) via Cholesky (reference Linalg.choleskyInverse:104).
    """
    if kind == VarianceComputationType.NONE:
        return None
    if kind == VarianceComputationType.SIMPLE:
        d = objective.hessian_diag(w, batch)
        return 1.0 / jnp.where(d == 0, jnp.inf, d)
    if kind == VarianceComputationType.FULL:
        h = objective.hessian(w, batch)
        return jnp.diagonal(cholesky_inverse(h))
    raise ValueError(f"unknown variance computation type {kind!r}")
