"""Strong-Wolfe line search (bracket + zoom) as a single ``lax.while_loop``
state machine — jittable and vmappable.

The reference delegates line search to Breeze's StrongWolfeLineSearch
(optimization/LBFGS.scala:39-108 wraps Breeze LBFGS which owns the search).
The search decides from ``phi(alpha)`` and ``phi'(alpha)`` alone, so it takes
an EVALUATOR ``alpha -> (phi, dphi, payload)`` and hands back the payload of
the best point; what a trial costs is the evaluator's business (opt/lbfgs.py
has the two):

- by passes: a trial is one fused value+grad pass at ``w + alpha d`` (psum'd
  under SPMD) and its payload the full gradient, carried along so that the
  optimizer never re-evaluates the accepted point;
- on the margins: a GLM's margins are affine in the step, so a trial is
  elementwise work over ``z + alpha u`` that never reads the design, and its
  payload is empty: the state holds scalars only.

Either way at most ``max_evals`` trials, with static control flow.

Algorithm: Nocedal & Wright, Algorithms 3.5 (bracketing) / 3.6 (zoom), with a
safeguarded quadratic-interpolation zoom step.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array

_BRACKET, _ZOOM, _DONE, _FAILED = 0, 1, 2, 3


class LineSearchResult(NamedTuple):
    alpha: Array  # accepted step (0.0 on failure)
    phi: Array  # f(w + alpha*d)
    payload: Any  # the evaluator's payload at alpha (payload0 on failure)
    success: Array  # bool: some Armijo-satisfying step found
    wolfe: Array  # bool: strong Wolfe conditions met
    num_evals: Array  # int32


class _State(NamedTuple):
    stage: Array
    i: Array  # eval count
    alpha: Array  # next trial step
    # bracketing history
    alpha_prev: Array
    phi_prev: Array
    # zoom interval
    lo: Array
    hi: Array
    phi_lo: Array
    dphi_lo: Array
    phi_hi: Array
    # best Armijo point so far (its payload rides along)
    best_alpha: Array
    best_phi: Array
    best_payload: Any
    wolfe: Array


def strong_wolfe(
    evaluate: Callable[[Array], Tuple[Array, Array, Any]],
    phi0: Array,
    dphi0: Array,
    payload0: Any,
    alpha0: Array,
    c1: float = 1e-4,
    c2: float = 0.9,
    max_evals: int = 25,
    max_alpha: float = 1e10,
) -> LineSearchResult:
    """Find alpha satisfying strong Wolfe conditions along a direction.

    evaluate(alpha) -> (phi(alpha), phi'(alpha), payload): the objective and
    its directional derivative at ``w + alpha d``, and a pytree the search
    carries for the caller (the gradient there, or nothing).
    phi0 / dphi0 / payload0: the same three at alpha = 0.
    """
    dtype = phi0.dtype
    dphi0 = dphi0.astype(dtype)
    # Approximate-Wolfe slack (Hager & Zhang 2005's remedy, eq. 4.1): near
    # an optimum the available decrease c1*alpha*dphi0 drops below the
    # ROUNDING of phi itself (easy at f32 with large-n objectives, where
    # one ulp of phi0 can exceed any resolvable descent), and the exact
    # Armijo test then fails every trial — burning all max_evals objective
    # passes before the optimizer can conclude OBJECTIVE_NOT_IMPROVING
    # (measured 5x on full-scale glmix2).  Accepting decrease up to
    # PLATEAU_ULPS ulps of phi0 lets the search succeed at the
    # working-precision plateau; the optimizer's convergence check floors
    # its function tolerance at the SAME width (opt/types.PLATEAU_ULPS —
    # see the invariant note there), so the accepted step terminates the
    # solve instead of compounding.
    from photon_ml_tpu.opt.types import PLATEAU_ULPS

    slack = (PLATEAU_ULPS * jnp.asarray(jnp.finfo(dtype).eps, dtype)
             * jnp.abs(phi0))

    def armijo_ok(alpha, phi):
        return phi <= phi0 + c1 * alpha * dphi0 + slack

    def curvature_ok(dphi):
        return jnp.abs(dphi) <= -c2 * dphi0

    def bracket_step(s: _State, phi, dphi, payload):
        fail_cond = ~armijo_ok(s.alpha, phi) | ((s.i > 0) & (phi >= s.phi_prev))
        curv = curvature_ok(dphi)
        pos = dphi >= 0

        # case 1: Armijo violated (or no decrease) -> zoom(alpha_prev, alpha).
        # phi_lo/dphi_lo describe alpha_prev; its payload is already in
        # best_payload (alpha_prev always satisfied Armijo, or is 0 with
        # best_payload = payload0).
        z1 = s._replace(
            stage=jnp.int32(_ZOOM),
            lo=s.alpha_prev, hi=s.alpha,
            phi_lo=s.phi_prev, dphi_lo=jnp.where(s.i > 0, s.dphi_lo, dphi0),
            phi_hi=phi,
        )
        # case 2: strong Wolfe satisfied -> done at alpha.
        z2 = s._replace(stage=jnp.int32(_DONE), best_alpha=s.alpha, best_phi=phi,
                        best_payload=payload, wolfe=jnp.bool_(True))
        # case 3: derivative >= 0 -> zoom(alpha, alpha_prev); alpha is best.
        z3 = s._replace(
            stage=jnp.int32(_ZOOM),
            lo=s.alpha, hi=s.alpha_prev,
            phi_lo=phi, dphi_lo=dphi, phi_hi=s.phi_prev,
            best_alpha=s.alpha, best_phi=phi, best_payload=payload,
        )
        # case 4: keep expanding; alpha satisfies Armijo and decreases -> best.
        z4 = s._replace(
            alpha=jnp.minimum(2.0 * s.alpha, max_alpha),
            alpha_prev=s.alpha, phi_prev=phi, dphi_lo=dphi,
            best_alpha=s.alpha, best_phi=phi, best_payload=payload,
        )

        out = jax.tree.map(
            lambda a, b, c, dd: jnp.where(fail_cond, a, jnp.where(curv, b, jnp.where(pos, c, dd))),
            z1, z2, z3, z4,
        )
        return out

    def zoom_step(s: _State, phi, dphi, payload):
        # s.alpha is the interpolated trial inside [lo, hi].
        fail_cond = ~armijo_ok(s.alpha, phi) | (phi >= s.phi_lo)
        curv = curvature_ok(dphi)
        flip = dphi * (s.hi - s.lo) >= 0

        # shrink from the hi side
        z1 = s._replace(hi=s.alpha, phi_hi=phi)
        # done
        z2 = s._replace(stage=jnp.int32(_DONE), best_alpha=s.alpha, best_phi=phi,
                        best_payload=payload, wolfe=jnp.bool_(True))
        # new lo, possibly flipping hi to old lo
        z3 = s._replace(
            lo=s.alpha, phi_lo=phi, dphi_lo=dphi,
            hi=jnp.where(flip, s.lo, s.hi),
            phi_hi=jnp.where(flip, s.phi_lo, s.phi_hi),
            best_alpha=s.alpha, best_phi=phi, best_payload=payload,
        )
        out = jax.tree.map(
            lambda a, b, c: jnp.where(fail_cond, a, jnp.where(curv, b, c)),
            z1, z2, z3,
        )
        # interval collapse -> stop at best
        tiny = jnp.abs(out.hi - out.lo) <= 1e-12 * jnp.maximum(1.0, jnp.abs(out.hi))
        return out._replace(
            stage=jnp.where((out.stage == _ZOOM) & tiny, jnp.int32(_DONE), out.stage)
        )

    def next_zoom_alpha(s: _State) -> Array:
        """Safeguarded quadratic interpolation using (phi_lo, dphi_lo, phi_hi)."""
        dx = s.hi - s.lo
        denom = 2.0 * (s.phi_hi - s.phi_lo - s.dphi_lo * dx)
        quad = s.lo - s.dphi_lo * dx * dx / jnp.where(denom == 0, 1.0, denom)
        bad = (denom == 0) | ~jnp.isfinite(quad)
        mid = s.lo + 0.5 * dx
        a_min = s.lo + 0.1 * dx
        a_max = s.lo + 0.9 * dx
        safe = jnp.clip(quad, jnp.minimum(a_min, a_max), jnp.maximum(a_min, a_max))
        return jnp.where(bad, mid, safe)

    def body(s: _State) -> _State:
        phi, dphi, payload = evaluate(s.alpha)
        dphi = dphi.astype(dtype)
        s2 = lax.cond(s.stage == _BRACKET,
                      lambda: bracket_step(s, phi, dphi, payload),
                      lambda: zoom_step(s, phi, dphi, payload))
        s2 = s2._replace(i=s.i + 1)
        # pick the next zoom trial point
        nz = next_zoom_alpha(s2)
        s2 = s2._replace(alpha=jnp.where(s2.stage == _ZOOM, nz, s2.alpha))
        return s2

    def cond(s: _State) -> Array:
        return (s.stage < _DONE) & (s.i < max_evals)

    init = _State(
        stage=jnp.int32(_BRACKET),
        i=jnp.int32(0),
        alpha=jnp.asarray(alpha0, dtype),
        alpha_prev=jnp.zeros((), dtype),
        phi_prev=phi0,
        lo=jnp.zeros((), dtype),
        hi=jnp.zeros((), dtype),
        phi_lo=phi0,
        dphi_lo=dphi0,
        phi_hi=phi0,
        best_alpha=jnp.zeros((), dtype),
        best_phi=phi0,
        best_payload=payload0,
        wolfe=jnp.bool_(False),
    )
    # Non-descent direction: fail immediately (caller restarts with -g).
    init = init._replace(stage=jnp.where(dphi0 >= 0, jnp.int32(_FAILED), init.stage))

    final = lax.while_loop(cond, body, init)
    success = final.best_alpha > 0
    return LineSearchResult(
        alpha=final.best_alpha,
        phi=final.best_phi,
        payload=final.best_payload,
        success=success,
        wolfe=final.wolfe,
        num_evals=final.i,
    )
