"""Plain reference: the per-entity regularised GLM solve.

Each problem is  min_b  sum_s wt_s * l(y_s, x_s . b + off_s) + l2/2 |b|^2
over one entity's active rows, with l the logistic loss
l(y, z) = softplus(z) - y z.  Solved by damped Newton steps on the full
[d, d] Hessian, in float32 ``jax.numpy`` at ``highest`` matmul precision,
with no kernel, no bucketing and no import from ``photon_ml_tpu``.  It is
the yardstick the training cells hold the program's published coefficients
to: same rows, same offsets, same weights, so the same minimiser.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEWTON_STEPS = 30
HALVINGS = 6


def _objective(b, x, y, off, wt, l2):
    z = x @ b + off
    return jnp.sum(wt * (jax.nn.softplus(z) - y * z)) + 0.5 * l2 * b @ b


def _solve_one(x, y, off, wt, l2):
    """x [s, d], y/off/wt [s] (wt 0 on padding rows) -> b [d]."""
    d = x.shape[1]

    def step(b, _):
        z = x @ b + off
        p = jax.nn.sigmoid(z)
        g = x.T @ (wt * (p - y)) + l2 * b
        h = (x * (wt * p * (1.0 - p))[:, None]).T @ x + l2 * jnp.eye(d)
        delta = jnp.linalg.solve(h, g)
        f0 = _objective(b, x, y, off, wt, l2)

        # the largest of 1, 1/2, 1/4, ... that does not raise the objective
        def try_step(k):
            return _objective(b - delta * 0.5 ** k, x, y, off, wt, l2)

        f = jax.vmap(try_step)(jnp.arange(HALVINGS, dtype=jnp.float32))
        ok = f <= f0
        k = jnp.argmax(ok)
        scale = jnp.where(ok.any(), 0.5 ** k.astype(jnp.float32), 0.0)
        return b - scale * delta, None

    b, _ = jax.lax.scan(step, jnp.zeros(d, jnp.float32), None,
                        length=NEWTON_STEPS)
    return b


def solve(x, y, off, wt, l2: float):
    """x [e, s, d], y/off/wt [e, s] -> coefficients [e, d], float32."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.vmap(_solve_one, in_axes=(0, 0, 0, 0, None)))(
            jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32),
            jnp.asarray(off, jnp.float32), jnp.asarray(wt, jnp.float32),
            jnp.float32(l2))
