"""Plain reference: the GLMix forward pass (the served score).

score(row) = x_fixed . w_fixed + sum over random effects of
x_re . W_re[entity]   (an entity the model has never seen contributes 0),
in float32 ``jax.numpy`` at ``highest`` matmul precision, with no kernel,
no store, no batching and no import from ``photon_ml_tpu``.

``bound`` is what a chip that multiplies float32 matmul operands as bf16
(8 significant bits each, the TPU's default precision) may be off by: every
product by at most 2^-8 of itself, so a row by at most 2^-8 * sum|x||w|.
(``chip_smoke.reference_scores`` is the pattern.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def scores(x_fixed, w_fixed, random_effects):
    """x_fixed [n, d_g], w_fixed [d_g]; random_effects: a list of
    (x [n, d], table [entities, d], slots [n] with -1 = unseen).
    Returns (score [n], bound [n]) as float64 numpy arrays."""
    xg = jnp.asarray(x_fixed, jnp.float32)
    wg = jnp.asarray(w_fixed, jnp.float32)
    with jax.default_matmul_precision("highest"):
        total = xg @ wg
        absum = jnp.abs(xg) @ jnp.abs(wg)
        for x, table, slots in random_effects:
            slots = np.asarray(slots)
            rows = np.where(slots[:, None] >= 0,
                            np.asarray(table)[np.maximum(slots, 0)], 0.0)
            prod = jnp.asarray(x, jnp.float32) * jnp.asarray(rows,
                                                             jnp.float32)
            total = total + jnp.sum(prod, axis=1)
            absum = absum + jnp.sum(jnp.abs(prod), axis=1)
    return (np.asarray(total, np.float64),
            2.0 ** -8 * np.asarray(absum, np.float64))
