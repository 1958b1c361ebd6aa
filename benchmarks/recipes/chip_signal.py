"""Data recipe ``chip_signal``: the two-coordinate GLMix of ``glmix_chip``.

A copy of the generative recipe of ``bench.py`` (``_chip_signal_cols``,
``synth_glmix_chip``, the chunked device fill of ``run_glmix_chip``), which
this benchmark does not import.  The copy no longer follows the program.

The fixed design [n, d_g] never exists on the host: its leading
``signal_columns`` are counter-based (a sine of an exact integer phase of
the row index), the rest is seeded noise, and the whole of it is written
chunk by chunk into one device buffer inside ONE jitted call.  The per-user
shard and the labels are made in the same call and pulled to the host,
because the program's bucketer (``game/coordinate.build_coordinate``) wants
them there.  Generative logits have std ~1.3, so the task carries real
label noise (Bayes AUC ~0.8) and the AUC band is falsifiable.

The POPULATION is the configuration's, the SAMPLE is the seed's: the true
coefficients come from the config's ``truth_seed``, and ``--seed`` draws the
features, the noise and the labels.  With the truth drawn from ``--seed``
the problem itself changed from run to run (training AUC 0.79 to 0.85, the
fixed effect's L-BFGS 7 to 18 passes a sweep, a fit 1.70 to 2.10 s on a v5e:
my chip runs, PR 22), and the spread of the rate was the spread of the
problems.
"""

from __future__ import annotations

import numpy as np

_PHASE_PERIOD = 8191  # prime period of the counter-based signal columns
CHUNK_ROWS = 1 << 19


def chunking(n: int) -> tuple:
    """(rows per chunk, chunks): equal chunks of at most CHUNK_ROWS rows."""
    chunks = -(-n // CHUNK_ROWS)
    if n % chunks:
        raise ValueError(f"{n} rows do not split into {chunks} equal chunks")
    return n // chunks, chunks


def signal_cols(i, d_sig: int, xp):
    """h[i, j] = sin(2 pi ((i mod P) k_j mod P) / P): exact integer phase
    arithmetic, so the columns are the same numbers wherever computed."""
    k = 1 + 37 * (xp.arange(d_sig, dtype=xp.int32) + 1)
    im = (xp.asarray(i) % _PHASE_PERIOD).astype(xp.int32)
    ph = (im[:, None] * k[None, :]) % _PHASE_PERIOD  # < P*P < 2^31
    return xp.sin(ph.astype(xp.float32)
                  * np.float32(2.0 * np.pi / _PHASE_PERIOD))


def sizes(cfg: dict) -> dict:
    fixed, user = cfg["coordinates"]
    users, per_user = int(cfg["users"]), int(cfg["rows_per_user"])
    return dict(users=users, per_user=per_user, n=users * per_user,
                d_g=int(fixed["dim"]), d_u=int(user["dim"]),
                d_sig=int(cfg["signal_columns"]),
                storage=fixed.get("storage_dtype"))


def make_training(cfg: dict, seed: int, mesh=None) -> dict:
    """{"y", "features": {"g": device [n, d_g], "u": host [n, d_u]},
    "id_tags": {"userId": host [n]}} from ``seed``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    s = sizes(cfg)
    n, d_g, d_u, d_sig = s["n"], s["d_g"], s["d_u"], s["d_sig"]
    users, per_user = s["users"], s["per_user"]
    rows, chunks = chunking(n)
    xdt = jnp.dtype(s["storage"]) if s["storage"] else jnp.float32

    def generate(k_truth, k_rows):
        k_wg, k_wu = jax.random.split(k_truth)
        wg_sig = jax.random.normal(k_wg, (d_sig,), jnp.float32) * 0.4
        wu = jax.random.normal(k_wu, (users, d_u), jnp.float32) * 0.35

        def body(c, bufs):
            xg, xu_t, y = bufs
            start = c * rows
            i = start + jnp.arange(rows, dtype=jnp.int32)
            k1, k2, k3 = jax.random.split(jax.random.fold_in(k_rows, c), 3)
            h = signal_cols(i, d_sig, jnp)
            noise = jax.random.normal(k1, (rows, d_g - d_sig), jnp.float32)
            xu_c = jax.random.normal(k2, (d_u, rows), jnp.float32)
            logit = (jnp.sum(h * wg_sig[None, :], axis=1)
                     + jnp.sum(xu_c.T * wu[i // per_user], axis=1))
            y_c = (jax.random.uniform(k3, (rows,))
                   < jax.nn.sigmoid(logit)).astype(jnp.float32)
            xg = lax.dynamic_update_slice(
                xg, jnp.concatenate([h, noise], axis=1).astype(xdt),
                (start, 0))
            xu_t = lax.dynamic_update_slice(xu_t, xu_c, (0, start))
            y = lax.dynamic_update_slice(y, y_c, (start,))
            return xg, xu_t, y

        return lax.fori_loop(0, chunks, body, (
            jnp.zeros((n, d_g), xdt), jnp.zeros((d_u, n), jnp.float32),
            jnp.zeros((n,), jnp.float32)))

    out_shardings = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        out_shardings = (NamedSharding(mesh, P(mesh.axis_names, None)),
                         NamedSharding(mesh, P()), NamedSharding(mesh, P()))
    xg, xu_t, y = jax.jit(generate, out_shardings=out_shardings)(
        jax.random.PRNGKey(int(cfg["truth_seed"])), jax.random.PRNGKey(seed))
    # the narrow shard comes back transposed: [n, 4] on the device would be
    # padded 32-fold by the tiling
    xu = np.ascontiguousarray(np.asarray(xu_t).T)
    uids = np.repeat(np.arange(users, dtype=np.int64), per_user)
    return {"y": np.asarray(y), "features": {"g": xg, "u": xu},
            "id_tags": {"userId": uids}}


def draw_model(cfg: dict, seed: int) -> dict:
    """Coefficients from ``seed`` for a served model of this shape."""
    s = sizes(cfg)
    rng = np.random.default_rng(seed)
    return {"fixed": (rng.normal(size=s["d_g"]) * 0.05).astype(np.float32),
            "per-user": (rng.normal(size=(s["users"], s["d_u"]))
                         * 0.35).astype(np.float32)}
