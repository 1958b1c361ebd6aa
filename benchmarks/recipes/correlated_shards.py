"""Data recipe ``correlated_shards``: the three-coordinate GLMix of
``glmix3_wide``.

The generative recipe of ``bench.py synth_glmix(three=True)``, copied and
moved onto the device (this benchmark does not import ``bench.py``, and the
copy no longer follows it): a float32 fixed design of unit normals, two
random-effect shards that CORRELATE with the fixed shard's leading columns
(0.6 of them plus 0.8 of fresh noise, so independent per-coordinate fits
double-count the shared signal and a broken residual fold moves the fixed
coefficients), coefficient scales that put the generative logit's std near
1 (Bayes AUC 0.73 at the reference's sizes).  Rows are shuffled: a user's
and an item's rows lie anywhere.

The POPULATION is the configuration's, the SAMPLE is the seed's: the true
coefficients come from the config's ``truth_seed``; ``--seed`` draws the
features, the noise, the labels and which row belongs to whom.  How MANY
rows an entity has is fixed too: every user ``rows_per_user``, and the items
in two halves, one ``item_rows_spread`` under the mean and one over it
(240 and 272 rows at the full size).  Items drawn uniformly per row (which
this recipe first did) land in the same two capacity classes of the
program's bucketer, 256 and 512, but with lane counts that change with the
seed, so every seed was a new program and every run compiled for about
150 s (``setup_s`` 193 to 196 s on a v5e: my chip runs, PR 22).  Fixed
counts keep the two classes and make one program serve every seed.

Everything [n, .] is made in ONE jitted call, chunk by chunk into device
buffers.  The two narrow shards and the labels are pulled to the host
(transposed: [n, 16] on the device would be padded 8-fold), because the
program's bucketer wants them there; the fixed design stays on the device.
"""

from __future__ import annotations

import numpy as np

from recipes.chip_signal import chunking


def sizes(cfg: dict) -> dict:
    fixed, user, item = cfg["coordinates"]
    users, per_user = int(cfg["users"]), int(cfg["rows_per_user"])
    return dict(users=users, items=int(cfg["items"]), per_user=per_user,
                n=users * per_user, d_g=int(fixed["dim"]),
                d_u=int(user["dim"]), d_i=int(item["dim"]))


def item_row_counts(cfg: dict) -> np.ndarray:
    """Rows of each item: the first half of the items ``item_rows_spread``
    under the mean, the second half as much over it."""
    s = sizes(cfg)
    mean, odd = divmod(s["n"], s["items"])
    delta = int(round(mean * float(cfg["item_rows_spread"])))
    if odd or s["items"] % 2 or not 0 < delta < mean:
        raise ValueError(f"{s['n']} rows over {s['items']} items do not "
                         f"split into two halves around {mean}")
    half = s["items"] // 2
    return np.repeat([mean - delta, mean + delta], [half, half])


def entity_columns(cfg: dict, seed: int) -> tuple:
    """(uids [n], iids [n]) int64: a fixed number of rows for every entity,
    which rows from ``seed``."""
    s = sizes(cfg)
    rng = np.random.default_rng([seed, 1])
    uids = rng.permutation(
        np.repeat(np.arange(s["users"], dtype=np.int64), s["per_user"]))
    iids = rng.permutation(
        np.repeat(np.arange(s["items"], dtype=np.int64), item_row_counts(cfg)))
    return uids, iids


def make_training(cfg: dict, seed: int, mesh=None) -> dict:
    import jax
    import jax.numpy as jnp
    from jax import lax

    s = sizes(cfg)
    n, d_g, d_u, d_i = s["n"], s["d_g"], s["d_u"], s["d_i"]
    if d_u + d_i > d_g:
        raise ValueError("the random-effect shards are built from the "
                         "fixed shard's leading columns: d_u + d_i <= d_g")
    rows, chunks = chunking(n)
    uids, iids = entity_columns(cfg, seed)

    def generate(k_truth, k_rows, uid, iid):
        k_wg, k_wu, k_wi = jax.random.split(k_truth, 3)
        wg = jax.random.normal(k_wg, (d_g,), jnp.float32) * 0.05
        wu = jax.random.normal(k_wu, (s["users"], d_u), jnp.float32) * 0.15
        wi = jax.random.normal(k_wi, (s["items"], d_i), jnp.float32) * 0.15

        def body(c, bufs):
            xg, xu_t, xi_t, y = bufs
            start = c * rows
            k1, k2, k3, k4 = jax.random.split(
                jax.random.fold_in(k_rows, c), 4)
            xg_c = jax.random.normal(k1, (rows, d_g), jnp.float32)
            xu_c = (0.6 * xg_c[:, :d_u].T
                    + 0.8 * jax.random.normal(k2, (d_u, rows), jnp.float32))
            xi_c = (0.6 * xg_c[:, d_u:d_u + d_i].T
                    + 0.8 * jax.random.normal(k3, (d_i, rows), jnp.float32))
            uid_c = lax.dynamic_slice(uid, (start,), (rows,))
            iid_c = lax.dynamic_slice(iid, (start,), (rows,))
            logit = (jnp.sum(xg_c * wg[None, :], axis=1)
                     + jnp.sum(xu_c.T * wu[uid_c], axis=1)
                     + jnp.sum(xi_c.T * wi[iid_c], axis=1))
            y_c = (jax.random.uniform(k4, (rows,))
                   < jax.nn.sigmoid(logit)).astype(jnp.float32)
            return (lax.dynamic_update_slice(xg, xg_c, (start, 0)),
                    lax.dynamic_update_slice(xu_t, xu_c, (0, start)),
                    lax.dynamic_update_slice(xi_t, xi_c, (0, start)),
                    lax.dynamic_update_slice(y, y_c, (start,)))

        return lax.fori_loop(0, chunks, body, (
            jnp.zeros((n, d_g), jnp.float32),
            jnp.zeros((d_u, n), jnp.float32),
            jnp.zeros((d_i, n), jnp.float32),
            jnp.zeros((n,), jnp.float32)))

    out_shardings = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(mesh, P())
        out_shardings = (NamedSharding(mesh, P(mesh.axis_names, None)),
                         rep, rep, rep)
    xg, xu_t, xi_t, y = jax.jit(generate, out_shardings=out_shardings)(
        jax.random.PRNGKey(int(cfg["truth_seed"])), jax.random.PRNGKey(seed),
        jnp.asarray(uids, jnp.int32),
        jnp.asarray(iids, jnp.int32))
    return {"y": np.asarray(y),
            "features": {"g": xg,
                         "u": np.ascontiguousarray(np.asarray(xu_t).T),
                         "i": np.ascontiguousarray(np.asarray(xi_t).T)},
            "id_tags": {"userId": uids, "itemId": iids}}


def draw_model(cfg: dict, seed: int) -> dict:
    """Coefficients from ``seed`` for a served model of this shape, at the
    scales of the generative recipe."""
    s = sizes(cfg)
    rng = np.random.default_rng([seed, 2])
    return {
        "fixed": (rng.normal(size=s["d_g"]) * 0.05).astype(np.float32),
        "per-user": (rng.normal(size=(s["users"], s["d_u"]))
                     * 0.15).astype(np.float32),
        "per-item": (rng.normal(size=(s["items"], s["d_i"]))
                     * 0.15).astype(np.float32)}
