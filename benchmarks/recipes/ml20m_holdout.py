"""Data recipe ``ml20m_holdout``: ``ml20m_counts``' GLMix, handed over as TWO
sets: the rows to train on and the rows held out to validate on.

WHO has HOW MANY rows and the sizes are ``recipes/ml20m_counts.py``'s
(``row_counts``, ``sizes``, imported): the configuration's and
``truth_seed``'s.  The generative model is that recipe's too, chunk for
chunk and key for key (float32 unit-normal fixed design, two random-effect
shards that correlate with its leading columns, the same coefficient
scales): a ``--seed`` draws the very features ``ml20m_counts`` draws from
it.  It is written out again here because there it is a closure inside
``make_training`` that fills ONE buffer, and a split of that buffer on the
device would hold the whole design and both parts at once (6.67 + 5.33 +
1.33 GB): here every chunk's rows go straight to the set they belong to,
so the device never holds more than the two sets.

**The split is the problem's, not the sample's**: WHICH rows are held out
is drawn row by row from ``truth_seed`` (``holdout_share`` of the rows, a
Bernoulli draw a row), and so is HOW MANY of each movie's rows lie in each
set (``entity_columns``: the movies of the held-out positions are a
multiset of ``truth_seed``'s; ``--seed`` draws which movie's row meets
which user INSIDE each set).  So both sets' rows per user and per movie,
and with them the capacity classes, the lanes and every layout of the
program, are one for every run: one program for every seed (with the split
drawn per row alone, a movie's held-out count was the seed's, every run's
program had shapes of its own and compiled for 190 s: PERF.md section 6,
PR 32).  A user keeps about four fifths of its rows; a movie whose every
row fell in the held-out set is UNSEEN in training and scores 0 from its
random effect there, as upstream's scoring does.  Both sets keep the
source's order: rows by user, ascending.

``make_sets(cfg, seed) -> (training, heldout)``, each what ``make_training``
returns elsewhere: ``y`` and the id columns on the host, the fixed design a
device array, the two narrow shards host arrays.  ``make_training`` hands
over the first alone.
"""

from __future__ import annotations

import functools

import numpy as np

from recipes.chip_signal import CHUNK_ROWS
from recipes.ml20m_counts import row_counts
from recipes.ml20m_counts import sizes as _sizes


@functools.lru_cache(maxsize=4)
def _held_out(n: int, share: float, truth_seed: int) -> np.ndarray:
    mask = np.random.default_rng([truth_seed, 9]).random(n) < share
    mask.setflags(write=False)
    return mask


def held_out_rows(cfg: dict) -> np.ndarray:
    """[n] bool: the rows held out, the configuration's and ``truth_seed``'s."""
    return _held_out(_sizes(cfg)["n"], float(cfg["holdout_share"]),
                     int(cfg["truth_seed"]))


def entity_columns(cfg: dict, seed: int) -> tuple:
    """(uids [n], iids [n]) int64: rows grouped by user, ascending.  The
    movies of the held-out positions, and so of the training positions, are
    multisets drawn from ``truth_seed``; ``seed`` shuffles each within its
    own set."""
    per_user, per_item = row_counts(cfg)
    held = held_out_rows(cfg)
    uids = np.repeat(np.arange(len(per_user), dtype=np.int64), per_user)
    movies = np.random.default_rng([int(cfg["truth_seed"]), 10]).permutation(
        np.repeat(np.arange(len(per_item), dtype=np.int64), per_item))
    rng = np.random.default_rng([seed, 1])
    iids = np.empty_like(movies)
    for mine in (~held, held):
        iids[mine] = rng.permutation(movies[mine])
    return uids, iids


def sizes(cfg: dict) -> dict:
    s = _sizes(cfg)
    held = int(held_out_rows(cfg).sum())
    return dict(s, n_train=s["n"] - held, n_heldout=held)


def make_sets(cfg: dict, seed: int, mesh=None) -> tuple:
    import jax
    import jax.numpy as jnp
    from jax import lax

    if mesh is not None:
        raise ValueError("ml20m_holdout hands both sets to one chip")
    s = sizes(cfg)
    n, d_g, d_u, d_i = s["n"], s["d_g"], s["d_u"], s["d_i"]
    if d_u + d_i > d_g:
        raise ValueError("the random-effect shards are built from the "
                         "fixed shard's leading columns: d_u + d_i <= d_g")
    rows = min(CHUNK_ROWS, n)
    chunks = -(-n // rows)
    uids, iids = entity_columns(cfg, seed)
    held = held_out_rows(cfg)
    # where a row goes in ITS set; in the other set's column it is sent past
    # the end, and a scatter drops what lies there
    into = (np.cumsum(~held) - 1, np.cumsum(held) - 1)
    dest = [np.where(mine, at, len(mine)).astype(np.int32)
            for mine, at in zip((~held, held), into)]

    def generate(k_truth, k_rows, uid, iid, dest_train, dest_held):
        k_wg, k_wu, k_wi = jax.random.split(k_truth, 3)
        wg = jax.random.normal(k_wg, (d_g,), jnp.float32) * 0.05
        wu = jax.random.normal(k_wu, (s["users"], d_u), jnp.float32) * 0.15
        wi = jax.random.normal(k_wi, (s["items"], d_i), jnp.float32) * 0.15

        def body(c, bufs):
            xg_train, xg_held, xu_t, xi_t, y = bufs
            start = jnp.minimum(c * rows, n - rows)
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

            def placed(buf, where):
                at = lax.dynamic_slice(where, (start,), (rows,))
                return buf.at[at].set(xg_c, mode="drop")

            return (placed(xg_train, dest_train), placed(xg_held, dest_held),
                    lax.dynamic_update_slice(xu_t, xu_c, (0, start)),
                    lax.dynamic_update_slice(xi_t, xi_c, (0, start)),
                    lax.dynamic_update_slice(y, y_c, (start,)))

        return lax.fori_loop(0, chunks, body, (
            jnp.zeros((s["n_train"], d_g), jnp.float32),
            jnp.zeros((s["n_heldout"], d_g), jnp.float32),
            jnp.zeros((d_u, n), jnp.float32),
            jnp.zeros((d_i, n), jnp.float32),
            jnp.zeros((n,), jnp.float32)))

    xg_train, xg_held, xu_t, xi_t, y = jax.jit(generate)(
        jax.random.PRNGKey(int(cfg["truth_seed"])), jax.random.PRNGKey(seed),
        jnp.asarray(uids, jnp.int32), jnp.asarray(iids, jnp.int32),
        jnp.asarray(dest[0]), jnp.asarray(dest[1]))
    y, xu_t, xi_t = np.asarray(y), np.asarray(xu_t), np.asarray(xi_t)

    def rows_of(x_t, mine):  # [d, n] -> the set's [rows, d]: 16 compressions
        return np.ascontiguousarray(x_t[:, mine].T)  # of a contiguous row

    def one(mine, xg):
        return {"y": y[mine],
                "features": {"g": xg, "u": rows_of(xu_t, mine),
                             "i": rows_of(xi_t, mine)},
                "id_tags": {"userId": uids[mine], "itemId": iids[mine]}}

    return one(~held, xg_train), one(held, xg_held)


def make_training(cfg: dict, seed: int, mesh=None) -> dict:
    return make_sets(cfg, seed, mesh)[0]
