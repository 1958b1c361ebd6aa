"""Data recipe ``ml20m_bags``: ``ml20m_counts``' GLMix with the per-user
effect moved onto the MOVIE's sparse feature bag (genres + Tag Genome).

WHO has HOW MANY rows, the users run, the movies and which movie's row meets
which user are ``recipes/ml20m_counts.py``'s for the same seeds
(``row_counts``, ``entity_columns``, imported).  The fixed design and the
per-item shard are that recipe's generative model too (float32 unit-normal
fixed design, a per-item shard that correlates with its leading columns,
the same coefficient scales), written out again here because there it is a
closure that also fills a dense per-user shard.

**The bags are the problem's, not the sample's** (``movie_bags``: the
configuration's and ``truth_seed``'s): every movie draws its bag ONCE and
every row of that movie carries it, as a join of ``ratings.csv`` with
``movies.csv`` and ``genome-scores.csv`` does.  The vocabulary is ``dim`` =
1 intercept (column 0, value 1 in every row) + ``genres`` values (value 1)
+ ``tags`` tags (value = the tag's relevance to the movie, in (0.5, 1]).  A
movie has 1 to ``genres_per_movie.max`` genres (1 + a Poisson draw, about
``genres_per_movie.mean``), picked by a popularity law; the ``tag_movies``
most-rated movies also carry their ``tags_per_movie`` most relevant tags,
picked by a popularity law over the tags.  A row's pairs are
``row_width`` wide: intercept, genres ascending, tags ascending, then
padding (index 0, value 0, as ``SparseShard`` says).

The true per-user coefficients are SPARSE over the vocabulary (an
intercept, a few genres and a few tags a user, drawn by the same popularity
laws so that a user meets them), from ``truth_seed``; ``--seed`` draws the
fixed design, the per-item shard, the noise and the labels.

``features["u"]`` is ``{"indices", "values", "dim"}``: host arrays [n, k],
what the program's ``SparseShard`` holds; the traffic kind wraps it.
"""

from __future__ import annotations

import numpy as np

from recipes.chip_signal import CHUNK_ROWS
from recipes.ml20m_counts import entity_columns, row_counts

USER_GENRES, USER_TAGS = 3, 6  # nonzero true coefficients a user, besides the intercept


def _popularity(count: int, flat: float) -> np.ndarray:
    """[count] probabilities falling as 1 / (rank + flat)."""
    p = 1.0 / (np.arange(count) + flat)
    return p / p.sum()


def _draw_distinct(rng, p: np.ndarray, rows: int, width: int) -> np.ndarray:
    """[rows, width] column ranks, ``width`` distinct draws a row with
    probabilities ``p`` (Gumbel keys, the ``width`` largest), each row's in
    no order."""
    keys = np.log(p)[None, :] + rng.gumbel(size=(rows, len(p)))
    if width >= len(p):
        return np.argsort(-keys, axis=1)[:, :width]
    return np.argpartition(-keys, width, axis=1)[:, :width]


def shape(cfg: dict) -> dict:
    user = cfg["coordinates"][1]
    genres, tags = int(cfg["genres"]), int(cfg["tags"])
    dim = 1 + genres + tags
    if int(user["dim"]) != dim:
        raise ValueError(f"the per-user vocabulary is 1 + genres + tags = "
                         f"{dim}, the coordinate says {user['dim']}")
    width = int(cfg["row_width"])
    most = 1 + int(cfg["genres_per_movie"]["max"]) + int(cfg["tags_per_movie"])
    if most > width:
        raise ValueError(f"a bag holds up to {most} pairs, row_width is "
                         f"{width}")
    return dict(genres=genres, tags=tags, dim=dim, width=width)


def movie_bags(cfg: dict) -> tuple:
    """(indices [items, k] int32, values [items, k] float32): each movie's
    bag, the configuration's and ``truth_seed``'s."""
    s = shape(cfg)
    genres, tags, width = s["genres"], s["tags"], s["width"]
    _, per_item = row_counts(cfg)
    items = len(per_item)
    tags_per_movie = int(cfg["tags_per_movie"])
    g_max = int(cfg["genres_per_movie"]["max"])
    g_mean = float(cfg["genres_per_movie"]["mean"])
    rng = np.random.default_rng([int(cfg["truth_seed"]), 11])
    idx = np.zeros((items, width), np.int32)
    val = np.zeros((items, width), np.float32)
    val[:, 0] = 1.0                                   # the intercept
    n_genres = 1 + np.minimum(rng.poisson(g_mean - 1.0, items), g_max - 1)
    drawn = _draw_distinct(rng, _popularity(genres, 2.0), items, g_max)
    live = np.arange(g_max)[None, :] < n_genres[:, None]
    # ascending columns, the unused draws behind them
    cols = np.sort(np.where(live, 1 + drawn, 1 + genres + tags), axis=1)
    idx[:, 1:1 + g_max] = np.where(live, cols, 0)
    val[:, 1:1 + g_max] = live
    # the Tag Genome covers the most-rated movies
    tagged = np.argsort(-per_item, kind="stable")[:int(cfg["tag_movies"])]
    picked = np.sort(_draw_distinct(rng, _popularity(tags, 20.0),
                                    len(tagged), tags_per_movie), axis=1)
    relevance = 1.0 - 0.5 * rng.random(picked.shape)  # (0.5, 1]
    at = 1 + n_genres[tagged]                         # behind the genres
    slot = at[:, None] + np.arange(tags_per_movie)[None, :]
    idx[tagged[:, None], slot] = 1 + genres + picked
    val[tagged[:, None], slot] = relevance
    return idx, val


def user_truth(cfg: dict, users: int) -> np.ndarray:
    """[users, dim] float32, sparse: an intercept, ``USER_GENRES`` genres
    and ``USER_TAGS`` tags a user, from ``truth_seed``."""
    s = shape(cfg)
    rng = np.random.default_rng([int(cfg["truth_seed"]), 12])
    w = np.zeros((users, s["dim"]), np.float32)
    w[:, 0] = rng.normal(0.0, 0.4, users)
    rows = np.arange(users)[:, None]
    g = _draw_distinct(rng, _popularity(s["genres"], 2.0), users, USER_GENRES)
    w[rows, 1 + g] = rng.normal(0.0, 0.6, g.shape)
    t = _draw_distinct(rng, _popularity(s["tags"], 20.0), users, USER_TAGS)
    w[rows, 1 + s["genres"] + t] = rng.normal(0.0, 0.8, t.shape)
    return w


def sizes(cfg: dict) -> dict:
    fixed, _, item = cfg["coordinates"]
    per_user, per_item = row_counts(cfg)
    return dict(users=len(per_user), items=len(per_item),
                n=int(per_user.sum()), d_g=int(fixed["dim"]),
                d_i=int(item["dim"]), **shape(cfg))


def make_training(cfg: dict, seed: int, mesh=None) -> dict:
    import jax
    import jax.numpy as jnp
    from jax import lax

    if mesh is not None:
        raise ValueError("ml20m_bags hands its rows to one chip")
    s = sizes(cfg)
    n, d_g, d_i = s["n"], s["d_g"], s["d_i"]
    rows = min(CHUNK_ROWS, n)
    chunks = -(-n // rows)
    uids, iids = entity_columns(cfg, seed)
    bag_idx, bag_val = movie_bags(cfg)
    indices, values = bag_idx[iids], bag_val[iids]    # [n, k]: a row, its movie's bag
    dim = s["dim"]

    def generate(k_truth, k_rows, uid, iid, bags, wu):
        k_wg, _, k_wi = jax.random.split(k_truth, 3)
        wg = jax.random.normal(k_wg, (d_g,), jnp.float32) * 0.05
        wi = jax.random.normal(k_wi, (s["items"], d_i), jnp.float32) * 0.15

        def body(c, bufs):
            xg, xi_t, y = bufs
            start = jnp.minimum(c * rows, n - rows)
            k1, _, k3, k4 = jax.random.split(
                jax.random.fold_in(k_rows, c), 4)
            xg_c = jax.random.normal(k1, (rows, d_g), jnp.float32)
            xi_c = (0.6 * xg_c[:, :d_i].T
                    + 0.8 * jax.random.normal(k3, (d_i, rows), jnp.float32))
            uid_c = lax.dynamic_slice(uid, (start,), (rows,))
            iid_c = lax.dynamic_slice(iid, (start,), (rows,))
            # the per-user term: k coefficients a row out of the sparse
            # truth, laid flat
            idx_c, val_c = bags[0][iid_c], bags[1][iid_c]
            logit = (jnp.sum(xg_c * wg[None, :], axis=1)
                     + jnp.sum(wu[uid_c[:, None] * dim + idx_c] * val_c,
                               axis=1)
                     + jnp.sum(xi_c.T * wi[iid_c], axis=1))
            y_c = (jax.random.uniform(k4, (rows,))
                   < jax.nn.sigmoid(logit)).astype(jnp.float32)
            return (lax.dynamic_update_slice(xg, xg_c, (start, 0)),
                    lax.dynamic_update_slice(xi_t, xi_c, (0, start)),
                    lax.dynamic_update_slice(y, y_c, (start,)))

        return lax.fori_loop(0, chunks, body, (
            jnp.zeros((n, d_g), jnp.float32),
            jnp.zeros((d_i, n), jnp.float32),
            jnp.zeros((n,), jnp.float32)))

    xg, xi_t, y = jax.jit(generate)(
        jax.random.PRNGKey(int(cfg["truth_seed"])), jax.random.PRNGKey(seed),
        jnp.asarray(uids, jnp.int32), jnp.asarray(iids, jnp.int32),
        (jnp.asarray(bag_idx), jnp.asarray(bag_val)),
        jnp.asarray(user_truth(cfg, s["users"]).reshape(-1)))
    return {"y": np.asarray(y),
            "features": {"g": xg,
                         "u": {"indices": indices, "values": values,
                               "dim": s["dim"]},
                         "i": np.ascontiguousarray(np.asarray(xi_t).T)},
            "id_tags": {"userId": uids, "itemId": iids}}
