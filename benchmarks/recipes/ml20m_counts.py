"""Data recipe ``ml20m_counts``: the three-coordinate GLMix of
``glmix3_wide`` over MovieLens-20M's rows per user and per movie.

WHO has HOW MANY rows is the configuration's and never the seed's: both
count vectors are functions of the config and its ``truth_seed`` alone, so
every ``--seed`` meets the same capacity classes with the same lanes, one
program (``recipes/correlated_shards.py`` tells how PR 22 learnt that).
``--seed`` draws the features, the noise, the labels and which movie meets
which user.

The counts (the source fixes the marginals; the family is ``assumed``):
rows per user and per movie are discrete log-normals laid on the quantile
grid ``(i + 1/2) / N`` of the WHOLE source population (no sampling noise),
``exp(mu)`` the median, truncated at the maximum, floored at the minimum,
``sigma`` found by bisection so that the population sums to the source's
rows.  The users run are the first ``users`` of a ``truth_seed``
permutation of that population (whole users); every movie stays, its count
scaled by rows run / rows of the source (largest remainder, at least 1).

The generative model is ``correlated_shards``' (float32 unit-normal fixed
design, two random-effect shards that correlate with its leading columns,
the same coefficient scales), written out again here because that recipe's
generator is a closure over equal counts.  Rows arrive grouped by user,
ascending, as ``ratings.csv`` does; a movie's rows lie anywhere.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from recipes.chip_signal import CHUNK_ROWS


def _ndtri(p: np.ndarray) -> np.ndarray:
    """The standard normal's quantile function in float64 numpy (Acklam's
    rational approximation, relative error under 1.2e-9): the same counts
    on every platform."""
    a = (-3.969683028665376e+01, 2.209460984245205e+02, -2.759285104469687e+02,
         1.383577518672690e+02, -3.066479806614716e+01, 2.506628277459239e+00)
    b = (-5.447609879822406e+01, 1.615858368580409e+02, -1.556989798598866e+02,
         6.680131188771972e+01, -1.328068155288572e+01)
    c = (-7.784894002430293e-03, -3.223964580411365e-01, -2.400758277161838e+00,
         -2.549732539343734e+00, 4.374664141464968e+00, 2.938163982698783e+00)
    d = (7.784695709041462e-03, 3.224671290700398e-01, 2.445134137142996e+00,
         3.754408661907416e+00)
    p = np.asarray(p, np.float64)
    tail = np.minimum(p, 1.0 - p)
    q = np.sqrt(-2.0 * np.log(tail))
    far = -np.sign(p - 0.5) * (np.polyval(c, q) / np.polyval(d + (1.0,), q))
    r = (p - 0.5) ** 2
    near = (p - 0.5) * np.polyval(a, r) / np.polyval(b + (1.0,), r)
    return np.where(tail < 0.02425, far, near)


def _phi(z: float) -> float:
    return 0.5 * (1.0 + math.erf(z / math.sqrt(2.0)))


def lognormal_counts(n: int, minimum: int, median: float, maximum: int,
                     total: int) -> np.ndarray:
    """[n] int64 ascending: ``max(minimum, round(exp(mu + sigma z)))`` on
    the quantile grid of a normal truncated above where the count reaches
    ``maximum``; ``exp(mu) = median``; ``sigma`` by bisection on the sum,
    what rounding leaves of ``total`` spread one each over the largest
    counts under the maximum."""
    mu = np.log(float(median))
    u = (np.arange(n) + 0.5) / n

    def counts(sigma):
        z = _ndtri(u * _phi((np.log(float(maximum)) - mu) / sigma))
        return np.clip(np.rint(np.exp(mu + sigma * z)), minimum,
                       maximum).astype(np.int64)

    # the sum rises with sigma until the truncation eats the upper half:
    # bracket its FIRST crossing of the total, then bisect
    lo, hi = 0.05, 0.0625
    while counts(hi).sum() < total:
        lo, hi = hi, hi * 1.25
        if hi > 8.0 or counts(lo).sum() > total:
            raise ValueError(f"no log-normal over [{minimum}, {maximum}] "
                             f"with median {median} sums to {total} over {n}")
    for _ in range(40):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if counts(mid).sum() <= total else (lo, mid)
    out = counts(lo)
    left = int(total - out.sum())  # >= 0, and far under n
    room = np.flatnonzero(out < maximum)[::-1][:left]
    if len(room) < left:
        raise ValueError("the rounding's remainder does not fit")
    out[room] += 1
    return np.sort(out)


def scaled_counts(counts: np.ndarray, total: int) -> np.ndarray:
    """``counts`` scaled to sum to ``total``: largest remainder, and at
    least 1 each (what that adds is taken off the largest)."""
    exact = counts * (total / counts.sum())
    out = np.maximum(np.floor(exact).astype(np.int64), 1)
    left = int(total - out.sum())
    if left >= 0:
        out[np.argsort(-(exact - np.floor(exact)), kind="stable")[:left]] += 1
    else:
        out[np.argsort(-out, kind="stable")[:-left]] -= 1
    return out


@functools.lru_cache(maxsize=4)
def _row_counts(users, source_users, source_items, source_rows, user_rows,
                item_rows, truth_seed) -> tuple:
    population = lognormal_counts(source_users, *user_rows, source_rows)
    keep = np.random.default_rng([truth_seed, 7]).permutation(
        source_users)[:users]
    per_user = population[keep]
    per_item = scaled_counts(
        lognormal_counts(source_items, *item_rows, source_rows),
        int(per_user.sum()))
    # movie ids in no order of popularity
    per_item = per_item[np.random.default_rng(
        [truth_seed, 8]).permutation(source_items)]
    per_user.setflags(write=False)
    per_item.setflags(write=False)
    return per_user, per_item


def row_counts(cfg: dict) -> tuple:
    """(rows of each user run [users], rows of each movie [items]): the
    config's and ``truth_seed``'s, the same for every ``--seed``."""
    def marginals(m):
        return int(m["min"]), float(m["median"]), int(m["max"])

    return _row_counts(
        int(cfg["users"]), int(cfg["source_users"]), int(cfg["source_items"]),
        int(cfg["source_rows"]), marginals(cfg["user_rows"]),
        marginals(cfg["item_rows"]), int(cfg["truth_seed"]))


def sizes(cfg: dict) -> dict:
    fixed, user, item = cfg["coordinates"]
    per_user, per_item = row_counts(cfg)
    return dict(users=len(per_user), items=len(per_item),
                n=int(per_user.sum()), d_g=int(fixed["dim"]),
                d_u=int(user["dim"]), d_i=int(item["dim"]))


def entity_columns(cfg: dict, seed: int) -> tuple:
    """(uids [n], iids [n]) int64: rows grouped by user, ascending; which
    of a movie's rows meets which user from ``seed``."""
    per_user, per_item = row_counts(cfg)
    uids = np.repeat(np.arange(len(per_user), dtype=np.int64), per_user)
    iids = np.random.default_rng([seed, 1]).permutation(
        np.repeat(np.arange(len(per_item), dtype=np.int64), per_item))
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
    # n is whatever the counts sum to: equal chunks, the last one moved back
    # to end at n (the rows it shares with the one before are written twice,
    # the second time for good)
    rows = min(CHUNK_ROWS, n)
    chunks = -(-n // rows)
    uids, iids = entity_columns(cfg, seed)

    def generate(k_truth, k_rows, uid, iid):
        k_wg, k_wu, k_wi = jax.random.split(k_truth, 3)
        wg = jax.random.normal(k_wg, (d_g,), jnp.float32) * 0.05
        wu = jax.random.normal(k_wu, (s["users"], d_u), jnp.float32) * 0.15
        wi = jax.random.normal(k_wi, (s["items"], d_i), jnp.float32) * 0.15

        def body(c, bufs):
            xg, xu_t, xi_t, y = bufs
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
        jnp.asarray(uids, jnp.int32), jnp.asarray(iids, jnp.int32))
    return {"y": np.asarray(y),
            "features": {"g": xg,
                         "u": np.ascontiguousarray(np.asarray(xu_t).T),
                         "i": np.ascontiguousarray(np.asarray(xi_t).T)},
            "id_tags": {"userId": uids, "itemId": iids}}
