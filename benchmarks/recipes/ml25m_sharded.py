"""Data recipe ``ml25m_sharded``: ``ml20m_counts``' GLMix, handed over in
shards: no chip ever holds an ``[n, d]`` array whole.

WHO has HOW MANY rows, and which movie meets which user, are
``recipes/ml20m_counts.py``'s (``row_counts``, ``entity_columns``, ``sizes``,
imported): the configuration's and ``truth_seed``'s, the same for every
``--seed``.  The generative model is that recipe's too (float32 unit-normal
fixed design, two random-effect shards that correlate with its leading
columns, the same coefficient scales), written out again here because it is
a closure inside ``make_training`` there and because here EACH CHIP draws
ITS OWN rows: the generator runs under ``shard_map`` over the mesh, a chip's
rows in chunks keyed by ``(--seed, the chip's index, the chunk)``.  So the
sample depends on the number of chips (the problem, its counts and its truth
do not); the fit's answers for one sample do not (tier-1 holds a fit under
a mesh to the one-device fit of the same data).

**The hand-over under a mesh** (the contract with ``build_coordinate``):

- the row count ``n`` is the source's and is NOT rounded to the chips.
  ``y`` [n] and the id columns [n] are host arrays of the true length;
- ``features["g"]``, the fixed design, is a ``jax.Array``
  ``[n_pad, d_g]`` sharded by rows over every axis of the mesh, ``n_pad``
  the program's own ``parallel/mesh.padded_samples(n, mesh)`` (asked of
  the program, not copied from it: whole tiles of every ``[n]`` vector on
  every chip; 25,000,095 -> 25,001,984 on four chips, 0.008% more).  Its
  trailing ``n_pad - n`` rows are PADDING, all zero, behind the last
  chip's last row.  ``GameData`` admits more rows than ``y`` has in a
  design that lies in row shards over more than one device, and only the
  fixed effect under that mesh takes it, at exactly that many rows: it
  gives them label 0 and weight 0.  Nothing copies or re-pads the
  design: the fixed effect's kernels run each chip's shard where it lies;
- ``features["u"]`` and ``["i"]`` [n, 16] are host arrays (each chip's
  ``[16, n / chips]`` slice fetched and transposed on the host): the
  bucketer cuts them into entity lanes on the host, and the coordinate
  places lanes and the entity-major design straight onto their chips.

Without a mesh (a one-chip cell) the same generator runs on one device and
``n_pad == n``.
"""

from __future__ import annotations

import numpy as np

from recipes.chip_signal import CHUNK_ROWS
from recipes.ml20m_counts import entity_columns, row_counts, sizes  # noqa: F401


def make_training(cfg: dict, seed: int, mesh=None) -> dict:
    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from photon_ml_tpu.parallel.mesh import padded_samples, spans_chips

    s = sizes(cfg)
    n, d_g, d_u, d_i = s["n"], s["d_g"], s["d_u"], s["d_i"]
    if d_u + d_i > d_g:
        raise ValueError("the random-effect shards are built from the "
                         "fixed shard's leading columns: d_u + d_i <= d_g")
    if mesh is None:
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    axes = tuple(mesh.axis_names)
    chips = mesh.size
    n_pad = padded_samples(n, spans_chips(mesh))  # the program's sample axis
    local = n_pad // chips   # rows a chip holds
    # equal chunks of a chip's rows, the last one moved back to end at the
    # shard's end (the rows it shares with the one before are written
    # twice, the second time for good)
    rows = min(CHUNK_ROWS, local)
    chunks = -(-local // rows)
    uids, iids = entity_columns(cfg, seed)

    def padded(ids):  # the padding rows name entity 0; they are zeroed below
        return np.concatenate([ids, np.zeros(n_pad - n, ids.dtype)]).astype(
            np.int32)

    def generate(k_truth, k_rows, uid, iid):
        """One chip's rows: uid, iid [local] are its slice."""
        chip = lax.axis_index(axes)
        k_chip = jax.random.fold_in(k_rows, chip)
        k_wg, k_wu, k_wi = jax.random.split(k_truth, 3)
        wg = jax.random.normal(k_wg, (d_g,), jnp.float32) * 0.05
        wu = jax.random.normal(k_wu, (s["users"], d_u), jnp.float32) * 0.15
        wi = jax.random.normal(k_wi, (s["items"], d_i), jnp.float32) * 0.15

        def body(c, bufs):
            xg, xu_t, xi_t, y = bufs
            start = jnp.minimum(c * rows, local - rows)
            k1, k2, k3, k4 = jax.random.split(
                jax.random.fold_in(k_chip, c), 4)
            # a row past the source's last is padding: all zero
            live = (chip * local + start + jnp.arange(rows)) < n
            xg_c = jnp.where(live[:, None], jax.random.normal(
                k1, (rows, d_g), jnp.float32), 0.0)
            xu_c = jnp.where(live[None, :], (
                0.6 * xg_c[:, :d_u].T
                + 0.8 * jax.random.normal(k2, (d_u, rows), jnp.float32)), 0.0)
            xi_c = jnp.where(live[None, :], (
                0.6 * xg_c[:, d_u:d_u + d_i].T
                + 0.8 * jax.random.normal(k3, (d_i, rows), jnp.float32)), 0.0)
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
            jnp.zeros((local, d_g), jnp.float32),
            jnp.zeros((d_u, local), jnp.float32),
            jnp.zeros((d_i, local), jnp.float32),
            jnp.zeros((local,), jnp.float32)))

    by_rows, by_columns = P(axes), P(None, axes)
    sharded = jax.jit(shard_map(
        generate, mesh=mesh, in_specs=(P(), P(), by_rows, by_rows),
        out_specs=(P(axes, None), by_columns, by_columns, by_rows),
        check_vma=False))
    put = lambda ids: jax.device_put(padded(ids), NamedSharding(mesh, by_rows))
    xg, xu_t, xi_t, y = sharded(
        jax.random.PRNGKey(int(cfg["truth_seed"])), jax.random.PRNGKey(seed),
        put(uids), put(iids))

    def rows_on_host(x_t):  # [d, n_pad] in column shards -> [n, d] host
        return np.ascontiguousarray(np.asarray(x_t).T[:n])

    return {"y": np.asarray(y)[:n],
            "features": {"g": xg, "u": rows_on_host(xu_t),
                         "i": rows_on_host(xi_t)},
            "id_tags": {"userId": uids, "itemId": iids}}
