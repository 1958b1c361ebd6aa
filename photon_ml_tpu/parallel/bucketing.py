"""Entity bucketing: the TPU-native replacement for the reference's
random-effect data layout (groupByKey shuffle -> RDD[(REId, LocalDataset)]).

Reference machinery being replaced (SURVEY.md §2.2):
  - RandomEffectDataset.apply: groupBy REId shuffle, deterministic reservoir
    cap with weight rescale count/cap (RandomEffectDataset.scala:358-420)
  - RandomEffectDatasetPartitioner: balanced entity->partition assignment
    (RandomEffectDatasetPartitioner.scala:30-171)
  - RandomEffectCoordinate.updateModel: per-entity serial solves inside
    mapValues (RandomEffectCoordinate.scala:104-153)

TPU-native design: entities are grouped ONCE on host into statically-shaped
buckets — all entities in a bucket share a sample capacity S (next power of
two of their active count) — then every entity in a bucket is solved
SIMULTANEOUSLY by ``vmap``-ing the jittable solver over the entity lane, with
the entity lane sharded across the whole mesh.  Padding rows carry weight 0
(inert by the core masking contract); padding lanes are whole fake entities
whose solves are discarded.  Millions of serial executor-core solves become a
handful of dense [E, S, d] batched programs on the MXU.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.core.batch import DenseBatch
from photon_ml_tpu.core.objective import GLMObjective
from photon_ml_tpu.opt.solve import make_solver
from photon_ml_tpu.opt.types import SolverConfig, SolverResult
from photon_ml_tpu.types import OptimizerType

Array = jax.Array


def _splitmix64(x: np.ndarray) -> np.ndarray:
    """Deterministic 64-bit mix for reservoir keys (the reference uses
    byteswap64(hash ^ uniqueId), RandomEffectDataset.scala:394-401 — any
    fixed avalanche mix gives the same recompute-stable property)."""
    x = (x + np.uint64(0x9E3779B97F4A7C15)).astype(np.uint64)
    x = ((x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)).astype(np.uint64)
    x = ((x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)).astype(np.uint64)
    return x ^ (x >> np.uint64(31))


@dataclasses.dataclass
class Bucket:
    """One capacity class of entities, device-ready.

    Arrays: x [E, S, d], y/offset/weight [E, S], rows [E, S] int32 (original
    sample row of each slot, -1 for padding), counts [E] int32 (real samples
    per entity), entity_lanes [E] int64 (original entity id per lane, -1 for
    padding lanes).  ``run_lanes``: in each of the ``lane_multiple`` equal
    shares of the lanes, the first ``run_lanes`` hold rows that are one
    consecutive run of samples, and the ``window_lanes`` behind them rows
    that lie inside one short window of samples (``_class_lanes``; both 0:
    the order the entities came in, padding lanes last).
    """

    x: np.ndarray
    y: np.ndarray
    offset: np.ndarray
    weight: np.ndarray
    rows: np.ndarray
    counts: np.ndarray
    entity_lanes: np.ndarray
    run_lanes: int = 0
    window_lanes: int = 0

    @property
    def num_lanes(self) -> int:
        return self.x.shape[0]

    @property
    def capacity(self) -> int:
        return self.x.shape[1]

    def batch(self) -> DenseBatch:
        return DenseBatch(
            x=jnp.asarray(self.x), y=jnp.asarray(self.y),
            offset=jnp.asarray(self.offset), weight=jnp.asarray(self.weight),
        )


@dataclasses.dataclass
class EntityBuckets:
    """All buckets for one random-effect coordinate + the entity directory.

    ``lane_of``: entity id -> (bucket index, lane) for model lookup/update.
    ``compact``: design blocks are per-lane OBSERVED-column bases (the
    sparse bucketer), not the shared full-vocabulary basis — an explicit
    marker because the padded compact width can EQUAL ``dim`` while lane
    column j still means "the lane's j-th observed feature", so width
    comparison cannot detect compactness.
    """

    buckets: List[Bucket]
    lane_of: Dict[int, Tuple[int, int]]
    dim: int
    num_entities: int
    num_samples: int  # original sample-row count (scores vector length)
    compact: bool = False
    capped_entities: int = 0  # entities over the active cap
    passive_rows: int = 0     # their rows outside the reservoir: scored only
    # compact buckets: the columns the entities observed in their active
    # rows, summed, and the entities a features-to-samples bound cut
    observed_columns: int = 0
    filtered_entities: int = 0

    def entity_ids(self) -> np.ndarray:
        return np.asarray(sorted(self.lane_of), np.int64)


EntityRuns = Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]


def entity_runs(entity_ids: np.ndarray) -> EntityRuns:
    """``(entities [U], counts [U], order [n])``: the sorted distinct
    entity ids, the rows of each, and ALL rows grouped by entity in that
    order, each entity's in sample order (a stable sort).  What the
    bucketers cut their lanes from and what ``entity_major_layout`` lays the
    full-sample design out by: computed once per coordinate.  Rows that
    arrive grouped already (ids ascending) are recognised in one pass and
    skip the sort: ``order`` is then None, for ``arange(n)``."""
    ids = np.asarray(entity_ids, np.int64)
    n = len(ids)
    if n and np.all(ids[1:] >= ids[:-1]):
        starts = np.flatnonzero(np.r_[True, ids[1:] != ids[:-1]])
        return ids[starts], np.diff(np.r_[starts, n]), None
    uniq, inverse, counts = np.unique(ids, return_inverse=True,
                                      return_counts=True)
    return uniq, counts, np.argsort(inverse, kind="stable")


def _group_rows(
    entity_ids: np.ndarray,
    active_cap: Optional[int],
    min_active_samples: int,
    seed: int,
    existing_model_keys: Optional[frozenset] = None,
    row_ids: Optional[np.ndarray] = None,
    runs: Optional[EntityRuns] = None,
) -> Tuple[List[np.ndarray], List[int], List[float]]:
    """Group sample rows by entity with the deterministic reservoir cap +
    weight rescale count/cap (reference RandomEffectDataset.scala:358-420)
    and the min-active lower bound (:319-341).  Shared by the dense and
    row-sparse bucketers.

    ``existing_model_keys`` (warm start): the reference's lower-bound filter
    drops an under-bound entity only when a prior model already covers it
    (that model then passes through unchanged — RandomEffectCoordinate
    .updateModel's leftOuterJoin :114-127); an under-bound NEW entity still
    trains, else it would never get a model at all
    (RandomEffectDataset.scala:322-333).

    ``row_ids``: GLOBAL sample-row id per local row (multihost entity-sharded
    reads, parallel/multihost.py).  Reservoir keys mix the global id, so an
    entity keeps the SAME samples no matter how many hosts the data is split
    over — the recompute-stable property the reference gets from hashing
    uniqueId (RandomEffectDataset.scala:394-401), extended across topology.

    ``runs``: ``entity_runs(entity_ids)`` where the caller has it already."""
    uniq, counts, order = entity_runs(entity_ids) if runs is None else runs
    if order is None:
        order = np.arange(len(entity_ids))
    starts = np.concatenate([[0], np.cumsum(counts)])

    kept_rows: List[np.ndarray] = []
    kept_entities: List[int] = []
    rescale: List[float] = []
    for e in range(len(uniq)):
        rows = order[starts[e]: starts[e + 1]]
        if len(rows) < min_active_samples and (
                existing_model_keys is None
                or int(uniq[e]) in existing_model_keys):
            continue
        scale = 1.0
        if active_cap is not None and len(rows) > active_cap:
            gids = rows if row_ids is None else row_ids[rows]
            keys = _splitmix64(gids.astype(np.uint64) ^ np.uint64(seed))
            rows = rows[np.argsort(keys, kind="stable")[:active_cap]]
            scale = len(keys) / active_cap  # weight rescale count/cap
        kept_rows.append(np.sort(rows))
        kept_entities.append(int(uniq[e]))
        rescale.append(scale)
    return kept_rows, kept_entities, rescale


def _capacity_classes(kept_rows: List[np.ndarray]) -> np.ndarray:
    """Per-entity bucket capacity: next power of two of the active count —
    ONE rounding rule for the dense and sparse bucketers."""
    return np.asarray([max(1, 1 << (len(r) - 1).bit_length())
                       for r in kept_rows])


def _passive(kept_rows: List[np.ndarray], rescale: List[float]) -> dict:
    """``capped_entities`` and ``passive_rows`` of ``_group_rows``' result:
    a capped entity's weight is count / kept, so it leaves kept x (weight
    - 1) of its rows out of training."""
    scale = np.asarray(rescale, np.float64)
    kept = np.fromiter(map(len, kept_rows), np.int64, len(kept_rows))
    capped = scale > 1.0
    return dict(capped_entities=int(capped.sum()),
                passive_rows=int(np.rint(kept[capped]
                                         * (scale[capped] - 1.0)).sum()))


def _class_lanes(idxs: np.ndarray, kept_rows: List[np.ndarray], cap: int,
                 lane_multiple: int, row_ids: Optional[np.ndarray] = None
                 ) -> Tuple[np.ndarray, int, int]:
    """``(lanes [n_lanes], run_lanes, window_lanes)``: the entity (an index
    into ``kept_rows``; -1: a padding lane) of each lane of one capacity
    class, the lane count padded to ``lane_multiple``, and how many lanes at
    the head of each of the ``lane_multiple`` equal shares are RUN lanes and
    how many behind those WINDOW lanes.

    One rule over a lane's rows as ``Bucket.rows`` stores them, ascending
    and distinct, ``span = last - first + 1``.  ``span == len(rows)``: one
    consecutive run of samples ``start .. start + k - 1`` (an uncapped
    entity of data that arrives grouped by entity), a run lane, which
    ``offsets_into_lanes`` addresses by its start.  ``len(rows) < span <=
    WINDOW_SPAN_MAX x cap``: the rows lie inside one short window of
    samples (a reservoir out of an entity's consecutive rows), a window
    lane, addressed by the window's start, its kept slots picked out of it
    on the chip.  The rule reads the rows and nothing else: rows that lie
    anywhere, a reservoir out of many times its capacity and every lane of a
    class under ``RUN_CAPACITY_MIN`` keep one index a slot.  Every share
    holds the SAME number of run lanes and of window lanes (under a mesh a
    share is a chip's, and the program is one for all chips): each kind is
    dealt out in turn, and those that do not fill a round stay index lanes.
    With neither the order is the entities' own, padding lanes last."""
    def kind(rows):  # 1: a run, 2: a window, 0: one index a slot
        stored = rows if row_ids is None else row_ids[rows]
        if int(rows[-1]) - int(rows[0]) == len(rows) - 1 and (
                row_ids is None or bool(np.all(np.diff(stored) == 1))):
            return 1
        if row_ids is not None and not np.all(stored[1:] > stored[:-1]):
            return 0
        span = int(stored[-1]) - int(stored[0]) + 1
        return 2 if len(rows) < span <= WINDOW_SPAN_MAX * cap else 0

    n_lanes = -(-len(idxs) // lane_multiple) * lane_multiple
    share = n_lanes // lane_multiple
    lanes = np.full(n_lanes, -1, np.int64)
    kinds = (np.fromiter((kind(kept_rows[ei]) for ei in idxs), np.int8,
                         len(idxs)) if cap >= RUN_CAPACITY_MIN
             else np.zeros(len(idxs), np.int8))
    rest, heads = np.ones(len(idxs), bool), []
    for k in (1, 2):
        found = np.flatnonzero(kinds == k)
        per_share = len(found) // lane_multiple
        dealt = found[:per_share * lane_multiple]
        at = sum(heads)
        lanes.reshape(lane_multiple, share)[:, at:at + per_share] = idxs[
            dealt].reshape(per_share, lane_multiple).T
        rest[dealt] = False
        heads.append(per_share)
    tails = (np.arange(lane_multiple)[:, None] * share
             + np.arange(sum(heads), share)[None, :]).ravel()
    lanes[tails[:int(rest.sum())]] = idxs[rest]
    return (lanes, *heads)


def _pack_lane_meta(cap, lanes, kept_rows, kept_entities, rescale,
                    y, offset, weight, dtype, lane_of, bucket_index,
                    row_ids=None):
    """Fill one capacity class's NON-design lane arrays (labels, offsets,
    rescaled weights, row map, counts, entity directory) — identical between
    the dense and row-sparse bucketers, factored so their padding/rescale
    semantics cannot diverge.  ``lanes``: ``_class_lanes``' entity of each
    lane.  Returns (by, boff, bw, brows, bcounts,
    blanes); ``lane_of`` is updated in place.  ``row_ids`` maps local row
    positions to the GLOBAL sample-row ids stored in ``brows`` (multihost)."""
    n_lanes = len(lanes)
    by = np.zeros((n_lanes, cap), dtype)
    boff = np.zeros((n_lanes, cap), dtype)
    bw = np.zeros((n_lanes, cap), dtype)
    brows = np.full((n_lanes, cap), -1, np.int32)
    bcounts = np.zeros((n_lanes,), np.int32)
    blanes = np.full((n_lanes,), -1, np.int64)
    for lane, ei in enumerate(lanes):
        if ei < 0:
            continue
        rows = kept_rows[ei]
        k = len(rows)
        by[lane, :k] = y[rows]
        boff[lane, :k] = offset[rows]
        bw[lane, :k] = weight[rows] * rescale[ei]
        brows[lane, :k] = rows if row_ids is None else row_ids[rows]
        bcounts[lane] = k
        blanes[lane] = kept_entities[ei]
        lane_of[kept_entities[ei]] = (bucket_index, lane)
    return by, boff, bw, brows, bcounts, blanes


def bucket_by_entity(
    entity_ids: np.ndarray,
    x: np.ndarray,
    y: np.ndarray,
    offset: Optional[np.ndarray] = None,
    weight: Optional[np.ndarray] = None,
    active_cap: Optional[int] = None,
    min_active_samples: int = 1,
    lane_multiple: int = 1,
    seed: int = 0,
    dtype=np.float32,
    existing_model_keys: Optional[frozenset] = None,
    row_ids: Optional[np.ndarray] = None,
    num_samples: Optional[int] = None,
    groups: Optional[Tuple[List[np.ndarray], List[int], List[float]]] = None,
    runs: Optional[EntityRuns] = None,
) -> EntityBuckets:
    """Group samples by entity into power-of-two-capacity buckets.

    - ``active_cap``: deterministic reservoir cap per entity with weight
      rescale count/cap (reference RandomEffectDataset.scala:358-420).
      Overflow samples are DROPPED from training here; the score-only
      "passive" path keeps them via score_random_effects on the full data.
    - ``min_active_samples``: entities with fewer samples are excluded
      (reference lower-bound filter, RandomEffectDataset.scala:319-341).
    - ``lane_multiple``: pad each bucket's entity count to a multiple (set to
      the mesh device count so the entity axis shards evenly).
    - ``row_ids`` / ``num_samples``: multihost entity-sharded reads — the
      local rows' GLOBAL sample ids (stored in ``Bucket.rows`` and mixed
      into reservoir keys so decisions are topology-invariant) and the
      GLOBAL score-vector length (parallel/multihost.py).
    - ``groups``: a precomputed ``(kept_rows, kept_entities, rescale)``
      triple (stream.EntityStats accumulated chunk-by-chunk) replacing the
      ``_group_rows`` scan; it must have been built with the SAME cap /
      min-active / seed / warm-start arguments (EntityStats.groups enforces
      the cap+seed half and returns None on mismatch).
    - ``runs``: ``entity_runs(entity_ids)`` where the caller has it already
      (the coordinate lays its full-sample design out by the same grouping).

    ``x`` may be a device-resident ``jax.Array`` (streaming ingest
    assembles design shards on device): the per-lane design blocks are then
    built by an on-device gather — bit-identical to the host fill, since a
    gather copies rows and the padding is exact zeros either way — and the
    [n, d] array never materializes on host.
    """
    n = len(entity_ids)
    entity_ids = np.asarray(entity_ids, np.int64)
    x_is_device = isinstance(x, jax.Array)
    if x_is_device:
        if row_ids is not None:
            raise NotImplementedError(
                "device-resident design shards do not support multihost "
                "row_ids yet (ROADMAP item 5 follow-on)")
        if x.dtype != np.dtype(dtype):
            x = x.astype(dtype)  # on-device cast: never host-materialize
    else:
        x = np.asarray(x, dtype)
    y = np.asarray(y, dtype)
    offset = np.zeros(n, dtype) if offset is None else np.asarray(offset, dtype)
    weight = np.ones(n, dtype) if weight is None else np.asarray(weight, dtype)
    d = x.shape[1]
    if row_ids is not None:
        row_ids = np.asarray(row_ids, np.int64)

    if groups is not None:
        kept_rows, kept_entities, rescale = groups
    else:
        kept_rows, kept_entities, rescale = _group_rows(
            entity_ids, active_cap, min_active_samples, seed,
            existing_model_keys=existing_model_keys, row_ids=row_ids,
            runs=runs)

    # Capacity classes: next power of two of the active count.
    caps = _capacity_classes(kept_rows)
    buckets: List[Bucket] = []
    lane_of: Dict[int, Tuple[int, int]] = {}
    for cap in sorted(set(caps.tolist())):
        lanes, run_lanes, window_lanes = _class_lanes(
            np.nonzero(caps == cap)[0], kept_rows, cap, lane_multiple, row_ids)
        by, boff, bw, brows, bcounts, blanes = _pack_lane_meta(
            cap, lanes, kept_rows, kept_entities, rescale,
            y, offset, weight, dtype, lane_of, len(buckets), row_ids=row_ids)
        if x_is_device:
            # on-device lane gather: rows copy exactly, padding lanes/slots
            # are exact zeros — bitwise-equal to the host fill below
            valid = brows >= 0
            safe = np.where(valid, brows, 0).astype(np.int64)
            bx = jnp.where(jnp.asarray(valid)[..., None],
                           x[jnp.asarray(safe)], jnp.zeros((), x.dtype))
        else:
            bx = np.zeros((len(lanes), cap, d), dtype)
            for lane, ei in enumerate(lanes):
                if ei >= 0:
                    rows = kept_rows[ei]
                    bx[lane, :len(rows)] = x[rows]
        buckets.append(Bucket(x=bx, y=by, offset=boff, weight=bw, rows=brows,
                              counts=bcounts, entity_lanes=blanes,
                              run_lanes=run_lanes, window_lanes=window_lanes))

    return EntityBuckets(buckets=buckets, lane_of=lane_of, dim=d,
                         num_entities=len(kept_entities),
                         num_samples=n if num_samples is None else num_samples,
                         **_passive(kept_rows, rescale))


# Active rows whose pairs one block of ``bucket_by_entity_sparse`` holds on
# the host at a time, the (lane, column) cells up to which a block's pairs
# are grouped by counting rather than by sorting, and the blocks in flight.
_COMPACT_BLOCK_ROWS = 1 << 20
_COMPACT_COUNTED_CELLS = 1 << 26
_COMPACT_WORKERS = 4


def _merged_pairs(iv: np.ndarray, vv: np.ndarray):
    """``(indices, values, seen)`` [rows, k] of row-sparse pairs with each
    row's duplicate columns merged into one pair (their values accumulate,
    as ``SparseBatch`` margins do); ``seen`` marks the pairs that stand for
    a nonzero entry.  A row whose nonzero pairs rise strictly by column, up
    to a tail of zero-valued padding, has no duplicate and is left as it
    is; only the others are sorted, in place (the caller's arrays are its
    own copies of the rows)."""
    seen = vv != 0
    if iv.shape[1] < 2:
        return iv, vv, seen
    tail = np.logical_and.accumulate(~seen[:, ::-1], axis=1)[:, ::-1]
    mixed = np.flatnonzero(~np.all((iv[:, 1:] > iv[:, :-1]) | tail[:, 1:],
                                   axis=1))
    if mixed.size:
        order = np.argsort(iv[mixed], axis=1, kind="stable")
        si = np.take_along_axis(iv[mixed], order, 1)
        sv = np.take_along_axis(vv[mixed], order, 1)
        sn = sv != 0
        for j in range(si.shape[1] - 1, 0, -1):
            same = si[:, j] == si[:, j - 1]
            sv[:, j - 1] += np.where(same, sv[:, j], 0)
            sn[:, j - 1] |= same & sn[:, j]
            sv[same, j] = 0
            sn[same, j] = False
        iv[mixed], vv[mixed], seen[mixed] = si, sv, sn
    return iv, vv, seen


def _distinct(key: np.ndarray, size: int):
    """``np.unique(key, return_inverse=True)`` for keys in ``[0, size)``:
    by counting where the key space is small, else by sorting."""
    if size > _COMPACT_COUNTED_CELLS:
        return np.unique(key, return_inverse=True)
    hit = np.bincount(key, minlength=size) > 0
    return np.flatnonzero(hit), (np.cumsum(hit) - 1)[key]


def _compact_lanes(rows_of: List[np.ndarray], indices: np.ndarray,
                   values: np.ndarray, y: np.ndarray, weight: np.ndarray,
                   dim: int, ratio: Optional[float],
                   intercept_index: Optional[int]):
    """The compact bases of a block of lanes, all lanes at once and at the
    cost of their nonzero pairs: ``rows_of[l]`` the active rows of lane
    ``l``.  Returns ``(lane, column, position)`` of every column a lane
    KEEPS (its observed columns, ascending; under ``ratio`` the
    ``max(1, ceil(ratio x rows))`` of them that rank highest by |Pearson
    correlation| with the label over the lane's active rows, ties to the
    lower column, ``intercept_index`` always: ``pearson_top_k``'s rule, from
    weighted sums in float64), ``(lane, slot, position, value)`` of every
    entry of the lanes' compact design blocks, and ``(columns observed,
    lanes the bound cut)``."""
    m, k = len(rows_of), indices.shape[1]
    lens = np.fromiter(map(len, rows_of), np.int64, m)
    rows = np.concatenate(rows_of) if m else np.empty(0, np.int64)
    lane = np.repeat(np.arange(m), lens)
    slot = np.arange(len(rows)) - np.repeat(np.cumsum(lens) - lens, lens)
    iv, vv, seen = _merged_pairs(indices[rows], values[rows])
    at = np.flatnonzero(seen.ravel())           # the entries, row by row
    r, val = at // k, vv.ravel()[at]
    cells, cell_of = _distinct((lane * dim)[r] + iv.ravel()[at], m * dim)
    cell_lane, cell_col = cells // dim, cells % dim
    observed = np.bincount(cell_lane, minlength=m)
    first = np.cumsum(observed) - observed      # a lane's first cell
    keep = np.ones(len(cells), bool)
    cut = 0
    if ratio is not None:
        keep_n = np.maximum(1, np.ceil(ratio * lens)).astype(np.int64)
        cut = int(np.count_nonzero(observed > keep_n))
        if cut:
            w = weight[rows].astype(np.float64)
            wy = w * y[rows]
            total = np.maximum(np.bincount(lane, w, m), 1e-12)[cell_lane]
            my = np.bincount(lane, wy, m)[cell_lane] / total
            vy = np.bincount(lane, wy * y[rows], m)[cell_lane] / total \
                - my * my
            u = len(cells)
            wx = w[r] * val
            mx = np.bincount(cell_of, wx, u) / total
            vx = np.bincount(cell_of, wx * val, u) / total - mx * mx
            cov = np.bincount(cell_of, wy[r] * val, u) / total - mx * my
            denom = np.sqrt(np.maximum(vx * vy, 0.0))
            with np.errstate(invalid="ignore", divide="ignore"):
                score = np.where(denom > 0, np.abs(cov) / denom, 0.0)
            score[vx <= 1e-12 * np.maximum(1.0, mx * mx)] = 0.0
            if intercept_index is not None:
                score[cell_col == intercept_index] = np.inf
            # cells lie by (lane, column); a stable sort by (lane, -score)
            # ranks a lane's columns with ties to the lower one
            order = np.lexsort((-score, cell_lane))
            rank = np.empty(u, np.int64)
            rank[order] = np.arange(u) - first[cell_lane[order]]
            keep = rank < keep_n[cell_lane]
    before = np.concatenate([[0], np.cumsum(keep)])
    position = before[:-1] - before[first][cell_lane]
    held = np.flatnonzero(keep[cell_of])
    r = r[held]
    return ((cell_lane[keep], cell_col[keep], position[keep]),
            (lane[r], slot[r], position[cell_of[held]], val[held]),
            (len(cells), cut))


def bucket_by_entity_sparse(
    entity_ids: np.ndarray,
    indices: np.ndarray,
    values: np.ndarray,
    dim: int,
    y: np.ndarray,
    offset: Optional[np.ndarray] = None,
    weight: Optional[np.ndarray] = None,
    active_cap: Optional[int] = None,
    min_active_samples: int = 1,
    lane_multiple: int = 1,
    seed: int = 0,
    dtype=np.float32,
    features_to_samples_ratio: Optional[float] = None,
    intercept_index: Optional[int] = None,
    existing_model_keys: Optional[frozenset] = None,
    row_ids: Optional[np.ndarray] = None,
    num_samples: Optional[int] = None,
    runs: Optional[EntityRuns] = None,
):
    """Compact per-entity buckets built DIRECTLY from row-sparse features.

    The reference keeps per-entity SPARSE Breeze vectors
    (data/LocalDataset.scala:35-247), so wide sparse random-effect feature
    bags never densify to the full vocabulary.  The TPU equivalent: each
    entity solves in the compact space of its OBSERVED columns (the
    IndexMapProjectorRDD.scala:222-261 set, built here straight from the
    row-sparse (indices, values) pairs), so the bucket design blocks are
    [E, S, d_obs] — never [E, S, d_full] — and HBM scales with observed
    features per entity, not vocabulary size.  Margin-exact: an unobserved
    feature has zero data gradient and stays at exactly 0 under L2/L1 from a
    zero init (same fact the reference's projection relies on).

    ``indices``/``values``: the SparseShard row-padded COO arrays [n, k]
    (padded slots carry value 0 and are ignored; duplicate indices within a
    row ACCUMULATE, matching core/batch.SparseBatch margins).
    ``features_to_samples_ratio``/``intercept_index``: per-entity top-k
    |Pearson| feature filter exactly as build_observed_indices applies it to
    dense buckets (LocalDataset.scala:185-247).  A class's lanes are
    compacted together (``_compact_lanes``), in blocks of lanes: no loop an
    entity over the pairs.
    ``runs``: ``entity_runs(entity_ids)`` where the caller has it already.

    Returns ``(EntityBuckets, projections)`` — compact buckets plus one
    BucketProjection per bucket mapping compact columns back to the full
    vocabulary (``EntityBuckets.dim`` stays the FULL dimension).
    """
    from photon_ml_tpu.parallel.projection import (BucketProjection,
                                                   _pow2_at_least)

    n = len(entity_ids)
    entity_ids = np.asarray(entity_ids, np.int64)
    indices = np.asarray(indices)
    values = np.asarray(values, dtype)
    y = np.asarray(y, dtype)
    offset = np.zeros(n, dtype) if offset is None else np.asarray(offset, dtype)
    weight = np.ones(n, dtype) if weight is None else np.asarray(weight, dtype)

    if row_ids is not None:
        row_ids = np.asarray(row_ids, np.int64)
    kept_rows, kept_entities, rescale = _group_rows(
        entity_ids, active_cap, min_active_samples, seed,
        existing_model_keys=existing_model_keys, row_ids=row_ids, runs=runs)

    caps = _capacity_classes(kept_rows)
    buckets: List[Bucket] = []
    projections: List[object] = []
    lane_of: Dict[int, Tuple[int, int]] = {}
    classes, blocks = [], []
    for cap in sorted(set(caps.tolist())):
        lanes, run_lanes, window_lanes = _class_lanes(
            np.nonzero(caps == cap)[0], kept_rows, cap, lane_multiple, row_ids)
        live = np.flatnonzero(lanes >= 0)
        # lanes a block, about 1M active rows: the host's peak follows the
        # blocks in flight and not the class
        per_block = max(1, _COMPACT_BLOCK_ROWS // cap)
        blocks += [(len(classes), live[at:at + per_block])
                   for at in range(0, len(live), per_block)]
        classes.append((cap, lanes, run_lanes, window_lanes))

    def compact(block):
        ci, live = block
        return _compact_lanes(
            [kept_rows[ei] for ei in classes[ci][1][live]], indices, values,
            y, weight, dim, features_to_samples_ratio, intercept_index)

    # the blocks are independent and numpy lets go of the interpreter in
    # nearly all of their work
    with concurrent.futures.ThreadPoolExecutor(_COMPACT_WORKERS) as pool:
        compacted = list(pool.map(compact, blocks))
    observed_columns = sum(seen[0] for _, _, seen in compacted)
    filtered_entities = sum(seen[1] for _, _, seen in compacted)
    for ci, (cap, lanes, run_lanes, window_lanes) in enumerate(classes):
        mine = [(live, kept, entries) for (at, live), (kept, entries, _)
                in zip(blocks, compacted) if at == ci]
        # one width a class: the power of two over its widest lane
        widest = max((int(pos.max()) + 1 for _, (_, _, pos), _ in mine
                      if len(pos)), default=1)
        d_proj = min(_pow2_at_least(widest), dim)
        by, boff, bw, brows, bcounts, blanes = _pack_lane_meta(
            cap, lanes, kept_rows, kept_entities, rescale,
            y, offset, weight, dtype, lane_of, len(buckets), row_ids=row_ids)
        bx = np.zeros((len(lanes), cap, d_proj), dtype)
        bidx = np.full((len(lanes), d_proj), -1, np.int32)
        slots = bx.reshape(-1, d_proj)
        for live, (k_lane, k_col, k_pos), (e_lane, e_slot, e_pos, e_val) \
                in mine:
            bidx[live[k_lane], k_pos] = k_col
            slots[live[e_lane] * cap + e_slot, e_pos] = e_val
        buckets.append(Bucket(x=bx, y=by, offset=boff, weight=bw, rows=brows,
                              counts=bcounts, entity_lanes=blanes,
                              run_lanes=run_lanes, window_lanes=window_lanes))
        projections.append(BucketProjection(indices=bidx, d_full=dim))

    ents = EntityBuckets(buckets=buckets, lane_of=lane_of, dim=dim,
                         num_entities=len(kept_entities),
                         num_samples=n if num_samples is None else num_samples,
                         compact=True, observed_columns=observed_columns,
                         filtered_entities=filtered_entities,
                         **_passive(kept_rows, rescale))
    return ents, projections


def _entity_sharding(mesh: Optional[Mesh]):
    if mesh is None:
        return None
    return NamedSharding(mesh, P(tuple(mesh.axis_names)))  # E over ALL devices


def fit_random_effects(
    objective: GLMObjective,
    buckets: EntityBuckets,
    mesh: Optional[Mesh] = None,
    optimizer: OptimizerType = OptimizerType.LBFGS,
    config: Optional[SolverConfig] = None,
    init: Optional[List[Array]] = None,
) -> Tuple[List[Array], List[SolverResult]]:
    """Solve every entity's GLM; returns per-bucket coefficients [E, d].

    The reference solves each entity SERIALLY inside a Spark mapValues
    (RandomEffectCoordinate.scala:114-127); here each capacity class is one
    vmapped solver launch with the entity lane sharded over the mesh.
    ``init``: per-bucket warm-start coefficients (e.g. from the previous
    coordinate-descent iteration).
    """
    solve = make_solver(objective, optimizer, config)
    # photonlint: disable=sharding-annotation -- mesh is Optional here: the
    # same jit serves the mesh-less single-device path, and when a mesh IS
    # given the [E, ...] lane layout propagates from the device_put of
    # w0/batch below (one broadcast spec would also pin scalar leaves)
    vsolve = jax.jit(jax.vmap(lambda w0, batch: solve(w0, batch)))
    shard = _entity_sharding(mesh)

    coeffs: List[Array] = []
    results: List[SolverResult] = []
    for bi, b in enumerate(buckets.buckets):
        w0 = (init[bi] if init is not None
              else jnp.zeros((b.num_lanes, buckets.dim), b.batch().x.dtype))
        batch = b.batch()
        if shard is not None:
            w0 = jax.device_put(w0, shard)
            batch = jax.tree.map(lambda a: jax.device_put(a, _spec_for(mesh, a)), batch)
        res = vsolve(w0, batch)
        coeffs.append(res.w)
        results.append(res)
    return coeffs, results


def _spec_for(mesh: Mesh, a: Array) -> NamedSharding:
    axes = tuple(mesh.axis_names)
    spec = P(axes, *([None] * (a.ndim - 1)))
    return NamedSharding(mesh, spec)


def score_random_effects(
    coeffs: Sequence[Array],
    buckets: EntityBuckets,
) -> Array:
    """Per-sample raw scores w_entity · x for every ACTIVE sample.

    Returns scores[num_samples] aligned with the original sample-row order
    (reference RandomEffectCoordinate.score:167-196, which shuffles scored
    tuples back to the uniqueId partitioner — here a scatter by row index).
    Samples of excluded/capped-out entities get 0.
    """
    total = jnp.zeros((buckets.num_samples,), coeffs[0].dtype if coeffs else jnp.float32)
    for b, w in zip(buckets.buckets, coeffs):
        margins = jnp.einsum("esd,ed->es", jnp.asarray(b.x), w)
        valid = b.rows >= 0
        safe_rows = jnp.where(valid, b.rows, 0)
        total = total.at[safe_rows.ravel()].add(
            jnp.where(valid, margins, 0.0).ravel()
        )
    return total


def stacked_coefficients(
    coeffs: Sequence[Array], buckets: EntityBuckets
) -> Tuple[Array, Dict[int, int]]:
    """Stack per-bucket lane coefficients into W[num_entities, d] + id->slot map.

    The dense W is the device-resident form of the reference's
    RDD[(REId, GLM)] model (RandomEffectModel.scala) — scoring any sample set
    becomes a gather + row-wise dot (see score_samples), covering the
    reference's "passive data" path (samples capped out of training still get
    scored, RandomEffectDataset passiveData / RandomEffectCoordinate.scala:210-231).
    """
    # ONE host transfer per bucket, then numpy gathers — indexing device
    # arrays per entity would issue thousands of tiny dispatches.
    host = [np.asarray(c) for c in coeffs]
    slot_of: Dict[int, int] = {}
    parts = []
    for eid in sorted(buckets.lane_of):
        bi, lane = buckets.lane_of[eid]
        slot_of[eid] = len(slot_of)
        parts.append(host[bi][lane])
    w = jnp.asarray(np.stack(parts)) if parts else jnp.zeros((0, buckets.dim))
    return w, slot_of


def stack_bucket_lanes(lane_ws: Sequence[Array], slot_idx: Sequence[Array],
                       num_entities: int) -> Array:
    """Traceable stacked_coefficients: scatter per-bucket lane coefficient
    rows into W[num_entities, d].  ``slot_idx[bi][lane]`` is the stacked row
    (out-of-range for invalid/padded lanes, which the 'drop' scatter
    discards).  Device-side counterpart of ``stacked_coefficients`` for
    fully-jitted sweeps (game/fused.py)."""
    d = lane_ws[0].shape[-1]
    w = jnp.zeros((num_entities, d), lane_ws[0].dtype)
    for idx, lw in zip(slot_idx, lane_ws):
        w = w.at[idx].set(lw, mode="drop")
    return w


def score_samples(w_stack: Array, slots: Array, x: Array) -> Array:
    """Raw per-sample scores (x_i · w_entity(i)) for ANY sample set.

    ``slots``: per-sample row index into w_stack, -1 for samples whose entity
    has no model (score 0 — reference scores missing random effects as 0).
    """
    safe = jnp.where(slots >= 0, slots, 0)
    margins = jnp.einsum("nd,nd->n", x, w_stack[safe])
    return jnp.where(slots >= 0, margins, 0.0)


NARROW_SCORE_DIM_MAX = 32  # the narrow layouts only ever help below this width
# Three layouts of a coordinate's full-sample design, and ONE rule over what
# the coordinate can observe of its data (RandomEffectCoordinate.__init__):
#   - row-major [n, d] (``score_samples``) under the padded-footprint line
#     below.  On a v5e in 2026-08, before the ledger: glmix2
#     [524288, 16] f32 pads to 268 MB and the one gather + einsum was 1.56x
#     FASTER than d serial passes (0.47 s vs 0.73 s a sweep).  No benchmark
#     cell sits under the line yet (PERF.md section 7, ROADMAP D3).
#   - over the line, where TPU tiling would pad the narrow minor axis to 128
#     lanes (glmix_chip [8.39M, 4]: 2.1 GB a copy and two copies in the
#     scoring HLO, an OOM on a 16 GB chip), the design is stored with the
#     samples on the lanes.  ENTITY-MAJOR (``entity_major_layout`` +
#     ``score_samples_em``) where the rows of an entity fill chunks of C rows
#     within 1.3x padding: the entity's coefficient ROW is gathered once per
#     chunk, one index for all d columns, where a gather a column cost d x
#     n / C indices (PERF.md section 6).
#   - TRANSPOSED [d, n] in sample order (``score_samples_t``) where no C
#     exists (fewer than ~30 rows an entity): a coefficient is gathered once
#     per row and column.  The chip showed what that costs (PERF.md section 6,
#     PR 23): the time is INDICES, 7 to 29 ns each, not bytes: d x 8.39M of
#     them were 86.6% of glmix_chip's busy time and 49.7% of glmix3_wide's.
# The entity-major scores come BACK to sample order one of three ways, read
# off the layout the bucketer produced (``EntityMajorLayout.back``,
# ``to_sample_order``; no flag):
#   - ``identity``: the chunks ARE the sample order (rows grouped by entity,
#     counts that fill whole chunks: glmix_chip): nothing.
#   - ``unpad``: rows grouped by entity, counts that do not fill chunks
#     (MovieLens' per-user rows, training and held-out).  ``pos`` rises and
#     only skips each entity's tail padding, so an order-preserving
#     compaction does it by data movement, no index a sample (``Unpad``,
#     ``unpad``): 10.6 ms for 13.0M samples out of 16.0M slots on a v5e
#     where ``acc[pos]`` took 97.5 (PERF.md section 6, PR 33).
#   - ``gather``: rows that lie anywhere (per-item; shuffled ids):
#     ``acc[pos]``, one index a sample, 6.6 to 8.6 ns each.
NARROW_SCORE_PAD_BYTES_MIN = 1 << 30


def use_transposed_scoring(n: int, d: int, itemsize: int) -> bool:
    """True when full-sample dense scoring should keep the samples on the
    lanes (``score_samples_em`` or ``score_samples_t``) instead of row-major
    [n, d] (``score_samples``).  See the note above."""
    return (d <= NARROW_SCORE_DIM_MAX
            and n * 128 * itemsize >= NARROW_SCORE_PAD_BYTES_MIN)


def score_samples_t(w_stack: Array, slots: Array, x_t: Array) -> Array:
    """``score_samples`` for a TRANSPOSED [d, n] full-sample array in sample
    order: the narrow layout for data that finds no entity-major chunk
    (``entity_major_chunk`` is None).

    TPU tiling pads an array's minor axis to 128 lanes, so a narrow [n, d]
    design (random-effect shards are typically d<=16 wide) occupies 128/d x
    its logical bytes in HBM and so does every [n, d] gather from it — 32x
    at d=4, which turned glmix_chip's 8.39M-sample scoring into 2 x 4GB of
    HLO temp and OOMed a 16GB v5e.  Samples-on-lanes layout
    keeps every large intermediate 1-D over n: d static gathers of [E]
    coefficient columns, and no padded [n, d] array ever exists.  Each of
    the d gathers has n indices, and on the v5e the indices are the cost
    (7.4 ns each where PR 23 measured them, up to 28.9): ``score_samples_em``
    issues n / C of them in all, one ROW of d a chunk.
    """
    safe = jnp.where(slots >= 0, slots, 0)
    w_t = w_stack.T  # [d, E]: entities on lanes, tiny either way
    acc = jnp.zeros(x_t.shape[1],
                    jnp.promote_types(x_t.dtype, w_stack.dtype))
    for j in range(x_t.shape[0]):  # d is static and small by contract
        acc = acc + x_t[j] * w_t[j][safe]
    return jnp.where(slots >= 0, acc, 0.0)


EM_ROW = 128  # lanes of a stored row: a chunk is EM_ROW / k of them
EM_CHUNK_MIN = 8
EM_PAD_MAX = 1.3  # padded rows over rows; per-item at C = 128 pads to 1.25
# The capacity from which a lane whose rows are one run of samples is
# addressed by its start (``_class_lanes``, ``offsets_into_lanes``).  On a
# v5e (PR 31, scratch, 24,656 lanes a class out of 13.0M offsets): a run
# lane costs 14.7 ns at every capacity up to 64 (two gathered ROWS of 128
# and the shift), a lane of one index a slot 9.4 / 17.1 / 30.4 / 56.3 / 109 /
# 216 ns at capacity 1 / 2 / 4 / 8 / 16 / 32 (6.7 ns a slot from 8 up): even
# at 2, twice as fast from 4.  At glmix_ml20m's per-user classes (32 to
# 1,024) 2.25 ms an update for 110.4.
RUN_CAPACITY_MIN = 4
# The span over the capacity up to which a lane whose kept rows are NOT one
# run (a reservoir out of an entity's consecutive rows) is addressed by the
# start of its WINDOW and picked out of it (``_windows_at``), and the bytes
# of a block of its lanes.  On a v5e (PR 35, scratch, about 2M slots a
# class out of 13.0M offsets, every form bitwise one index a slot), ms a
# class: one index a slot / window lanes as landed at a span of 2x, 4x, 8x
# the capacity, and (blocks unrolled) 16x:
#   capacity    4 (524,288 lanes): 17.6 / 9.6, -, -, 7.8
#   capacity   16 (131,072 lanes): 15.7 / -, 3.7, 4.6, 4.0
#   capacity   64 (32,768 lanes):  15.1 / -, -, 3.5, 3.7
#   capacity  256 (8,192 lanes):   15.0 / -, -, 2.4, 5.9
#   capacity 1024 (2,048 lanes):   15.0 / 0.53 (unrolled), 1.2, 2.5, 6.0
# A window lane wins 1.8 to 12 times over at every span and capacity
# measured; what stops at 8x is the HOST: a class of 2M slots paints 16.8M
# words in 1.7 to 2.0 s at 8x and 33.5M in 5.5 to 6.2 s at 16x
# (``lane_windows``, the chip's host), and uploads as many.  glmix_chip's
# class (131,072 lanes, 32 kept of 64), ms a call: 30.4 one index a slot;
# 8.9 with the windows gathered in one piece (``_runs_at`` alone 7.8: its
# [lanes, 256] view is 134 MB and each of the seven rolls a pass over HBM,
# 59 ns a lane where PR 31's 24,656 lanes cost 14.7); in blocks of 4 / 8 /
# 16 / 32 / 64 MiB of that view 1.7 / 2.0 / 1.8 / 2.3 / 6.0 unrolled and 2.9
# / 3.5 / 4.4 / 4.4 as ONE loop, which landed at 8: at 16 blocks unrolled
# the program grows from 527 to 1,712 instructions and a warm set-up by 0.8
# s (the loop: 0.03), and from 2,048 lanes of 8,192 slots up the loop is
# the faster (2.5 ms for 4.9).  The stages on the flat stream of all
# windows (PR 33's form) 4.0 to 4.9 blocked, 9.9 not; a compare-select-
# reduce over the window 8.7, transposed 7.9 (one piece): the [lanes, W]
# view and the stages win here.
WINDOW_SPAN_MAX = 8
WINDOW_BLOCK_BYTES = 8 << 20


def _runs_at(offsets: Array, run_start: Array, capacity: int) -> Array:
    """``offsets[run_start[l] + s]`` for ``s < capacity`` [lanes, capacity]
    (whatever lies there: the caller masks), addressed by ROWS: ``offsets``
    read as ``[n / EM_ROW, EM_ROW]``, a lane takes the ``capacity / EM_ROW +
    1`` (at least 2) rows from ``run_start // EM_ROW`` (indices past the
    last row clamp: what they stand for lies past the vector), and its rows,
    laid end to end, are rotated left by ``run_start % EM_ROW``: seven
    static rolls, each kept where the shift has that bit.  Of the lowerings
    measured on the chip (PERF.md section 6, PR 31) the fastest; a
    ``dynamic_slice`` a lane under ``vmap`` is a loop over the lanes."""
    n = offsets.shape[0]
    whole = -(-n // EM_ROW) * EM_ROW
    table = (offsets if whole == n else jnp.pad(offsets, (0, whole - n))
             ).reshape(-1, EM_ROW)
    take = (capacity + EM_ROW - 2) // EM_ROW + 1
    first = (run_start // EM_ROW)[:, None]
    picked = table[jnp.minimum(first + jnp.arange(take, dtype=first.dtype),
                               table.shape[0] - 1)]   # [lanes, take, EM_ROW]
    picked = picked.reshape(picked.shape[0], -1)
    shift = (run_start % EM_ROW)[:, None]
    for bit in range(EM_ROW.bit_length() - 1):
        picked = jnp.where((shift >> bit) & 1 == 1,
                           jnp.roll(picked, -(1 << bit), axis=1), picked)
    return picked[:, :capacity]


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["start", "pull"], meta_fields=["stages"])
@dataclasses.dataclass(frozen=True)
class LaneWindows:
    """A class's WINDOW lanes (``_class_lanes``), as ``offsets_into_lanes``
    addresses them: ``start`` int32 ``[lanes]`` the sample each lane's window
    starts at; ``pull`` int32 ``[lanes, W]`` (``W`` the class's widest
    window, at least its capacity) the pick of every lane's kept slots out
    of its window into its first places, in their stored order, as
    ``pull_stages``' order-preserving compaction a lane: bit ``b`` at slot
    ``q`` says stage ``b`` pulls the lane's slot ``q + 2^b`` into ``q``
    (``_pulled`` along the lane); ``stages`` its passes (static), the bits
    of the most rows any lane skips in front of a kept one."""

    start: Array
    pull: Array
    stages: int

    @property
    def window(self) -> int:
        return self.pull.shape[1]


def lane_windows(rows: np.ndarray) -> LaneWindows:
    """``LaneWindows`` (its leaves on the host) of window lanes' stored
    ``rows`` [lanes, capacity]: each lane's ascending, -1 behind them.

    A kept row has ``skipped`` rows of its window in front of it that are
    not kept, and so many places to go left: never fewer than the kept row
    before it, so the lane's elements keep their order and their distance
    through ``pull_stages``' stages (stage ``b`` moves those whose count
    has bit ``b`` by ``2^b``), and no lane's leave its window.  The stages
    are painted by running them here over the counts, a lane a row: half
    the rows of a reservoir start a run of their own, and ``pull_stages``
    run by run takes ten times as long."""
    lanes, capacity = rows.shape
    kept = rows >= 0
    within = np.where(kept, rows - rows[:, :1], -1)
    window = max(int(within.max()) + 1, capacity)
    skipped = np.where(kept, within - np.arange(capacity, dtype=rows.dtype),
                       0)
    if np.any(skipped[:, 1:] < skipped[:, :-1] * kept[:, 1:]):
        raise ValueError("window lanes: rows that do not rise along a lane")
    stages = int(skipped.max()).bit_length()
    word = np.int8 if stages < 8 else np.int16 if stages < 16 else np.int32
    # the stream as the chip sees it: at a slot the count its element has
    # still to go (0: an element at rest, or nothing)
    at = np.zeros((lanes, window + 1), word)
    np.put_along_axis(at, np.where(kept, within, window),
                      skipped.astype(word), axis=1)
    at = np.ascontiguousarray(at[:, :window])
    pull = np.zeros((lanes, window), word)
    for b in range(stages):
        moves = (at >> b) & 1
        pull[:, :window - (1 << b)] |= moves[:, 1 << b:] << b
        moved = np.where(moves == 1, at, 0)
        at -= moved
        at[:, :window - (1 << b)] |= moved[:, 1 << b:]
    return LaneWindows(rows[:, 0].astype(np.int32), pull.astype(np.int32),
                       stages)


def _windows_at(offsets: Array, windows: LaneWindows, capacity: int) -> Array:
    """The window lanes' kept rows out of ``offsets`` [lanes, capacity]:
    every window read by rows of 128 (``_runs_at``), its kept slots pulled
    to its head (``_pulled`` along the lane: no index a slot, and a slot
    never pulls from beyond its window), the first ``capacity`` places
    kept.  Copies only.  In BLOCKS of lanes whose wide view (the gathered
    rows of 128, before the cut to the window) holds
    ``WINDOW_BLOCK_BYTES``: the seven rolls and the stages of a block then
    run without a pass over HBM each; the whole blocks are one loop (one
    body in the program however many lanes), what is left behind them a
    block of its own."""
    lanes, window = windows.pull.shape
    take = (window + EM_ROW - 2) // EM_ROW + 1
    block = max(8, WINDOW_BLOCK_BYTES // (
        take * EM_ROW * offsets.dtype.itemsize) // 8 * 8)

    def pick(start, pull):
        return _pulled(_runs_at(offsets, start, window), pull,
                       windows.stages, axis=1)[:, :capacity]

    whole = lanes // block * block
    picked = []
    if whole:
        picked.append(jax.lax.map(
            lambda by: pick(*by),
            (windows.start[:whole].reshape(-1, block),
             windows.pull[:whole].reshape(-1, block, window))
        ).reshape(whole, capacity))
    if lanes > whole:
        picked.append(pick(windows.start[whole:], windows.pull[whole:]))
    return picked[0] if len(picked) == 1 else jnp.concatenate(picked)


def offsets_into_lanes(offsets: Array, rows: Array, valid: Array,
                       run_start: Optional[Array] = None,
                       windows: Optional[LaneWindows] = None) -> Array:
    """One capacity class's residual offsets, ``where(valid, offsets[rows
    of each slot], 0)`` [lanes, capacity], from the ``[n]`` vector.  The
    class's first ``len(run_start)`` lanes are RUN lanes (``_class_lanes``)
    addressed by the sample their run starts at, the ``len(windows.start)``
    behind them WINDOW lanes addressed by their window's start and picked
    out of it; ``rows`` [index lanes, capacity] holds the rows of the lanes
    behind those, one index a slot; ``valid`` masks all of them.  Copies
    every way: the lanes are bitwise what one index a slot gives.
    ``run_start`` / ``windows`` None: a class with no such lane."""
    capacity = valid.shape[1]
    picked = []
    if run_start is not None:
        picked.append(_runs_at(offsets, run_start, capacity))
    if windows is not None:
        picked.append(_windows_at(offsets, windows, capacity))
    if rows.shape[0] or not picked:
        picked.append(offsets[rows])
    return jnp.where(
        valid, picked[0] if len(picked) == 1 else jnp.concatenate(picked),
        0.0)


def entity_major_chunk(counts: np.ndarray) -> Optional[int]:
    """The chunk length C of the entity-major layout, read off the rows of
    each entity: the largest power of two in [EM_CHUNK_MIN, EM_ROW] at which
    cutting every entity's rows into chunks of C, the last one padded, keeps
    the padded row count within EM_PAD_MAX of the real one.  A longer chunk
    is fewer gathered indices; a long entity takes several chunks, so a
    heavy tail costs nothing.  None where there is none (under ~30 rows an
    entity on average)."""
    counts = np.asarray(counts, np.int64)
    c = EM_ROW
    while c >= EM_CHUNK_MIN:
        if int((-(-counts // c)).sum()) * c <= EM_PAD_MAX * int(counts.sum()):
            return c
        c //= 2
    return None


@dataclasses.dataclass
class EntityMajorLayout:
    """Where each sample sits when every entity's rows are contiguous.

    The design is stored ``[d, R, EM_ROW]``: R rows of 128 samples, each
    row ``k = EM_ROW / chunk`` chunks of ``chunk`` samples, a chunk all one
    entity's (the last chunk of an entity zero-padded; chunks that only
    complete the last row belong to nobody).  Chunk ``(i, r)`` covers flat
    positions ``r * EM_ROW + i * chunk + [0, chunk)``.

    ``entities``: the sorted distinct entity ids of ALL rows [U].
    ``chunk_entity`` [k, R] int32: index into ``entities`` of each chunk's
    entity, -1 for nobody's.  ``pos`` [n] int32: flat position of sample i,
    or None when the layout IS the sample order (``pos == arange(n)``: no
    padding but behind the last sample).  ``grouped``: the rows arrived
    grouped by entity (``entity_runs``' ``order is None``), so ``pos``
    rises and only skips padding."""

    chunk: int
    entities: np.ndarray
    chunk_entity: np.ndarray
    pos: Optional[np.ndarray]
    num_samples: int
    grouped: bool

    @property
    def back(self) -> str:
        """The way back to sample order (the note above
        ``use_transposed_scoring``): ``identity``, ``unpad`` or ``gather``."""
        if self.pos is None:
            return "identity"
        return "unpad" if self.grouped else "gather"

    def way_back(self, parts: int = 1, part_samples: Optional[int] = None
                 ) -> "WayBack":
        """What ``to_sample_order`` takes, its leaves on the host: None,
        ``pos``, or the ``unpad`` way back.  Of that: one part brings the
        whole stream to the ``num_samples`` samples.  Under a mesh part
        ``c`` of ``parts`` brings back samples ``[c m, (c + 1) m)``, ``m =
        part_samples`` (those from ``num_samples`` on are padding and come
        back 0): ``pos`` rises, so they lie in ONE range of the stream,
        which the part cuts out at its own row ``start`` (every part the
        length of the longest) and compacts by its own stages.  Where no
        part has a stage (``pos`` only cuts the tail) there is nothing to
        pull and ``pull`` is empty."""
        if self.back != "unpad":
            return self.pos
        total = self.lanes * self.chunk
        if parts == 1:
            pull, stages = pull_stages(self.pos, total)
            return Unpad(pull if stages else pull[:0], None, None,
                         self.num_samples, stages, total)
        n, m = self.num_samples, part_samples
        own = [self.pos[min(c * m, n): min((c + 1) * m, n)]
               for c in range(parts)]
        span = max(m, max(int(p[-1]) + 1 - int(p[0]) // EM_ROW * EM_ROW
                          for p in own if len(p)))
        span = -(-span // EM_ROW) * EM_ROW
        start = np.asarray([min(int(p[0]) // EM_ROW if len(p) else 0,
                                (total - span) // EM_ROW) for p in own],
                           np.int32)
        pulls, stages = zip(*(pull_stages(p - s * EM_ROW, span)
                              for p, s in zip(own, start)))
        pull = np.concatenate(pulls)
        return Unpad(pull if max(stages) else pull[:0], start,
                     np.asarray(list(map(len, own)), np.int32), m,
                     max(stages), span)

    @property
    def lanes(self) -> int:
        return self.chunk_entity.size

    @property
    def fill(self) -> float:
        return self.num_samples / (self.lanes * self.chunk)

    def lane_slots(self, slot_of_entity: np.ndarray) -> np.ndarray:
        """[k, R] int32 stacked-model row of each chunk from a per-ENTITY
        slot vector aligned with ``entities`` (-1: no model, scores 0)."""
        ce = self.chunk_entity
        return np.where(ce >= 0, np.asarray(slot_of_entity, np.int32)[ce],
                        -1).astype(np.int32)

    def source_rows(self) -> np.ndarray:
        """[R * EM_ROW] int32: the sample stored at each flat position,
        ``num_samples`` (one past the last row) at padding."""
        n = self.num_samples
        src = np.full(self.lanes * self.chunk, n, np.int32)
        src[slice(n) if self.pos is None else self.pos] = np.arange(
            n, dtype=np.int32)
        return src


def entity_major_layout(runs: EntityRuns, row_multiple: int = 1
                        ) -> Optional[EntityMajorLayout]:
    """The entity-major layout of ``entity_runs``' grouping, or None where
    ``entity_major_chunk`` finds no chunk length.  Counts and row order
    only: the design is not touched.  ``row_multiple`` (under a mesh, its
    devices times the stored rows of a tile):
    R is a multiple of it, so that the rows of chunks shard evenly, and at
    least one chunk behind the last entity's is nobody's: the LAST flat
    position is then padding, which scores exactly 0 (where the sample
    axis is padded too, the padding samples are sent there)."""
    entities, counts, order = runs
    c = entity_major_chunk(counts)
    if c is None:
        return None
    n, k = int(counts.sum()), EM_ROW // c
    chunks = -(-counts // c)                      # chunks of each entity
    first = np.cumsum(chunks) - chunks            # its first chunk
    starts = np.cumsum(counts) - counts           # its first grouped row
    rows = -(-int(chunks.sum()) // k)             # R
    if row_multiple > 1:
        rows = -(-(int(chunks.sum()) + 1) // k)
        rows = -(-rows // row_multiple) * row_multiple
    ce = np.full(rows * k, -1, np.int32)
    ce[:int(chunks.sum())] = np.repeat(
        np.arange(len(counts), dtype=np.int32), chunks)
    pos = None
    if order is not None or np.any(counts[:-1] % c):
        # grouped row g of entity e sits at first[e] * C + (g - starts[e])
        pos = (np.arange(n) + np.repeat(first * c - starts, counts)
               ).astype(np.int32)
        if order is not None:
            grouped, pos = pos, np.empty(n, np.int32)
            pos[order] = grouped
    return EntityMajorLayout(chunk=c, entities=entities,
                             chunk_entity=np.ascontiguousarray(
                                 ce.reshape(rows, k).T),
                             pos=pos, num_samples=n, grouped=order is None)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["pull", "start", "live"],
                   meta_fields=["num_samples", "stages", "slots"])
@dataclasses.dataclass(frozen=True)
class Unpad:
    """The ``unpad`` way back: an order-preserving compaction of a stream of
    slots whose live elements (the samples, in sample order) stand among
    padding, as ``stages`` static shifts (``pull_stages``, ``unpad``).

    ``pull`` int32 ``[slots]``: bit ``b`` at slot ``q`` says stage ``b``
    pulls slot ``q + 2^b`` into ``q`` (one word for all stages: on a v5e a
    uint8 plane a byte of stages costs its unpacking a second pass a stage,
    19.4 ms for 10.6; PERF.md section 6, PR 33); empty where there is no
    stage.  ``num_samples``: the samples that come back.  ``slots``: the
    length of the stream compacted.  Under a mesh
    (``EntityMajorLayout.way_back``) that stream is a chip's own range of
    the whole one, and ``pull`` holds the chips' side by side, sharded over
    them with ``start`` and ``live`` ``[chips]`` int32: the ROW of
    ``EM_ROW`` slots at which a chip's range starts, and how many of its
    ``num_samples`` samples are real (the rest come back exactly 0)."""

    pull: Array
    start: Optional[Array]
    live: Optional[Array]
    num_samples: int
    stages: int
    slots: int


# None (identity), ``Unpad``, or ``pos`` [n] int32 (gather)
WayBack = Union[None, Unpad, Array, np.ndarray]


def pull_stages(pos: np.ndarray, slots: int) -> Tuple[np.ndarray, int]:
    """``(Unpad.pull, stages)`` for live elements at the rising positions
    ``pos`` [n] of a stream of ``slots``, bound for positions ``0 .. n - 1``.

    Element ``i`` has ``shift = pos[i] - i`` to go left; the shifts never
    fall along the stream and two live elements' differ by less than their
    distance.  Stage ``b`` (least significant bit first) moves every element
    whose shift has bit ``b`` left by ``2^b``: it lands on a slot whose own
    element has left or is padding, never on a live one (Hacker's Delight's
    ``compress``, on words).  After stage ``b`` an element stands at ``pos -
    shift % 2^(b + 1)``; a RUN of elements with one shift (an entity's rows)
    moves as one interval, so a stage's pulls are painted run by run."""
    n = len(pos)
    shift = np.asarray(pos, np.int32) - np.arange(n, dtype=np.int32)
    heads = np.flatnonzero(np.r_[True, shift[1:] != shift[:-1]][:n])
    run_shift = shift[heads].astype(np.int64)
    if np.any(np.diff(run_shift) < 0) or (n and run_shift[0] < 0):
        raise ValueError("un-pad: positions that do not rise with the samples")
    run_pos = np.asarray(pos)[heads].astype(np.int64)
    run_len = np.diff(np.r_[heads, n])
    stages = int(run_shift[-1]).bit_length() if n else 0
    pull = np.zeros(slots, "<i4")
    planes = pull.view(np.uint8).reshape(slots, 4)  # byte g: stages 8g..8g+7
    for b in range(stages):
        moved = np.flatnonzero((run_shift >> b) & 1)
        lo = run_pos[moved] - (run_shift[moved] & ((2 << b) - 1))
        edges = np.empty(2 * len(moved) + 2, np.int64)
        edges[0], edges[-1] = 0, slots
        edges[1:-1:2], edges[2:-1:2] = lo, lo + run_len[moved]
        bit = np.zeros(len(edges) - 1, np.uint8)
        bit[1::2] = 1 << (b % 8)
        planes[:, b // 8] |= np.repeat(bit, np.diff(edges))
    return pull, stages


def _pulled(acc: Array, pull: Array, stages: int,
            axis: Optional[int] = None) -> Array:
    """``pull_stages``' stages run over the stream ``acc`` (flat, or each
    row of it along ``axis``): stage ``b`` one pass ``where(pull bit b,
    roll(acc, -2^b), acc)``."""
    for b in range(stages):
        acc = jnp.where((pull >> b) & 1 == 1,
                        jnp.roll(acc, -(1 << b), axis=axis), acc)
    return acc


def unpad(acc: Array, back: Unpad) -> Array:
    """The samples out of the flat entity-major stream ``acc``, in sample
    order, bitwise ``acc[pos]``: copies, no arithmetic touches a score and
    no index a sample.  A stage is one pass over the slots (two reads of
    the stream, one of the pulls, one write: 0.48 ms over 16.0M slots on a
    v5e; PERF.md section 6, PR 33).  With ``back.start`` (a chip's, under
    ``shard_map``) ``acc`` is the WHOLE stream and the chip first cuts its
    own range out of it."""
    if back.start is not None:
        acc = jax.lax.dynamic_slice_in_dim(
            acc.reshape(-1, EM_ROW), back.start[0], back.slots // EM_ROW
        ).reshape(-1)
    out = _pulled(acc, back.pull, back.stages)[:back.num_samples]
    if back.live is not None:
        out = jnp.where(jnp.arange(back.num_samples) < back.live[0], out, 0)
    return out


def to_sample_order(acc: Array, way_back: WayBack) -> Array:
    """Flat entity-major scores back in sample order, by the way the
    layout says (``EntityMajorLayout.back``): as they are, un-padded, or
    gathered at one index a sample."""
    if way_back is None:
        return acc
    if isinstance(way_back, Unpad):
        return unpad(acc, way_back)
    return acc[way_back]


# photonlint: disable=sharding-annotation -- set-up, on one device: the
# full-sample design is an unsharded device_put under a mesh too (as the
# [d, n] design was), and the output is its only consumer's argument
@jax.jit
def _columns_at(x_t: Array, src: Array) -> Array:
    return jnp.take(x_t, src, axis=1, mode="fill",
                    fill_value=0).reshape(x_t.shape[0], -1, EM_ROW)


def entity_major_design_over(layout: EntityMajorLayout, x: np.ndarray,
                             mesh: Mesh) -> Array:
    """``entity_major_design`` under a mesh, from the HOST design ``x``
    [n, d]: [d, R, EM_ROW] with the rows of chunks over every device.  Each
    chip's rows are gathered and transposed on the host and go straight to
    that chip: neither design is ever whole on one device (the device
    gather of ``entity_major_design`` would need both there)."""
    from photon_ml_tpu.parallel.mesh import over_chips

    n, d = x.shape
    src = layout.source_rows().reshape(-1, EM_ROW)   # [R, EM_ROW]

    def shard(index):
        rows = src[index[1]]
        part = x[np.minimum(rows, n - 1)]            # [R_s, EM_ROW, d]
        part[rows >= n] = 0
        return np.ascontiguousarray(part.transpose(2, 0, 1))

    return jax.block_until_ready(jax.make_array_from_callback(
        (d,) + src.shape, NamedSharding(mesh, over_chips(mesh, 3, 1)), shard))


def entity_major_design(layout: EntityMajorLayout, x_t: Array) -> Array:
    """The transposed design ``x_t`` [d, n], on the device in sample order,
    stored by ``layout``: [d, R, EM_ROW], padding zero.  A reshape where the
    layout is the sample order; else ONE gather of whole columns on the
    device, at set-up (0.19 to 0.26 s for 8.39M x 16 f32 on a v5e, where
    numpy takes 2.0 to 3.4 s on the chip's host: my chip run, PR 24).
    Returns when ``x_t`` is no longer needed: a coordinate that went on
    uploading its buckets while both designs were alive raised the
    process's peak device memory by 0.9 GB."""
    d, n = x_t.shape
    if layout.pos is None and layout.lanes * layout.chunk == n:
        out = x_t.reshape(d, -1, EM_ROW)
    else:
        out = _columns_at(x_t, jnp.asarray(layout.source_rows()))
    return jax.block_until_ready(out)


def score_samples_em(w_stack: Array, lane_slot: Array, x_em: Array,
                     way_back: WayBack = None) -> Array:
    """``score_samples`` for an ENTITY-MAJOR design (``EntityMajorLayout``):
    ``x_em`` [d, R, EM_ROW], ``lane_slot`` [k, R] the stacked-model row of
    each chunk (-1: no model, its samples score exactly 0), ``way_back`` the
    layout's way back to sample order (``to_sample_order``): None where the
    layout is the sample order (the result is then [R * EM_ROW]: the
    samples, then the tail's zeros).

    The same d products a sample as ``score_samples_t``, summed in the same
    order in the same promoted dtype; but each chunk gathers its entity's
    coefficient ROW once (ONE gather of k x R = n / C indices, where
    ``score_samples_t`` issues d gathers of n), and each column of the row
    is spread along the chunk (``_along_chunks``); the finished scores go
    back into sample order by an un-pad where the rows arrive grouped by
    entity, by ONE n-sized gather where they lie anywhere.
    """
    k = lane_slot.shape[0]
    has = lane_slot >= 0
    # ONE gather, a row a chunk, then the block [d, k, R] with the chunks on
    # the lanes: a column sliced out of [k, R, d] is an array of its own
    # padded to 128 lanes on a TPU (3.6 ms more a call at 118,528 chunks:
    # PERF.md section 6)
    cols = jnp.moveaxis(w_stack[jnp.where(has, lane_slot, 0)], -1, 0)
    acc = jnp.zeros(x_em.shape[1:],
                    jnp.promote_types(x_em.dtype, w_stack.dtype))
    for j in range(x_em.shape[0]):  # d is static and small by contract
        acc = acc + x_em[j] * _along_chunks(cols[j], k)
    acc = jnp.where(_along_chunks(has, k), acc, 0.0).reshape(-1)
    return to_sample_order(acc, way_back)


def score_samples_sparse(w_stack: Array, slots: Array, indices: Array,
                         values: Array) -> Array:
    """Raw per-sample scores for ROW-SPARSE features:
    sum_k w_stack[slot_i, indices[i,k]] * values[i,k].

    The sparse twin of ``score_samples`` — no [n, d_full] densification, an
    O(n*k) two-level gather instead.  Padded COO slots carry value 0
    (SparseShard contract), so whatever coefficient they gather is inert;
    samples with slot -1 (entity without a model) score 0.
    """
    safe = jnp.where(slots >= 0, slots, 0)
    gathered = w_stack[safe[:, None], indices]  # [n, k]
    margins = jnp.sum(gathered * values, axis=-1)
    return jnp.where(slots >= 0, margins, 0.0)


# The part of a coefficient table one gather of the sparse rescore may read.
# On a v5e a gathered coefficient costs 7.2 to 7.6 ns out of a table of up
# to 64 MB, 12.5 ns out of 128 MB and 15.8 ns out of 301 MB (PERF.md
# section 6, PR 36).
SPARSE_TABLE_BYTES_MAX = 1 << 26
SPARSE_BLOCKS_MAX = 1 << 10


def sample_blocks(slots: np.ndarray, row_bytes: int) -> int:
    """Into how many equal blocks (a power of two) ``block_slots`` cuts the
    sample axis for ``score_samples_sparse_blocks``: the fewest whose
    samples' slots span at most ``SPARSE_TABLE_BYTES_MAX`` of a table of
    ``row_bytes`` a slot.  Rows that arrive grouped by entity get there
    (the slots rise with the samples); rows that lie anywhere never do, and
    stay one block."""
    blocks = 1
    while blocks <= SPARSE_BLOCKS_MAX:
        if block_slots(slots, blocks)[2] * row_bytes <= SPARSE_TABLE_BYTES_MAX:
            return blocks
        blocks *= 2
    return 1


def block_slots(slots: np.ndarray, blocks: int
                ) -> Tuple[np.ndarray, np.ndarray, int]:
    """``(slots [blocks, r], first [blocks], table_rows)``: the per-sample
    slot vector cut into equal blocks (the tail padded with -1), the lowest
    slot of each block and the most slots one block spans."""
    slots = np.asarray(slots, np.int32)
    rows = max(1, -(-len(slots) // blocks))
    cut = np.full(blocks * rows, -1, np.int32)
    cut[:len(slots)] = slots
    cut = cut.reshape(blocks, rows)
    has = cut >= 0
    first = np.where(has, cut, np.iinfo(np.int32).max).min(axis=1)
    last = cut.max(axis=1)
    first = np.where(last >= 0, first, 0).astype(np.int32)
    return cut, first, int(np.max(np.where(last >= 0, last - first + 1, 1)))


def block_pairs(a: np.ndarray, blocks: int) -> np.ndarray:
    """A sparse shard's [n, k] array as ``score_samples_sparse_blocks``
    reads it: [blocks, k, r], the samples on the lanes, the tail zero."""
    n, k = a.shape
    rows = max(1, -(-n // blocks))
    padded = np.zeros((blocks * rows, k), a.dtype)
    padded[:n] = a
    return np.ascontiguousarray(
        padded.reshape(blocks, rows, k).transpose(0, 2, 1))


def score_samples_sparse_blocks(w_stack: Array, slots: Array, first: Array,
                                indices: Array, values: Array,
                                table_rows: int) -> Array:
    """``score_samples_sparse`` for pairs stored by BLOCKS of samples with
    the samples on the lanes: ``indices`` / ``values`` [blocks, k, r],
    ``slots`` [blocks, r] (-1: no model, and the tail's padding), ``first``
    [blocks] the lowest slot of each block, ``table_rows`` (static) the
    most slots one block spans (``block_slots``).  Returns [blocks x r]:
    the samples, then the tail's zeros.

    The samples go on the lanes for ``score_samples_t``'s reason (an [n, k]
    array of k <= 32 takes 128 / k times its bytes in HBM, and so does
    every [n, k] gather out of it: ``use_transposed_scoring``).  A block
    gathers out of ITS rows of the table laid flat, k gathers of r
    coefficients at ``(slot - first) x d + column``: a gathered coefficient
    costs by the size of what it is gathered out of (see
    ``SPARSE_TABLE_BYTES_MAX``).  Any slots score right, whatever they
    span; what they span decides the speed."""
    entities, d = w_stack.shape
    table_rows = min(int(table_rows), entities)
    if table_rows * d >= 1 << 31:
        raise ValueError(
            f"{table_rows} rows of a coefficient table {d} wide cannot be "
            "addressed flat by int32")

    def one(block):
        slot, lowest, idx, val = block
        start = jnp.clip(lowest, 0, entities - table_rows)
        table = jax.lax.dynamic_slice(w_stack, (start, jnp.zeros_like(start)),
                                      (table_rows, d)).reshape(-1)
        at = jnp.where(slot >= 0, slot - start, 0) * d
        acc = jnp.zeros(slot.shape[0],
                        jnp.promote_types(val.dtype, w_stack.dtype))
        for j in range(idx.shape[0]):  # k is static and small by contract
            acc = acc + val[j] * table[at + idx[j]]
        return jnp.where(slot >= 0, acc, 0.0)

    return jax.lax.map(one, (slots, first, indices, values)).reshape(-1)


# A compact coordinate's pairs ENTITY-MAJOR (``score_pairs_em``): a chunk
# gathers its entity's whole compact row with one index, and each pair picks
# its coefficient out of that row by its compact place, with no index.  On a
# v5e at glmix_userbag_ml20m's size (65,536 entities, 9.49M samples in 11.5M
# slots at C = 64, 16 pairs, D = 64; PERF.md section 6), ms a call
# with the un-pad (6.0 of it): the sparse gathers by blocks 1,153; a tree of
# selects on the place's bits 36.1; a compare-select-sum a pair 25.5; a
# column's values summed over the pairs that pick it, times the column,
# 23.3 (``_pair_sums``); blocks of 2 to 32 MiB alike, so a block's rows
# spread along their chunks, ``[D, rows, EM_ROW]``, hold the window lanes'
# ``WINDOW_BLOCK_BYTES``.  ``PAIR_ROWS``: the samples of a pass of the host's
# lookup of the places (``pair_words``) and of its transposes.
PAIR_ROWS = 1 << 14


def pair_words(indices: np.ndarray, runs: EntityRuns,
               lane_entity: np.ndarray, lane_columns: np.ndarray,
               values: np.ndarray) -> Tuple[np.ndarray, int]:
    """``(words [k, n + 1] int32, kept_pairs)``: every pair of a sparse
    shard ``indices`` [n, k] as ``score_pairs_em`` reads it, one word a
    pair, ``column << bits | q`` (``bits = D.bit_length()``), ``q`` the
    column's place among its entity's compact columns, or ``D`` where the
    entity did not keep the column (or has no lane); column ``n`` is
    padding's word (column 0, ``q = D``).  ``kept_pairs``: the pairs of a
    nonzero ``values`` whose column their entity kept.

    ``runs``: the shard's ``entity_runs``; ``lane_entity`` [L] each compact
    lane's entity as an index into ``runs``' entities (-1: a padding lane),
    ``lane_columns`` [L, D] its columns (-1 behind them).  The places are
    looked up through a ``[entities, d_full]`` map, the entities' rows
    taken in their grouped order, ``PAIR_ROWS`` of them a pass (a pass's
    arrays stay in the host's caches)."""
    entities, counts, order = runs
    n, k = indices.shape
    d_full = int(indices.max(initial=0)) + 1
    width = lane_columns.shape[1]
    bits = width.bit_length()
    if d_full << bits > 1 << 31:
        raise ValueError(f"{d_full} columns and {width} places do not fit "
                         "one int32 word a pair")
    place = (np.int8 if width < 1 << 7 else np.int16 if width < 1 << 15
             else np.int32)
    places = np.full(len(entities) * d_full, width, place)
    has = np.flatnonzero(lane_entity >= 0)
    lane, q = np.nonzero(lane_columns[has] >= 0)
    places[lane_entity[has][lane] * d_full
           + lane_columns[has][lane, q]] = q
    entity_of = np.repeat(np.arange(len(entities), dtype=np.int32), counts)
    words = np.empty((k, n + 1), np.int32)
    words[:, n] = width
    kept = 0
    for g0 in range(0, n, PAIR_ROWS):
        g1 = min(n, g0 + PAIR_ROWS)
        rows = slice(g0, g1) if order is None else order[g0:g1]
        idx = indices[rows].astype(np.int32, copy=False)
        at = np.take(places, (entity_of[g0:g1] * d_full)[:, None] + idx)
        kept += int(np.count_nonzero((at < width) & (values[rows] != 0)))
        words[:, rows] = ((idx << bits) | at).T
    return words, kept


def pair_planes(a: np.ndarray, dtype) -> np.ndarray:
    """A sparse shard's ``[n, k]`` array as ``entity_major_pairs`` takes
    it: ``[k, n + 1]`` of ``dtype``, a 0 behind the samples for padding,
    transposed ``PAIR_ROWS`` samples a pass."""
    n, k = a.shape
    out = np.zeros((k, n + 1), dtype)
    for r0 in range(0, n, PAIR_ROWS):
        out[:, r0:min(n, r0 + PAIR_ROWS)] = a[r0:r0 + PAIR_ROWS].T
    return out


def pick_block(layout: EntityMajorLayout, width: int, itemsize: int) -> int:
    """The rows of 128 samples in a block of ``score_pairs_em``: those
    whose chunks' rows of ``width`` coefficients, spread along the chunks,
    hold ``WINDOW_BLOCK_BYTES``, a multiple of 8 and no more than the
    layout's rows need."""
    rows = layout.lanes * layout.chunk // EM_ROW
    return max(8, min(WINDOW_BLOCK_BYTES // (width * EM_ROW * itemsize),
                      rows + 7) // 8 * 8)


# photonlint: disable=sharding-annotation -- set-up, on one device: the
# pairs of a shard that has no mesh, the output their only consumer's
# argument
@functools.partial(jax.jit, static_argnums=2)
def _planes_at(planes: Array, src: Array, block: int) -> Array:
    return jnp.moveaxis(jnp.take(planes, src, axis=1, mode="clip").reshape(
        planes.shape[0], -1, block, EM_ROW), 1, 0)


def entity_major_pairs(layout: EntityMajorLayout, planes: Array,
                       block: int) -> Array:
    """A sparse shard's ``[k, n + 1]`` pair planes (``pair_words``, or
    ``pair_planes`` of its values), on the device in sample order, stored
    by ``layout`` in BLOCKS of ``block`` rows of 128: ``[blocks, k, block,
    EM_ROW]``, padding (and the rows behind the layout's last) taking
    column ``n``.  Block-major, so that ``score_pairs_em``'s loop takes a
    block as it comes (an operand sliced along another axis inside a loop
    inside the fit's is copied whole first: 1.5 GB at glmix_userbag_ml20m's
    size).  ONE gather of whole columns on the device, at set-up, as
    ``entity_major_design``; returns when ``planes`` is no longer needed."""
    src = layout.source_rows()
    rows = len(src) // EM_ROW
    src = np.pad(src, (0, (-(-rows // block) * block - rows) * EM_ROW),
                 constant_values=layout.num_samples)
    return jax.block_until_ready(_planes_at(planes, jnp.asarray(src), block))


def _along_chunks(v: Array, k: int) -> Array:
    """``[k, ..., R]`` a chunk -> ``[..., R, EM_ROW]`` a stored sample: the
    value of each sample's chunk, spread along the chunk."""
    chunk_of_lane = jax.lax.broadcasted_iota(
        jnp.int32, (1,) * (v.ndim - 1) + (EM_ROW,), v.ndim - 1) // (
            EM_ROW // k)
    out = v[0][..., None]
    # photonlint: disable=tracer-safety -- k, the chunks of a row, is a
    # static Python int
    for i in range(1, k):
        out = jnp.where(chunk_of_lane == i, v[i][..., None], out)
    return out


def _by_blocks(one, per_chunk: Array, fill, words: Array,
               values: Array) -> Array:
    """``one(chunks, words, values)`` [block, EM_ROW] over the blocks of
    ``entity_major_pairs``' planes, ONE loop, ``per_chunk`` [k_em, R, ...]
    cut into the same blocks (``fill`` behind its last row): the flat
    entity-major stream of ``R x EM_ROW`` scores."""
    blocks, _, block, _ = words.shape
    k_em, r = per_chunk.shape[:2]
    chunks = jnp.pad(per_chunk, ((0, 0), (0, blocks * block - r))
                     + ((0, 0),) * (per_chunk.ndim - 2), constant_values=fill)
    chunks = jnp.moveaxis(chunks.reshape((k_em, blocks, block)
                                         + per_chunk.shape[2:]), 1, 0)
    acc = jax.lax.map(lambda x: one(*x), (chunks, words, values))
    return acc.reshape(-1)[: r * EM_ROW]


def _pair_sums(rows: Array, q: Array, val: Array) -> Array:
    """``sum_j val[j] rows[q[j]]`` [r, EM_ROW], ``rows`` [D, r, EM_ROW] the
    chunks' rows spread along them, ``q`` / ``val`` [k, r, EM_ROW] the
    pairs' places and values: each column's values summed over the pairs
    that pick it (a compare and a select a pair and column, no index),
    times the column.  A place of ``D`` or more adds exactly 0."""
    places = jnp.arange(rows.shape[0], dtype=q.dtype)[:, None, None]
    per = jnp.sum(jnp.where(q[:, None] == places, val[:, None], 0.0),
                  axis=0)                              # [D, r, EM_ROW]
    return jnp.sum(per * rows, axis=0)


def score_pairs_em(lanes: Array, chunk_row: Array, words: Array,
                   values: Array, way_back: WayBack = None) -> Array:
    """``score_samples_sparse`` of a COMPACT coordinate, from its compact
    lanes: ``lanes`` [L, D] every class's lanes side by side (each padded to
    the widest ``d_proj``, D), ``chunk_row`` [k_em, R] the lane of each
    chunk's entity (-1: no model, its samples score exactly 0), ``words``
    / ``values`` [blocks, k, block, EM_ROW] the pairs stored entity-major
    (``pair_words``, ``entity_major_pairs``), ``way_back`` the layout's
    (``to_sample_order``).

    Exact where an entity's published row is zero off its compact columns
    (an index-map projection with no box fill): a chunk gathers its lane's
    whole row with ONE index, ``k_em x R = n / C`` indices a call where the
    sparse gathers issue one a pair, and the pairs pick out of it by their
    places (``_pair_sums``); a pair whose column the entity did not keep
    adds exactly 0, as the zero it stands for would."""
    k_em, width = chunk_row.shape[0], lanes.shape[1]
    has = chunk_row >= 0
    rows = jnp.where(has[..., None], lanes[jnp.where(has, chunk_row, 0)],
                     0.0)                              # [k_em, R, D]
    mask = (1 << width.bit_length()) - 1
    return to_sample_order(_by_blocks(
        lambda chunks, q, val: _pair_sums(
            _along_chunks(jnp.moveaxis(chunks, 2, 1), k_em), q & mask, val),
        rows, 0.0, words, values), way_back)


# photonlint: disable=sharding-annotation -- off the timed path, on one
# device: a coordinate whose pairs are entity-major has no mesh
@functools.partial(jax.jit, static_argnums=4)
def score_pairs_full(w_stack: Array, lane_slot: Array, words: Array,
                     values: Array, bits: int) -> Array:
    """``score_samples_sparse`` of ANY table ``w_stack`` [E, d_full] from
    ``score_pairs_em``'s planes, as the flat entity-major stream of ``R x
    EM_ROW`` scores (the layout's ``pos`` brings it to sample order):
    ``lane_slot`` [k_em, R] the table's row of each chunk (-1: none), each
    pair's column its word's high bits (``bits``: ``pair_words``').  One
    index a pair: off the timed path (a foreign model, carried
    entities)."""
    k_em = lane_slot.shape[0]

    def one(slots, cols, val):
        has = slots >= 0
        slot = _along_chunks(jnp.where(has, slots, 0), k_em)
        acc = jnp.zeros(slot.shape, jnp.promote_types(val.dtype,
                                                      w_stack.dtype))
        for j in range(cols.shape[0]):  # k is static and small by contract
            acc = acc + val[j] * w_stack[slot, cols[j] >> bits]
        return jnp.where(_along_chunks(has, k_em), acc, 0.0)

    return _by_blocks(one, lane_slot, -1, words, values)


def gather_entity_coefficients(
    coeffs: Sequence[Array], buckets: EntityBuckets
) -> Dict[int, np.ndarray]:
    """Entity id -> coefficient vector (host-side model export)."""
    host = [np.asarray(c) for c in coeffs]
    return {eid: host[bi][lane] for eid, (bi, lane) in buckets.lane_of.items()}
