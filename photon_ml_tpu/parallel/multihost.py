"""Multi-host (multi-process) SPMD: initialization, global meshes, and
process-local data placement.

Reference analog: the Spark driver/executor cluster — Netty RPC broadcast +
treeAggregate over the cluster network (SURVEY.md §5 "Distributed
communication backend").  TPU-native shape: every host runs THIS SAME
program under ``jax.distributed``; collectives ride ICI within a slice and
DCN across slices, inserted by XLA from the sharding annotations.  There is
no driver process — the "driver loop" (coordinate descent) runs identically
on every host, operating on globally-sharded arrays.

Data loading is split by sample id BEFORE reading (each host reads only its
row range — the reference's executor-partitioned Avro read), PADDED to the
balanced per-host row count (padding rows carry weight 0, so they are inert
in every objective/metric), then assembled into global arrays with
``jax.make_array_from_process_local_data``.

The recipe (each host runs the same code):

    initialize(...)                      # no-op for a single process
    mesh = global_mesh()
    rows = padded_per_host_rows(n, mesh)
    start, stop = process_row_range(n)
    block = load_rows(start, stop)       # host-local read
    block = pad_local_rows(block, rows)  # weight column padded with 0
    g = global_batch_from_local(block, mesh)

Cross-process scope (tested in tests/test_parallel.py
::test_multihost_two_processes and ::test_multihost_glmix_four_processes):
the fixed-effect solve runs multihost both data-parallel
(ShardMapObjective — the one DCN all-reduce) and FEATURE-SHARDED
(ShardSparseObjective, w blocked over the within-process feature axis).
RANDOM-EFFECT coordinates run multihost via ENTITY-sharded reads: every
entity's samples are owned by exactly one host
(``process_entity_assignment`` — the deterministic-hash analog of the
reference's shuffle into balanced entity partitions,
RandomEffectDatasetPartitioner.scala:30-171), each host buckets its own
entities locally (``parallel/bucketing.py`` with global ``row_ids``), the
hosts agree on global bucket shapes with one tiny metadata all-gather
(``global_entity_buckets``), and the entity-lane arrays assemble into
globally-sharded buckets with ``jax.make_array_from_process_local_data``.
``multihost_glmix_sweep`` then runs residual coordinate descent (fixed +
random effects) with every score vector a global device array."""

from __future__ import annotations

import logging
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from photon_ml_tpu.parallel.mesh import DATA_AXIS, ENTITY_AXIS, FEATURE_AXIS

Array = jax.Array
logger = logging.getLogger(__name__)


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               expected_processes: Optional[int] = None) -> None:
    """Bring up the jax.distributed runtime.

    Explicit ``num_processes <= 1`` is a no-op.  With no arguments,
    auto-detection is attempted (TPU pods infer everything from the
    environment); if no cluster environment is found this degenerates to
    single-process — at WARNING level, because on a real pod that means N
    independent jobs training divergent models.  Pass
    ``expected_processes`` to turn a short job into a hard error (the
    recommended pod setting)."""
    if num_processes is not None and num_processes <= 1:
        if expected_processes is not None and expected_processes != num_processes:
            raise RuntimeError(
                f"expected {expected_processes} processes but launched with "
                f"num_processes={num_processes}")
        return
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    try:
        jax.distributed.initialize(**kwargs)
    except (RuntimeError, ValueError) as e:
        if kwargs:
            raise  # explicit cluster config that fails must be loud
        logger.warning("no cluster environment detected (%s); running "
                       "single-process", e)
    got = jax.process_count()
    want = expected_processes if expected_processes is not None else num_processes
    if want is not None and got != want:
        raise RuntimeError(
            f"expected {want} processes but jax.process_count() == {got}: "
            "the cluster did not form (check coordinator address / pod env)")


def global_mesh(n_entity: int = 1, n_feature: int = 1) -> Mesh:
    """A (data, entity, feature) mesh over ALL processes' devices, laid out
    so collectives ride the right interconnect tier.

    ICI/DCN mapping (the multi-slice story, SURVEY §5): the ``entity`` and
    ``feature`` axes are placed INNERMOST WITHIN each process's (slice's)
    devices, so their collectives — the per-evaluation feature-axis margin
    psum of the sharded sparse objective, the entity-lane layouts — always
    ride ICI.  Only the ``data`` axis strides ACROSS processes, so the one
    gradient all-reduce per objective evaluation is the only collective that
    ever touches DCN — exactly the reference's cluster-network role
    (treeAggregate over Spark executors), and DP gradient all-reduce is the
    one collective that amortizes DCN latency well.

    Within a single process this degenerates to ``make_mesh``'s layout.
    """
    devices = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    n = len(devices)
    n_proc = jax.process_count()
    local = n // n_proc
    cell = n_entity * n_feature
    if n % cell:
        raise ValueError(
            f"{n} global devices not divisible by entity*feature = {cell}")
    if n_proc > 1 and local % cell:
        raise ValueError(
            f"entity*feature = {cell} does not fit within one process's "
            f"{local} devices — entity/feature collectives must stay on ICI "
            "(within a slice); shrink those axes or grow the slice")
    arr = (np.asarray(devices)
           .reshape(n // cell, n_entity, n_feature))
    return Mesh(arr, (DATA_AXIS, ENTITY_AXIS, FEATURE_AXIS))


def process_row_range(n: int,
                      process_id: Optional[int] = None,
                      num_processes: Optional[int] = None) -> Tuple[int, int]:
    """[start, stop) of the global sample rows THIS host should read.

    Contiguous row split by process id; the last host's range is short when
    ``n`` doesn't divide (pad with ``pad_local_rows`` before assembly).
    """
    pid = jax.process_index() if process_id is None else process_id
    np_ = jax.process_count() if num_processes is None else num_processes
    if not 0 <= pid < np_:
        raise ValueError(f"process id {pid} out of range for {np_} processes")
    per = -(-n // np_)  # ceil: every host but the last reads `per` rows
    start = min(pid * per, n)
    stop = min(start + per, n)
    return start, stop


def padded_per_host_rows(n: int, mesh: Mesh,
                         num_processes: Optional[int] = None) -> int:
    """Per-host row count every host must pad its block to: ceil(n / hosts)
    rounded up so each host's rows divide its share of the data axis."""
    np_ = jax.process_count() if num_processes is None else num_processes
    per = -(-n // np_)
    data_size = mesh.shape[DATA_AXIS]
    if data_size % np_:
        raise ValueError(
            f"data axis ({data_size}) must be divisible by the process "
            f"count ({np_}) — one host cannot own a fraction of a device row")
    local_devices = data_size // np_
    return -(-per // local_devices) * local_devices


def pad_local_rows(block: Dict[str, np.ndarray], rows: int) -> Dict[str, np.ndarray]:
    """Zero-pad every column's leading dim to ``rows`` (weight columns pad
    with 0, making the extra rows inert everywhere)."""
    from photon_ml_tpu.parallel.mesh import _pad_axis

    out = {}
    for name, a in block.items():
        try:
            out[name] = _pad_axis(np.asarray(a), rows, 0)
        except ValueError as e:
            raise ValueError(f"column {name!r}: {e}") from e
    return out


def global_batch_from_local(
    local: Dict[str, np.ndarray],
    mesh: Mesh,
    specs: Optional[Dict[str, PartitionSpec]] = None,
) -> Dict[str, Array]:
    """Host-local row blocks -> globally data-sharded device arrays.

    Every host must pass the same keys with the SAME per-host row count
    (use ``padded_per_host_rows`` + ``pad_local_rows``); rows concatenate
    across hosts in process order.  ``specs`` overrides the default
    row-sharded PartitionSpec per key (e.g. ``{"x": P(DATA_AXIS,
    FEATURE_AXIS)}`` for a feature-sharded design matrix).
    """
    specs = specs or {}
    n_proc = jax.process_count()
    out: Dict[str, Array] = {}
    for name, a in local.items():
        a = np.asarray(a)
        spec = specs.get(name,
                         PartitionSpec(DATA_AXIS, *([None] * (a.ndim - 1))))
        sharding = NamedSharding(mesh, spec)
        global_shape = (a.shape[0] * n_proc,) + a.shape[1:]
        out[name] = jax.make_array_from_process_local_data(
            sharding, a, global_shape=global_shape)
    return out


# ---------------------------------------------------------------------------
# Random effects across hosts: entity-sharded reads -> host-local bucketing
# -> globally-sharded entity lanes.  Reference analog: the shuffle of
# per-entity data into balanced partitions (RandomEffectDataset.scala:302-341,
# RandomEffectDatasetPartitioner.scala:30-171).  TPU-native shape: there is
# no shuffle fabric — ownership is decided BEFORE the read by a deterministic
# hash of the entity id, every host keeps only its entities' rows (carrying
# their GLOBAL sample ids), and the per-host buckets concatenate into global
# [E, S, d] lane arrays whose entity axis is sharded over the whole mesh.
# ---------------------------------------------------------------------------


def process_entity_assignment(entity_ids: np.ndarray,
                              num_processes: Optional[int] = None,
                              seed: int = 0) -> np.ndarray:
    """Owning process id per sample, by deterministic hash of the entity id.

    Every host computes the same assignment with no global view — the
    shuffle-free analog of the reference's entity partitioner; with many
    entities the load balances statistically (the reference balances by
    exact counts because a Spark shuffle is already paying for the global
    pass, RandomEffectDatasetPartitioner.scala:68-117)."""
    from photon_ml_tpu.parallel.bucketing import _splitmix64

    np_ = jax.process_count() if num_processes is None else num_processes
    ids = np.asarray(entity_ids, np.int64).astype(np.uint64)
    return (_splitmix64(ids ^ np.uint64(seed)) % np.uint64(np_)).astype(np.int64)


def local_entity_rows(entity_ids: np.ndarray,
                      process_id: Optional[int] = None,
                      num_processes: Optional[int] = None,
                      seed: int = 0) -> np.ndarray:
    """GLOBAL row ids of the samples THIS host owns for a random-effect
    coordinate (its entities' rows).  Feed the filtered columns plus these
    ids into ``bucket_by_entity(..., row_ids=..., num_samples=n_global)``."""
    pid = jax.process_index() if process_id is None else process_id
    owner = process_entity_assignment(entity_ids, num_processes, seed)
    return np.nonzero(owner == pid)[0].astype(np.int64)


def global_entity_buckets(local, mesh: Mesh, projections=None):
    """Host-local EntityBuckets -> globally-sharded EntityBuckets.

    Every host calls this with ITS entities' buckets (built with global
    ``row_ids``/``num_samples``).  One metadata all-gather agrees on the
    union of capacity classes, the per-host lane count of each, and — for
    COMPACT buckets — the class's compact width; then every field assembles
    via ``make_array_from_process_local_data`` with the entity lane sharded
    over ALL mesh devices (the layout ``fit_random_effects`` solves under).
    The returned ``lane_of`` maps THIS host's entities to (bucket, GLOBAL
    lane); ``num_entities`` is the global total.  Hosts missing a capacity
    class contribute all-padding lanes (weight 0, entity -1) — inert by the
    core masking contract.

    ``projections``: the per-bucket BucketProjection list from
    ``bucket_by_entity_sparse`` (wide-vocabulary compact buckets: design
    blocks are [E, S, d_obs], never [E, S, vocab]).  Per-host compact
    widths differ, so the agreement pass takes the max per class and each
    host zero-pads its blocks (padded columns carry index -1 / value 0 —
    margin-inert).  Returns ``(global_buckets, padded_projections)`` in
    this mode; projections stay HOST-LOCAL (publish back-projects each
    host's own lanes — ``export_local_random_effects``)."""
    from jax.experimental import multihost_utils

    from photon_ml_tpu.parallel.bucketing import Bucket, EntityBuckets

    n_proc = jax.process_count()
    pid = jax.process_index()
    n_dev = mesh.size
    if n_dev % n_proc:
        raise ValueError(f"{n_dev} devices not divisible by {n_proc} processes")
    ldc = n_dev // n_proc  # per-host device share of the entity lane

    # 1. agree on capacity classes + per-host lane counts + compact widths
    #    (tiny all-gather: two ints per log2-capacity per host)
    MAXLOG = 33
    vec = np.zeros((MAXLOG, 2), np.int64)
    by_cap = {}
    for local_bi, b in enumerate(local.buckets):
        c = int(b.capacity)
        log = c.bit_length() - 1
        if (1 << log) != c:
            raise ValueError(f"bucket capacity {c} is not a power of two")
        vec[log, 0] = b.num_lanes
        vec[log, 1] = b.x.shape[2]
        by_cap[c] = (local_bi, b)
    if local.compact and projections is None:
        # the explicit marker, NOT width comparison: a padded compact width
        # can equal dim while lane column j still means "j-th observed
        # feature" (EntityBuckets.compact docstring)
        raise ValueError(
            "compact buckets need their projections: pass "
            "bucket_by_entity_sparse's BucketProjection list so the "
            "agreement pass can align per-host compact widths and export "
            "can back-project to the full vocabulary")
    # with n_proc == 1 process_allgather returns the input shape unchanged
    # in some jax versions and stacks a leading axis of one in others (0.9)
    # — normalize both gathers to [n_proc, ...] so the per-host indexing
    # below holds either way
    all_vec = np.asarray(multihost_utils.process_allgather(vec)
                         ).reshape((n_proc,) + vec.shape)
    ent_counts = np.asarray(multihost_utils.process_allgather(
        np.asarray([local.num_entities], np.int64))).reshape(n_proc, 1)
    num_entities_global = int(ent_counts.sum())

    shard = NamedSharding(mesh, PartitionSpec(tuple(mesh.axis_names)))
    buckets = []
    padded_projections = []
    lane_of: Dict[int, Tuple[int, int]] = {}
    dtype = (local.buckets[0].x.dtype if local.buckets else np.float32)
    for log in range(MAXLOG):
        host_lanes = all_vec[:, log, 0]
        if not host_lanes.any():
            continue
        cap = 1 << log
        per_host = int(-(-host_lanes.max() // ldc) * ldc)
        local_bi, b = by_cap.get(cap, (None, None))
        d = (int(all_vec[:, log, 1].max()) if projections is not None
             else local.dim)

        def _pad2(a, fill, shape_tail, dt):
            """Pad lanes AND (for 3-d design blocks) trailing compact dim."""
            out = np.full((per_host,) + shape_tail, fill, dt)
            if a is not None:
                if a.ndim == 3:
                    out[: a.shape[0], :, : a.shape[2]] = a
                elif a.ndim == 2:
                    out[: a.shape[0], : a.shape[1]] = a
                else:
                    out[: a.shape[0]] = a
            return out

        fields = dict(
            x=_pad2(b.x if b else None, 0, (cap, d), dtype),
            y=_pad2(b.y if b else None, 0, (cap,), dtype),
            offset=_pad2(b.offset if b else None, 0, (cap,), dtype),
            weight=_pad2(b.weight if b else None, 0, (cap,), dtype),
            rows=_pad2(b.rows if b else None, -1, (cap,), np.int32),
            counts=_pad2(b.counts if b else None, 0, (), np.int32),
            entity_lanes=_pad2(b.entity_lanes if b else None, -1, (),
                               np.int64),
        )
        g = {
            k: jax.make_array_from_process_local_data(
                shard, a, global_shape=(per_host * n_proc,) + a.shape[1:])
            for k, a in fields.items()
        }
        bi = len(buckets)
        if b is not None:
            for eid, (lbi, lane) in local.lane_of.items():
                if lbi == local_bi:
                    lane_of[eid] = (bi, pid * per_host + lane)
        buckets.append(Bucket(**g))
        if projections is not None:
            from photon_ml_tpu.parallel.projection import BucketProjection

            p = projections[local_bi] if b is not None else None
            idx = _pad2(p.indices if p is not None else None, -1, (d,),
                        np.int32)
            padded_projections.append(
                BucketProjection(indices=idx, d_full=local.dim))
    out = EntityBuckets(buckets=buckets, lane_of=lane_of, dim=local.dim,
                        num_entities=num_entities_global,
                        num_samples=local.num_samples,
                        compact=local.compact)
    if projections is not None:
        return out, padded_projections
    return out


def build_re_scoring(global_train, local_scoring, mesh: Mesh):
    """Multihost analog of the reference's PASSIVE data path: samples capped
    out of an entity's training reservoir still get scored with the entity's
    model (RandomEffectDataset passiveData; RandomEffectCoordinate.scala:
    210-231).  ``local_scoring``: THIS host's UNCAPPED buckets (same entity
    filter, ``active_cap=None``, global ``row_ids``).  Returns
    ``(global_scoring_buckets, coeff_idx)`` where ``coeff_idx[bi]`` maps each
    scoring lane to its entity's row in the CONCATENATED training lane
    arrays (-1 for padding lanes) — the cross-bucket coefficient gather
    ``multihost_glmix_sweep`` scores with."""
    if global_train.compact:
        raise ValueError(
            "passive scoring does not compose with COMPACT training buckets "
            "(each lane's coefficients live in its own observed-column "
            "basis); omit the reservoir cap for compact multihost "
            "coordinates, so the training buckets score every sample")
    bases = np.cumsum([0] + [b.num_lanes for b in global_train.buckets])
    flat_of = {eid: int(bases[bi] + lane)
               for eid, (bi, lane) in global_train.lane_of.items()}
    gs = global_entity_buckets(local_scoring, mesh)
    n_proc = jax.process_count()
    shard = NamedSharding(mesh, PartitionSpec(tuple(mesh.axis_names)))
    coeff_idx = []
    for b in gs.buckets:
        per_host = b.num_lanes // n_proc
        local_lanes = np.full((per_host,), -1, np.int32)
        for eid, (bi2, glane) in gs.lane_of.items():
            if gs.buckets[bi2] is b:
                local_lanes[glane - jax.process_index() * per_host] = \
                    flat_of.get(eid, -1)
        coeff_idx.append(jax.make_array_from_process_local_data(
            shard, local_lanes, global_shape=(b.num_lanes,)))
    return gs, coeff_idx


def multihost_glmix_sweep(
    mesh: Mesh,
    fixed_batch,
    re_buckets,
    fixed_objective,
    re_objective,
    num_iterations: int = 2,
    optimizer=None,
    config=None,
    re_scoring=None,
    num_samples: Optional[int] = None,
    on_iteration=None,
    initial=None,
    start_iteration: int = 0,
):
    """Residual coordinate descent (one fixed + one random-effect
    coordinate) where EVERY score vector is a global device array — the
    multihost GLMix training loop (reference CoordinateDescent.scala:197-204
    run on a cluster; here the same program runs on every host and XLA's
    collectives replace the shuffle/broadcast).

    ``fixed_batch``: globally row-sharded DenseBatch (``global_batch_from_
    local``); its ``offset`` is the base offset.  ``re_buckets``: globally
    entity-sharded EntityBuckets (``global_entity_buckets``) whose
    ``Bucket.rows`` carry GLOBAL sample ids into the fixed batch's row
    space.  Update order per iteration: fixed (offsets += RE scores), then
    random effects (offsets += fixed margins) — the 2-coordinate residual
    schedule of game/descent.py.

    ``re_scoring``: optional ``build_re_scoring`` result — under a
    reservoir cap, RE scores come from the UNCAPPED scoring buckets (the
    reference's passive-data path), not just the training rows; without it
    the training buckets score (exact when no cap drops rows).

    ``num_samples``: the TRUE global sample count ``n``.  ``Bucket.rows``
    carry ORIGINAL global row ids (so reservoir decisions stay
    topology-invariant), but the fixed batch lives in the PADDED per-host
    layout — whenever ceil(n/nproc) is not a multiple of the per-host
    data-device count the two row spaces differ, and every gather/scatter
    here translates original -> padded ids.  Required; the two tests'
    sizes aligning by accident is exactly the trap.

    MULTIPLE random-effect coordinates (the reference's per-user +
    per-item GLMix shape): pass ``re_buckets`` as an ORDERED dict
    {cid: EntityBuckets} — ``re_objective`` then takes a matching dict (or
    one shared objective) and ``re_scoring`` a dict of ``build_re_scoring``
    results; the update schedule becomes fixed, then each RE coordinate in
    dict order, every one training against the residual of ALL others
    (CoordinateDescent.scala:197-204).  Returns dicts in this mode.

    Checkpoint/resume (the multihost twin of storage/checkpoint.py's
    mid-job resume): ``on_iteration(it, w_fixed, re_coeffs)`` fires after
    every completed iteration with the live device values — the CLI driver
    writes per-host npz checkpoints from it.  To resume, pass
    ``initial=(w_fixed_host, {cid: [host-local lane blocks per bucket]})``
    (each host ITS OWN addressable blocks, as saved) plus
    ``start_iteration``; RE scores are recomputed from the loaded
    coefficients, so the resumed trajectory equals the uninterrupted one.

    Normalization rides the objectives (shared contexts, the reference's
    NormalizationContextBroadcast semantics): solves run transformed,
    every exchanged score carries eff(w) + the margin shift (margins are
    invariant), and the returned coefficients stay in SOLVER space — the
    caller publishes original-space via
    ``norm.model_to_original_space`` / ``export_local_random_effects(
    norm=...)``.  Compact buckets refuse non-identity normalization
    (per-lane projected contexts are the single-process path's domain).

    Returns ``(w_fixed, re_coeffs, re_scores)`` — replicated fixed
    coefficients, per-bucket GLOBAL [E, d] lane coefficients, and the
    final replicated RE score vector(s)."""
    import functools

    from photon_ml_tpu.opt.solve import make_solver
    from photon_ml_tpu.parallel.fixed import ShardMapObjective
    from photon_ml_tpu.types import OptimizerType

    single = not isinstance(re_buckets, dict)
    re_b = {"__re__": re_buckets} if single else dict(re_buckets)
    if isinstance(re_objective, dict):
        if set(re_objective) != set(re_b):
            raise ValueError("re_objective keys must match re_buckets keys")
        re_obj = dict(re_objective)
    else:
        re_obj = {cid: re_objective for cid in re_b}
    if re_scoring is None:
        re_sc = {}
    elif single:
        re_sc = {"__re__": re_scoring}
    else:
        re_sc = dict(re_scoring)
        unknown = set(re_sc) - set(re_b)
        if unknown:
            # a misspelled key would silently fall back to scoring with the
            # CAPPED training buckets — the exact failure mode the passive
            # path exists to prevent
            raise ValueError(f"re_scoring keys {sorted(unknown)} not in "
                             f"re_buckets {sorted(re_b)}")

    # Normalization rides the objectives (the single-process shared-context
    # semantics: solve transformed, margins invariant): the fixed margins
    # and RE scores below carry eff(w) + the margin shift, and the CALLER
    # publishes original-space coefficients (export_local_random_effects
    # norm=/model_to_original_space).  Compact buckets would need PER-LANE
    # projected contexts — refused, like the sparse feature-sharded fixed
    # objective refuses shifts.
    for cid, rb in re_b.items():
        o = re_obj[cid]
        if rb.compact and (o.norm.factors is not None
                           or o.norm.shifts is not None):
            raise ValueError(
                f"multihost coordinate {cid!r}: normalization with COMPACT "
                "(observed-column) buckets needs per-lane projected "
                "contexts — use dense buckets or identity normalization")
    optimizer = OptimizerType.LBFGS if optimizer is None else optimizer
    n_pad = int(fixed_batch.y.shape[0])
    d_fixed = int(fixed_batch.x.shape[1])
    dtype = fixed_batch.y.dtype
    rep = NamedSharding(mesh, PartitionSpec())
    row_sharded = NamedSharding(mesh, PartitionSpec(DATA_AXIS))
    # per-entity lanes over ALL devices — the exact placement
    # global_entity_buckets gave every bucket array
    entity_shard = NamedSharding(mesh, PartitionSpec(tuple(mesh.axis_names)))

    if num_samples is None:
        raise ValueError(
            "multihost_glmix_sweep needs num_samples (the true global n) to "
            "translate original row ids into the padded fixed-batch layout")
    n_proc = jax.process_count()
    per = -(-num_samples // n_proc)       # process_row_range's host stride
    rows_per = n_pad // n_proc            # padded_per_host_rows's stride
    if per == rows_per:
        to_padded = lambda rows: rows
    else:
        # original global id r lives in host r // per at padded position
        # (r // per) * rows_per + r % per; -1 padding slots pass through
        to_padded = lambda rows: jnp.where(
            rows >= 0, (rows // per) * rows_per + rows % per, rows)

    zeros_n = jax.jit(lambda: jnp.zeros((n_pad,), dtype), out_shardings=rep)

    add_offsets = jax.jit(lambda base, s: base + s, out_shardings=row_sharded)
    fnorm = fixed_objective.norm
    fixed_margin = jax.jit(
        lambda w, b: b.margins(fnorm.effective_coefficients(w))
        + fnorm.margin_shift(w), out_shardings=rep)

    def _lane_margins(norm, w, x):
        """[E, S] margins of lane models under the coordinate's shared
        context: x·eff(w) plus each lane's own margin shift."""
        eff = norm.effective_coefficients(w)
        m = jnp.einsum("esd,ed->es", x, eff)
        if norm.shifts is not None:
            m = m - (eff @ norm.shifts)[:, None]
        return m
    # residual bookkeeping on replicated [n_pad] vectors (the descent loop's
    # numpy adds in game/descent.py, kept on device)
    rep_other = jax.jit(lambda m, t, s: m + t - s, out_shardings=rep)
    rep_swap = jax.jit(lambda t, old, new: t - old + new, out_shardings=rep)

    @functools.partial(jax.jit, out_shardings=entity_shard)
    def bucket_offset(off0, rows, margins):
        rows = to_padded(rows)
        safe = jnp.where(rows >= 0, rows, 0)
        return off0 + jnp.where(rows >= 0, margins[safe], 0.0)

    def _make_scorer(norm):
        @functools.partial(jax.jit, out_shardings=rep)
        def re_score(ws, xs, rows_list):
            total = jnp.zeros((n_pad,), dtype)
            # photonlint: disable=tracer-safety -- zip over tuple pytrees:
            # one lane per capacity bucket, a static structure deliberately
            # unrolled (bucket count is small and fixed per model)
            for w, x, rows in zip(ws, xs, rows_list):
                rows = to_padded(rows)
                margins = _lane_margins(norm, w, x)
                valid = rows >= 0
                safe = jnp.where(valid, rows, 0)
                total = total.at[safe.ravel()].add(
                    jnp.where(valid, margins, 0.0).ravel())
            return total
        return re_score

    def _make_passive_scorer(norm):
        @functools.partial(jax.jit, out_shardings=rep)
        def re_score_passive(ws, xs, rows_list, idx_list):
            # cross-bucket coefficient gather: scoring lanes look their
            # entity's trained row up in the concatenated training arrays
            flat = jnp.concatenate(ws, axis=0)
            total = jnp.zeros((n_pad,), dtype)
            # photonlint: disable=tracer-safety -- zip over tuple pytrees:
            # static per-bucket lane structure, deliberately unrolled
            for x, rows, idx in zip(xs, rows_list, idx_list):
                rows = to_padded(rows)
                wl = flat[jnp.clip(idx, 0, flat.shape[0] - 1)]
                wl = jnp.where((idx >= 0)[:, None], wl, 0.0)
                margins = _lane_margins(norm, wl, x)
                valid = rows >= 0
                safe = jnp.where(valid, rows, 0)
                total = total.at[safe.ravel()].add(
                    jnp.where(valid, margins, 0.0).ravel())
            return total
        return re_score_passive

    scorers = {cid: _make_scorer(re_obj[cid].norm) for cid in re_b}
    passive_scorers = {cid: _make_passive_scorer(re_obj[cid].norm)
                       for cid in re_b}

    # photonlint: disable=sharding-annotation -- SolverResult is a pytree of
    # [E, ...] entity lanes whose layout follows w0/batch (both placed
    # entity-sharded by global_entity_buckets); one broadcast spec would
    # also pin the result's scalar diagnostics, so propagation IS the
    # annotation here
    vsolves = {cid: jax.jit(jax.vmap(make_solver(re_obj[cid], optimizer,
                                                 config)))
               for cid in re_b}
    # ONE compile for the fixed solve (the same explicit-SPMD path
    # fit_fixed_effect takes), reused across descent iterations
    solve_fixed = jax.jit(
        make_solver(ShardMapObjective(fixed_objective, mesh), optimizer,
                    config), out_shardings=rep)

    import dataclasses as _dc

    from photon_ml_tpu.core.batch import DenseBatch

    def _score_of(cid, coeffs):
        if cid in re_sc and re_sc[cid] is not None:
            gs, coeff_idx = re_sc[cid]
            return passive_scorers[cid](
                tuple(coeffs), tuple(b.x for b in gs.buckets),
                tuple(b.rows for b in gs.buckets), tuple(coeff_idx))
        rb = re_b[cid]
        return scorers[cid](tuple(coeffs), tuple(b.x for b in rb.buckets),
                            tuple(b.rows for b in rb.buckets))

    if initial is not None:
        w0_host, re_blocks = initial
        w_fixed = jax.make_array_from_process_local_data(
            rep, np.asarray(w0_host, dtype), global_shape=(d_fixed,))
        if single and not isinstance(re_blocks, dict):
            re_blocks = {"__re__": re_blocks}
        re_coeffs = {
            cid: [jax.make_array_from_process_local_data(
                      entity_shard, np.asarray(blk),
                      global_shape=(b.num_lanes,) + np.asarray(blk).shape[1:])
                  for b, blk in zip(re_b[cid].buckets, re_blocks[cid])]
            for cid in re_b
        }
        # scores recomputed from the loaded coefficients — the resumed
        # trajectory equals the uninterrupted one
        re_scores = {cid: _score_of(cid, re_coeffs[cid]) for cid in re_b}
    else:
        # photonlint: disable=recompile-hazard -- one-shot cold-start init:
        # runs once per training job; jit is the supported way to build a
        # sharded zeros array across processes
        w_fixed = jax.jit(lambda: jnp.zeros((d_fixed,), dtype),
                          out_shardings=rep)()
        # per-bucket solve width = the bucket's design width (compact
        # buckets solve in their observed-column space, not the vocabulary)
        re_coeffs = {
            # photonlint: disable=recompile-hazard -- one-shot cold-start
            # init, one compile per bucket shape per training job
            cid: [jax.jit(functools.partial(jnp.zeros,
                                            (b.num_lanes, int(b.x.shape[2])),
                                            dtype),
                          out_shardings=entity_shard)()
                  for b in rb.buckets]
            for cid, rb in re_b.items()
        }
        re_scores = {cid: zeros_n() for cid in re_b}
    total_re = zeros_n()
    for s in re_scores.values():
        total_re = rep_swap(total_re, zeros_n(), s)
    base_offset = fixed_batch.offset
    for it in range(start_iteration, num_iterations):
        batch_f = _dc.replace(fixed_batch,
                              offset=add_offsets(base_offset, total_re))
        w_fixed = solve_fixed(w_fixed, batch_f).w
        margins = fixed_margin(w_fixed, fixed_batch)
        for cid, rb in re_b.items():
            # everything the OTHER coordinates explain becomes this one's
            # offset (fresh scores from coordinates already updated this
            # iteration — the game/descent.py schedule)
            other = rep_other(margins, total_re, re_scores[cid])
            new_coeffs = []
            for b, w0 in zip(rb.buckets, re_coeffs[cid]):
                off = bucket_offset(b.offset, b.rows, other)
                dbatch = DenseBatch(x=b.x, y=b.y, offset=off, weight=b.weight)
                new_coeffs.append(vsolves[cid](w0, dbatch).w)
            re_coeffs[cid] = new_coeffs
            new_score = _score_of(cid, new_coeffs)
            total_re = rep_swap(total_re, re_scores[cid], new_score)
            re_scores[cid] = new_score
        if on_iteration is not None:
            on_iteration(it, w_fixed,
                         re_coeffs["__re__"] if single else re_coeffs)
    if single:
        return w_fixed, re_coeffs["__re__"], re_scores["__re__"]
    return w_fixed, re_coeffs, re_scores


def host_lane_blocks(re_coeffs) -> "list[np.ndarray]":
    """THIS host's addressable [per_host, d] block of each global entity-lane
    array — the unit the CLI checkpoints and ``initial=`` resumes from."""
    out = []
    for arr in re_coeffs:
        shards = sorted(arr.addressable_shards, key=lambda s: s.index[0].start)
        out.append(np.concatenate([np.asarray(s.data) for s in shards])
                   if shards else np.zeros((0, arr.shape[1])))
    return out


def export_local_random_effects(re_coeffs, re_buckets, mesh: Mesh,
                                projections=None, norm=None,
                                intercept_index=None) -> Dict[int, np.ndarray]:
    """THIS host's entities' coefficient vectors from globally-sharded lane
    arrays — each host publishes its own entity range (the reference writes
    the RandomEffectModel RDD partition-wise the same way).

    ``projections``: the padded host-local BucketProjection list from
    ``global_entity_buckets(..., projections=...)`` — compact lanes
    back-project through THIS host's observed-column maps to full
    vocabulary width before export.

    ``norm``/``intercept_index``: the coordinate's shared
    NormalizationContext — solver-space lanes map to ORIGINAL-space
    coefficients per lane (NormalizationContext.scala:73-99), like the
    single-process publish path."""
    n_proc = jax.process_count()
    pid = jax.process_index()
    out: Dict[int, np.ndarray] = {}
    blocks = host_lane_blocks(re_coeffs)
    for bi, (arr, block) in enumerate(zip(re_coeffs, blocks)):
        if norm is not None and not norm.is_identity:
            if norm.shifts is not None and intercept_index is None:
                raise ValueError("shift normalization needs "
                                 "intercept_index to publish")
            # the ONE definition of the coefficient-space map
            # (NormalizationContext.scala:73-99), vmapped over lanes
            block = np.asarray(jax.vmap(
                lambda r: norm.model_to_original_space(r, intercept_index)
            )(jnp.asarray(block))).astype(block.dtype)
        if projections is not None:
            block = projections[bi].back_project(block)
        per_host = arr.shape[0] // n_proc
        base = pid * per_host
        for eid, (ebi, lane) in re_buckets.lane_of.items():
            if ebi == bi:
                out[eid] = block[lane - base]
    return out
