"""Device mesh + sharding helpers.

Replaces the reference's Spark communication substrate (SURVEY.md §2.7):
  - broadcast of coefficients per evaluation (DistributedObjectiveFunction.scala:61)
      -> weights live REPLICATED in HBM; nothing is re-shipped per step.
  - treeAggregate gradient reductions (ValueAndGradientAggregator.scala:248)
      -> XLA all-reduce over the ``data`` mesh axis, inserted by GSPMD when the
         batch is sharded on ``data`` and outputs are replicated.  ICI
         all-reduce is already tree/torus-optimal, so the reference's
         ``treeAggregateDepth`` knob has no analog.
  - shuffle/groupBy for per-entity data (RandomEffectDataset.scala:302-341)
      -> one-time host-side bucketing (parallel/bucketing.py) + ``entity``-axis
         sharding.

Mesh axes:
  - ``data``    : examples of the fixed-effect batch (DP)
  - ``entity``  : independent random-effect problems (the reference's
                  "per-entity model parallelism", RandomEffectCoordinate.scala:109-127)
  - ``feature`` : model/feature-axis sharding for huge-d fixed effects — the
                  TPU counterpart of the reference's feature-axis scaling story
                  (PalDB 1e8-feature index maps + treeAggregateDepth keeping
                  driver merge memory flat, SURVEY.md §5): w and the per-feature
                  gradient partial sums are sharded so no single device holds
                  the full coefficient vector, and the feature-axis reduction of
                  margins rides ICI (GSPMD inserts the psum from the shardings).
Multi-host later slices these over DCN by constructing the mesh from
``jax.devices()`` spanning hosts; the code below is agnostic.

What crosses chips inside a fit is written down HERE, not left to the
partitioner (the second half of this file): a random effect's update moves
its residuals from sample order into entity lanes (``lanes_of``),
its lanes into the replicated coefficient table (``stack_lanes``) and its
entity-major scores back to sample order (``score_entity_major``): the
reference's shuffle by entity (RandomEffectCoordinate.scala:104-231) as
three exchanges of ``[n]`` vectors and tables.  No design array crosses a
chip.  Each runs under ``device_scope("exchange", <kind>)``, and
``exchange_bytes`` says what a chip sends for each.

How the scores come back, by what the layout observed
(``EntityMajorLayout.back``): ``identity`` (the chunks are the sample order
shard for shard): nothing crosses, no ``scores`` exchange.  ``unpad`` (rows
grouped by entity): ONE all-gather, then each chip cuts the one contiguous
range that holds its samples out of the whole vector and compacts it, data
movement with no index a sample.  ``gather`` (rows that lie anywhere): ONE
all-gather, then each chip gathers its samples' positions, one index each,
out of the whole vector.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.core.batch import Batch, DenseBatch, SparseBatch
from photon_ml_tpu.obs.trace import device_scope

DATA_AXIS = "data"
ENTITY_AXIS = "entity"
FEATURE_AXIS = "feature"
SHARD_AXIS = "shard"  # serving-side coefficient-table entity partition


def make_mesh(n_data: Optional[int] = None, n_entity: int = 1, n_feature: int = 1,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Create a (data, entity, feature) mesh over the available devices.

    Default: all devices on the data axis.  A single-device mesh is valid and
    produces the exact same program (collectives become no-ops), so every code
    path is mesh-agnostic — the chip-count-invariance property the tests rely
    on (SURVEY.md §4).
    """
    devices = list(devices if devices is not None else jax.devices())
    if n_data is None:
        n_data = len(devices) // (n_entity * n_feature)
    need = n_data * n_entity * n_feature
    if need > len(devices):
        raise ValueError(
            f"mesh {n_data}x{n_entity}x{n_feature} needs {need} devices, have {len(devices)}")
    arr = np.asarray(devices[:need]).reshape(n_data, n_entity, n_feature)
    return Mesh(arr, (DATA_AXIS, ENTITY_AXIS, FEATURE_AXIS))


def serving_mesh(n_shards: int,
                 devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """A 1-axis ``(shard,)`` mesh over the first ``n_shards`` devices — the
    serving-side coefficient-table partition (serving/coefficient_store.py
    slices each random-effect table's entity axis over it; the engine's AOT
    kernels psum shard-local margins across it).  Kept separate from
    ``make_mesh``'s training axes: serving never shards data or features,
    only the entity rows of the hot tables."""
    devices = list(devices if devices is not None else jax.devices())
    if n_shards < 1:
        raise ValueError(f"serving mesh needs n_shards >= 1, got {n_shards}")
    if n_shards > len(devices):
        raise ValueError(
            f"serving mesh over {n_shards} shards needs {n_shards} devices, "
            f"have {len(devices)}")
    return Mesh(np.asarray(devices[:n_shards]), (SHARD_AXIS,))


def replicate(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _pad_axis(a, target: int, axis: int):
    """Zero-pad ``axis`` up to ``target`` where the array lives: a host array
    pads on the host, a device array on its devices (no host round trip)."""
    pad = target - a.shape[axis]
    if pad < 0:
        raise ValueError(
            f"array has {a.shape[axis]} entries on axis {axis} > target {target}")
    if pad == 0:
        return a
    widths = [(0, pad if i == axis else 0) for i in range(a.ndim)]
    if isinstance(a, jax.Array):
        return jnp.pad(a, widths)
    return np.pad(np.asarray(a), widths)


def padded_dim(d: int, mesh: Mesh, axis: str = FEATURE_AXIS) -> int:
    """Feature count padded up to a multiple of the feature-axis size."""
    size = mesh.shape[axis]
    return ((d + size - 1) // size) * size


def shard_coefficients(w, mesh: Mesh, axis: str = FEATURE_AXIS):
    """Place a coefficient vector sharded over the feature axis (zero-padded).

    Padded slots see only zero feature columns, so their gradient is exactly
    the regularization term at w=0, which is 0 — they stay 0 through any solve.

    Device arrays stay on device (pad + reshard, no host round-trip) so
    warm-starting from a previous sweep's sharded w never all-gathers the
    full vector to the host.
    """
    sharding = NamedSharding(mesh, P(axis))
    if jax.process_count() > 1 and getattr(w, "is_fully_addressable", True):
        # multihost: any PROCESS-LOCAL input (host numpy or a
        # fully-addressable jax.Array — e.g. the coordinate's jnp.zeros
        # cold start) becomes a GLOBAL sharded array via the per-shard
        # callback (device_put of process-local data to a multi-process
        # sharding is not portable).  Every host passes the same w, and the
        # feature axis lives within each process (multihost.global_mesh),
        # so each callback index is addressable.  An already-global array
        # (is_fully_addressable False) takes the reshard path below.
        w_np = np.asarray(w)
        pad = padded_dim(w_np.shape[0], mesh, axis) - w_np.shape[0]
        if pad:
            w_np = np.concatenate([w_np, np.zeros(pad, w_np.dtype)])
        return jax.make_array_from_callback(
            w_np.shape, sharding, lambda idx: w_np[idx])
    w = jnp.asarray(w)
    pad = padded_dim(w.shape[0], mesh, axis) - w.shape[0]
    if pad:
        w = jnp.pad(w, (0, pad))
    return jax.device_put(w, sharding)


def shard_batch(batch: Batch, mesh: Mesh, axis: str = DATA_AXIS,
                feature_axis: Optional[str] = None,
                pad_to: Optional[int] = None) -> Batch:
    """Place a batch with its example dimension sharded over ``axis``.

    Pads the example count up to a multiple of the axis size (at least
    ``pad_to`` rows when given) with weight-0 rows (inert by the core masking
    contract), then device_puts each leaf with a NamedSharding.  This is the
    one-time data layout step that replaces the reference's per-step
    broadcast + shuffle choreography.  Host leaves are padded on the host and
    each shard is transferred to its own device; device leaves are padded and
    resharded device to device.  No device ever receives the whole batch.

    ``feature_axis``: additionally shard the feature dimension of a dense
    design matrix (zero-padding d up to a multiple of the axis size) so the
    margin matmul contracts over a sharded axis — GSPMD turns the row of
    per-shard partial margins into one psum over ``feature_axis``.  Sparse
    batches address w by global index and are deliberately left unsharded on
    features (their w stays replicated; see parallel/fixed.py).
    """
    size = mesh.shape[axis]
    n = max(batch.num_examples, pad_to or 0)
    target = ((n + size - 1) // size) * size

    def place(a, spec):
        return jax.device_put(_pad_axis(a, target, 0),
                              NamedSharding(mesh, spec))

    row = P(axis)
    vectors = dict(y=place(batch.y, row), offset=place(batch.offset, row),
                   weight=place(batch.weight, row))
    if isinstance(batch, DenseBatch):
        x = batch.x
        if feature_axis is not None:
            x = _pad_axis(x, padded_dim(x.shape[1], mesh, feature_axis), 1)
        return DenseBatch(x=place(x, P(axis, feature_axis)), **vectors)
    if isinstance(batch, SparseBatch):
        return SparseBatch(indices=place(batch.indices, P(axis, None)),
                           values=place(batch.values, P(axis, None)),
                           dim=batch.dim, **vectors)
    raise TypeError(f"unknown batch type {type(batch)!r}")


# -- samples, chunks and lanes over every chip; what crosses them -------------
# A coordinate's sample axis (its full-sample arrays, the sweep's [n]
# vectors), the chunk axis of an entity-major design and the lane axis of
# every capacity class are sharded over ALL the mesh's devices.  On
# ``make_mesh``'s default mesh (all on ``data``) that is the fixed design's
# row sharding too.


def spans_chips(mesh: Optional[Mesh]) -> Optional[Mesh]:
    """``mesh`` where it holds more than one device, else None: a
    one-device mesh gives the one-chip program, with no collective and
    nothing that stands in for absent chips."""
    return mesh if mesh is not None and mesh.size > 1 else None


SAMPLE_TILE = 1024  # a 1-D float32 array's (8, 128) tile on the chip


def padded_samples(n: int, mesh: Optional[Mesh]) -> int:
    """The sample axis under ``mesh``: ``n`` up to the next multiple of
    ``SAMPLE_TILE`` a device (the rows behind ``n`` are padding: weight 0,
    no entity).  Whole tiles a device, not just a multiple of the devices:
    an all-gather of shards that end inside a tile is no all-gather on the
    chip (the TPU compiler makes it an all-reduce of a zero-padded vector,
    twice the bytes)."""
    if mesh is None:
        return n
    granule = mesh.size * SAMPLE_TILE
    return -(-n // granule) * granule


def over_chips(mesh: Mesh, ndim: int = 1, axis: int = 0) -> P:
    """``axis`` of an ``ndim``-array over every device of ``mesh``."""
    spec = [None] * ndim
    spec[axis] = tuple(mesh.axis_names)
    return P(*spec)


def put_over_chips(a, mesh: Mesh, axis: int = 0, length: Optional[int] = None,
                   fill=0) -> jax.Array:
    """``a`` with ``axis`` padded to ``length`` (``fill``; default:
    ``padded_samples``) and sharded over every device: a host
    array goes shard by shard straight to its chips, a device array is
    padded and resharded where it is.  Nothing is staged whole on one
    device."""
    length = padded_samples(a.shape[axis], mesh) if length is None else length
    widths = [(0, length - a.shape[axis] if i == axis else 0)
              for i in range(a.ndim)]
    if any(w for _, w in widths):
        pad = jnp.pad if isinstance(a, jax.Array) else np.pad
        a = pad(a, widths, constant_values=fill)
    return jax.device_put(a, NamedSharding(mesh, over_chips(mesh, a.ndim,
                                                            axis)))


def samples_on_device(v, mesh: Optional[Mesh], dtype) -> jax.Array:
    """A host ``[n]`` vector as the traceable steps carry it: on the
    default device, or under a mesh ``padded_samples`` long, shard by
    shard to its chips."""
    v = np.asarray(v, dtype)
    return jnp.asarray(v) if mesh is None else put_over_chips(v, mesh)


def on_chips(fn: Callable, mesh: Mesh, in_specs, out_specs) -> Callable:
    """``fn`` as each chip's own program over its shards (``shard_map``).
    ``check_vma`` off as in ``ShardMapObjective``: the bodies hold Mosaic
    kernels and loops the check cannot type; every replicated output is an
    explicit collective's."""
    from jax import shard_map

    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def lanes_of(offsets: jax.Array, classes: Sequence[Dict[str, jax.Array]],
             mesh: Mesh) -> tuple:
    """Exchange ``offsets``: ``bucketing.offsets_into_lanes`` for each
    capacity class, the sample-sharded ``[n]`` offsets meeting
    entity-sharded lanes (``classes``: that function's ``rows``, ``valid``
    and, where the class has run or window lanes, ``run_start`` and
    ``windows`` of each, their lanes over every chip).  ONE all-gather makes the vector whole on every chip,
    then each chip gathers its own lanes' rows out of it, class by class.
    One program a chip for both halves:
    handed from one ``shard_map`` to the next, the TPU compiler turned the
    all-gather into an all-reduce of a zero-padded vector, twice the
    bytes."""
    from photon_ml_tpu.parallel.bucketing import offsets_into_lanes

    axes = tuple(mesh.axis_names)

    def local(v, classes):
        with device_scope("exchange", "offsets"):
            whole = jax.lax.all_gather(v, axes, tiled=True)
            with device_scope("entity_gather"):
                return tuple(offsets_into_lanes(whole, **c) for c in classes)

    classes = tuple(classes)
    by_lane = jax.tree.map(lambda a: over_chips(mesh, a.ndim), classes)
    return on_chips(local, mesh, (over_chips(mesh), by_lane),
                    over_chips(mesh, 2))(offsets, classes)


def stack_lanes(lane_ws: Sequence[jax.Array], slot_idx: Sequence[jax.Array],
                num_entities: int, mesh: Mesh) -> jax.Array:
    """Exchange ``publish``: ``bucketing.stack_bucket_lanes`` with the lanes
    on their chips.  Each chip scatters its own lanes into a table of
    zeros, ONE psum makes the table whole on every chip (an entity has one
    lane, so every row is one chip's value plus zeros: exact)."""
    from photon_ml_tpu.parallel.bucketing import stack_bucket_lanes

    axes = tuple(mesh.axis_names)

    def local(lws, idxs):
        return jax.lax.psum(stack_bucket_lanes(lws, idxs, num_entities), axes)

    lanes = P(axes)
    with device_scope("exchange", "publish"):
        return on_chips(local, mesh, (lanes, lanes), P())(
            tuple(lane_ws), tuple(slot_idx))


def score_entity_major(w_stack: jax.Array, lane_slot: jax.Array,
                       x_em: jax.Array, way_back, mesh: Mesh) -> jax.Array:
    """``bucketing.score_samples_em`` with the chunk rows on their chips,
    and exchange ``scores``: each chip scores its own rows of chunks from
    the replicated table, ONE all-gather makes the entity-major scores
    whole on every chip, and each chip takes its own samples out of them by
    the layout's way back (``bucketing.to_sample_order``; ``way_back`` with
    its sample axis over the chips): un-padded out of the ONE range of the
    whole vector in which the chip's samples lie where the rows arrive
    grouped by entity (``EntityMajorLayout.way_back``: a ``dynamic_slice`` at
    the chip's own start, then copies), else gathered at one index a
    sample.  ``way_back`` None: the chunks ARE the sample order, shard for
    shard, and nothing crosses."""
    from photon_ml_tpu.parallel.bucketing import (score_samples_em,
                                                  to_sample_order)

    axes = tuple(mesh.axis_names)

    def local(w, slots, x, back=None):
        acc = score_samples_em(w, slots, x)
        if back is None:
            return acc
        with device_scope("exchange", "scores"):
            return to_sample_order(
                jax.lax.all_gather(acc, axes, tiled=True), back)

    specs = (P(), over_chips(mesh, 2, 1), over_chips(mesh, 3, 1))
    if way_back is None:
        return on_chips(local, mesh, specs, over_chips(mesh))(
            w_stack, lane_slot, x_em)
    by_sample = jax.tree.map(lambda a: over_chips(mesh, a.ndim, a.ndim - 1),
                             way_back)
    return on_chips(local, mesh, specs + (by_sample,), over_chips(mesh))(
        w_stack, lane_slot, x_em, way_back)


def score_in_sample_order(score: Callable, w_stack: jax.Array, mesh: Mesh,
                          by_sample: Sequence[jax.Array],
                          sample_axis: int = 0) -> jax.Array:
    """A sample-order layout's scoring, ``score(w_stack, *by_sample)``, on
    each chip's own samples against the replicated table: nothing crosses.
    ``by_sample``: the slot vector ``[n]``, then the design, its samples on
    ``sample_axis``."""
    slots, *design = by_sample
    specs = (over_chips(mesh),) + tuple(
        over_chips(mesh, a.ndim, sample_axis) for a in design)
    return on_chips(score, mesh, (P(),) + specs, over_chips(mesh))(
        w_stack, slots, *design)


def exchange_bytes(mesh: Mesh, gathered: Dict[str, int],
                   summed: Dict[str, int]) -> Dict[str, int]:
    """{kind: bytes a chip sends}, from shapes alone.  ``gathered``: the
    bytes of each all-gathered array (a chip sends its shard to every other
    chip: (chips - 1) / chips of it); ``summed``: the bytes of each psum'd
    one (a ring all-reduce sends 2 (chips - 1) / chips of it)."""
    chips = mesh.size
    out = {k: (chips - 1) * b // chips for k, b in gathered.items()}
    out.update({k: 2 * (chips - 1) * b // chips for k, b in summed.items()})
    return out
