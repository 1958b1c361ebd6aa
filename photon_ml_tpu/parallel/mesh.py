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
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.core.batch import Batch, DenseBatch, SparseBatch

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
        import jax.numpy as jnp

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
    import jax.numpy as jnp

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
