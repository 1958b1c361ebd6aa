"""GAME dataset container.

Reference data model: GameDatum(response, offsetOpt, weightOpt,
featureShardContainer: Map[shard -> Vector], idTagToValueMap)
(photon-lib .../data/GameDatum.scala:39-74) held as
RDD[(UniqueSampleId, GameDatum)] after GameConverters (photon-api
.../data/GameConverters.scala:173).

TPU-native shape: one host-side columnar container for the WHOLE dataset —
labels/offsets/weights as flat arrays, one design matrix per feature shard,
and integer id columns per id-tag (entity ids already passed through a feature
index map / entity index).  Sample order IS the unique-sample-id space: row i
everywhere refers to the same example, which replaces the reference's
uniqueId-keyed joins with positional alignment.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Union

import numpy as np


@dataclasses.dataclass
class SparseShard:
    """Row-padded COO design "matrix" for wide sparse vocabularies.

    The reference streams Breeze SparseVectors per datum; here the whole
    shard is two [n, k] arrays (k = max active features per row + intercept)
    matching core/batch.SparseBatch's layout, so a 1e6-feature CTR shard
    costs O(n*k), not O(n*d).  Padded slots carry (index 0, value 0) —
    inert in margins and gradients.  Duplicate indices within a row are
    tolerated (they accumulate in margins/gradients, like repeated (name,
    term) entries accumulate in the dense path) but make SIMPLE-variance
    Hessian diagonals approximate.
    """

    indices: np.ndarray  # [n, k] int32 column ids
    values: np.ndarray   # [n, k] float
    dim: int             # vocabulary size (d)

    @property
    def shape(self):
        # mimics a dense [n, d] matrix so shard_dim / row checks just work
        return (self.indices.shape[0], self.dim)


ShardData = Union[np.ndarray, SparseShard]


@dataclasses.dataclass
class GameData:
    """Columnar GAME dataset (training or validation)."""

    y: np.ndarray  # [n]
    #: shard id -> [n, d] dense matrix or SparseShard.  A dense design handed
    #: over in ROW SHARDS (a ``jax.Array`` over more than one device) may
    #: have more rows than ``n``: a row count that does not divide by the
    #: devices cannot be sharded, so the rows behind ``n`` are padding.  Only
    #: a fixed effect under that mesh takes such a shard, and holds it to
    #: ``parallel/mesh.padded_samples(n, mesh)`` rows.
    features: Dict[str, "ShardData"]
    offset: Optional[np.ndarray] = None  # [n]
    weight: Optional[np.ndarray] = None  # [n]
    id_tags: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)  # tag -> [n] int64
    uids: Optional[np.ndarray] = None  # [n] original unique sample ids (object)
    #: tag -> stream.EntityStats accumulated during streaming ingest; lets
    #: random-effect coordinates reuse the per-entity grouping computed
    #: chunk-by-chunk instead of re-scanning the id column.  None on the
    #: eager path (coordinates fall back to bucketing._group_rows).
    entity_stats: Optional[Dict[str, object]] = None

    def __post_init__(self):
        n = len(self.y)
        self.y = np.asarray(self.y)
        if self.offset is None:
            self.offset = np.zeros(n, self.y.dtype if self.y.dtype.kind == "f" else np.float32)
        if self.weight is None:
            self.weight = np.ones(n, self.offset.dtype)
        self.offset = np.asarray(self.offset)
        self.weight = np.asarray(self.weight)
        for shard, x in self.features.items():
            in_row_shards = (x.shape[0] > n and len(getattr(
                getattr(x, "sharding", None), "device_set", ())) > 1)
            if x.shape[0] != n and not in_row_shards:
                raise ValueError(f"feature shard {shard!r} has {x.shape[0]} rows, expected {n}")
        if self.uids is not None and len(self.uids) != n:
            raise ValueError(f"uids has {len(self.uids)} rows, expected {n}")
        for tag, ids in self.id_tags.items():
            if len(ids) != n:
                raise ValueError(f"id tag {tag!r} has {len(ids)} rows, expected {n}")
            self.id_tags[tag] = np.asarray(ids, np.int64)

    @property
    def num_samples(self) -> int:
        return len(self.y)

    def shard_dim(self, shard: str) -> int:
        return self.features[shard].shape[1]
