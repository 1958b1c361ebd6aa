"""GAME model containers: fixed-effect, random-effect, and the composite model.

Reference: photon-api .../model/FixedEffectModel.scala:146 (Broadcast[GLM] +
feature shard), RandomEffectModel.scala:304 (RDD[(REId, GLM)] + REType +
shard, score via join by REId), photon-lib .../model/GameModel.scala:32-110
(Map[CoordinateId -> DatumScoringModel], score = sum of coordinate scores).

TPU-native shape: the random-effect "RDD of models" is a dense stacked matrix
W[num_entities, d] plus a host-side entity-id -> row map; scoring any sample
set is a gather + row-wise dot (parallel/bucketing.score_samples).  Missing
entities score 0, matching the reference.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from typing import TYPE_CHECKING

if TYPE_CHECKING:  # annotation-only: avoids models<->game import cycle
    from photon_ml_tpu.game.data import GameData

from photon_ml_tpu.models.glm import Coefficients, GLMModel
from photon_ml_tpu.parallel.bucketing import score_samples
from photon_ml_tpu.types import TaskType

Array = jax.Array


class DatumScoringModel:
    """Contract: score a GameData (reference DatumScoringModel.scala)."""

    def score(self, data: GameData) -> Array:
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class FixedEffectModel(DatumScoringModel):
    """Global GLM over one feature shard (reference FixedEffectModel.scala:146).

    No Broadcast wrapper: under SPMD the coefficient vector is a replicated
    array; nothing is shipped per evaluation.
    """

    coefficients: Coefficients
    feature_shard: str
    task: TaskType = TaskType.LOGISTIC_REGRESSION

    def score(self, data: GameData) -> Array:
        shard = data.features[self.feature_shard]
        if hasattr(shard, "indices"):  # SparseShard: gather-based margins
            w = jnp.asarray(self.coefficients.means)
            vals = jnp.asarray(shard.values)
            return jnp.einsum("nk,nk->n", vals, w[jnp.asarray(shard.indices)])
        return self.coefficients.score(shard)

    def glm(self) -> GLMModel:
        return GLMModel(coefficients=self.coefficients, task=self.task)


@dataclasses.dataclass(frozen=True)
class RandomEffectModel(DatumScoringModel):
    """Per-entity GLMs as a stacked coefficient matrix
    (reference RandomEffectModel.scala:304).

    ``w_stack[slot_of[entity_id]]`` is that entity's coefficient vector;
    samples whose entity has no model score 0 (reference convention).
    ``variances`` optional, aligned with w_stack rows.

    Scale note: the stack is DENSE [num_entities, d] — the right layout for
    device gather-scoring and the modest per-entity bags the reference's
    GLMix deployments use.  For wide vocabularies, ``to_compact()`` yields
    the sparse twin (CompactRandomEffectModel below: memory ∝ observed
    columns, like the reference's per-REId sparse vectors), matching the
    training path, which never densifies (bucket_by_entity_sparse).
    On-disk NTV storage is already sparse (nonzero means only,
    storage/model_io.py); the compact container also saves natively sparse
    in the columnar format."""

    w_stack: np.ndarray  # [num_entities, d]
    slot_of: Dict[int, int]
    random_effect_type: str  # the id-tag column name
    feature_shard: str
    task: TaskType = TaskType.LOGISTIC_REGRESSION
    variances: Optional[np.ndarray] = None

    @property
    def num_entities(self) -> int:
        return self.w_stack.shape[0]

    def slots_for(self, data: GameData) -> np.ndarray:
        return _entity_slots(self, data)

    def score(self, data: GameData) -> Array:
        shard = data.features[self.feature_shard]
        slots = jnp.asarray(self.slots_for(data))
        # the stack uploads ONCE per instance (repeat scoring of one model
        # used to re-transfer the full [E, d] stack every call)
        (w_dev,) = _cached_device_copies(self, self.w_stack)
        if hasattr(shard, "indices"):
            # row-sparse shard: O(n*k) two-level gather, never [n, d_full]
            from photon_ml_tpu.parallel.bucketing import score_samples_sparse

            return score_samples_sparse(
                w_dev, slots,
                jnp.asarray(np.asarray(shard.indices)),
                jnp.asarray(np.asarray(shard.values, self.w_stack.dtype)))
        x = jnp.asarray(shard)
        return score_samples(w_dev, slots, x)

    def coefficients_for(self, entity_id: int) -> Optional[Coefficients]:
        slot = self.slot_of.get(int(entity_id))
        if slot is None:
            return None
        var = self.variances[slot] if self.variances is not None else None
        return Coefficients(means=self.w_stack[slot], variances=var)

    def to_compact(self, k: Optional[int] = None) -> "CompactRandomEffectModel":
        """Sparse per-entity container: O(entities x observed columns)
        instead of O(entities x vocabulary) — the published-model twin of
        the training path's bucket_by_entity_sparse (see the scale note
        above).  ``k``: per-entity coefficient capacity (default: the max
        nonzero count across entities; an explicit k BELOW that is an error
        — truncation would silently change scores — while a roomier k just
        pads).  Models carrying coefficient VARIANCES refuse: the variance
        rows are dense on a different support (prior-only fill lives at
        zero-coefficient columns), so compacting on the coefficient pattern
        would silently drop them."""
        if self.variances is not None:
            raise ValueError(
                "to_compact would silently drop coefficient variances "
                "(their support differs from the coefficients' — prior-only "
                "variances live at zero-coefficient columns); keep the "
                "dense model, or compact a variance-free copy deliberately")
        w = np.asarray(self.w_stack)
        e, d = w.shape
        # O(nnz) build: np.nonzero walks row-major, so cols arrive grouped
        # by row in ascending column order (searchsorted-ready) — no
        # full-width [e, d] argsort/int64 transient (which would dwarf the
        # stack itself at the wide-vocabulary scale this container targets)
        rows, cols = np.nonzero(w)
        counts = np.bincount(rows, minlength=e)
        k_need = int(counts.max()) if e else 0
        if k is None:
            k = max(1, k_need)
        elif k < k_need:
            raise ValueError(
                f"capacity k={k} < densest entity's {k_need} nonzero "
                "coefficients — truncation would silently change scores")
        offsets = np.zeros(e + 1, np.int64)
        np.cumsum(counts, out=offsets[1:])
        pos = np.arange(len(rows)) - offsets[rows]  # position within row
        idx = np.full((e, k), d, np.int32)
        val = np.zeros((e, k), w.dtype)
        idx[rows, pos] = cols
        val[rows, pos] = w[rows, cols]
        return CompactRandomEffectModel(
            indices=idx, values=val, dim=d, slot_of=dict(self.slot_of),
            random_effect_type=self.random_effect_type,
            feature_shard=self.feature_shard, task=self.task)


def _entity_slots(model, data: "GameData") -> np.ndarray:
    from photon_ml_tpu.game.coordinate import _slots_from

    return _slots_from(model.slot_of, data.id_tags[model.random_effect_type])


def score_compact_dense(w_idx: Array, w_val: Array, slots: Array,
                        x: Array) -> Array:
    """Σ_t values[e,t] * x[i, indices[e,t]] — gather the DENSE design at
    each entity's observed columns (never materializing [E, d]).  Plain
    traceable math: the model wrapper below jits it, and the serving
    engine's AOT kernels (serving/engine.py) inline it so batch and online
    compact scoring share ONE definition."""
    e = jnp.where(slots >= 0, slots, 0)
    idx = w_idx[e]  # [n, k]
    xv = jnp.take_along_axis(x, jnp.clip(idx, 0, x.shape[1] - 1), axis=1)
    s = jnp.sum(w_val[e] * jnp.where(idx < x.shape[1], xv, 0.0), axis=1)
    return jnp.where(slots >= 0, s, 0.0)


def score_compact_sparse_xla(w_idx: Array, w_val: Array, slots: Array,
                             f_idx: Array, f_val: Array) -> Array:
    """Sparse-features x sparse-model margins: binary-search each sample
    feature id into its entity's sorted coefficient columns (miss -> 0).
    Plain traceable math (see score_compact_dense), and the reference the
    pallas match-dot is checked against."""
    e = jnp.where(slots >= 0, slots, 0)
    rows_idx = w_idx[e]  # [n, k_model] sorted, padded with dim
    rows_val = w_val[e]
    pos = jax.vmap(jnp.searchsorted)(rows_idx, f_idx)  # [n, k_feat]
    pos_c = jnp.clip(pos, 0, rows_idx.shape[1] - 1)
    hit = jnp.take_along_axis(rows_idx, pos_c, axis=1) == f_idx
    wv = jnp.where(hit, jnp.take_along_axis(rows_val, pos_c, axis=1), 0.0)
    s = jnp.sum(f_val * wv, axis=1)
    return jnp.where(slots >= 0, s, 0.0)


def score_compact_sparse(w_idx: Array, w_val: Array, slots: Array,
                         f_idx: Array, f_val: Array) -> Array:
    """``score_compact_sparse_xla``'s margins; on TPU, shapes inside the
    kernel's gate take the pallas match-dot instead (ops/compact_score.py —
    same math, one VMEM pass)."""
    from photon_ml_tpu.ops import compact_score

    if compact_score.eligible(w_idx.shape[1], f_idx.shape[1],
                              w_val.dtype.itemsize):
        return compact_score.score_sparse_compact(w_idx, w_val, slots,
                                                  f_idx, f_val)
    return score_compact_sparse_xla(w_idx, w_val, slots, f_idx, f_val)


_score_dense_compact = jax.jit(score_compact_dense)
_score_sparse_compact = jax.jit(score_compact_sparse)


def _cached_device_copies(model, *arrays) -> tuple:
    """Per-instance device copies of host coefficient arrays, uploaded ONCE.

    Scoring previously re-ran ``jnp.asarray`` on the full stacks every
    call — a full host->device upload per batch on accelerator backends.
    The cache is keyed by the host arrays' identities, so the functional
    mutation idiom (``dataclasses.replace`` with new arrays — the only
    mutation these frozen containers support) naturally invalidates it:
    a replaced instance starts with no cache, and rebinding an array in
    place (object.__setattr__) changes the identity key."""
    cache = getattr(model, "_dev_cache", None)
    if cache is not None and len(cache[0]) == len(arrays) and all(
            c is a for c, a in zip(cache[0], arrays)):
        return cache[1]
    dev = tuple(jnp.asarray(a) for a in arrays)
    object.__setattr__(model, "_dev_cache", (arrays, dev))
    return dev


@dataclasses.dataclass(frozen=True)
class CompactRandomEffectModel(DatumScoringModel):
    """Per-entity GLMs as SPARSE coefficient rows — the wide-vocabulary
    published container (reference RandomEffectModel.scala:304 holds
    per-REId GLMs whose coefficient vectors are sparse Breeze vectors; the
    dense ``RandomEffectModel`` is the right layout for modest bags, this
    one decouples entity count from vocabulary width).

    ``indices[slot]`` are that entity's observed column ids, ascending,
    padded with ``dim`` (out of range — inert everywhere); ``values`` align,
    padded with 0.  Scoring never builds an [E, d] stack: dense shards
    gather x at the entity's observed columns, sparse shards binary-search
    each sample feature into the entity's sorted columns.  Missing entities
    score 0 (reference convention)."""

    indices: np.ndarray  # [num_entities, k] int32, sorted, dim-padded
    values: np.ndarray   # [num_entities, k]
    dim: int
    slot_of: Dict[int, int]
    random_effect_type: str
    feature_shard: str
    task: TaskType = TaskType.LOGISTIC_REGRESSION

    @property
    def num_entities(self) -> int:
        return self.indices.shape[0]

    def slots_for(self, data: GameData) -> np.ndarray:
        return _entity_slots(self, data)

    def score(self, data: GameData) -> Array:
        shard = data.features[self.feature_shard]
        if shard.shape[1] != self.dim:
            # loud, like the dense twin's einsum shape error — the padding
            # mask in the scoring kernels would otherwise silently zero
            # real coefficients on a mis-bound shard
            raise ValueError(
                f"shard {self.feature_shard!r} has {shard.shape[1]} "
                f"features but this model was trained on {self.dim}")
        slots = jnp.asarray(self.slots_for(data))
        # one upload per instance, not per call (the satellite fix: every
        # score() used to re-run jnp.asarray on the full indices/values)
        w_idx, w_val = _cached_device_copies(self, self.indices, self.values)
        if hasattr(shard, "indices"):
            return _score_sparse_compact(
                w_idx, w_val, slots,
                jnp.asarray(np.asarray(shard.indices, np.int32)),
                jnp.asarray(np.asarray(shard.values, self.values.dtype)))
        return _score_dense_compact(w_idx, w_val, slots,
                                    jnp.asarray(shard, self.values.dtype))

    def to_dense(self) -> RandomEffectModel:
        e, k = self.indices.shape
        w = np.zeros((e, self.dim), self.values.dtype)
        rows = np.repeat(np.arange(e), k)
        idx = self.indices.reshape(-1)
        keep = idx < self.dim
        w[rows[keep], idx[keep]] = self.values.reshape(-1)[keep]
        return RandomEffectModel(
            w_stack=w, slot_of=dict(self.slot_of),
            random_effect_type=self.random_effect_type,
            feature_shard=self.feature_shard, task=self.task)


@dataclasses.dataclass
class GameModel:
    """Composite model: coordinate id -> scoring model
    (reference GameModel.scala:32-110)."""

    models: Dict[str, DatumScoringModel]

    def score(self, data: GameData) -> Array:
        """Sum of coordinate raw scores (GameModel.score:99-110) via the
        shared composition (game/scoring.additive_total — the same function
        the online serving kernels use, so batch and serving cannot drift)."""
        from photon_ml_tpu.game.scoring import additive_total

        return additive_total(data.num_samples,
                              (m.score(data) for m in self.models.values()))

    def predict(self, data: GameData, task: TaskType) -> Array:
        from photon_ml_tpu.core.losses import loss_for_task

        z = self.score(data) + jnp.asarray(data.offset)
        return loss_for_task(task).mean(z)

    def updated(self, coordinate_id: str, model: DatumScoringModel) -> "GameModel":
        out = dict(self.models)
        out[coordinate_id] = model
        return GameModel(models=out)

    def __getitem__(self, cid: str) -> DatumScoringModel:
        return self.models[cid]

    def __contains__(self, cid: str) -> bool:
        return cid in self.models
