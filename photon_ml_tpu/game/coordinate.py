"""Training coordinates: the per-coordinate update/score contract.

Reference: photon-lib .../algorithm/Coordinate.scala:28-81 (updateModel folds
residual scores into offsets then optimizes; score produces this coordinate's
contribution), photon-api .../algorithm/FixedEffectCoordinate.scala:35-166 and
RandomEffectCoordinate.scala:39-232.

TPU-native shape:
- Data is laid out on device ONCE at coordinate construction (the reference
  re-broadcasts/joins per update).  Updates re-enter the same jitted solver
  with new residual offsets — same shapes, zero recompilation.
- The fixed effect solves over the ``data``-sharded batch (GSPMD all-reduce).
- The random effect solves all entities at once: vmapped solver over padded
  entity buckets (parallel/bucketing.py), replacing per-entity serial
  executor solves (RandomEffectCoordinate.scala:114-127).
- Scoring is total: every sample gets this coordinate's raw score (the
  reference's active+passive union), so residual bookkeeping in the descent
  loop is positionally aligned.
"""

from __future__ import annotations

import contextlib
import time as _time
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from photon_ml_tpu.core.batch import DenseBatch, SparseBatch
from photon_ml_tpu.core.losses import loss_for_task
from photon_ml_tpu.core.normalization import NormalizationContext, no_normalization
from photon_ml_tpu.core.objective import GLMObjective
from photon_ml_tpu.core.regularization import Regularization
from photon_ml_tpu.game.config import CoordinateConfig, FixedEffectConfig, RandomEffectConfig
from photon_ml_tpu.game.data import GameData, SparseShard
from photon_ml_tpu.models.game import DatumScoringModel, FixedEffectModel, RandomEffectModel
from photon_ml_tpu.models.glm import Coefficients
from photon_ml_tpu.obs import get_probe, get_registry, set_family_bounds
from photon_ml_tpu.obs.trace import device_scope
from photon_ml_tpu.obs.trace import span as obs_span
from photon_ml_tpu.opt.solve import line_search_kind, make_solver
from photon_ml_tpu.opt.types import SolverResult
from photon_ml_tpu.parallel.bucketing import (block_pairs, block_slots,
                                              bucket_by_entity,
                                              entity_major_pairs, lane_windows,
                                              offsets_into_lanes, pair_planes,
                                              pair_words, pick_block,
                                              sample_blocks, score_pairs_full,
                                              stacked_coefficients)
from photon_ml_tpu.parallel.mesh import (SAMPLE_TILE, exchange_bytes,
                                         lanes_of, on_chips, over_chips,
                                         padded_samples, put_over_chips,
                                         replicate, samples_on_device,
                                         score_entity_major,
                                         score_in_sample_order, shard_batch,
                                         spans_chips, stack_lanes)
from photon_ml_tpu.types import (OptimizerType, ProjectorType, TaskType,
                                 VarianceComputationType)
from photon_ml_tpu.utils.transfer import device_put_counted

Array = jax.Array

# Per-entity bucket solves live in ms..minutes, not the default 1µs..67s
# span ladder — register sane bins once at import (obs follow-on: per-family
# histogram bound overrides).  100µs .. ~7min, factor 2.
set_family_bounds("solve_bucket_seconds",
                  [1e-4 * (2.0 ** i) for i in range(23)])


def _share_lanes(rows: np.ndarray, shares: int, lo: int,
                 hi: Optional[int]) -> np.ndarray:
    """Lanes ``lo .. hi - 1`` of each of the ``shares`` equal shares of a
    class's ``rows`` [lanes, capacity], share after share."""
    return rows.reshape(shares, -1, rows.shape[1])[:, lo:hi].reshape(
        -1, rows.shape[1])


def _slots_from(slot_of: Dict[int, int], entity_ids: np.ndarray) -> np.ndarray:
    """Vectorized entity-id -> slot lookup (-1 for unknown ids)."""
    if not slot_of:
        return np.full(len(entity_ids), -1, np.int32)
    keys = np.fromiter(slot_of.keys(), np.int64, len(slot_of))
    vals = np.fromiter(slot_of.values(), np.int32, len(slot_of))
    order = np.argsort(keys)
    keys, vals = keys[order], vals[order]
    pos = np.searchsorted(keys, entity_ids)
    pos = np.clip(pos, 0, len(keys) - 1)
    hit = keys[pos] == entity_ids
    return np.where(hit, vals[pos], -1).astype(np.int32)


class Coordinate:
    """update/score contract (reference Coordinate.scala:28-81)."""

    coordinate_id: str
    _n: int

    @property
    def num_samples(self) -> int:
        return self._n

    @property
    def carry_samples(self) -> int:
        """Length of the ``[n]`` vectors the traceable steps exchange
        (``trace_update``'s offsets and scores): ``num_samples``, or under
        a mesh ``padded_samples`` of it (the sample axis is sharded in
        whole tiles a device; the rows behind ``num_samples`` are
        padding)."""
        return padded_samples(self._n, getattr(self, "mesh", None))

    def exchange_bytes(self) -> Dict[str, int]:
        """{exchange kind: bytes a chip sends in ONE update (``psum``: one
        objective evaluation)}, from the shapes and shardings of what
        crosses chips; empty without a mesh."""
        return {}

    def _base_offset_host(self) -> np.ndarray:
        """Dataset base offsets [n] (residual offsets are added on top)."""
        return self._base_offset

    def update(self, total_offsets: np.ndarray, seed: int,
               init: Optional[DatumScoringModel]) -> Tuple[DatumScoringModel, object]:
        """Train with residual-folded offsets; returns (model, tracker)."""
        raise NotImplementedError

    def score(self, model: DatumScoringModel) -> np.ndarray:
        """This coordinate's raw score for every training sample."""
        raise NotImplementedError

    # --- traceable-step interface (fully-jitted sweeps, game/fused.py) ---
    # The host-paced contract above crosses the device boundary per call; the
    # methods below keep the whole descent on device: ``state`` is a pytree of
    # device arrays carried through lax.scan.  Both built-in coordinate
    # flavors implement every configuration (down-sampling, variances,
    # projection all run in-program); a custom Coordinate subclass that only
    # implements the host-paced contract inherits these raising defaults, and
    # the estimator's fused="auto" then falls back to CoordinateDescent.

    @property
    def dtype(self):
        return self._dtype

    @property
    def num_solves(self) -> int:
        """Solves an update makes (rows of ``trace_update``'s
        ``iterations_out`` entry): one, or a random effect's capacity
        classes."""
        return 1

    # How the coordinate's solver evaluates a trial step of its line search
    # (opt/solve.line_search_kind): "margins", "passes", or "none" where it
    # has no strong-Wolfe search.  Set by _bind_solver.
    line_search = "none"

    def init_sweep_state(self, init: Optional[DatumScoringModel] = None):
        """Host: initial device state (cold or warm-started from a model)."""
        raise NotImplementedError

    def sweep_data(self):
        """Host: pytree of device arrays the traceable steps read (the design
        matrices).  The fused sweep passes it back through ``trace_*``'s
        ``data=`` so the big arrays enter the compiled program as ARGUMENTS —
        closed-over jax.Arrays lower to baked XLA constants and compile time
        grows linearly with constant bytes."""
        return None

    def trace_update(self, state, offsets: Array,
                     reg: "Optional[Regularization]" = None,
                     key=None, data=None,
                     iterations_out: Optional[list] = None
                     ) -> Tuple[object, Array]:
        """Traceable: one update against residual-folded ``offsets[n]``;
        returns (state', this coordinate's new score[n]).  ``reg`` (possibly
        traced) overrides the config's regularization weights so one compiled
        sweep serves a whole reg grid.  ``key``: per-(iteration, coordinate)
        PRNG key the fused sweep folds for stochastic per-update work
        (down-sampling); coordinates without such work ignore it.  ``data``:
        this coordinate's ``sweep_data()`` passed back as traced arguments
        (None = read the coordinate's own device arrays, the host-paced
        path).  ``iterations_out``: a list the update appends ONE int32
        array [solves, 4] to (``_solve_counts``): for each of its solves (a
        fixed effect has one, a random effect one per capacity class) the
        sum and the maximum of ``SolverResult.iterations`` over the solve's
        problems, then the sum and the maximum of ``SolverResult.trials``;
        a vmapped solve runs as many trips as its slowest problem, and its
        line searches as many trials as theirs."""
        raise NotImplementedError

    def trace_publish(self, state, data=None) -> Array:
        """Traceable: state -> the publishable coefficient array.  ``data``:
        this coordinate's ``sweep_data()`` (same convention as trace_update)."""
        raise NotImplementedError

    def init_sweep_variances(self):
        """Host: placeholder pytree the sweep carries for this coordinate's
        variances (a zero-length array when variance=NONE)."""
        return jnp.zeros(0)

    def trace_variances(self, state, offsets: Array,
                        reg: "Optional[Regularization]" = None, key=None,
                        data=None):
        """Traceable: variances at this update's iterate/offsets/reg; same
        pytree structure as ``init_sweep_variances()``."""
        raise NotImplementedError

    def export_variances(self, v) -> np.ndarray:
        """Host: program variance output -> array for the published model."""
        raise NotImplementedError

    def export_model(self, published: np.ndarray) -> DatumScoringModel:
        """Host: the array from trace_publish -> this coordinate's model."""
        raise NotImplementedError

    def merge_carry_through(self, model: DatumScoringModel,
                            init: Optional[DatumScoringModel]
                            ) -> DatumScoringModel:
        """Host: fold warm-start state this update could NOT retrain into the
        published model (reference RandomEffectCoordinate.updateModel's
        leftOuterJoin :114-127: a prior per-entity model with no active data
        passes through unchanged).  Default: nothing to carry."""
        return model

    def carry_through_scores(self, init: Optional[DatumScoringModel]
                             ) -> "Optional[np.ndarray]":
        """Host: per-sample scores [n] of the warm-start state that
        merge_carry_through would pass through (the carried entities'
        contribution).  The fused sweep folds this CONSTANT into its base
        offsets so every in-program residual matches the host loop, whose
        re-scoring of the merged model includes it.  None = nothing
        carried."""
        return None

    # --- external (validation) scoring for fused validated sweeps --------
    # The fused validated program (game/fused.FusedSweep.run_validated)
    # scores a HELD-OUT sample set with each coordinate's published
    # coefficients inside the scanned program; these two methods are that
    # contract.  Subclasses without them inherit raising defaults and the
    # estimator falls back to the host-paced CoordinateDescent.

    def external_data(self, data: "GameData"):
        """Host: pytree of device arrays for scoring ``data`` with this
        coordinate's published coefficients inside a traced program
        (the validated sweep passes it back through ``trace_score_external``
        as ARGUMENTS — the same baked-constant-avoidance convention as
        ``sweep_data``)."""
        raise NotImplementedError

    def trace_score_external(self, published: Array, vdata) -> Array:
        """Traceable: published coefficient array + ``external_data``
        pytree -> this coordinate's raw score for every external sample
        (the traced twin of ``model.score(data)`` on the exported model)."""
        raise NotImplementedError

    def carry_through_scores_on(self, init: "Optional[DatumScoringModel]",
                                data: "GameData") -> "Optional[np.ndarray]":
        """Host: per-sample scores on ``data`` of the warm-start state this
        coordinate cannot retrain (``carry_through_scores``' semantics on an
        EXTERNAL sample set) — the validated sweep folds this constant into
        its held-out score base.  None = nothing carried."""
        return None

    def sweep_key(self) -> tuple:
        """Identity of this coordinate's compiled sweep contribution: the
        device data layout + every config field EXCEPT the regularization
        VALUES (those enter the program as traced arguments).  The L1 regime
        (l1 > 0) must survive in the key: make_solver dispatches OWLQN vs
        L-BFGS statically on it, so a reg override may never cross the
        smooth/L1 boundary inside one compiled sweep."""
        import dataclasses

        regime = Regularization(l1=1.0 if self.config.reg.l1 > 0.0 else 0.0)
        return (self.data_key(),
                dataclasses.replace(self.config, reg=regime))


def _solve_counts(res: SolverResult, valid: Optional[Array] = None) -> Array:
    """One row of ``trace_update``'s ``iterations_out``: int32 [4], the sum
    and the maximum of the solve's iterations over its problems (those of
    ``valid``, where given), then of its line-search trials (0 for a solver
    that counts none)."""
    trials = (res.trials if res.trials is not None
              else jnp.zeros_like(res.iterations))
    counts = [c if valid is None else jnp.where(valid, c, 0)
              for c in (res.iterations, trials)]
    return jnp.stack([f(c) for c in counts
                      for f in (jnp.sum, jnp.max)]).astype(jnp.int32)


def _storage_np_dtype(storage_dtype: Optional[str]):
    """Resolve a config storage dtype string to a numpy dtype (ml_dtypes
    registers bfloat16 etc. with numpy), or None when unset."""
    if storage_dtype is None:
        return None
    import ml_dtypes  # noqa: F401  (registers the 16-bit dtypes with numpy)

    return np.dtype(storage_dtype)


def _way_back_says(em, way_back) -> dict:
    """What ``coord.rescore_layout`` / ``coord.external_layout`` record of
    an entity-major layout's way back to sample order: ``back`` =
    ``identity`` / ``unpad`` / ``gather`` and, un-padded, the ``stages`` and
    ``slots`` of the compaction."""
    if em.back != "unpad":
        return dict(back=em.back)
    return dict(back="unpad", stages=way_back.stages, slots=way_back.slots)


@contextlib.contextmanager
def _upload_span(coordinate_id: str, mesh: Optional[Mesh]):
    """``coord.upload``: a coordinate's arrays on their way to the device;
    ``bytes`` is what ``device_put_counted`` counted inside (0 where the
    design was already there — the span is recorded all the same).  Yields
    ``placed(tree)``, which the coordinate hands what it keeps on the
    device: over the ``devices`` of the mesh, ``bytes_sharded`` are held
    once (each device a part) and ``bytes_replicated`` whole on every
    device (counted once); on one device nothing is replicated."""
    with obs_span("coord.upload", coordinate=coordinate_id) as sp:
        probe = get_probe()
        before = probe.transfer_bytes("h2d", site="device_put")
        held = {"bytes_sharded": 0, "bytes_replicated": 0}

        def placed(tree):
            for a in jax.tree.leaves(tree):
                if isinstance(a, jax.Array):
                    whole = (mesh is not None
                             and a.sharding.shard_shape(a.shape) == a.shape)
                    held["bytes_replicated" if whole
                         else "bytes_sharded"] += a.nbytes

        yield placed
        sp.set(bytes=probe.transfer_bytes("h2d", site="device_put") - before,
               devices=1 if mesh is None else mesh.size, **held)


def _where_it_is(a, dtype=None):
    """``a`` at ``dtype`` without moving it: a device array stays on its
    devices, anything else becomes a host array (narrowed on the host)."""
    if isinstance(a, jax.Array):
        return a if dtype is None or a.dtype == dtype else a.astype(dtype)
    return np.asarray(a, dtype)


class FixedEffectCoordinate(Coordinate):
    """Global GLM coordinate (reference FixedEffectCoordinate.scala:35-166)."""

    def __init__(self, coordinate_id: str, data: GameData, config: FixedEffectConfig,
                 task: TaskType, mesh: Optional[Mesh] = None,
                 norm: Optional[NormalizationContext] = None, dtype=np.float32):
        self.coordinate_id = coordinate_id
        self.config = config
        self.task = task
        self.mesh = mesh = spans_chips(mesh)
        self.dim = data.shard_dim(config.feature_shard)
        self._n = data.num_samples
        self._dtype = dtype
        self._base_offset = np.asarray(data.offset, np.float64)

        shard_data = data.features[config.feature_shard]
        # a design handed over in row shards ends in the sample axis's
        # padding rows (GameData): they get label 0, offset 0 and weight 0
        tail = shard_data.shape[0] - self._n
        if tail and (mesh is None or shard_data.shape[0]
                     != padded_samples(self._n, mesh)):
            raise ValueError(
                f"coordinate {coordinate_id!r}: feature shard "
                f"{config.feature_shard!r} has {shard_data.shape[0]} rows, "
                f"expected {self._n}"
                + ("" if mesh is None else
                   f" or, in row shards, {padded_samples(self._n, mesh)}"))
        # Storage narrowing happens ON HOST so the device transfer and the
        # resident array are storage-width from the start (an on-device cast
        # would transfer f32 and transiently hold both copies in HBM).
        x_dtype = _storage_np_dtype(config.storage_dtype) or dtype
        from photon_ml_tpu.ops.fused_glm import (_pick_block_rows, eligible,
                                                 runs_in_place,
                                                 storage_narrowing_ok)
        from photon_ml_tpu.parallel.mesh import (DATA_AXIS, FEATURE_AXIS,
                                                 padded_dim)

        with _upload_span(coordinate_id, mesh) as placed:
            # Under a mesh every leaf goes from where it is (host, for loaded
            # data) straight to its shards: staging the whole design on the
            # default device first would need one chip to hold all of it.
            put = device_put_counted if mesh is None else _where_it_is
            if mesh is None:
                y = jnp.asarray(np.asarray(data.y, dtype))
                # default offsets (all-zero) / weights (all-one) are created on
                # device: an [n]-sized constant needs no transfer
                offs_np = np.asarray(data.offset, dtype)
                offs0 = (jnp.zeros(self._n, dtype) if not offs_np.any()
                         else jnp.asarray(offs_np))
                wt_np = np.asarray(data.weight, dtype)
                wt0 = (jnp.ones(self._n, dtype) if np.all(wt_np == 1.0)
                       else jnp.asarray(wt_np))
            else:
                y, offs0, wt0 = (np.pad(np.asarray(a, dtype), (0, tail))
                                 for a in (data.y, data.offset, data.weight))
            if isinstance(shard_data, SparseShard):
                batch = SparseBatch(
                    indices=put(shard_data.indices),
                    values=put(shard_data.values, x_dtype),
                    y=y, offset=offs0, weight=wt0, dim=shard_data.dim)
            else:
                batch = DenseBatch(x=put(shard_data, x_dtype),
                                   y=y, offset=offs0, weight=wt0)

            # Feature-axis (model-parallel) sharding: active only when the mesh
            # actually has a feature axis > 1, so the same config is valid on any
            # mesh (mesh-agnostic property, SURVEY §4).
            self._fs = bool(getattr(config, "feature_sharded", False)) \
                and mesh is not None and mesh.shape[FEATURE_AXIS] > 1
            self._d_pad = padded_dim(self.dim, mesh) if self._fs else self.dim
            # One-time row padding to the fused-kernel block granule so the
            # pallas path never re-pads (and re-copies X) per solver call.
            # Narrow float storage (bf16/f16) keeps the pallas path — the
            # kernels take storage-width MXU operands with f32 accumulation
            # (GLMObjective._fused_eligible).  Wider-than-solver storage (f64)
            # falls back to XLA.  Same predicate GLMObjective._fused_eligible
            # consults at solve time — the pre-pad must never disagree with the
            # per-call gate.
            fused_ok = (storage_narrowing_ok(x_dtype, dtype) and eligible(batch)
                        and not self._fs)  # pallas kernels assume full-width w
            n_dev = 1 if mesh is None else mesh.shape[DATA_AXIS]
            pad_to = None
            if fused_ok:
                # pad so each device's LOCAL shard is a block multiple.  A
                # shard of MANY blocks stays as it is, on one device and
                # under a mesh (``ShardMapObjective`` runs the kernels on
                # each device's own rows): they take its whole blocks in
                # place and its last rows as a batch of their own
                # (fused_glm.runs_in_place), where padding would copy all
                # of it beside the original that the caller's data still
                # holds.  Under a mesh the rows are then padded to the next
                # multiple of the devices only (shard_batch), and a design
                # that arrives in its shards with that many rows is not
                # touched at all
                local = -(-batch.num_examples // n_dev)
                bn = _pick_block_rows(local, batch.dim,
                                      np.dtype(batch.x.dtype).itemsize)
                if not runs_in_place(local, bn):
                    pad_to = (-(-local // bn) * bn) * n_dev
            if mesh is not None:
                # at least the sweep's sample axis (carry_samples: over
                # EVERY device, where the batch is over the data axis)
                pad_to = max(pad_to or 0,
                             padded_samples(batch.num_examples, mesh))
                batch = shard_batch(
                    batch, mesh, pad_to=pad_to,
                    feature_axis=FEATURE_AXIS
                    if (self._fs and isinstance(batch, DenseBatch)) else None)
            elif pad_to is not None:
                from photon_ml_tpu.ops.fused_glm import _pad_rows

                batch = _pad_rows(batch, pad_to)
            placed(batch)
        self._batch = batch
        self._padded_n = batch.num_examples
        self._base_weight = batch.weight

        norm = norm or no_normalization()
        # match the batch dtype or the normalization algebra promotes the
        # whole solver carry (f64 stats ctx x f32 batch -> while_loop error)
        self._norm = norm.replace(
            factors=None if norm.factors is None else jnp.asarray(norm.factors, dtype),
            shifts=None if norm.shifts is None else jnp.asarray(norm.shifts, dtype))
        if self._fs and self._d_pad != self.dim:
            # padded coefficient slots: identity scale, no shift — they see
            # only zero feature columns so they stay pinned at 0
            pad = self._d_pad - self.dim
            self._norm = self._norm.replace(
                factors=None if self._norm.factors is None
                else jnp.pad(self._norm.factors, (0, pad), constant_values=1.0),
                shifts=None if self._norm.shifts is None
                else jnp.pad(self._norm.shifts, (0, pad)))
        self._bind_solver()
        # The batch is an ARGUMENT of every jitted program, never a closure:
        # closed-over jax.Arrays lower to baked XLA constants, and compile
        # time grows linearly with constant bytes (~9s per GB-touch on CPU;
        # far worse on the TPU backend) — X here is the biggest array in the
        # system.
        self._score = jax.jit(lambda w, batch: batch.margins(w))

    def _bind_solver(self) -> None:
        # Both paths use the pallas fused kernels (ops/fused_glm.py) where
        # eligible: X streams through VMEM once per value_and_grad instead of
        # 2-3 XLA passes.  Under a mesh the objective runs as explicit SPMD
        # (shard_map + one psum per evaluation, parallel/fixed.py) — GSPMD
        # cannot auto-partition a pallas custom call, shard_map runs it
        # per-device on local rows.
        objective = GLMObjective(loss=loss_for_task(self.task), reg=self.config.reg,
                                 norm=self._norm, fused=not self._fs)
        if self._fs and isinstance(self._batch, SparseBatch):
            from photon_ml_tpu.parallel.fixed import ShardSparseObjective
            from photon_ml_tpu.parallel.mesh import FEATURE_AXIS

            objective = ShardSparseObjective(
                objective, self.mesh,
                self._d_pad // self.mesh.shape[FEATURE_AXIS])
        elif self._fs:
            # dense + feature-sharded: plain objective; GSPMD partitions the
            # margin/gradient contractions from the (data, feature) shardings
            pass
        elif self.mesh is not None:
            from photon_ml_tpu.parallel.fixed import ShardMapObjective

            objective = ShardMapObjective(objective, self.mesh)
        self._objective = objective
        box = _box_from_constraints(
            self.config.constraints, self.dim, self._dtype, self._norm,
            d_pad=self._d_pad if self._fs else None,
            space=self.config.constraint_space)
        solve = make_solver(objective, self.config.optimizer,
                            self.config.solver, box=box)
        self.line_search = line_search_kind(
            objective, self.config.optimizer, self._batch,
            jax.ShapeDtypeStruct((self._d_pad if self._fs else self.dim,),
                                 self._dtype), box)

        # reg is a TRACED argument: a reg-weight grid re-enters this exact
        # compiled program (the optimizer/L1-regime dispatch inside
        # make_solver stays keyed to the build-time reg — see _solver_key).
        # The batch is an argument too (see __init__ compile-time note).
        def _solve(w0: Array, batch, reg: Regularization) -> SolverResult:
            return solve(w0, batch, objective=objective.with_reg(reg))

        # Feature-sharded solves keep w P("feature") end-to-end (propagated
        # from w0) — replicating the output would defeat the sharding.
        out_shard = (replicate(self.mesh)
                     if self.mesh is not None and not self._fs else None)
        self._solve = (jax.jit(_solve, out_shardings=out_shard)
                       if out_shard is not None else jax.jit(_solve))
        self._solver_key = self._make_solver_key()

    def _make_solver_key(self) -> tuple:
        """Everything (besides reg VALUES) that shapes the compiled solver."""
        c = self.config
        return (c.optimizer, c.solver, c.reg.l1 > 0.0, c.variance,
                c.intercept_index, c.constraints, c.constraint_space)

    def data_key(self) -> tuple:
        """Identity of the device data layout (reuse across optimization
        configs — reference GameEstimator prepares datasets once, fit:454-557)."""
        return ("fixed", self.config.feature_shard, self.config.storage_dtype,
                self._fs)

    def rebind(self, config: FixedEffectConfig) -> "FixedEffectCoordinate":
        """New optimization settings over the SAME device-resident data.
        A reg-weight-only change keeps the compiled solver (reg is a traced
        argument of ``_solve``) — zero recompilation across a λ grid."""
        import copy

        if (config.feature_shard != self.config.feature_shard
                or config.storage_dtype != self.config.storage_dtype
                or config.feature_sharded != self.config.feature_sharded):
            raise ValueError("rebind cannot change the feature shard, its "
                             "storage dtype, or feature sharding")
        new = copy.copy(self)
        new.config = config
        if new._make_solver_key() != self._solver_key:
            new._bind_solver()
        return new

    def _pad(self, a: np.ndarray) -> np.ndarray:
        pad = self._padded_n - len(a)
        return a if pad == 0 else np.concatenate([a, np.zeros(pad, a.dtype)])

    def _down_sample_mult(self, keep, y):
        """Per-task sampling rule (reference DownSamplerHelper.scala:33-40):
        binary tasks keep every positive and reweight sampled negatives by
        1/rate (BinaryClassificationDownSampler.scala:32-55); regression
        tasks sample uniformly with NO reweight (DefaultDownSampler)."""
        rate = self.config.down_sampling_rate
        xp = jnp if isinstance(keep, jax.Array) else np
        if self.task in (TaskType.LOGISTIC_REGRESSION,
                         TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM):
            mult = xp.where(keep, 1.0 / rate, 0.0)
            return xp.where(y > 0.5, 1.0, mult).astype(self._dtype)
        return keep.astype(self._dtype)

    def _down_sample_weights(self, seed: int) -> Array:
        """Host-paced resample-per-update path (reference
        DistributedOptimizationProblem.runWithSampling:159-174)."""
        rate = self.config.down_sampling_rate
        if rate >= 1.0:
            return self._base_weight
        rng = np.random.default_rng(seed)
        keep = rng.random(self._padded_n) < rate
        mult = self._down_sample_mult(keep, np.asarray(self._batch.y))
        return self._base_weight * jnp.asarray(mult)


    def _initial_state(self, init: Optional[FixedEffectModel]) -> Array:
        """Initial transformed-space solver state (cold, or an ORIGINAL-space
        warm-start model mapped in), padded + P("feature")-sharded when the
        coordinate is feature-sharded.  The ONE definition shared by the
        host-paced update() and the fused sweep's init_sweep_state — the
        fused==host parity tests rely on them never drifting."""
        if init is not None:
            means = np.asarray(init.coefficients.means, self._dtype)
            if self._fs and len(means) < self._d_pad:
                means = np.pad(means, (0, self._d_pad - len(means)))
            w = self._norm.model_to_transformed_space(
                jnp.asarray(means), self.config.intercept_index)
        else:
            w = jnp.zeros(self._d_pad, self._dtype)  # _d_pad == dim unless _fs
        if self._fs:
            from photon_ml_tpu.parallel.mesh import shard_coefficients

            w = shard_coefficients(w, self.mesh)
        return w

    def update(self, total_offsets: np.ndarray, seed: int = 0,
               init: Optional[FixedEffectModel] = None) -> Tuple[FixedEffectModel, SolverResult]:
        """Solve in TRANSFORMED space, publish the model in ORIGINAL space
        (reference Optimizer.optimize:175 modelToTransformedSpace on entry,
        GeneralizedLinearOptimizationProblem.createModel original-space exit;
        NormalizationContext.scala:73-124).  Models/scores everywhere else are
        original-space, so warm starts convert back in."""
        ii = self.config.intercept_index
        w0 = self._initial_state(init)
        offs = self._pad(np.asarray(total_offsets, self._dtype))
        # under a mesh each shard goes to its own device, like the batch
        offs = (jnp.asarray(offs) if self.mesh is None
                else jax.device_put(offs, self._batch.offset.sharding))
        weights = self._down_sample_weights(seed)
        res = self._solve(w0, self._batch.replace(offset=offs, weight=weights),
                          self.config.reg)
        w_orig = self._norm.model_to_original_space(res.w, ii)
        variances = None
        if self.config.variance != VarianceComputationType.NONE:
            # Computed at the optimization-space coefficients, then mapped
            # through the SAME coefficient transform as the means — exact
            # reference behavior (DistributedOptimizationProblem.scala:84-108;
            # GeneralizedLinearOptimizationProblem.createModel:89-95 applies
            # modelToOriginalSpace to the variances vector verbatim).
            from photon_ml_tpu.opt.solve import compute_variances

            v = compute_variances(
                self._objective.with_reg(self.config.reg), res.w,
                self._batch.replace(offset=offs, weight=weights),
                self.config.variance)
            variances = np.asarray(self._norm.model_to_original_space(v, ii))
            variances = variances[: self.dim]
        model = FixedEffectModel(
            coefficients=Coefficients(means=np.asarray(w_orig)[: self.dim],
                                      variances=variances),
            feature_shard=self.config.feature_shard,
            task=self.task,
        )
        return model, res

    def score(self, model: FixedEffectModel) -> np.ndarray:
        means = np.asarray(model.coefficients.means, self._dtype)
        if self._fs and len(means) < self._d_pad:
            means = np.pad(means, (0, self._d_pad - len(means)))
        s = self._score(jnp.asarray(means), self._batch)
        return np.asarray(s)[: self._n]

    def tracker_summary(self, tracker) -> dict:
        """Solver telemetry for the job log (FixedEffectOptimizationTracker)."""
        from photon_ml_tpu.opt.types import summarize_solver_results

        return summarize_solver_results(tracker)

    # --- traceable-step interface (game/fused.py) ---
    # State = transformed-space coefficient vector [d].

    def init_sweep_state(self, init: Optional[FixedEffectModel] = None) -> Array:
        """Sweep state = transformed-space coefficients.  Feature-sharded
        coordinates carry a P("feature")-sharded [d_pad] state through the
        scanned program — the residual fold only ever consumes the [n]-vector
        scores (already feature-axis-reduced by trace_update), so the fused
        descent runs one program for every model size, like the reference's
        single CoordinateDescent path (CoordinateDescent.scala:93-107)."""
        return self._initial_state(init)

    def sweep_data(self):
        """The batch enters the fused program as an ARGUMENT (compile-time
        note in __init__)."""
        return self._batch

    def _sweep_batch_inputs(self, offsets: Array, key, batch) -> Tuple[Array, Array]:
        """(padded offsets, per-update weights) — the ONE definition of what a
        sweep update sees; trace_update and trace_variances must agree on it
        (down-sampled weights are re-drawn from the same key, so XLA CSEs the
        duplicate draw and the variance weights match the update's exactly)."""
        pad = self._padded_n - self.carry_samples
        offs = (jnp.pad(offsets, (0, pad)) if pad else offsets).astype(self._dtype)
        if self.config.down_sampling_rate < 1.0 and key is not None:
            keep = (jax.random.uniform(key, (self._padded_n,))
                    < self.config.down_sampling_rate)
            return offs, batch.weight * self._down_sample_mult(keep, batch.y)
        return offs, batch.weight

    def trace_update(self, state: Array, offsets: Array,
                     reg: Optional[Regularization] = None,
                     key=None, data=None,
                     iterations_out: Optional[list] = None
                     ) -> Tuple[Array, Array]:
        batch = self._batch if data is None else data
        with device_scope("fixed_solve"):
            offs, weights = self._sweep_batch_inputs(offsets, key, batch)
            res = self._solve(state,
                              batch.replace(offset=offs, weight=weights),
                              self.config.reg if reg is None else reg)
            if iterations_out is not None:
                iterations_out.append(_solve_counts(res)[None])
        w_pub = self.trace_publish(res.w)
        with device_scope("rescore"):
            if self._fs and isinstance(batch, SparseBatch):
                # pinned communication: one [n_local] feature-axis psum
                # instead of GSPMD all-gathering the full sharded
                # coefficient vector
                return res.w, self._objective.margins(
                    w_pub, batch)[: self.carry_samples]
            return res.w, batch.margins(w_pub)[: self.carry_samples]

    def trace_publish(self, state: Array, data=None) -> Array:
        with device_scope("publish"):
            return self._norm.model_to_original_space(
                state, self.config.intercept_index)

    def export_model(self, published: np.ndarray) -> FixedEffectModel:
        return FixedEffectModel(
            coefficients=Coefficients(
                means=np.asarray(published)[: self.dim]),
            feature_shard=self.config.feature_shard, task=self.task)

    def exchange_bytes(self) -> Dict[str, int]:
        """``psum``: ShardMapObjective's one all-reduce of (value, gradient
        [d], residual sum) an objective evaluation."""
        from photon_ml_tpu.parallel.fixed import ShardMapObjective

        if not isinstance(self._objective, ShardMapObjective):
            return {}
        return exchange_bytes(self.mesh, {}, {
            "psum": (self.dim + 2) * np.dtype(self._dtype).itemsize})

    def init_sweep_variances(self) -> Array:
        if self.config.variance == VarianceComputationType.NONE:
            return jnp.zeros(0, self._dtype)
        v = jnp.zeros(self._d_pad if self._fs else self.dim, self._dtype)
        if self._fs:
            from photon_ml_tpu.parallel.mesh import shard_coefficients

            v = shard_coefficients(v, self.mesh)
        return v

    def trace_variances(self, state: Array, offsets: Array,
                        reg: Optional[Regularization] = None,
                        key=None, data=None) -> Array:
        """Traced coefficient variances at this update's iterate against this
        update's offsets, (down-sampled) weights AND traced ``reg`` — the
        exact inputs trace_update solved with, so the last iteration's values
        match what the host path publishes
        (DistributedOptimizationProblem.scala:84-108: variances are computed
        per update; only the final update's survive into the model)."""
        from photon_ml_tpu.opt.solve import compute_variances

        batch = self._batch if data is None else data
        offs, weights = self._sweep_batch_inputs(offsets, key, batch)
        v = compute_variances(
            self._objective.with_reg(self.config.reg if reg is None else reg),
            state, batch.replace(offset=offs, weight=weights),
            self.config.variance)
        return self._norm.model_to_original_space(v, self.config.intercept_index)

    def export_variances(self, v) -> np.ndarray:
        return np.asarray(v)[: self.dim]

    # --- external (validation) scoring (fused validated sweeps) ---------

    def external_data(self, data: GameData):
        """Held-out design for this shard, device-resident once (dense
        [n, d] or the SparseShard COO pair) — the same layout
        FixedEffectModel.score consumes."""
        shard = data.features[self.config.feature_shard]
        if isinstance(shard, SparseShard):
            return {"x_idx": device_put_counted(shard.indices, np.int32),
                    "x_val": device_put_counted(shard.values, self._dtype)}
        # a design the caller made on the device stays there: no byte moves
        return {"x": device_put_counted(shard, self._dtype)}

    def trace_score_external(self, published: Array, vdata) -> Array:
        """== FixedEffectModel.score: x @ w (dense) or the gather-einsum
        (sparse), on the ORIGINAL-space published coefficients."""
        w = published[: self.dim]
        if "x" in vdata:
            return vdata["x"] @ w
        return jnp.einsum("nk,nk->n", vdata["x_val"], w[vdata["x_idx"]])


def _box_from_constraints(constraints, dim: int, dtype, norm=None,
                          d_pad: Optional[int] = None,
                          space: str = "original"):
    """(lower, upper) solver box arrays in the SOLVE (transformed) space.

    Reference: OptimizerConfig.constraintMap (OptimizerConfig.scala:47)
    applied by OptimizationUtils.projectCoefficientsToSubspace per iteration
    — here the bounds become the LBFGS projected-gradient box
    (opt/lbfgs.py:97 via make_solver(box=...)).

    ``space="original"`` (default): bounds constrain the PUBLISHED
    original-space coefficients; with scaling normalization
    w_orig = factors * w_t (factors > 0) the transformed-space box is
    [lo/f, hi/f], and shift normalization is refused loudly (the
    -<w, shifts> intercept fold makes per-feature original bounds
    non-separable).

    ``space="transformed"``: reference-compat — raw bounds applied to the
    transformed-space iterate regardless of normalization, reproducing
    TRON.scala:228 / OptimizationUtils.scala:56-58 (which silently apply
    original-space constraintMap bounds in the scaled+shifted space); the
    published original-space coefficients can then violate the written
    bounds.  See game/config._canonicalize_constraints and MIGRATION.md.
    """
    if not constraints:
        return None
    if space == "transformed":
        norm = None  # raw bounds in solver space: the reference's behavior
    total = d_pad or dim
    lo = np.full(total, -np.inf, dtype)
    hi = np.full(total, np.inf, dtype)
    if total != dim:
        lo[dim:] = 0.0  # padded coefficient slots stay pinned at 0
        hi[dim:] = 0.0
    for j, l, h in constraints:
        if not 0 <= j < dim:
            raise ValueError(
                f"constraint feature index {j} out of range [0, {dim})")
        lo[j], hi[j] = l, h
    if norm is not None:
        if norm.shifts is not None:
            raise ValueError(
                "box constraints with shift normalization are not supported "
                "(original-space bounds are non-separable under shifts); use "
                "a scaling-only normalization type, or "
                "constraint_space='transformed' for reference-compat raw "
                "bounds on the transformed iterate (MIGRATION.md)")
        if norm.factors is not None:
            f = np.asarray(norm.factors)
            lo, hi = lo / f, hi / f
    return jnp.asarray(lo), jnp.asarray(hi)


def _re_data_key(c: RandomEffectConfig) -> tuple:
    """Every field that affects the DATA layout (buckets + projection); a
    config differing only in optimization settings may reuse device arrays."""
    return ("random", c.random_effect_type, c.feature_shard, c.active_cap,
            c.min_active_samples, c.projector, c.projected_dim,
            c.features_to_samples_ratio, c.intercept_index, c.storage_dtype)


class RandomEffectCoordinate(Coordinate):
    """Per-entity GLM coordinate (reference RandomEffectCoordinate.scala:39-232).

    All entities are bucketed once at construction; every update solves every
    bucket with a vmapped jitted solver.  Scoring covers ALL samples —
    including those capped out of the active set — via the stacked-coefficient
    gather (the reference's passive-data path).
    """

    def __init__(self, coordinate_id: str, data: GameData, config: RandomEffectConfig,
                 task: TaskType, mesh: Optional[Mesh] = None, seed: int = 0,
                 dtype=np.float32, norm: Optional[NormalizationContext] = None,
                 existing_model_keys: Optional[frozenset] = None):
        self.coordinate_id = coordinate_id
        self.config = config
        self.task = task
        self.mesh = mesh = spans_chips(mesh)
        self._n = data.num_samples
        self._dtype = dtype
        self.dim = data.shard_dim(config.feature_shard)
        # Per-entity normalization (reference: one NormalizationContext per
        # REId — NormalizationContextRDD, RandomEffectOptimizationProblem
        # .scala:154-178, built by GameEstimator.prepareNormalizationContext
        # Wrappers:646-680).  Three cases, exactly the reference's:
        #   IDENTITY projector  -> ONE shared context for every entity
        #                          (NormalizationContextBroadcast);
        #   INDEX_MAP projector -> the coordinate context PROJECTED into each
        #                          entity's compact space (the RDD case) —
        #                          here: per-lane gathered factor arrays that
        #                          ride the vmapped solve as traced leaves;
        #   RANDOM projector    -> the context pushed through the Gaussian
        #                          matrix, shared by every entity (reference
        #                          ProjectionMatrixBroadcast
        #                          .projectNormalizationContext:102-112);
        #                          shifts need the intercept pass-through
        #                          slot (intercept_index set).
        if (norm is not None and norm.shifts is not None
                and config.projector == ProjectorType.RANDOM
                and config.intercept_index is None):
            raise ValueError(
                f"coordinate {coordinate_id!r}: shift normalization under a "
                "RANDOM projection needs intercept_index — the Gaussian "
                "matrix then carries the reference's intercept pass-through "
                "slot (ProjectionMatrix.scala:112-120)")
        self._norm = None
        if norm is not None and (norm.factors is not None
                                 or norm.shifts is not None):
            self._norm = norm.replace(
                factors=None if norm.factors is None
                else jnp.asarray(norm.factors, dtype),
                shifts=None if norm.shifts is None
                else jnp.asarray(norm.shifts, dtype))
        self._base_offset = np.asarray(data.offset, np.float64)

        shard_data = data.features[config.feature_shard]
        if shard_data.shape[0] != self._n:  # only a fixed design may be padded
            raise ValueError(
                f"coordinate {coordinate_id!r}: feature shard "
                f"{config.feature_shard!r} has {shard_data.shape[0]} rows, "
                f"expected {self._n}")
        entity_ids = data.id_tags[config.random_effect_type]
        lane_multiple = 1 if mesh is None else mesh.size
        self._sparse = isinstance(shard_data, SparseShard)
        if (self._norm is not None and self._norm.shifts is not None
                and config.intercept_index is None
                and (self._sparse
                     or config.projector == ProjectorType.INDEX_MAP)):
            # Shift normalization under observed-column compaction projects
            # the context per entity, exactly like the reference's per-REId
            # NormalizationContextRDD through its per-entity projectors
            # (IndexMapProjectorRDD.scala:34-262): the intercept is observed
            # in every active sample, so compaction keeps a per-entity
            # intercept column whose per-lane position absorbs the margin
            # shift — but the coordinate must know WHICH full-dim column
            # that is.
            raise ValueError(
                f"coordinate {coordinate_id!r}: shift normalization under "
                "per-entity compaction needs intercept_index (the per-lane "
                "intercept column absorbs the projected margin shift)")
        # coord.bucket: host grouping + the Python packing loops, the
        # full-sample layout (coord.rescore_layout) inside it;
        # coord.upload: the design's way onto the device
        # a dense shard, a sparse shard's pairs over the footprint line
        narrow, pairs_t, runs = False, False, None
        with obs_span("coord.bucket", coordinate=coordinate_id) as bucket_span:
            if self._sparse:
                # Row-sparse RE feature bag (the reference's per-entity sparse
                # LocalDataset, data/LocalDataset.scala:35-247): each entity
                # solves in the compact space of its observed columns, built
                # DIRECTLY from the sparse rows — the full-vocabulary [E, S, d]
                # bucket tensors never exist (bucket_by_entity_sparse).
                # (projected_dim without RANDOM is rejected at CONFIG time —
                # RandomEffectConfig.__post_init__ — so no guard here)
                from photon_ml_tpu.parallel.bucketing import (
                    bucket_by_entity_sparse, entity_runs,
                    use_transposed_scoring)
                from photon_ml_tpu.parallel.projection import ProjectedBuckets

                ratio = (config.features_to_samples_ratio
                         if config.projector == ProjectorType.INDEX_MAP else None)
                # over the padded-footprint line the pairs, [n, k] of a
                # small k, keep the samples on the lanes as a narrow dense
                # design does, in blocks of samples
                # (bucketing.score_samples_sparse_blocks); under a mesh
                # they stay [n, k], each chip its rows
                pairs_t = mesh is None and use_transposed_scoring(
                    *shard_data.indices.shape, np.dtype(dtype).itemsize)
                runs = entity_runs(entity_ids)
                self.buckets, projections = bucket_by_entity_sparse(
                    entity_ids, shard_data.indices, shard_data.values, self.dim,
                    np.asarray(data.y, dtype),
                    offset=np.asarray(data.offset, dtype),
                    weight=np.asarray(data.weight, dtype),
                    active_cap=config.active_cap,
                    min_active_samples=config.min_active_samples,
                    lane_multiple=lane_multiple, seed=seed, dtype=dtype,
                    features_to_samples_ratio=ratio,
                    intercept_index=config.intercept_index,
                    existing_model_keys=existing_model_keys,
                    runs=runs,
                )
                self._proj = ProjectedBuckets(base=self.buckets,
                                              buckets=self.buckets.buckets,
                                              projections=projections)
                if config.projector == ProjectorType.RANDOM:
                    # RANDOM over a sparse shard: the shared Gaussian matrix's
                    # rows GATHERED through each lane's observed-column map
                    # project the compact design into d_proj — exactly what the
                    # densified x @ A computes, because unobserved columns
                    # contribute zero either way (reference builds the same
                    # shared matrix per coordinate, ProjectionMatrixBroadcast
                    # .scala:150; the full-vocabulary [E, S, d] tensors still
                    # never exist).
                    import dataclasses as _dc

                    from photon_ml_tpu.parallel.projection import (
                        build_random_projection)

                    if config.projected_dim is None:
                        raise ValueError("RANDOM projection requires projected_dim")
                    shared = build_random_projection(
                        self.dim, config.projected_dim, seed, dtype=dtype,
                        intercept_index=config.intercept_index)
                    proj_buckets = []
                    for b, p in zip(self.buckets.buckets, projections):
                        safe = np.where(p.indices < 0, 0, p.indices)
                        a_sub = shared.matrix[safe]  # [lanes, d_compact, d_proj]
                        a_sub = np.where((p.indices >= 0)[:, :, None], a_sub, 0.0)
                        x_proj = np.einsum("lsd,ldp->lsp", b.x,
                                           a_sub).astype(dtype)
                        proj_buckets.append(_dc.replace(b, x=x_proj))
                    self._proj = ProjectedBuckets(
                        base=self.buckets, buckets=proj_buckets,
                        projections=[shared] * len(proj_buckets))
            else:
                # A streamed (device-assembled) dense shard stays on device: the
                # bucketer gathers lanes on device, and the [n, d] array never
                # materializes on host — the point of out-of-core ingest.
                shard_is_device = isinstance(shard_data, jax.Array)
                if shard_is_device and config.projector != ProjectorType.IDENTITY:
                    raise NotImplementedError(
                        f"coordinate {coordinate_id!r}: projector "
                        f"{config.projector.name} over a device-assembled "
                        "(streamed) design shard would host-materialize it; "
                        "IDENTITY only for now (ROADMAP item 5 follow-on)")
                x = shard_data if shard_is_device else np.asarray(shard_data, dtype)
                # Narrow shards whose padded [n, d] footprint threatens HBM
                # keep the samples on the lanes: TPU tiling pads the minor
                # axis to 128, so a [n, d<=32] array (and every scoring
                # gather from it) occupies 128/d x its logical HBM bytes —
                # 32x at glmix_chip's d=4, an OOM at 8.39M samples.  Which
                # narrow layout, and the chip-measured crossover, live with
                # score_samples_em in parallel/bucketing.py; the grouping
                # it is laid out by is the bucketer's own.
                from photon_ml_tpu.parallel.bucketing import (
                    entity_runs, use_transposed_scoring)
                narrow = use_transposed_scoring(
                    x.shape[0], x.shape[1], np.dtype(dtype).itemsize)
                runs = entity_runs(entity_ids) if narrow else None
                groups = None
                if data.entity_stats is not None:
                    stats = data.entity_stats.get(config.random_effect_type)
                    if stats is not None:
                        # per-entity grouping accumulated chunk-by-chunk during
                        # streaming ingest; None on cap/seed mismatch -> the
                        # bucketer rescans the host id column as usual
                        groups = stats.groups(config.active_cap,
                                              config.min_active_samples, seed,
                                              existing_model_keys)
                self.buckets = bucket_by_entity(
                    entity_ids, x, np.asarray(data.y, dtype),
                    offset=np.asarray(data.offset, dtype),
                    weight=np.asarray(data.weight, dtype),
                    active_cap=config.active_cap,
                    min_active_samples=config.min_active_samples,
                    lane_multiple=lane_multiple,
                    seed=seed, dtype=dtype,
                    existing_model_keys=existing_model_keys,
                    groups=groups, runs=runs,
                )
            # Optional per-entity feature projection (reference
            # RandomEffectCoordinateInProjectedSpace.scala:149): solve each bucket
            # in a compact feature space, back-project coefficients to full dim.
            # (A sparse shard arrives here with self._proj already built — its
            # buckets ARE the compact space.)
            if not self._sparse:
                self._proj = None
                if config.projector != ProjectorType.IDENTITY:
                    from photon_ml_tpu.parallel.projection import project_buckets

                    self._proj = project_buckets(
                        self.buckets, config.projector,
                        projected_dim=config.projected_dim,
                        features_to_samples_ratio=config.features_to_samples_ratio,
                        intercept_index=config.intercept_index,
                        seed=seed,
                    )
            solve_buckets = (self._proj.buckets if self._proj is not None
                             else self.buckets.buckets)
            if self._proj is not None:
                # Device twins of each bucket's back-projection (gather indices /
                # shared Gaussian matrix); they travel through sweep_data() into
                # the fused program as arguments.  The Gaussian matrix is SHARED
                # across buckets — upload it once, not once per bucket.
                from photon_ml_tpu.parallel.projection import BucketProjection

                # kinds are STATIC (python strings can't be jit-arg leaves);
                # the arrays are the traced half
                matrix_dev: Dict[int, Array] = {}
                self._proj_kinds = []
                self._proj_dev = []
                for p in self._proj.projections:
                    if isinstance(p, BucketProjection):
                        self._proj_kinds.append("index")
                        self._proj_dev.append(jnp.asarray(p.indices))
                    else:
                        self._proj_kinds.append("random")
                        key = id(p.matrix)
                        if key not in matrix_dev:  # one upload for the shared matrix
                            matrix_dev[key] = jnp.asarray(p.matrix)
                        self._proj_dev.append(matrix_dev[key])
                self._proj_dev = tuple(self._proj_dev)
            self._bind_solver()
            # what the bucketer made of the rows per entity: one vmapped
            # solve per class, lanes x capacity slots of which active_rows
            # hold a row; passive rows are scored and never trained on
            classes = self.buckets.buckets
            by_run = [b.run_lanes * lane_multiple for b in classes]
            by_window = [b.window_lanes * lane_multiple for b in classes]
            # the window lanes' starts and picks (bucketing.lane_windows),
            # painted here on the host, uploaded with the lanes' rows
            windows_of = [
                lane_windows(_share_lanes(b.rows, lane_multiple, b.run_lanes,
                                          b.run_lanes + b.window_lanes))
                if b.window_lanes else None for b in classes]
            slots = sum(b.num_lanes * b.capacity for b in classes)
            run_slots = sum(r * b.capacity for r, b in zip(by_run, classes))
            window_slots = sum(w * b.capacity
                               for w, b in zip(by_window, classes))
            bucket_span.set(
                line_search=self.line_search,
                classes=len(classes),
                capacities=[b.capacity for b in classes],
                lanes=[b.num_lanes for b in classes],
                lanes_per_device=[b.num_lanes // lane_multiple
                                  for b in classes],
                slots=slots,
                # lanes addressed by the start of their run of samples, and
                # by the start of the window their rows lie in, its width
                # and the passes of the pick (bucketing._class_lanes,
                # lane_windows); their slots, and the slots that keep one
                # gathered index each
                run_lanes=by_run,
                window_lanes=by_window,
                window=[w.window if w else 0 for w in windows_of],
                pick_stages=[w.stages if w else 0
                             for w in windows_of],
                run_slots=run_slots,
                window_slots=window_slots,
                index_slots=slots - run_slots - window_slots,
                active_rows=sum(int(b.counts.sum()) for b in classes),
                capped_entities=self.buckets.capped_entities,
                passive_rows=self.buckets.passive_rows)
            if self._proj is not None and "index" in self._proj_kinds:
                # a compact coordinate: the width every class solves at, the
                # columns its lanes keep and the columns that width holds
                maps = self._proj.projections
                bucket_span.set(
                    projector=config.projector.name, d_full=self.dim,
                    d_proj=[p.d_proj for p in maps],
                    kept_columns=sum(int(np.count_nonzero(p.indices >= 0))
                                     for p in maps),
                    compact_columns=sum(p.indices.size for p in maps))
                if self._sparse:
                    bucket_span.set(
                        row_width=int(shard_data.indices.shape[1]),
                        observed_columns=self.buckets.observed_columns,
                        filtered_entities=self.buckets.filtered_entities)
            # slot order for the stacked model = sorted entity id (stacked_coefficients)
            self._sorted_ids = sorted(self.buckets.lane_of)
            self._slot_of = {eid: i for i, eid in enumerate(self._sorted_ids)}
            # per-bucket lane -> stacked-model row; invalid lanes get an
            # out-of-range index so device scatters drop them (stack_bucket_lanes)
            ne = len(self._sorted_ids)
            self._slot_idx_dev = [
                (jnp.asarray if mesh is None else self._put_entity)(np.where(
                    (s := _slots_from(self._slot_of,
                                      np.asarray(b.entity_lanes, np.int64))) < 0,
                    ne, s).astype(np.int32))
                for b in self.buckets.buckets
            ]
            # The full-sample design's layout (bucketing.py's note): sparse, or
            # row-major [n, d], or over the padded-footprint line entity-major
            # where the rows of an entity fill chunks, else transposed [d, n].
            # Sample-order layouts score by per-SAMPLE slots (``_slot_ids``:
            # the entity of each sample); the entity-major one by per-CHUNK
            # slots (the entity of each chunk), its design and ``way_back``
            # (to sample order: nothing, an un-pad or a position gather) in
            # the place of ``x_full`` and ``slots``, not beside them.
            from photon_ml_tpu.parallel.bucketing import (EM_ROW,
                                                          entity_major_layout)
            self._em = None
            self._x_full_is_t = False
            # a sparse shard's pairs over the footprint line go entity-major
            # too where every entity's published row is exactly zero off
            # its compact columns: they are then scored from the compact
            # lanes (bucketing.score_pairs_em), D = the widest d_proj
            self._pick_columns = self._compact_pick_columns() if pairs_t else 0
            with obs_span("coord.rescore_layout",
                          coordinate=coordinate_id) as layout_span:
                if narrow:
                    # whole tiles of the entity-major scores a device, as
                    # of every [n] vector (mesh.padded_samples)
                    self._em = entity_major_layout(
                        runs, 1 if mesh is None else lane_multiple
                        * SAMPLE_TILE // EM_ROW)
                elif self._pick_columns:
                    self._em = entity_major_layout(runs)
                    if self._em is None:
                        self._pick_columns = 0
                if (mesh is not None and self._em is not None
                        and self._em.pos is None and self._em.lanes
                        * self._em.chunk != self.carry_samples):
                    # under a mesh the chunks are the sample order only
                    # where they are so shard for shard
                    self._em.pos = np.arange(self._n, dtype=np.int32)
                way_back = None
                if self._em is not None:
                    self._slot_ids = self._em.entities
                    way_back = self._em.way_back(
                        lane_multiple, self.carry_samples // lane_multiple)
                    # coef_indices: what the scorer gathers a call, ONE
                    # row of the table a chunk
                    layout_span.set(layout="entity_major", chunk=self._em.chunk,
                                    lanes=self._em.lanes, fill=self._em.fill,
                                    coef_indices=self._em.lanes,
                                    **_way_back_says(self._em, way_back))
                else:
                    self._x_full_is_t = narrow
                    self._slot_ids = np.asarray(entity_ids, np.int64)
                    if not self._sparse:
                        layout_span.set(layout="transposed" if narrow
                                        else "row_major")
                slots = self._slots_under(self._slot_of)
                # a sparse shard's pairs by blocks of samples: how many
                # blocks, and how much of the table one reads, by the slots
                self._pair_blocks, self._table_rows = 0, None
                if pairs_t and not self._pick_columns:
                    self._pair_blocks = sample_blocks(
                        slots, self.dim * np.dtype(dtype).itemsize)
                    by_block, self._table_rows = self._slots_in_blocks(slots)
                else:
                    slots = self._put_slots(slots)
                if self._sparse:
                    layout_span.set(
                        layout="sparse", pairs="entity_major"
                        if self._pick_columns else "blocks"
                        if self._pair_blocks else "rows",
                        blocks=self._pair_blocks,
                        table_rows=self._table_rows,
                        row_width=int(shard_data.indices.shape[1]),
                        nonzeros=int(np.count_nonzero(shard_data.values)))
                if self._pick_columns:
                    chunk_row, words, kept_pairs = self._compact_pairs(
                        runs, shard_data)
                    layout_span.set(pick_columns=self._pick_columns,
                                    kept_pairs=kept_pairs)
        from photon_ml_tpu.parallel.bucketing import (entity_major_design,
                                                      entity_major_design_over)
        with _upload_span(coordinate_id, mesh) as placed:
            # what sweep_data() hands the rescore of _score_samples_full.
            # Under a mesh the sample axis (the entity-major design's rows
            # of chunks) goes over every device, each shard from the host
            # straight to its chip, padded to the devices' multiple: slot
            # -1 and zero features score 0, a padding sample's position is
            # the layout's last, which is nobody's
            if mesh is not None:
                def put(a, axis=0, **kw):
                    return put_over_chips(_where_it_is(a), mesh, axis, **kw)

                if self._sparse:
                    self._full = dict(
                        slots=slots,
                        x_idx=put(np.asarray(shard_data.indices, np.int32)),
                        x_val=put(np.asarray(shard_data.values, dtype)))
                elif self._em is not None:
                    # a streamed (device) shard is fetched: it is narrow
                    # a gathered padding sample's position is the layout's
                    # last, an un-padded one comes back 0 by ``live``
                    if self._em.back == "gather":
                        way_back = put(
                            way_back, length=self.carry_samples,
                            fill=self._em.lanes * self._em.chunk - 1)
                    else:
                        way_back = jax.tree.map(
                            lambda a: put(a, a.ndim - 1,
                                          length=a.shape[-1]), way_back)
                    self._full = dict(
                        lane_slot=slots,
                        x_em=entity_major_design_over(self._em,
                                                      np.asarray(x), mesh),
                        way_back=way_back)
                else:
                    self._full = dict(slots=slots, x_full=put(
                        x.T, 1) if self._x_full_is_t else put(x))
            elif self._pick_columns:
                # the pairs entity-major [blocks, k, block, EM_ROW], a word a
                # pair (its column and its compact place) and its value,
                # reordered on the device; each chunk's compact lane
                # (score_pairs_em), and its slot for full-width tables
                # (score_pairs_full)
                values = pair_planes(shard_data.values, dtype)
                block = pick_block(self._em, self._pick_columns,
                                   np.dtype(dtype).itemsize)
                self._full = dict(
                    lane_slot=slots, chunk_row=jnp.asarray(chunk_row),
                    x_word=entity_major_pairs(
                        self._em, device_put_counted(words), block),
                    x_val=entity_major_pairs(
                        self._em, device_put_counted(values), block),
                    way_back=jax.tree.map(jnp.asarray, way_back))
                del words, values
            elif self._sparse:
                # full-sample scoring stays sparse: the pairs [n, k] or, by
                # blocks of samples, [blocks, k, r]; never an [n, d_full]
                # densified design (score_samples_sparse[_blocks])
                def pairs(a, as_type):
                    a = np.asarray(a, as_type)
                    return device_put_counted(
                        block_pairs(a, self._pair_blocks)
                        if self._pair_blocks else a)

                self._full = dict(
                    by_block if self._pair_blocks else dict(slots=slots),
                    x_idx=pairs(shard_data.indices, np.int32),
                    x_val=pairs(shard_data.values, dtype))
            elif self._em is not None:
                self._full = dict(
                    lane_slot=slots,
                    x_em=entity_major_design(self._em,
                                             device_put_counted(x.T)),
                    way_back=jax.tree.map(jnp.asarray, way_back))
            else:
                self._full = dict(slots=slots, x_full=device_put_counted(
                    x.T if self._x_full_is_t else x))
            placed(self._full)

        self._refresh_lane_mult()

        put = self._put_entity
        sd = _storage_np_dtype(self.config.storage_dtype)  # host-side cast:
        # transfer + HBM residency are storage-width from the start

        def _narrow(bx):
            if sd is None:
                return bx
            if isinstance(bx, jax.Array):
                # streamed shard: bucket tensors are already device-resident;
                # cast on device (transiently double-width, then freed)
                return bx.astype(sd)
            return np.asarray(bx).astype(sd)

        def _lane_rows(b, windows):
            """What ``offsets_into_lanes`` addresses a class's lanes by:
            the row of every slot, or where the bucketer found run lanes
            (the first ``b.run_lanes`` of each device's share) their starts,
            where it found window lanes (the ``b.window_lanes`` behind
            those) their ``LaneWindows``, and the rows of the lanes behind
            them."""
            rows = np.where(b.rows < 0, 0, b.rows)
            head = b.run_lanes + b.window_lanes
            if not head:
                return dict(rows=put(rows))
            by = dict(rows=put(_share_lanes(rows, lane_multiple, head, None)))
            if b.run_lanes:
                by["run_start"] = put(_share_lanes(
                    rows, lane_multiple, 0, b.run_lanes)[:, 0])
            if windows is not None:
                by["windows"] = jax.tree.map(put, windows)
            return by

        with _upload_span(coordinate_id, mesh) as placed:
            self._dev = [
                dict(x=put(_narrow(b.x)),
                     y=put(b.y), w=put(b.weight),
                     valid=put(b.rows >= 0), **_lane_rows(b, windows))
                for b, windows in zip(solve_buckets, windows_of)
            ]
            placed((self._dev, self._slot_idx_dev))
        # INDEX_MAP/sparse + normalization: project the coordinate context
        # into each entity's compact space (the reference's per-REId
        # contexts, NormalizationContextRDD through the per-entity
        # projectors, IndexMapProjectorRDD.scala:34-262) — gather the factor
        # AND shift vectors through every lane's column map; padded slots get
        # the identity factor 1 / shift 0.  Shift normalization additionally
        # tracks each lane's compact-space INTERCEPT position: the intercept
        # is observed in every active sample, so compaction keeps it, and the
        # per-lane coefficient-space maps fold the margin shift into it.
        # (RANDOM instead shares ONE projected context, baked by
        # _bind_solver.)
        self._norm_fac_dev = None
        self._norm_shift_dev = None
        self._norm_ii_dev = None
        if self._norm_per_lane:
            from photon_ml_tpu.parallel.projection import BucketProjection

            fac = (np.asarray(self._norm.factors, self._dtype)
                   if self._norm.factors is not None
                   else np.ones(self.dim, self._dtype))
            sh = (np.asarray(self._norm.shifts, self._dtype)
                  if self._norm.shifts is not None else None)
            ii = self.config.intercept_index
            lanes_fac, lanes_sh, lanes_ii = [], [], []
            for p, b in zip(self._proj.projections, self.buckets.buckets):
                assert isinstance(p, BucketProjection)
                safe = np.where(p.indices < 0, 0, p.indices)
                obs = p.indices >= 0
                lanes_fac.append(np.where(obs, fac[safe],
                                          1.0).astype(self._dtype))
                if sh is not None:
                    lanes_sh.append(np.where(obs, sh[safe],
                                             0.0).astype(self._dtype))
                    has_ii = np.any(p.indices == ii, axis=1)
                    valid = np.asarray(b.entity_lanes) >= 0
                    if not np.all(has_ii[valid]):
                        raise ValueError(
                            f"coordinate {self.coordinate_id!r}: shift "
                            "normalization under compaction requires the "
                            "intercept column (feature "
                            f"{ii}) observed in every entity's active "
                            "samples, but some entity never observes it")
                    lanes_ii.append(np.argmax(p.indices == ii,
                                              axis=1).astype(np.int32))
            self._norm_fac_np = lanes_fac  # host twins for warm starts
            self._norm_fac_dev = [put(f) for f in lanes_fac]
            if sh is not None:
                self._norm_shift_np = lanes_sh
                self._norm_ii_np = lanes_ii
                self._norm_shift_dev = [put(s) for s in lanes_sh]
                self._norm_ii_dev = [put(i) for i in lanes_ii]

    def _put_entity(self, a):
        """Device-resident per-lane arrays, the entity lane sharded over ALL
        mesh devices (the reference's balanced entity partitioner,
        RandomEffectDatasetPartitioner.scala:30-171): host arrays go
        straight to their shards, nothing is staged whole on the default
        device first."""
        if self.mesh is None:
            return device_put_counted(a)
        return jax.device_put(a, NamedSharding(
            self.mesh, over_chips(self.mesh, a.ndim)))

    def _put_slots(self, slots: np.ndarray) -> Array:
        """The full-sample layout's slot vector (``_slots_under``) on the
        device; under a mesh its sample axis ([n], padded with -1) or its
        rows of chunks ([k, R]) over every device."""
        if self.mesh is None:
            return jnp.asarray(slots)
        if slots.ndim == 2:  # the layout's R is a multiple of the devices
            return put_over_chips(slots, self.mesh, 1, slots.shape[1])
        return put_over_chips(slots, self.mesh, fill=-1)

    def _slots_in_blocks(self, slots: np.ndarray) -> Tuple[dict, int]:
        """A per-sample slot vector as a sparse shard's blocked pairs are
        scored by it (``bucketing.score_samples_sparse_blocks``): the
        entries of ``sweep_data`` it makes, and how many rows of the table
        one block reads (static: the program's shape)."""
        by_block, first, table_rows = block_slots(slots, self._pair_blocks)
        return (dict(slots=jnp.asarray(by_block), first=jnp.asarray(first)),
                table_rows)

    def _compact_pick_columns(self) -> int:
        """D, the widest class's ``d_proj``, where a sparse shard's pairs
        can be scored from the compact lanes (``score_pairs_em``), else 0:
        every class compacted by an index map and no box fill, so an
        entity's published row is exactly zero off its compact columns, and
        a column and a place fit one int32 word."""
        from photon_ml_tpu.parallel.projection import BucketProjection

        maps = self._proj.projections if self._proj is not None else []
        if (not maps or self._box_fill is not None
                or not all(isinstance(p, BucketProjection) for p in maps)):
            return 0
        width = max(p.d_proj for p in maps)
        return width if self.dim << width.bit_length() <= 1 << 31 else 0

    def _compact_pairs(self, runs, shard: SparseShard):
        """``(chunk_row [k_em, R], words [k, n + 1], kept_pairs)`` of the
        entity-major layout ``self._em``: each chunk's lane among every
        class's compact lanes laid side by side (-1: no lane), and the
        pairs' words (``bucketing.pair_words``)."""
        width = self._pick_columns
        classes = self.buckets.buckets
        lane_ids = np.concatenate([np.asarray(b.entity_lanes, np.int64)
                                   for b in classes])
        lane_columns = np.concatenate([
            np.pad(p.indices, ((0, 0), (0, width - p.d_proj)),
                   constant_values=-1) for p in self._proj.projections])
        valid = lane_ids >= 0
        lane_entity = np.where(
            valid, np.searchsorted(self._em.entities, lane_ids), -1)
        row_of = np.full(len(self._em.entities), -1, np.int32)
        row_of[lane_entity[valid]] = np.flatnonzero(valid)
        words, kept = pair_words(shard.indices, runs, lane_entity,
                                 lane_columns, shard.values)
        return self._em.lane_slots(row_of), words, kept

    def _offsets_into_lanes(self, offsets: Array, devs):
        """``gather(bi)``: the residual offsets of bucket ``bi``'s lanes,
        ``where(valid, offsets[rows], 0)``, its run lanes addressed by
        their start and its window lanes by their window's
        (``bucketing.offsets_into_lanes``).  Under a mesh the
        sample-sharded offsets meet entity-sharded lanes: exchange
        ``offsets``, ONE all-gather of the ``[n]`` vector an update and
        each chip's own lane gathers, every class's at once
        (``lanes_of``)."""
        classes = [{k: dev[k]
                    for k in ("rows", "valid", "run_start", "windows")
                    if k in dev} for dev in devs]
        if self.mesh is None:
            def gather(bi):
                with device_scope("entity_gather"):
                    return offsets_into_lanes(offsets, **classes[bi])
            return gather
        return lanes_of(offsets, classes, self.mesh).__getitem__

    def exchange_bytes(self) -> Dict[str, int]:
        """What one update of this coordinate sends over the chips: the
        ``[n]`` offsets (all-gather), the coefficient table (psum), where
        the entity-major scores have to go back to sample order their
        ``[R x 128]`` (all-gather), and the solves' iteration counts (four
        int32 a class, all-reduced)."""
        if self.mesh is None:
            return {}
        size = np.dtype(self._dtype).itemsize
        scores = (self._em.lanes * self._em.chunk * size
                  if self._em is not None and self._em.pos is not None else 0)
        return exchange_bytes(
            self.mesh, {"offsets": self.carry_samples * size,
                        "scores": scores},
            {"publish": len(self._sorted_ids) * self.dim * size,
             "counts": self.num_solves * 4 * 4})

    def _bind_solver(self) -> None:
        # shared-context normalization (IDENTITY projector) bakes into the
        # objective; per-lane contexts (INDEX_MAP, and any sparse shard —
        # whose solve space is always compact) enter the vmapped solve as
        # traced factor arrays instead (see _vsolve below); a RANDOM
        # projection shares ONE context pushed through the Gaussian matrix
        # (reference ProjectionMatrixBroadcast
        # .projectNormalizationContext:102-112), baked like IDENTITY's
        shared_norm = (self._norm if self._norm is not None
                       and self.config.projector == ProjectorType.IDENTITY
                       and not self._sparse
                       else None)
        self._norm_proj = None
        self._norm_proj_intercept = None
        if (self._norm is not None
                and self.config.projector == ProjectorType.RANDOM):
            rp = self._proj.projections[0]  # shared across buckets
            ctx, p_ii = rp.project_normalization(self._norm)
            self._norm_proj = NormalizationContext(
                factors=None if ctx.factors is None
                else jnp.asarray(ctx.factors, self._dtype),
                shifts=None if ctx.shifts is None
                else jnp.asarray(ctx.shifts, self._dtype))
            self._norm_proj_intercept = p_ii
            shared_norm = self._norm_proj
        objective = GLMObjective(loss=loss_for_task(self.task), reg=self.config.reg,
                                 norm=shared_norm or no_normalization())
        self._objective = objective
        self._norm_per_lane = (self._norm is not None and shared_norm is None)
        box = None
        self._box_lanes = None  # per-bucket (lo, hi) [lanes, d_compact] pairs
        self._box_fill = None   # [dim] publish value for unobserved features
        if self.config.constraints:
            compact = (self._sparse
                       or self.config.projector == ProjectorType.INDEX_MAP)
            if self.config.projector == ProjectorType.RANDOM:
                raise ValueError(
                    f"coordinate {self.coordinate_id!r}: box constraints have "
                    "no meaning in a RANDOM-projected solve space (the "
                    "Gaussian matrix mixes features); use IDENTITY or "
                    "INDEX_MAP")
            if not compact:
                box = _box_from_constraints(self.config.constraints, self.dim,
                                            self._dtype, self._norm,
                                            space=self.config.constraint_space)
            else:
                # Compact solve spaces get PER-LANE bounds: the full-space
                # original bounds gathered through each lane's observed-column
                # map (the reference applies its constraintMap in full
                # coefficient space regardless of storage,
                # OptimizationUtils.projectCoefficientsToSubspace; the compact
                # twin of that is bound-per-observed-column).  Padded slots
                # pin to [0, 0].  Unobserved features publish clip(0, lo, hi)
                # — the full-space box optimum of the L2 pull toward 0 —
                # via the back-projection fill.
                from photon_ml_tpu.opt.solve import check_box_support

                check_box_support(self.config.optimizer,
                                  self.config.reg.l1 > 0.0)
                if self._norm is not None and self._norm.shifts is not None:
                    # constraint_space="transformed" does NOT lift this:
                    # a compact solve publishes through per-lane original-
                    # space maps whose intercept fold would have to include
                    # the unobserved-column fill values to match the
                    # reference's full-space semantics — refusing is the
                    # honest call on both settings (MIGRATION.md)
                    raise ValueError(
                        f"coordinate {self.coordinate_id!r}: box constraints "
                        "with shift normalization are not supported under "
                        "compaction (original-space bounds are non-separable "
                        "under shifts; the constraint_space='transformed' "
                        "compat flag covers non-compact coordinates only)")
                if (self.config.constraint_space == "transformed"
                        and self._norm is not None):
                    # scaling-only compact: the per-lane solve applies
                    # bounds with ORIGINAL semantics (lane-factor division
                    # + original-space publish fill) — silently accepting
                    # the flag here would produce exactly the divergence it
                    # exists to prevent
                    raise ValueError(
                        f"coordinate {self.coordinate_id!r}: "
                        "constraint_space='transformed' is not supported "
                        "for compact (sparse/INDEX_MAP) solves under "
                        "normalization — use the IDENTITY projector for "
                        "reference-compat constrained coordinates "
                        "(MIGRATION.md)")
                lo, hi = _box_from_constraints(self.config.constraints,
                                               self.dim, self._dtype)
                lo, hi = np.asarray(lo), np.asarray(hi)
                self._box_fill = np.clip(0.0, lo, hi).astype(self._dtype)
                lanes_box = []
                for p in self._proj.projections:
                    safe = np.where(p.indices < 0, 0, p.indices)
                    lo_c = np.where(p.indices >= 0, lo[safe],
                                    0.0).astype(self._dtype)
                    hi_c = np.where(p.indices >= 0, hi[safe],
                                    0.0).astype(self._dtype)
                    lanes_box.append((jnp.asarray(lo_c), jnp.asarray(hi_c)))
                self._box_lanes = lanes_box
        solve = make_solver(objective, self.config.optimizer,
                            self.config.solver, box=box)

        # reg traced PER LANE (vmapped like the data): λ sweeps reuse this
        # compilation, and per-entity regularization costs nothing extra.
        # Optional per-lane extras ride the same vmap, in a fixed order:
        # normalization factor rows (per-lane contexts), then box lo/hi rows
        # (compact-space constrained solves) — _solve_extras builds the
        # matching argument tuple.
        per_lane_norm = self._norm_per_lane
        per_lane_shift = (per_lane_norm and self._norm.shifts is not None)
        per_lane_box = self._box_lanes is not None

        def _one(w, xx, yy, oo, ww, rr, *ex):
            i = 0
            obj = objective.with_reg(rr)
            fa = None
            if per_lane_norm:
                fa = ex[i]
                i += 1
                sh = None
                if per_lane_shift:
                    sh = ex[i]
                    i += 1
                obj = obj.replace(
                    norm=NormalizationContext(factors=fa, shifts=sh))
            kw = {}
            if per_lane_box:
                lo_r, hi_r = ex[i], ex[i + 1]
                if fa is not None:  # original-space bounds -> solve space
                    lo_r, hi_r = lo_r / fa, hi_r / fa
                kw["box"] = (lo_r, hi_r)
            return solve(w, DenseBatch(x=xx, y=yy, offset=oo, weight=ww),
                         objective=obj, **kw)

        def _vsolve(w0, x_b, y_b, off_b, wt_b, reg, *extras_b):
            return jax.vmap(_one)(w0, x_b, y_b, off_b, wt_b, reg, *extras_b)

        if self.mesh is not None:
            # lanes are independent problems, entity-sharded over every
            # mesh axis: each device solves its own lanes as a local
            # program whose loops run to ITS slowest lane, with no
            # collective (under GSPMD every trip's any-lane-active
            # reduction would be one)
            lanes = over_chips(self.mesh)
            _vsolve = on_chips(_vsolve, self.mesh, lanes, lanes)
        self._vsolve = jax.jit(_vsolve)

        # Narrow dense lanes swap in the structure-of-arrays Newton solver:
        # the vmapped path's [lanes, d] / [lanes, m, d] solver state pads
        # its trailing axis to 128 TPU lanes (32x HBM at d=4 — profiled as
        # 63% of the glmix_chip sweep), while the [d, lanes] Newton state
        # pads at most 2x and converges in a fraction of the iterations.
        # Same strictly convex objective, same convergence contract, same
        # optimum to solver tolerance (opt/newton_soa.py; parity-tested).
        # The bucket device arrays keep their [lanes, ...] layout — the
        # transpose below reads them once per solve call, not per solver
        # iteration — so the variance path and bucket plumbing are
        # untouched.
        from photon_ml_tpu.opt.newton_soa import (soa_eligible,
                                                  solve_newton_soa)

        # The swap wins where the vmapped path's 128-lane padding waste
        # dominates (tiny d, modest caps, many lanes); at larger d/cap the
        # Hessian assembly (d^2/2 weighted column products over the cap)
        # outweighs it.  Measured on a real v5e in 2026-08, before the ledger:
        # glmix_chip (d=4, cap 32, 131k lanes) 2.7x FASTER; glmix2 (d=16,
        # cap 256, 2k lanes) 1.5x SLOWER.  cap*d^2/2 <= 1280 keeps the
        # winning regime: per-iteration Hessian traffic at or below the
        # vmapped path's padded-state traffic (128 lanes x m=10 history).
        # That traffic has since shrunk twice: with the history
        # newest-first (opt/lbfgs.py) a solver trip of the vmapped path
        # over 65,536 lanes x 128 rows x d=16 took 35 ms on a v5e where it
        # had taken 116, and with the line search on the margins it takes
        # 5.8 (PERF.md section 6, PRs 26 and 28); over 24,656 lanes x 32
        # rows 1.0 ms where it took 28.8.  The line itself was not measured
        # again.
        # The SOLVE-space shapes decide: compact sparse buckets and
        # projected (INDEX_MAP / RANDOM) buckets solve at their compact /
        # projected width, which is exactly where narrow dims live — the
        # back-projection and publish plumbing run on res.w and are
        # solver-agnostic.
        solve_shapes = [
            (b.x.shape[1], b.x.shape[2])
            for b in (self._proj.buckets if self._proj is not None
                      else self.buckets.buckets)]
        worst = max((cap * dd * dd for cap, dd in solve_shapes), default=0)
        max_solve_dim = max((dd for _, dd in solve_shapes), default=0)
        self._use_soa = (
            soa_eligible(max_solve_dim, objective.loss.name)
            and worst <= 2 * 1280
            and self._norm is None
            and box is None and self._box_lanes is None
            and not self.config.constraints
            and self.config.reg.l1 == 0.0
            and self.config.optimizer in (OptimizerType.LBFGS,
                                          OptimizerType.TRON))
        storage = (_storage_np_dtype(self.config.storage_dtype)
                   or np.dtype(self._dtype))
        # one lane's problem as the solver will see it (shapes alone)
        cap, dd = solve_shapes[0] if solve_shapes else (0, self.dim)
        rows = jax.ShapeDtypeStruct((cap,), self._dtype)
        self.line_search = "none" if self._use_soa else line_search_kind(
            objective, self.config.optimizer,
            DenseBatch(x=jax.ShapeDtypeStruct((cap, dd), storage), y=rows,
                       offset=rows, weight=rows),
            jax.ShapeDtypeStruct((dd,), self._dtype),
            box if self._box_lanes is None else self._box_lanes)
        if self._use_soa:
            solver_cfg = self.config.solver

            def _solve_lanes(w0, x_b, y_b, off_b, wt_b, l2):
                res = solve_newton_soa(
                    objective.loss, jnp.transpose(w0),
                    jnp.transpose(x_b, (1, 2, 0)), jnp.transpose(y_b),
                    jnp.transpose(off_b), jnp.transpose(wt_b), l2,
                    solver_cfg)
                return res.replace(w=jnp.transpose(res.w))

            if self.mesh is not None:
                # Lanes are independent problems, entity-sharded over every
                # mesh axis: each device solves its own lanes as a local
                # program with no collective (not even the loop
                # conditions' any-lane-active reductions).  It also has to
                # be explicit — Mosaic kernels cannot be partitioned
                # automatically, so under plain GSPMD the pallas Newton step
                # fails at lowering.  check_vma off: see ShardMapObjective.
                lanes = over_chips(self.mesh)
                _solve_lanes = on_chips(_solve_lanes, self.mesh, lanes, lanes)

            def _vsolve_soa(w0, x_b, y_b, off_b, wt_b, reg):
                return _solve_lanes(w0, x_b, y_b, off_b, wt_b, reg.l2)

            self._vsolve = jax.jit(_vsolve_soa)

        kind = self.config.variance
        # BOTH variance kinds are EXACT under observed-column compaction
        # (sparse shards / INDEX_MAP): an unobserved feature's column is
        # identically zero in this entity's data, so the full-space Hessian
        # H = Σ w·l''·x xᵀ + λ2 I is BLOCK-DIAGONAL — the observed block is
        # the compact Hessian and the unobserved block is exactly λ2 I with
        # no cross terms.  Hence SIMPLE (1/diag H) and FULL (diag H⁻¹) both
        # decompose: observed features from the compact computation,
        # unobserved features prior-only 1/λ2.  RANDOM mixes features, so
        # neither is exact there (refused below, as in _bind_solver's
        # RANDOM-variance guard).
        self._compact_variances = (kind != VarianceComputationType.NONE
                                   and (self._sparse or self.config.projector
                                        == ProjectorType.INDEX_MAP))
        if kind != VarianceComputationType.NONE:
            if self.config.projector == ProjectorType.RANDOM:
                raise ValueError(
                    "per-entity variances are not defined under a RANDOM "
                    "projection (the Gaussian matrix mixes features); use "
                    "IDENTITY or INDEX_MAP "
                    f"(coordinate {self.coordinate_id!r})")
            if self._compact_variances and self._norm is not None:
                raise NotImplementedError(
                    "coefficient variances under compaction do not support "
                    "per-entity normalization contexts — drop the "
                    "normalization or use an uncompacted (IDENTITY, dense) "
                    f"layout (coordinate {self.coordinate_id!r})")
            from photon_ml_tpu.opt.solve import compute_variances

            def _vvar(w_b, x_b, y_b, off_b, wt_b, reg):
                return jax.vmap(
                    lambda w, xx, yy, oo, ww, rr: compute_variances(
                        objective.with_reg(rr), w,
                        DenseBatch(x=xx, y=yy, offset=oo, weight=ww), kind)
                )(w_b, x_b, y_b, off_b, wt_b, reg)

            self._vvar = jax.jit(_vvar)
        else:
            self._vvar = None
        self._solver_key = self._make_solver_key()

    def _expand_compact_variances(self, v_compact: Array, bucket_index: int,
                                  lane_reg: Regularization) -> Array:
        """[lanes, d_compact] variances -> [lanes, d_full]: observed features
        carry their computed variance, every other feature is prior-only
        1/λ2 (the per-lane effective λ2, so per-entity multipliers are
        honored).  Exact for BOTH kinds: the full-space Hessian is
        block-diagonal (unobserved columns are identically zero in this
        entity's data), its unobserved block exactly λ2 I — so SIMPLE's
        1/diag(H) and FULL's diag(H⁻¹) are each 1/λ2 there, and the observed
        block's computation is untouched by the unobserved one.  NOTE: the NTV model format stores nonzero-MEAN features
        only (reference sparse storage), so prior-only variances live in the
        in-memory/columnar model but do not survive an NTV save — absent
        features reload as variance 0, the format's "not estimated" marker.
        Padded compact slots route OUT of range and drop — a
        'set' scatter with a duplicate target is order-nondeterministic, so
        letting them collide with a genuinely observed column 0 could
        clobber its variance."""
        idxs = self._proj_dev[bucket_index]  # [lanes, d_compact], -1 padding
        lanes = v_compact.shape[0]
        fill = 1.0 / jnp.maximum(
            jnp.broadcast_to(jnp.asarray(lane_reg.l2, v_compact.dtype),
                             (lanes,)), 1e-30)
        out = jnp.broadcast_to(fill[:, None], (lanes, self.dim))
        safe = jnp.where(idxs < 0, self.dim, idxs)  # out-of-range -> dropped
        return out.at[jnp.arange(lanes)[:, None], safe].set(
            v_compact, mode="drop")

    def _make_solver_key(self) -> tuple:
        c = self.config
        return (c.optimizer, c.solver, c.reg.l1 > 0.0, c.variance,
                c.constraints, c.constraint_space)

    def _refresh_lane_mult(self) -> None:
        """Cache per-bucket (ones, multiplier) lane vectors — constant per
        config, rebuilt only when the config changes (rebind)."""
        mult = dict(self.config.per_entity_l2_multipliers or ())
        self._lane_mult = []
        for b in self.buckets.buckets:
            ones = jnp.ones(b.num_lanes, self._dtype)
            if mult:
                m = jnp.asarray(np.asarray(
                    [mult.get(int(e), 1.0) for e in b.entity_lanes],
                    self._dtype))
            else:
                m = ones
            self._lane_mult.append((ones, m))

    def _solve_extras(self, bi: int, data=None) -> tuple:
        """Per-bucket extra vmapped solver arguments, in ``_one``'s fixed
        order: per-lane normalization factor rows, per-lane shift rows, then
        per-lane box lo/hi rows.  ``data``: sweep_data() pytree when tracing
        (fused program argument convention), None for the host-paced path."""
        out = ()
        if self._norm_per_lane:
            out += ((data["norm_fac"] if data is not None
                     else self._norm_fac_dev)[bi],)
            if self._norm.shifts is not None:
                out += ((data["norm_shift"] if data is not None
                         else self._norm_shift_dev)[bi],)
        if self._box_lanes is not None:
            lo, hi = (data["box"] if data is not None
                      else self._box_lanes)[bi]
            out += (lo, hi)
        return out

    def _lane_regs(self, reg: Regularization) -> List[Regularization]:
        """Per-bucket per-lane Regularization pytrees: the scalar (possibly
        traced) ``reg`` broadcast over lanes, L2 scaled by the per-entity
        multipliers (default 1; padded lanes get 1, they're inert anyway)."""
        return [Regularization(l1=reg.l1 * ones, l2=reg.l2 * m)
                for ones, m in self._lane_mult]

    def data_key(self) -> tuple:
        return _re_data_key(self.config)

    def rebind(self, config: RandomEffectConfig) -> "RandomEffectCoordinate":
        """New optimization settings over the SAME buckets/device arrays.
        Reg-weight-only changes keep the compiled vmapped solver."""
        import copy

        if _re_data_key(config) != _re_data_key(self.config):
            raise ValueError("rebind cannot change the data configuration")
        new = copy.copy(self)
        new.config = config
        if new._make_solver_key() != self._solver_key:
            new._bind_solver()
        if config.per_entity_l2_multipliers != self.config.per_entity_l2_multipliers:
            new._refresh_lane_mult()
        return new

    @staticmethod
    def _dense_init(init):
        """Warm-start models arrive in either random-effect container; the
        warm-start gathers below need the dense stack, so a compact model
        densifies HERE (once per update, logged — at true wide-vocabulary
        scale the caller should warm-start selectively instead)."""
        from photon_ml_tpu.models.game import CompactRandomEffectModel

        if isinstance(init, CompactRandomEffectModel):
            import logging

            logging.getLogger("photon_ml_tpu.coordinate").info(
                "densifying a CompactRandomEffectModel warm start "
                "(%d entities x %d features)", init.num_entities, init.dim)
            return init.to_dense()
        return init

    def _warm_start(self, bucket_index: int, init: RandomEffectModel) -> np.ndarray:
        """Full-dim warm-start lanes, projected into the solve space if needed."""
        b = self.buckets.buckets[bucket_index]
        w0 = np.zeros((b.num_lanes, self.dim), self._dtype)
        for lane, eid in enumerate(b.entity_lanes):
            slot = init.slot_of.get(int(eid)) if eid >= 0 else None
            if slot is not None:
                w0[lane] = init.w_stack[slot]
        if self._proj is not None:
            from photon_ml_tpu.parallel.projection import BucketProjection

            proj = self._proj.projections[bucket_index]
            if isinstance(proj, BucketProjection):
                safe = np.where(proj.indices < 0, 0, proj.indices)
                w0 = np.where(proj.indices >= 0,
                              np.take_along_axis(w0, safe, axis=1), 0.0)
            else:
                # Gaussian projection has no exact inverse; restart cold
                # (zeros are zeros under any normalization of the projected
                # space, so no transformed-space mapping applies either)
                return np.zeros((b.num_lanes, proj.d_proj), self._dtype)
        if self._norm is not None:
            # published models are ORIGINAL-space; solves run transformed
            # (same convention as the fixed effect's update())
            if self._norm_per_lane:
                if self._norm.shifts is not None:
                    # per-lane modelToTransformedSpace: the shift dot folds
                    # into each lane's own compact intercept position.
                    # DELIBERATELY the COMPACT dot (observed columns only):
                    # the compact objective computes margins as
                    # <eff, x_c> - <eff, sh_c> (objective.py margin_shift),
                    # i.e. the unobserved-column data term -w_j*shift_j that
                    # would cancel a full-dim fold was deleted by compaction
                    # — folding the full <w, shifts> here would shift every
                    # margin by sum_unobserved(w_j*shift_j).  Warm-start
                    # mass at unobserved columns is margin-inert on this
                    # entity's data (raw x_j == 0 there), so truncating it
                    # is exact, not lossy (advisor r4, resolved r5).
                    sh = self._norm_shift_np[bucket_index]
                    iis = self._norm_ii_np[bucket_index]
                    dots = np.einsum("ld,ld->l", w0, sh)
                    w0[np.arange(len(w0)), iis] += dots
                w0 = w0 / self._norm_fac_np[bucket_index]
            else:
                n = self._norm
                if n.shifts is not None:
                    ii = self.config.intercept_index
                    w0[:, ii] += w0 @ np.asarray(n.shifts)
                if n.factors is not None:
                    w0 = w0 / np.asarray(n.factors)
        return w0.astype(self._dtype)

    def _lanes_to_original(self, lanes: Array, bucket_index: int,
                           data=None) -> Array:
        """Map a bucket's transformed-space lane vectors to original space
        (the reference applies modelToOriginalSpace per entity problem —
        GeneralizedLinearOptimizationProblem.createModel).  ``data``:
        sweep_data() pytree when tracing, None for the host-paced path."""
        if self._norm is None:
            return lanes
        if self._norm_per_lane:
            fac = (data["norm_fac"] if data is not None
                   else self._norm_fac_dev)[bucket_index]
            eff = lanes * fac
            if self._norm.shifts is not None:
                # fold -<eff, shifts> into each lane's OWN intercept column
                # (NormalizationContext.scala:73-99, per projected context)
                sh = (data["norm_shift"] if data is not None
                      else self._norm_shift_dev)[bucket_index]
                ii_l = (data["norm_ii"] if data is not None
                        else self._norm_ii_dev)[bucket_index]
                adj = -jnp.sum(eff * sh, axis=1)
                eff = eff.at[jnp.arange(eff.shape[0]), ii_l].add(adj)
            return eff
        if self._norm_proj is not None:
            # RANDOM projection: the model leaves the solver in the
            # TRANSFORMED PROJECTED space; the projected context (with its
            # pass-through intercept slot) maps it to the original projected
            # space, and back-projection to full dim happens afterwards —
            # the reference order (createModel in projected space, then
            # projectCoefficientsRDD)
            ii = self._norm_proj_intercept
            return jax.vmap(
                lambda w: self._norm_proj.model_to_original_space(w, ii))(lanes)
        ii = self.config.intercept_index
        return jax.vmap(
            lambda w: self._norm.model_to_original_space(w, ii))(lanes)

    def update(self, total_offsets: np.ndarray, seed: int = 0,
               init: Optional[RandomEffectModel] = None
               ) -> Tuple[RandomEffectModel, List[SolverResult]]:
        init = self._dense_init(init)
        lane_offsets = self._offsets_into_lanes(
            samples_on_device(total_offsets, self.mesh, self._dtype),
            self._dev)
        coeffs = []
        variances = [] if self._vvar is not None else None
        results = []
        lane_regs = self._lane_regs(self.config.reg)
        for bi, (b, dev) in enumerate(zip(self.buckets.buckets, self._dev)):
            solve_dim = dev["x"].shape[2]
            if init is not None:
                w0 = self._put_entity(self._warm_start(bi, init))
            else:
                w0 = self._put_entity(np.zeros((b.num_lanes, solve_dim), self._dtype))
            # residual offsets gathered into the bucket layout
            off_b = lane_offsets(bi).astype(self._dtype)
            # one span + histogram sample per bucket solve, device-accurate
            # (block inside the span — the host-paced loop is per-phase
            # dispatch anyway; the fused sweep is where pipelining lives)
            with obs_span("solve.bucket", coordinate=self.coordinate_id,
                          bucket=bi, lanes=b.num_lanes,
                          soa=self._use_soa):
                t0 = _time.perf_counter()
                res = self._vsolve(w0, dev["x"], dev["y"], off_b,
                                   dev["w"], lane_regs[bi],
                                   *self._solve_extras(bi))
                jax.block_until_ready(res.w)
                get_registry().observe(
                    "solve_bucket_seconds", _time.perf_counter() - t0,
                    coordinate=self.coordinate_id,
                    soa=str(self._use_soa).lower())
            coeffs.append(self._lanes_to_original(res.w, bi))
            results.append(res)
            if variances is not None:
                # per-entity variances, vmapped over the bucket's lanes
                # (reference computes them per SingleNodeOptimizationProblem),
                # at the TRANSFORMED-space iterates, then mapped through the
                # same coefficient transform as the means (createModel:89-95)
                v = self._vvar(res.w, dev["x"], dev["y"],
                               off_b, dev["w"], lane_regs[bi])
                if self._compact_variances:
                    v = self._expand_compact_variances(v, bi, lane_regs[bi])
                variances.append(self._lanes_to_original(v, bi))

        if self._proj is not None:
            coeffs = self._proj.back_project([np.asarray(c) for c in coeffs],
                                             fill=self._box_fill)
        w_stack, slot_of = stacked_coefficients(coeffs, self.buckets)
        var_stack = None
        if variances is not None:
            var_stack, _ = stacked_coefficients(variances, self.buckets)
            var_stack = np.asarray(var_stack)
        model = RandomEffectModel(
            w_stack=np.asarray(w_stack), slot_of=slot_of,
            random_effect_type=self.config.random_effect_type,
            feature_shard=self.config.feature_shard, task=self.task,
            variances=var_stack,
        )
        return self.merge_carry_through(model, init), results

    def merge_carry_through(self, model: RandomEffectModel,
                            init: Optional[RandomEffectModel]
                            ) -> RandomEffectModel:
        """Prior-model entities this update did not retrain (no active data —
        e.g. dropped by the existing-model-aware lower bound, or simply
        absent from this dataset) keep their old coefficients in the
        published model: the reference's leftOuterJoin passthrough
        (RandomEffectCoordinate.scala:114-127)."""
        if init is None:
            return model
        init = self._dense_init(init)
        carried = sorted(eid for eid in init.slot_of
                         if eid not in model.slot_of)
        if not carried:
            return model
        import dataclasses

        # the pipeline's dtype stays authoritative: a float64 avro prior
        # must not upcast a float32 model just because an entity carried
        out_dtype = np.asarray(model.w_stack).dtype
        rows = np.stack([init.w_stack[init.slot_of[eid]]
                         for eid in carried]).astype(out_dtype)
        slot_of = dict(model.slot_of)
        base = len(slot_of)
        for i, eid in enumerate(carried):
            slot_of[eid] = base + i
        w_stack = np.concatenate([np.asarray(model.w_stack), rows])
        var_stack = model.variances
        if var_stack is not None:
            # carried rows keep the prior model's variances when it has
            # them; a variance-less prior contributes zeros (its uncertainty
            # was never computed — 0 is the explicit "not estimated" marker
            # model_io uses for absent variances)
            if init.variances is not None:
                vrows = np.stack([init.variances[init.slot_of[eid]]
                                  for eid in carried]).astype(out_dtype)
            else:
                vrows = np.zeros_like(rows)
            var_stack = np.concatenate(
                [np.asarray(var_stack, vrows.dtype), vrows])
        return dataclasses.replace(model, w_stack=w_stack, slot_of=slot_of,
                                   variances=var_stack)

    def _slots_under(self, slot_of: Dict[int, int],
                     only: Optional[np.ndarray] = None) -> np.ndarray:
        """The full-sample layout's slot vector under ANY slot map (``only``:
        restricted to these entity ids, the rest -1): per sample, or per
        CHUNK of the entity-major layout, where a chunk is one entity's and
        a foreign model costs a lookup per entity, not per sample."""
        slots = _slots_from(slot_of, self._slot_ids)
        if only is not None:
            slots = np.where(np.isin(self._slot_ids, only), slots,
                             -1).astype(np.int32)
        return slots if self._em is None else self._em.lane_slots(slots)

    def _score_full(self, w_stack: np.ndarray, slot_of: Dict[int, int],
                    only: Optional[np.ndarray] = None) -> np.ndarray:
        """Host: every training sample's score under a model's ``w_stack``
        and ``slot_of``: this coordinate's own map, or a foreign one (a
        model trained elsewhere: an entity may be absent from our training
        buckets yet present in the model)."""
        data, table_rows = self._full, self._table_rows
        if only is not None or slot_of != self._slot_of:
            slots = self._slots_under(slot_of, only)
            if self._pair_blocks:  # what a block reads, under THIS map
                by_block, table_rows = self._slots_in_blocks(slots)
                data = dict(data, **by_block)
            else:
                key = "slots" if self._em is None else "lane_slot"
                data = dict(data, **{key: self._put_slots(slots)})
        w = jnp.asarray(np.asarray(w_stack, self._dtype))
        if self._pick_columns:
            # entity-major pairs: the stream comes to the host and back to
            # sample order there (the way back on the device, op by op,
            # held 1.3 GB more device memory at once on a v5e at
            # glmix_userbag_ml20m's size)
            stream = np.asarray(score_pairs_full(
                w, data["lane_slot"], data["x_word"], data["x_val"],
                self._pick_columns.bit_length()))
            return (stream if self._em.pos is None
                    else stream[self._em.pos])[: self._n]
        return np.asarray(self._score_samples_full(
            w, data, table_rows))[: self._n]

    def carry_through_scores(self, init: Optional[RandomEffectModel]
                             ) -> Optional[np.ndarray]:
        if init is None:
            return None
        init = self._dense_init(init)
        carried = np.fromiter(
            (eid for eid in init.slot_of if eid not in self._slot_of),
            np.int64)
        if carried.size == 0:
            return None
        return self._score_full(init.w_stack, init.slot_of, only=carried)

    def score(self, model: RandomEffectModel) -> np.ndarray:
        return self._score_full(model.w_stack, model.slot_of)

    def _score_samples_full(self, w_stack: Array, data,
                            table_rows: Optional[int] = None) -> Array:
        """Every sample's score in whichever layout the full-sample design
        has (``data``: what ``sweep_data`` passes of it): sparse (by rows
        or by blocks of samples, of which one reads ``table_rows`` of the
        table: this coordinate's own where not given), entity-major,
        [d, n] or [n, d] (parallel/bucketing.py); entity-major pairs are
        scored in ``_score_full`` and ``_score_compact``."""
        from photon_ml_tpu.parallel.bucketing import (
            score_samples, score_samples_em, score_samples_sparse,
            score_samples_sparse_blocks, score_samples_t)

        if self._em is not None:
            if self.mesh is not None:
                return score_entity_major(w_stack, data["lane_slot"],
                                          data["x_em"], data["way_back"],
                                          self.mesh)
            return score_samples_em(w_stack, data["lane_slot"], data["x_em"],
                                    data["way_back"])
        if self._pair_blocks:
            return score_samples_sparse_blocks(
                w_stack, data["slots"], data["first"], data["x_idx"],
                data["x_val"], table_rows or self._table_rows)
        if self._sparse:
            score, design = score_samples_sparse, (data["x_idx"],
                                                   data["x_val"])
        else:
            score = score_samples_t if self._x_full_is_t else score_samples
            design = (data["x_full"],)
        if self.mesh is not None:
            # each chip scores its own samples from the replicated table
            return score_in_sample_order(
                score, w_stack, self.mesh, (data["slots"], *design),
                sample_axis=int(self._x_full_is_t))
        return score(w_stack, data["slots"], *design)

    # --- traceable-step interface (game/fused.py) ---
    # State = tuple of per-bucket lane coefficient arrays [(lanes, d), ...].

    @property
    def num_solves(self) -> int:
        return len(self.buckets.buckets)

    def init_sweep_state(self, init: Optional[RandomEffectModel] = None) -> Tuple[Array, ...]:
        init = self._dense_init(init)
        lanes = []
        for bi, b in enumerate(self.buckets.buckets):
            if init is not None:
                lanes.append(self._put_entity(self._warm_start(bi, init)))
            else:
                # cold lanes in the SOLVE space (projected dim per bucket)
                solve_dim = self._dev[bi]["x"].shape[2]
                lanes.append(self._put_entity(
                    np.zeros((b.num_lanes, solve_dim), self._dtype)))
        return tuple(lanes)

    def sweep_data(self):
        """Bucket design matrices, full-sample scoring arrays and (when
        projecting) back-projection arrays, passed into the fused program as
        arguments (see Coordinate.sweep_data)."""
        d = dict(dev=self._dev,
                 proj=self._proj_dev if self._proj is not None else None,
                 norm_fac=self._norm_fac_dev,
                 norm_shift=self._norm_shift_dev, norm_ii=self._norm_ii_dev,
                 box=self._box_lanes,
                 box_fill=None if self._box_fill is None
                 else jnp.asarray(self._box_fill))
        d.update(self._full)
        return d

    def trace_update(self, state: Tuple[Array, ...], offsets: Array,
                     reg: Optional[Regularization] = None,
                     key=None, data=None,
                     iterations_out: Optional[list] = None
                     ) -> Tuple[Tuple[Array, ...], Array]:
        # ``key`` unused: random effects have no per-update stochastic work
        # (down-sampling is a fixed-effect-only config, as in the reference).
        if data is None:
            data = self.sweep_data()
        reg = self.config.reg if reg is None else reg
        lane_regs = self._lane_regs(reg)
        lane_offsets = self._offsets_into_lanes(offsets.astype(self._dtype),
                                                data["dev"])
        new_lanes, iterations = [], []
        for bi, (lanes, dev) in enumerate(zip(state, data["dev"])):
            off_b = lane_offsets(bi)
            with device_scope("entity_solve", f"b{bi}"):
                res = self._vsolve(lanes, dev["x"], dev["y"], off_b,
                                   dev["w"], lane_regs[bi],
                                   *self._solve_extras(bi, data))
                if iterations_out is not None:
                    # an entity's lane holds a row in its first slot; a
                    # padding lane (a mesh's lane multiple) counts for none.
                    # Under a mesh the sum and the maximum over the lanes
                    # cross chips: exchange ``counts``, four scalars a
                    # class, the partitioner's all-reduce
                    with (device_scope("exchange", "counts")
                          if self.mesh is not None
                          else contextlib.nullcontext()):
                        iterations.append(
                            _solve_counts(res, dev["valid"][:, 0]))
            new_lanes.append(res.w)
        if iterations_out is not None:
            iterations_out.append(jnp.stack(iterations))
        if self._pick_columns:
            with device_scope("rescore"):
                score = self._score_compact(new_lanes, data)
            return tuple(new_lanes), score[: self.carry_samples]
        w_stack = self.trace_publish(tuple(new_lanes), data=data)
        with device_scope("rescore"):
            score = self._score_samples_full(
                w_stack, data)[: self.carry_samples]
        return tuple(new_lanes), score

    def _score_compact(self, state: Tuple[Array, ...], data) -> Array:
        """Every sample's score from the lanes as publish back-projects
        them (original space), each class's padded to D and laid side by
        side (``bucketing.score_pairs_em``): no full-width table."""
        from photon_ml_tpu.parallel.bucketing import score_pairs_em

        width = self._pick_columns
        lanes = jnp.concatenate([
            jnp.pad(self._lanes_to_original(w, bi, data=data),
                    ((0, 0), (0, width - w.shape[1])))
            for bi, w in enumerate(state)])
        return score_pairs_em(lanes, data["chunk_row"], data["x_word"],
                              data["x_val"], data["way_back"])

    def trace_publish(self, state: Tuple[Array, ...], data=None) -> Array:
        with device_scope("publish"):
            return self._trace_publish(state, data)

    def _trace_publish(self, state: Tuple[Array, ...], data) -> Array:
        from photon_ml_tpu.parallel.bucketing import stack_bucket_lanes

        if self._norm is not None:
            # original-space lanes BEFORE back-projection/stacking (per-lane
            # context maps live in the compact solve space)
            if data is None:
                data = self.sweep_data()
            state = tuple(self._lanes_to_original(lanes, bi, data=data)
                          for bi, lanes in enumerate(state))
        if self._proj is not None:
            # traced twin of ProjectedBuckets.back_project (margin-exact):
            # lanes return to full dim before stacking.  Projection arrays
            # come through ``data`` so they enter the compiled program as
            # arguments (sweep_data convention), not baked constants.
            if data is None:
                data = self.sweep_data()
            proj = data["proj"]
            with device_scope("backproject"):
                state = tuple(
                    self._traced_back_project(bi, proj[bi], lanes,
                                              fill=data.get("box_fill"))
                    for bi, lanes in enumerate(state))
        if self.mesh is not None:
            return stack_lanes(state, self._slot_idx_dev,
                               len(self._sorted_ids), self.mesh)
        return stack_bucket_lanes(state, self._slot_idx_dev,
                                  len(self._sorted_ids))

    def _traced_back_project(self, bi: int, arr: Array, lanes: Array,
                             fill: Optional[Array] = None) -> Array:
        kind = self._proj_kinds[bi]
        if kind == "random":
            return lanes @ arr.T  # shared Gaussian (ProjectionMatrix.scala:127)
        e = lanes.shape[0]
        if fill is not None:
            # box-constrained compact solve: unobserved features publish
            # clip(0, lo, hi) (BucketProjection.back_project's fill
            # semantics); padded slots route out of range and drop so the
            # 'set' scatter can never clobber a genuinely observed column
            safe = jnp.where(arr < 0, self.dim, arr)
            out = jnp.broadcast_to(fill.astype(lanes.dtype), (e, self.dim))
            out = out.at[jnp.arange(e)[:, None], safe].set(lanes, mode="drop")
            # padding lanes (index row entirely -1) stay zero, matching
            # BucketProjection.back_project — no fill rows for nonexistent
            # entities (today both stacking paths drop them anyway)
            return jnp.where((arr >= 0).any(axis=1)[:, None], out, 0.0)
        # index compaction: scatter each lane's projected slots into full dim;
        # padded slots (idx<0) carry value 0, so colliding on column 0 is inert
        safe = jnp.where(arr < 0, 0, arr)
        vals = jnp.where(arr >= 0, lanes, 0.0)
        out = jnp.zeros((e, self.dim), lanes.dtype)
        return out.at[jnp.arange(e)[:, None], safe].add(vals)

    def export_model(self, published: np.ndarray) -> RandomEffectModel:
        return RandomEffectModel(
            w_stack=np.asarray(published), slot_of=dict(self._slot_of),
            random_effect_type=self.config.random_effect_type,
            feature_shard=self.config.feature_shard, task=self.task)

    def init_sweep_variances(self) -> "Array | Tuple[Array, ...]":
        if self.config.variance == VarianceComputationType.NONE:
            return jnp.zeros(0, self._dtype)
        return tuple(jnp.zeros((b.num_lanes, self.dim), self._dtype)
                     for b in self.buckets.buckets)

    def trace_variances(self, state: Tuple[Array, ...], offsets: Array,
                        reg: Optional[Regularization] = None,
                        key=None, data=None) -> Tuple[Array, ...]:
        """Traced per-entity variances at this update's lane iterates and
        traced ``reg``, vmapped per bucket exactly as the host path's
        update() does."""
        dev_buckets = self._dev if data is None else data["dev"]
        lane_offsets = self._offsets_into_lanes(offsets.astype(self._dtype),
                                                dev_buckets)
        lane_regs = self._lane_regs(self.config.reg if reg is None else reg)
        out = []
        for bi, (lanes, dev) in enumerate(zip(state, dev_buckets)):
            off_b = lane_offsets(bi)
            v = self._vvar(lanes, dev["x"], dev["y"], off_b,
                           dev["w"], lane_regs[bi])
            if self._compact_variances:
                v = self._expand_compact_variances(v, bi, lane_regs[bi])
            out.append(self._lanes_to_original(v, bi))
        return tuple(out)

    def export_variances(self, v) -> np.ndarray:
        var_stack, _ = stacked_coefficients([np.asarray(b) for b in v],
                                            self.buckets)
        return np.asarray(var_stack)

    # --- external (validation) scoring (fused validated sweeps) ---------

    def external_data(self, data: GameData):
        """Held-out slots + design for this coordinate, device-resident
        once.  Slots map the external entity ids through THIS RUN's trained
        slot order (the stacked layout ``trace_publish`` emits); entities
        this run never trained get -1 and score 0 — carried warm-start
        entities are a host-side CONSTANT (``carry_through_scores_on``).

        The design lies as the training twin's does, by the same one rule
        over what the held-out rows show (``parallel/bucketing.py``): under
        ``use_transposed_scoring``'s line row-major ``[n, d]``; over it the
        samples go on the lanes, entity-major where ``entity_major_chunk``
        finds a chunk length for the held-out rows of each entity, else
        transposed ``[d, n]``.  Span ``coord.external_layout`` says which,
        and of the entity-major one how its scores come back to sample
        order (``back``: ``EntityMajorLayout.back``)."""
        from photon_ml_tpu.parallel.bucketing import (entity_major_design,
                                                      entity_major_layout,
                                                      entity_runs,
                                                      use_transposed_scoring)

        shard = data.features[self.config.feature_shard]
        ids = np.asarray(data.id_tags[self.config.random_effect_type],
                         np.int64)
        with obs_span("coord.external_layout",
                      coordinate=self.coordinate_id, rows=len(ids)) as sp:
            if isinstance(shard, SparseShard):
                sp.set(layout="sparse")
                return {"slots": jnp.asarray(_slots_from(self._slot_of, ids)),
                        "x_idx": device_put_counted(shard.indices, np.int32),
                        "x_val": device_put_counted(shard.values,
                                                    self._dtype)}
            n, d = shard.shape
            if not use_transposed_scoring(n, d,
                                          np.dtype(self._dtype).itemsize):
                sp.set(layout="row_major")
                return {"slots": jnp.asarray(_slots_from(self._slot_of, ids)),
                        "x": device_put_counted(shard, self._dtype)}
            x_t = device_put_counted(shard.T, self._dtype)
            em = entity_major_layout(entity_runs(ids))
            if em is None:
                sp.set(layout="transposed")
                return {"slots": jnp.asarray(_slots_from(self._slot_of, ids)),
                        "x_t": x_t}
            if em.pos is None and em.lanes * em.chunk != n:
                em.pos = np.arange(n, dtype=np.int32)  # cut the tail's zeros
            way_back = em.way_back()
            sp.set(layout="entity_major", chunk=em.chunk, lanes=em.lanes,
                   fill=em.fill, **_way_back_says(em, way_back))
            return {"lane_slot": jnp.asarray(em.lane_slots(
                        _slots_from(self._slot_of, em.entities))),
                    "x_em": entity_major_design(em, x_t),
                    "way_back": jax.tree.map(jnp.asarray, way_back)}

    def trace_score_external(self, published: Array, vdata) -> Array:
        """== RandomEffectModel.score on the published stack, in whichever
        layout ``external_data`` chose: the entity-major or transposed
        narrow layouts, a gather + row dot (row-major) or the two-level
        sparse gather."""
        from photon_ml_tpu.parallel.bucketing import (score_samples,
                                                      score_samples_em,
                                                      score_samples_sparse,
                                                      score_samples_t)

        if "x_em" in vdata:
            return score_samples_em(published, vdata["lane_slot"],
                                    vdata["x_em"], vdata["way_back"])
        if "x_t" in vdata:
            return score_samples_t(published, vdata["slots"], vdata["x_t"])
        if "x" in vdata:
            return score_samples(published, vdata["slots"], vdata["x"])
        return score_samples_sparse(published, vdata["slots"],
                                    vdata["x_idx"], vdata["x_val"])

    def carry_through_scores_on(self, init: Optional[RandomEffectModel],
                                data: GameData) -> Optional[np.ndarray]:
        """Carried (never-retrained) entities' contribution on an EXTERNAL
        sample set — ``carry_through_scores``' exact semantics evaluated on
        ``data`` instead of the training samples."""
        from photon_ml_tpu.parallel.bucketing import (score_samples,
                                                      score_samples_sparse)

        if init is None:
            return None
        init = self._dense_init(init)
        carried = np.fromiter(
            (eid for eid in init.slot_of if eid not in self._slot_of),
            np.int64)
        if carried.size == 0:
            return None
        ids = np.asarray(data.id_tags[self.config.random_effect_type],
                         np.int64)
        slots = _slots_from(init.slot_of, ids)
        slots = np.where(np.isin(ids, carried), slots, -1).astype(np.int32)
        w = jnp.asarray(np.asarray(init.w_stack, self._dtype))
        shard = data.features[self.config.feature_shard]
        if isinstance(shard, SparseShard):
            s = score_samples_sparse(
                w, jnp.asarray(slots),
                jnp.asarray(np.asarray(shard.indices, np.int32)),
                jnp.asarray(np.asarray(shard.values, self._dtype)))
        else:
            s = score_samples(w, jnp.asarray(slots),
                              jnp.asarray(np.asarray(shard, self._dtype)))
        return np.asarray(s)

    def tracker_summary(self, trackers) -> dict:
        """Per-entity solve statistics, padded lanes excluded (reference
        RandomEffectOptimizationTracker.scala:158 summary over thousands of
        entity solves)."""
        from photon_ml_tpu.opt.types import summarize_solver_results

        masks = [np.asarray(b.entity_lanes) >= 0 for b in self.buckets.buckets]
        return summarize_solver_results(list(trackers), valid_masks=masks)


def build_coordinate(coordinate_id: str, data: GameData, config: CoordinateConfig,
                     task: TaskType, mesh: Optional[Mesh] = None,
                     norm: Optional[NormalizationContext] = None,
                     seed: int = 0, dtype=np.float32,
                     existing_model_keys: Optional[frozenset] = None) -> Coordinate:
    """Reference CoordinateFactory.build (CoordinateFactory.scala:34-113).

    ``dtype``: compute precision for this coordinate's device arrays; the
    reference computes in JVM float64 throughout — pass ``np.float64`` for
    reference-precision parity, keep the float32 default for TPU throughput.
    ``existing_model_keys``: warm-start entity ids for the random-effect
    lower bound's existing-model semantics (see bucketing._group_rows).
    """
    if np.dtype(dtype).itemsize == 8 and not jax.config.jax_enable_x64:
        raise ValueError(
            f"dtype {np.dtype(dtype).name} requires jax_enable_x64: without it "
            "jax silently truncates every array to 32 bits and the solve would "
            'NOT run at the requested precision — jax.config.update('
            '"jax_enable_x64", True) first (CPU; TPU hardware is 32-bit)')
    if isinstance(config, FixedEffectConfig):
        return FixedEffectCoordinate(coordinate_id, data, config, task, mesh, norm,
                                     dtype=dtype)
    if isinstance(config, RandomEffectConfig):
        return RandomEffectCoordinate(coordinate_id, data, config, task, mesh, seed,
                                      dtype=dtype, norm=norm,
                                      existing_model_keys=existing_model_keys)
    raise TypeError(f"unknown coordinate config {type(config)!r}")
