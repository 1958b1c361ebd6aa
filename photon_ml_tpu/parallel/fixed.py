"""Distributed fixed-effect GLM fitting — the DP hot path.

Reference call stack (SURVEY.md §3.2): FixedEffectCoordinate.updateModel ->
DistributedOptimizationProblem.run -> Optimizer.optimize, where every
objective evaluation costs one driver->executor coefficient broadcast + one
treeAggregate reduction.

TPU-native shape: the ENTIRE solver (L-BFGS/TRON while_loop included) is one
jitted SPMD program over the mesh.  The batch arrives sharded on the ``data``
axis, w0 replicated; GSPMD partitions the margin matmul by rows and inserts
one all-reduce per value+grad evaluation over ICI — the exact communication
pattern of the reference's treeAggregate but with zero per-step weight
shipping and no host round-trip between optimizer iterations.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from photon_ml_tpu.core.batch import Batch, DenseBatch, SparseBatch
from photon_ml_tpu.core.objective import GLMObjective
from photon_ml_tpu.obs.trace import device_scope
from photon_ml_tpu.opt.solve import make_solver
from photon_ml_tpu.opt.types import SolverConfig, SolverResult
from photon_ml_tpu.parallel.mesh import (
    DATA_AXIS,
    FEATURE_AXIS,
    padded_dim,
    replicate,
    shard_batch,
    shard_coefficients,
)
from photon_ml_tpu.types import OptimizerType

Array = jax.Array


class ShardMapObjective:
    """GLMObjective computed as EXPLICIT SPMD: per-shard raw sums + psum.

    The psum over the ``data`` mesh axis is the reference's treeAggregate
    (ValueAndGradientAggregator.scala:248-252) mapped onto ICI.  Two reasons
    to be explicit rather than letting GSPMD partition the math:

    - pallas kernels (ops/fused_glm.py) are custom calls GSPMD cannot
      auto-partition; under shard_map each device runs the kernel on its
      LOCAL rows, so the fused path works multi-chip;
    - the communication pattern is pinned (exactly one all-reduce per
      objective evaluation), not left to the partitioner's cost model.

    Presents the same (reg / value_and_grad / hvp) surface the solvers bind
    (opt/solve.make_solver), so it drops into any of them.  The batch must
    arrive sharded on the leading example axis (parallel/mesh.shard_batch).

    ``value_and_grad`` and ``hvp`` — the two sites a pallas kernel can run
    in — pass ``check_vma=False``: a ``pallas_call`` declares no
    varying-manual-axes type for its outputs, and tracing one under the
    check raises ("vma on jax.ShapeDtypeStruct must not be None"; in
    interpret mode the interpreter's own loop carries fail it as well).
    Every output of those sites is an explicit psum, so the replication
    that the check would have verified holds by construction.
    """

    def __init__(self, objective: GLMObjective, mesh: Mesh, axis: str = DATA_AXIS):
        self.obj = objective
        self.mesh = mesh
        self.axis = axis

    @property
    def reg(self):
        return self.obj.reg

    def with_reg(self, reg) -> "ShardMapObjective":
        """Reg-overridden copy (see GLMObjective.with_reg); used inside a
        trace, so plain construction is fine."""
        return ShardMapObjective(self.obj.with_reg(reg), self.mesh, self.axis)

    def _specs(self, batch: Batch):
        row_sharded = lambda a: P(self.axis, *([None] * (a.ndim - 1)))
        return jax.tree.map(row_sharded, batch)

    def _psum(self, tree):
        """The one all-reduce of an evaluation, named as what crosses chips
        (scope ``photon.exchange.psum``, parallel/mesh.py's vocabulary)."""
        with device_scope("exchange", "psum"):
            return jax.lax.psum(tree, self.axis)

    def value_and_grad(self, w: Array, batch: Batch) -> Tuple[Array, Array]:
        obj, psum = self.obj, self._psum

        def local(w, b):
            # one psum call over the tuple = one pinned fused all-reduce
            return psum(obj.raw_value_and_grad(w, b))

        rv, gr, rs = shard_map(
            local, mesh=self.mesh, in_specs=(P(), self._specs(batch)),
            out_specs=(P(), P(), P()), check_vma=False)(w, batch)
        return obj.finish_value_and_grad(w, rv, gr, rs)

    def hvp(self, w: Array, batch: Batch, v: Array) -> Array:
        obj, psum = self.obj, self._psum

        def local(w, b, v):
            return psum(obj.raw_hvp(w, b, v))

        hv, qs = shard_map(
            local, mesh=self.mesh, in_specs=(P(), self._specs(batch), P()),
            out_specs=(P(), P()), check_vma=False)(w, batch, v)
        return obj.finish_hvp(v, hv, qs)

    # Variance computation (opt/solve.compute_variances) needs the Hessian
    # diagonal / matrix.  Both are sums over examples followed by elementwise
    # (linear) normalization maps, so per-shard values psum exactly — except
    # the L2 term, which every shard adds once; subtract it locally and re-add
    # after the reduction (reference treeAggregate reduces UN-regularized
    # aggregators for the same reason, HessianDiagonalAggregator.scala:128).

    def hessian_diag(self, w: Array, batch: Batch) -> Array:
        obj, psum = self.obj, self._psum

        def local(w, b):
            return psum(obj.hessian_diag(w, b) - obj.reg.l2)

        return shard_map(
            local, mesh=self.mesh, in_specs=(P(), self._specs(batch)),
            out_specs=P())(w, batch) + obj.reg.l2

    def hessian(self, w: Array, batch: Batch) -> Array:
        obj, psum = self.obj, self._psum
        d = w.shape[-1]

        def local(w, b):
            eye = jnp.eye(d, dtype=w.dtype)
            return psum(obj.hessian(w, b) - obj.reg.l2 * eye)

        h = shard_map(
            local, mesh=self.mesh, in_specs=(P(), self._specs(batch)),
            out_specs=P())(w, batch)
        return h + obj.reg.l2 * jnp.eye(d, dtype=h.dtype)


class ShardSparseObjective:
    """Sparse GLM objective with w sharded over the ``feature`` mesh axis.

    The huge-vocabulary path (reference scale story: sparse vectors over
    PalDB 1e8-feature index maps, PalDBIndexMap.scala:16-60): no device holds
    the full coefficient vector.  Each device owns a contiguous block of
    ``shard_d`` coefficients and the batch rows of its ``data`` shard
    (indices stay GLOBAL — the data layout is identical to the replicated-w
    case, so the data path needs no shard-local reindexing pass):

      margins   masked gather from the LOCAL w block (out-of-block slots
                contribute 0) -> one psum over ``feature`` assembles the full
                margin for the shard's rows;
      gradient  per-block masked scatter-add -> one psum over ``data``; the
                result STAYS feature-sharded (P('feature')) — the per-feature
                partial-sum layout the reference gets from treeAggregate
                segments, mapped onto ICI.

    Communication per value+grad evaluation: exactly one feature-axis
    all-reduce of an [n_local] vector + one data-axis all-reduce of the
    [shard_d] block (vs the replicated-w path's single [d] all-reduce — for
    d >> n/D this is the cheaper direction, which is the point).

    All normalization/regularization algebra runs OUTSIDE the shard_map at
    GSPMD level on sharded (d_pad,) vectors (elementwise ops keep the
    sharding; dots psum over ICI).  Scaling-only normalization is supported;
    shifts would densify sparse margins, so they raise — same reason the
    reference recommends scaling-only normalization for sparse data
    (NormalizationType SCALE_WITH_*).
    """

    def __init__(self, objective: GLMObjective, mesh: Mesh, shard_d: int,
                 data_axis: str = DATA_AXIS, feature_axis: str = FEATURE_AXIS):
        if objective.norm.shifts is not None:
            raise ValueError(
                "feature-sharded sparse fitting supports scaling-only "
                "normalization (shifts densify sparse margins)")
        self.obj = objective
        self.mesh = mesh
        self.shard_d = shard_d
        self.data_axis = data_axis
        self.feature_axis = feature_axis

    @property
    def reg(self):
        return self.obj.reg

    def with_reg(self, reg) -> "ShardSparseObjective":
        return ShardSparseObjective(self.obj.with_reg(reg), self.mesh,
                                    self.shard_d, self.data_axis,
                                    self.feature_axis)

    def _specs(self, batch: SparseBatch):
        row_sharded = lambda a: P(self.data_axis, *([None] * (a.ndim - 1)))
        return jax.tree.map(row_sharded, batch)

    def _local_margins(self, blk: Array, b: SparseBatch):
        """(raw margins x·w for local rows — psum over feature, no offset —,
        masked values, local ids).  The ONE definition of the shard-local
        gather/mask rule, shared by every objective pass and by margins()."""
        lo = jax.lax.axis_index(self.feature_axis) * self.shard_d
        lid = b.indices - lo
        ok = (lid >= 0) & (lid < self.shard_d)
        vals = jnp.where(ok, b.values.astype(blk.dtype), 0)
        lid = jnp.clip(lid, 0, self.shard_d - 1)
        z = jax.lax.psum(jnp.sum(vals * blk[lid], axis=-1), self.feature_axis)
        return z, vals, lid

    def _local_parts(self, blk: Array, b: SparseBatch):
        """(full margins incl. offset for local rows, masked values, local ids)."""
        z, vals, lid = self._local_margins(blk, b)
        return z + b.offset, vals, lid

    def _scatter(self, vals: Array, lid: Array, r: Array) -> Array:
        """Local block of X^T r (masked vals make clamped ids contribute 0)."""
        contrib = vals * r[..., None]
        return jnp.zeros((self.shard_d,), contrib.dtype).at[lid].add(contrib)

    def value_and_grad(self, w: Array, batch: SparseBatch) -> Tuple[Array, Array]:
        obj, data, feat = self.obj, self.data_axis, self.feature_axis
        eff = obj.norm.effective_coefficients(w)  # elementwise: stays sharded

        def local(eff_blk, b):
            z, vals, lid = self._local_parts(eff_blk, b)
            z = jnp.where(b.weight > 0, z, 0.0)  # core masking contract
            l, d1 = obj.loss.loss_and_d1(z, b.y)
            r = b.weight * d1
            return (jax.lax.psum(jnp.sum(b.weight * l), data),
                    jax.lax.psum(self._scatter(vals, lid, r), data),
                    jax.lax.psum(jnp.sum(r), data))

        rv, gr, rs = shard_map(
            local, mesh=self.mesh, in_specs=(P(feat), self._specs(batch)),
            out_specs=(P(), P(feat), P()))(eff, batch)
        return obj.finish_value_and_grad(w, rv, gr, rs)

    def hvp(self, w: Array, batch: SparseBatch, v: Array) -> Array:
        obj, data, feat = self.obj, self.data_axis, self.feature_axis
        eff_w = obj.norm.effective_coefficients(w)
        eff_v = obj.norm.effective_coefficients(v)

        def local(ew_blk, ev_blk, b):
            z, vals, lid = self._local_parts(ew_blk, b)
            z = jnp.where(b.weight > 0, z, 0.0)
            mv = jax.lax.psum(jnp.sum(vals * ev_blk[lid], axis=-1), feat)
            q = b.weight * obj.loss.d2(z, b.y) * mv
            return (jax.lax.psum(self._scatter(vals, lid, q), data),
                    jax.lax.psum(jnp.sum(q), data))

        hv, qs = shard_map(
            local, mesh=self.mesh,
            in_specs=(P(feat), P(feat), self._specs(batch)),
            out_specs=(P(feat), P()))(eff_w, eff_v, batch)
        return obj.finish_hvp(v, hv, qs)

    def margins(self, w: Array, batch: SparseBatch) -> Array:
        """Raw margins x·w of a feature-sharded w (same contract as
        Batch.margins: no offset, no normalization shift).  One [n_local]
        psum over the feature axis — the pinned-communication alternative to
        letting GSPMD all-gather the full [d_pad] coefficient vector for the
        gather in SparseBatch.margins.  Used by the fused sweep's re-scoring
        of a feature-sharded coordinate (game/coordinate.trace_update)."""
        def local(blk, b):
            return self._local_margins(blk, b)[0]

        return shard_map(
            local, mesh=self.mesh,
            in_specs=(P(self.feature_axis), self._specs(batch)),
            out_specs=P(self.data_axis))(w, batch)

    def hessian_diag(self, w: Array, batch: SparseBatch) -> Array:
        obj, data, feat = self.obj, self.data_axis, self.feature_axis
        eff = obj.norm.effective_coefficients(w)

        def local(eff_blk, b):
            z, vals, lid = self._local_parts(eff_blk, b)
            z = jnp.where(b.weight > 0, z, 0.0)
            q = b.weight * obj.loss.d2(z, b.y)
            return jax.lax.psum(self._scatter(vals * vals, lid, q), data)

        diag = shard_map(
            local, mesh=self.mesh, in_specs=(P(feat), self._specs(batch)),
            out_specs=P(feat))(eff, batch)
        if obj.norm.factors is not None:
            diag = diag * obj.norm.factors * obj.norm.factors
        return diag + obj.reg.l2

    def hessian(self, w: Array, batch: SparseBatch) -> Array:
        raise NotImplementedError(
            "FULL variance needs the dense d x d Hessian — not meaningful at "
            "feature-sharded scale; use SIMPLE (diagonal) variances")


def fit_fixed_effect(
    objective: GLMObjective,
    batch: Batch,
    w0: Array,
    mesh: Mesh,
    optimizer: OptimizerType = OptimizerType.LBFGS,
    config: Optional[SolverConfig] = None,
    box: Optional[Tuple[Array, Array]] = None,
    batch_presharded: bool = False,
    feature_sharded: bool = False,
) -> SolverResult:
    """Fit one fixed-effect GLM coordinate over the mesh.

    ``batch_presharded``: skip the device_put when the caller already laid the
    batch out (the coordinate-descent loop places data once and reuses it).

    ``feature_sharded``: shard w (and the dense design matrix's columns) over
    the mesh's ``feature`` axis for huge-d problems — no device holds the full
    coefficient vector, and each objective evaluation's margin contraction /
    per-feature gradient partial sums become GSPMD-inserted collectives over
    ICI.  This is the TPU analog of the reference keeping 1e8-feature models
    out of any single JVM heap (PalDB index maps + treeAggregate, SURVEY §5).
    The returned w is sliced back to the caller's d (padding is trimmed).
    """
    d = int(w0.shape[0])
    if not batch_presharded:
        batch = shard_batch(batch, mesh,
                            feature_axis=FEATURE_AXIS if feature_sharded else None)
    rep = replicate(mesh)
    if feature_sharded:
        d_pad = padded_dim(d, mesh)
        if isinstance(batch, DenseBatch) and batch.x.shape[-1] != d_pad:
            raise ValueError(
                f"feature-sharded batch has {batch.x.shape[-1]} feature "
                f"columns but w pads to {d_pad}; preshard with "
                f"shard_batch(..., feature_axis=FEATURE_AXIS)")
        if d_pad != d:
            # Pad every (d,)-shaped companion of w so padded slots stay
            # pinned at 0: box bounds pad with [0, 0], normalization factors
            # with 1 (identity scale) and shifts with 0 (no shift).
            pad = d_pad - d
            if box is not None:
                box = (jnp.pad(box[0], (0, pad)), jnp.pad(box[1], (0, pad)))
            norm = objective.norm
            if norm.factors is not None or norm.shifts is not None:
                objective = objective.replace(norm=norm.replace(
                    factors=None if norm.factors is None
                    else jnp.pad(norm.factors, (0, pad), constant_values=1.0),
                    shifts=None if norm.shifts is None
                    else jnp.pad(norm.shifts, (0, pad)),
                ))
        w0 = shard_coefficients(w0, mesh)
    else:
        w0 = jax.device_put(w0, rep)
    if feature_sharded:
        if isinstance(batch, SparseBatch):
            # Global-id sparse rows + blocked w: explicit shard_map objective
            # (masked gather/scatter per block — see ShardSparseObjective).
            # Solver state stays P("feature") via propagation from w0.
            sm = ShardSparseObjective(objective, mesh,
                                      d_pad // mesh.shape[FEATURE_AXIS])
            solve = make_solver(sm, optimizer, config, box=box)
            # photonlint: disable=sharding-annotation -- solver state stays
            # P("feature") via propagation from the sharded w0; the result
            # pytree mixes [d_pad] lanes with scalar diagnostics, so one
            # broadcast out_shardings spec cannot express the layout
            fitted = jax.jit(solve)
        else:
            # w stays P("feature") throughout; sharding propagates from
            # inputs and GSPMD inserts the feature-axis contractions.
            solve = make_solver(objective, optimizer, config, box=box)
            # photonlint: disable=sharding-annotation -- same propagation
            # contract as the sparse branch above: w0 pins P("feature")
            fitted = jax.jit(solve)
    else:
        # Explicit SPMD (one psum per evaluation); the caller's fused flag is
        # honored as-is — under shard_map the pallas kernels run per-device
        # on local rows, so fused=True works multi-chip too.
        sm = ShardMapObjective(objective, mesh)
        solve = make_solver(sm, optimizer, config, box=box)
        fitted = jax.jit(solve, out_shardings=rep)
    result = fitted(w0, batch)
    if feature_sharded and result.w.shape[0] != d:
        result = result.replace(w=result.w[:d])
    return result
