"""GLM objective: weighted-sum pointwise loss over a batch + smooth regularization.

Reference contract: photon-lib .../function/ObjectiveFunction.scala:25-74
(value / gradient / hessianVector / hessianDiagonal / hessianMatrix) with the
four aggregators (ValueAndGradientAggregator.scala, HessianVectorAggregator.scala,
HessianDiagonalAggregator.scala:128, HessianMatrixAggregator.scala:129).

Where the reference streams examples through mutable aggregator objects and
merges them via Spark ``treeAggregate``, here each quantity is one closed-form
batched expression — XLA fuses the elementwise loss into the margin matmul, and
the distributed version is exactly this code inside ``shard_map`` + ``psum``
(see photon_ml_tpu.parallel).  The abstract ``Data``/``Coefficients`` duality of
the reference (RDD vs local Iterable, ObjectiveFunction.scala:27-28) collapses:
the SAME function is psum'd across the mesh for fixed effects and ``vmap``-ed
over entity blocks for random effects.

Normalization follows the effective-coefficient + margin-shift algebra
(ValueAndGradientAggregator.scala:36-49) so the raw (sparse) design matrix is
never transformed; see core/normalization.py for the identities.

Note on semantics: objectives are weighted SUMS (not means), matching the
reference; convergence tolerances are relative so scale cancels.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import struct

from photon_ml_tpu.core.batch import Batch, DenseBatch, SparseBatch
from photon_ml_tpu.core.losses import PointwiseLoss
from photon_ml_tpu.core.normalization import NormalizationContext, no_normalization
from photon_ml_tpu.core.regularization import Regularization

Array = jax.Array


def _xt_dot(batch: Batch, r: Array, dim: int) -> Array:
    """X^T r against the raw design matrix (the gradient's scatter/reduce).

    Written as the LEFT product r @ X — the same contraction, but without an
    explicit transpose: XLA TPU folds either form into dot_general dimension
    numbers, while XLA *CPU* executes ``x.T @ r`` as a cache-hostile
    column-major walk (measured 20x slower than ``r @ x`` at [512k, 256] —
    the whole-solver fallback cost, since this runs once per L-BFGS/TRON
    function evaluation).

    Mixed precision mirrors DenseBatch.margins: narrow-stored x with MXU
    operands at storage width, accumulation/result at the residual's width."""
    if isinstance(batch, DenseBatch):
        if batch.x.dtype != r.dtype:
            return jnp.matmul(r.astype(batch.x.dtype), batch.x,
                              preferred_element_type=r.dtype)
        return r @ batch.x
    # Row-padded COO: scatter-add each value*r into its feature slot.  Padded
    # slots have value 0 so they contribute nothing wherever they point.
    contrib = batch.values.astype(r.dtype) * r[..., None]
    return jnp.zeros((dim,), contrib.dtype).at[batch.indices].add(contrib)


@struct.dataclass
class GLMObjective:
    """value / gradient / hvp / hessian_diag / hessian for one GLM coordinate.

    Pure-functional: all methods are (w, batch) -> arrays, jit/vmap/shard_map
    friendly.  ``loss`` and shapes are static; ``reg`` and ``norm`` are traced
    pytree leaves (so reg-path sweeps don't recompile).
    """

    loss: PointwiseLoss = struct.field(pytree_node=False)
    reg: Regularization = Regularization()
    norm: NormalizationContext = struct.field(default_factory=no_normalization)
    # Opt-in pallas fused kernels (ops/fused_glm.py): X streams through VMEM
    # once per value_and_grad / hvp instead of 2-3 XLA passes.  Dense batches
    # on TPU with lane-aligned dim only; silently identical math otherwise.
    fused: bool = struct.field(pytree_node=False, default=False)

    def with_reg(self, reg: Regularization) -> "GLMObjective":
        """Same objective, different (possibly traced) regularization weights
        — the vehicle for recompile-free reg-path sweeps."""
        return self.replace(reg=reg)

    @staticmethod
    def _fused_eligible(batch: Batch, w: Array = None) -> bool:
        """Trace-time gate for the pallas kernels; ineligible batches fall
        through to the reference XLA path below (single home for that math).

        Narrow float storage (bf16/f16 x against an f32 solver state) IS
        eligible: the callers cast the effective coefficients down to
        storage width, exactly mirroring DenseBatch.margins' mixed-precision
        contract (both MXU operands at storage width, f32 accumulation), so
        the kernel keeps the single-HBM-pass advantage at half the bytes.
        Any other dtype mix (e.g. f64 x / f32 w) stays on the XLA path."""
        from photon_ml_tpu.ops.fused_glm import eligible, storage_narrowing_ok

        if (w is not None and isinstance(batch, DenseBatch)
                and not storage_narrowing_ok(batch.x.dtype, w.dtype)):
            return False
        return eligible(batch)

    # -- margins ----------------------------------------------------------------

    def margins(self, w: Array, batch: Batch) -> Array:
        eff = self.norm.effective_coefficients(w)
        return batch.margins(eff) + batch.offset + self.norm.margin_shift(w)

    def _safe_margins(self, w: Array, batch: Batch) -> Array:
        """Margins with weight-0 (padded) rows zeroed.

        Guarantees the masking contract for unbounded losses: a garbage row
        with weight 0 must not poison reductions via 0 * inf = NaN (e.g.
        poisson exp(1e6)).  Zeroing z BEFORE the loss keeps every pointwise
        loss finite on padded rows.
        """
        z = self.margins(w, batch)
        return jnp.where(batch.weight > 0, z, 0.0)

    # -- objective value ---------------------------------------------------------

    def raw_value(self, w: Array, batch: Batch) -> Array:
        """Weighted loss sum, NO regularization (needed by eval / tracking)."""
        z = self._safe_margins(w, batch)
        return jnp.sum(batch.weight * self.loss.loss(z, batch.y))

    def l2_term(self, w: Array) -> Array:
        return 0.5 * self.reg.l2 * jnp.vdot(w, w)

    def l1_term(self, w: Array) -> Array:
        return self.reg.l1 * jnp.sum(jnp.abs(w))

    def value(self, w: Array, batch: Batch) -> Array:
        """Smooth objective: loss sum + L2 (L1 lives in OWLQN, as in reference)."""
        return self.raw_value(w, batch) + self.l2_term(w)

    # -- gradient ----------------------------------------------------------------

    def _chain(self, g_raw: Array, r_sum: Array) -> Array:
        """Apply normalization chain rule to a raw-space reduction X^T r.

        dmargin/dw = factor * (x - shift)  =>  g = factor*(X^T r - (Σr)·shift).
        """
        g = g_raw
        if self.norm.shifts is not None:
            g = g - r_sum * self.norm.shifts
        if self.norm.factors is not None:
            g = g * self.norm.factors
        return g

    def raw_value_and_grad(self, w: Array, batch: Batch) -> Tuple[Array, Array, Array]:
        """(Σ wt·l, X^T r, Σ r) — raw-space sums with NO regularization or
        normalization chain applied.  These are plain data-sums, so SPMD
        callers (parallel/fixed.ShardMapObjective) psum them across shards
        before finishing with ``finish_value_and_grad``."""
        if self.fused and self._fused_eligible(batch, w):
            from photon_ml_tpu.ops.fused_glm import fused_value_and_grad

            # storage-width effective coefficients: for narrow-stored x this
            # is DenseBatch.margins' mixed contract (bf16 MXU operands, f32
            # accumulation inside the kernel); a no-op for uniform dtypes
            eff = self.norm.effective_coefficients(w).astype(batch.x.dtype)
            raw_val, g_raw, r_sum = fused_value_and_grad(
                self.loss, eff, batch,
                margin_shift=self.norm.margin_shift(w))
            return (raw_val.astype(w.dtype), g_raw.astype(w.dtype),
                    r_sum.astype(w.dtype))
        return self._raw_sums_at(self._safe_margins(w, batch), batch,
                                 w.shape[-1])

    def _raw_sums_at(self, z: Array, batch: Batch, dim: int
                     ) -> Tuple[Array, Array, Array]:
        """raw_value_and_grad's XLA tail, from safe margins ``z``: ONE read
        of the design, for ``X^T r``."""
        l, d1 = self.loss.loss_and_d1(z, batch.y)
        r = batch.weight * d1
        return jnp.sum(batch.weight * l), _xt_dot(batch, r, dim), jnp.sum(r)

    def finish_value_and_grad(self, w: Array, raw_val: Array, g_raw: Array,
                              r_sum: Array) -> Tuple[Array, Array]:
        """Apply normalization chain rule + regularization to raw sums."""
        val = raw_val + self.l2_term(w)
        g = self._chain(g_raw, r_sum) + self.reg.l2 * w
        return val, g

    def value_and_grad(self, w: Array, batch: Batch) -> Tuple[Array, Array]:
        """Reference ValueAndGradientAggregator.calculateValueAndGradient:240-255,
        collapsed to one fused pass."""
        return self.finish_value_and_grad(w, *self.raw_value_and_grad(w, batch))

    def gradient(self, w: Array, batch: Batch) -> Array:
        return self.value_and_grad(w, batch)[1]

    # -- the objective along a direction, on the margins ---------------------------

    def value_grad_margins(self, w: Array, batch: Batch
                           ) -> Tuple[Array, Array, Array]:
        """``value_and_grad`` by its XLA path, and the safe margins it was
        computed from: where a search ``along`` the margins starts."""
        return self._value_grad_from(w, self._safe_margins(w, batch), batch)

    def _value_grad_from(self, w: Array, z: Array, batch: Batch
                         ) -> Tuple[Array, Array, Array]:
        """(value, gradient, z) at ``w``, whose safe margins are ``z``."""
        return (*self.finish_value_and_grad(
            w, *self._raw_sums_at(z, batch, w.shape[-1])), z)

    def along(self, w: Array, z: Array, p: Array, batch: Batch):
        """The objective along ``w + alpha p`` without reading the design
        again.  The margins are affine in the coefficients (normalization
        included): with ``z`` the safe margins at ``w`` and ``u = X p``,
        ``z(w + alpha p) = z + alpha u``, and

            phi(alpha)  = Σ weight·l(z + alpha u, y) + (l2/2)|w + alpha p|²
            phi'(alpha) = Σ weight·l'(z + alpha u, y)·u + l2 (w + alpha p)·p

        are elementwise work over [rows].  Returns ``(phi, value_and_grad)``:
        ``phi(alpha) -> (phi, phi')`` reads z, u, y and weight;
        ``value_and_grad(alpha) -> (value, gradient, z + alpha u)`` at
        ``w + alpha p`` is value_and_grad's own XLA tail from those margins.
        ONE read of the design to build the pair (``u``) and one for a
        gradient (``X^T r``).  The caller carries the margins from step to
        step (``z + alpha u`` is the next ``z``), so the search, the
        gradient and the next search see the same numbers; what they drift
        from ``X w`` by is a rounding of ``alpha u`` a step (float32, 30
        steps: under 1e-6 of the largest margin,
        tests/test_margin_linesearch.py).  Rows of weight 0 are zero in z
        and zeroed in u (_safe_margins' contract: 0 * inf must not reach a
        reduction).  The XLA path only: the fused kernel reads the design
        once an evaluation as it is (opt/solve.py's rule)."""
        u = self.norm.margin_shift(p) + batch.margins(
            self.norm.effective_coefficients(p))
        u = jnp.where(batch.weight > 0, u, 0.0)
        l2 = self.reg.l2
        ww, wp, pp = jnp.vdot(w, w), jnp.vdot(w, p), jnp.vdot(p, p)

        def phi(alpha: Array) -> Tuple[Array, Array]:
            l, d1 = self.loss.loss_and_d1(z + alpha * u, batch.y)
            return (jnp.sum(batch.weight * l)
                    + 0.5 * l2 * (ww + alpha * (2.0 * wp + alpha * pp)),
                    jnp.sum(batch.weight * d1 * u) + l2 * (wp + alpha * pp))

        def value_and_grad(alpha: Array) -> Tuple[Array, Array, Array]:
            return self._value_grad_from(w + alpha * p, z + alpha * u, batch)

        return phi, value_and_grad

    # -- Hessian-vector product --------------------------------------------------

    def raw_hvp(self, w: Array, batch: Batch, v: Array) -> Tuple[Array, Array]:
        """(X^T q, Σ q) raw sums — psum-able like raw_value_and_grad."""
        if self.fused and self._fused_eligible(batch, w):
            from photon_ml_tpu.ops.fused_glm import fused_hvp

            # storage-width operands (see raw_value_and_grad)
            eff = self.norm.effective_coefficients(w).astype(batch.x.dtype)
            eff_v = self.norm.effective_coefficients(v).astype(batch.x.dtype)
            hv_raw, q_sum = fused_hvp(
                self.loss, eff, eff_v, batch,
                margin_shift=self.norm.margin_shift(w),
                v_shift=self.norm.margin_shift(v))
            return hv_raw.astype(w.dtype), q_sum.astype(w.dtype)
        z = self._safe_margins(w, batch)
        eff_v = self.norm.effective_coefficients(v)
        # margin directional derivative: factor*(x - shift)·v
        mv = batch.margins(eff_v)
        if self.norm.shifts is not None:
            mv = mv - jnp.vdot(eff_v, self.norm.shifts)
        q = batch.weight * self.loss.d2(z, batch.y) * mv
        return _xt_dot(batch, q, w.shape[-1]), jnp.sum(q)

    def finish_hvp(self, v: Array, hv_raw: Array, q_sum: Array) -> Array:
        return self._chain(hv_raw, q_sum) + self.reg.l2 * v

    def hvp(self, w: Array, batch: Batch, v: Array) -> Array:
        """H·v = Xn^T diag(weight · l'') Xn v + l2·v
        (reference HessianVectorAggregator.calcHessianVector:30-80)."""
        return self.finish_hvp(v, *self.raw_hvp(w, batch, v))

    # -- Hessian diagonal / full matrix (variance computation) --------------------

    def hessian_diag(self, w: Array, batch: Batch) -> Array:
        """diag(H) = Σ weight·l''·x'_j²  (reference HessianDiagonalAggregator.scala:128;
        unlike the reference, normalization IS supported)."""
        z = self._safe_margins(w, batch)
        q = batch.weight * self.loss.d2(z, batch.y)
        d = w.shape[-1]
        if isinstance(batch, DenseBatch):
            x2 = _xt_dot(batch.replace(x=batch.x * batch.x), q, d)
            x1 = _xt_dot(batch, q, d) if self.norm.shifts is not None else None
        else:
            b2 = batch.replace(values=batch.values * batch.values)
            x2 = _xt_dot(b2, q, d)
            x1 = _xt_dot(batch, q, d) if self.norm.shifts is not None else None
        diag = x2
        if self.norm.shifts is not None:
            s = self.norm.shifts
            diag = x2 - 2.0 * s * x1 + s * s * jnp.sum(q)
        if self.norm.factors is not None:
            diag = diag * self.norm.factors * self.norm.factors
        return diag + self.reg.l2

    def hessian(self, w: Array, batch: Batch) -> Array:
        """Full d×d Hessian (FULL variance only; reference
        HessianMatrixAggregator.scala:129).  Dense-materializes x — small d only."""
        dense = batch if isinstance(batch, DenseBatch) else batch.to_dense()
        z = jnp.where(dense.weight > 0, self.margins(w, dense), 0.0)
        q = dense.weight * self.loss.d2(z, dense.y)
        xn = dense.x
        if self.norm.shifts is not None:
            xn = xn - self.norm.shifts
        if self.norm.factors is not None:
            xn = xn * self.norm.factors
        h = (xn * q[:, None]).T @ xn
        return h + self.reg.l2 * jnp.eye(w.shape[-1], dtype=h.dtype)

    # -- predictions ---------------------------------------------------------------

    def scores(self, w: Array, batch: Batch) -> Array:
        """Raw margins (coordinate-descent residual currency)."""
        return self.margins(w, batch)

    def means(self, w: Array, batch: Batch) -> Array:
        """Inverse-link predictions (reference GeneralizedLinearModel.computeMean)."""
        return self.loss.mean(self.margins(w, batch))
