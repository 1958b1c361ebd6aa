"""Fused GLM objective kernels (pallas TPU).

One optimizer iteration reads X twice under plain XLA (z = X@w, then
g = X^T r) and three times for TRON's Hv (z, mv = X@v, X^T q).  These kernels
tile X into row blocks and do all per-block work while the block is resident
in VMEM, so X streams from HBM exactly once per call:

  fused_value_and_grad:  (value, X^T r, sum r)   in one pass
  fused_hvp:             (X^T q,  sum q)         in one pass (z and X@v fused)

Raw-space outputs: callers (GLMObjective) apply the normalization chain rule
and regularization on the O(d) results — the same split the reference uses
(ValueAndGradientAggregator keeps normalization algebra outside the per-datum
hot loop via effectiveCoefficients + marginShift, scala:36-49).

Grid iterations on TPU run sequentially on a core, so accumulating into the
same output block across steps (init at program_id 0) is race-free.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from photon_ml_tpu.core.batch import DenseBatch
from photon_ml_tpu.core.losses import PointwiseLoss

Array = jax.Array

_LANE = 128  # TPU lane width: last dim of X blocks must be a multiple


@functools.cache
def has_tpu() -> bool:
    """Whether the default backend is a TPU.  A backend that fails to
    initialise raises here — it must not read as "no TPU" and quietly route
    every kernel to XLA."""
    return jax.devices()[0].platform == "tpu"


# Mosaic's default scoped VMEM is 16 MiB per core.  The lane-blocked kernels
# (ops/soa_newton.py, ops/compact_score.py) budget half of it for their
# pipelined (double-buffered) operand blocks; the rest is headroom for the
# kernel's own temporaries.
VMEM_BLOCK_BUDGET_BYTES = 8 << 20

_MAX_ROW_BYTES = 16 << 10  # VMEM rule: one design row at storage width —
# d <= 4096 in f32, 8192 in bf16.  Besides the double-buffered (128, d) X
# tile and the (_NACC, d) accumulator, the (d, 1)/(d, 2) coefficient block
# pads its lane axis to 128, so it costs as much as the X tile: compiling
# d=8192 in f32 for a v5e asked for 23.9 MiB of the 16 MiB scoped VMEM.


def _pick_block_rows(n: int, d: int, itemsize: int = 4,
                     vmem_budget_bytes: int = 1 << 20) -> int:
    """Multiple of 128: block_rows is the LANE dim of the (3, bn) yow block
    (and the sublane dim of the X block), so 128 is the only always-legal
    granule.  Budget counts only the X tile; double-buffering + accumulators
    bring actual VMEM use to ~3-4x this, against the ~16MB/core limit.
    ``itemsize`` is X's storage width — bf16 tiles carry twice the rows in
    the same VMEM, halving grid steps.

    IDEMPOTENT under its own padding: pick(pad(n, pick(n))) == pick(n), so a
    caller that pre-pads once (FixedEffectCoordinate) never re-pads per call.
    """
    budget_rows = max(_LANE, (vmem_budget_bytes // max(itemsize * d, 1)
                              // _LANE) * _LANE)
    if n <= budget_rows:
        return int(-(-max(n, 1) // _LANE) * _LANE)  # one block: ceil to 128
    return int(budget_rows)


def _pad_rows(batch: DenseBatch, block_rows: int) -> DenseBatch:
    """Pad the example axis to a block multiple with weight-0 rows."""
    n = batch.num_examples
    pad = (-n) % block_rows
    if pad == 0:
        return batch
    return DenseBatch(
        x=jnp.pad(batch.x, ((0, pad), (0, 0))),
        y=jnp.pad(batch.y, (0, pad)),
        offset=jnp.pad(batch.offset, (0, pad)),
        weight=jnp.pad(batch.weight, (0, pad)),
    )


# From this many kernel blocks on (a block holds about 1 MiB of X: 64 MiB),
# a design whose rows do not divide is run where it lies rather than padded
_IN_PLACE_BLOCKS = 64


def runs_in_place(n: int, block_rows: int) -> bool:
    """Whether a batch of ``n`` rows meets the kernels' grid unpadded: its
    rows do not divide into blocks, and it has MANY blocks, so that padding
    it, which copies all of it, is what costs (6.7 GB for 13.0M rows x 128
    float32, beside the original its owner still holds).  A smaller batch
    is padded, once by its owner (``FixedEffectCoordinate``) or here."""
    return n % block_rows != 0 and n >= _IN_PLACE_BLOCKS * block_rows


def _blocks_in_place(batch: DenseBatch, block_rows: int):
    """How a batch meets the kernels' grid: ``(batch to run, grid length,
    remainder)``.  Where ``runs_in_place`` says so, the grid covers the
    whole blocks where they lie, and the last ``n % block_rows`` rows come
    back as a batch of their own, one small slice, for a second call (the
    ``fused_glm_tail_*`` kernels: a pass over the design is one call of the
    main kernel).  Every other batch is padded and has no remainder."""
    n = batch.num_examples
    if not runs_in_place(n, block_rows):
        batch = _pad_rows(batch, block_rows)
        return batch, batch.num_examples // block_rows, None
    whole = n // block_rows * block_rows
    return batch, n // block_rows, DenseBatch(
        x=batch.x[whole:], y=batch.y[whole:], offset=batch.offset[whole:],
        weight=batch.weight[whole:])


def _acc_dtype(dtype) -> jnp.dtype:
    """Accumulate in >= f32 (f64 stays f64 for interpret-mode parity tests)."""
    return jnp.promote_types(dtype, jnp.float32)


# -- kernels -------------------------------------------------------------------


_HIGHEST = jax.lax.Precision.HIGHEST  # default MXU f32 precision is a single
# bf16 pass (~1e-3 rel err); HIGHEST uses the multi-pass f32 decomposition.


def _mxu_precision(dtype):
    """HIGHEST only makes sense for >=f32 operands (the multi-pass f32
    decomposition).  Sub-f32 storage (bf16) is already the MXU's native input
    width — a single DEFAULT pass is exact for those operands, and Mosaic
    rejects an fp32-precision contract on bf16 vregs outright ("Bad lhs
    type", seen on a real v5e)."""
    return _HIGHEST if jnp.dtype(dtype).itemsize >= 4 else jax.lax.Precision.DEFAULT


def _row_margins(w, x, acc):
    """(D,C)^T @ (BN,D)^T -> (C, BN): margins as ROWS.

    Row layout puts examples on the lane axis, so the loss/residual
    elementwise work uses all 128 VPU lanes (a (BN,1) column layout wastes
    127/128 of them) and the MXU emits a full-width row."""
    return jax.lax.dot_general(w, x, (((0,), (1,)), ((), ())),
                               preferred_element_type=acc,
                               precision=_mxu_precision(x.dtype))


def _rowsum(row, ones, acc):
    """(1,BN)·(1,BN) -> (1,1) lane-contraction on the MXU."""
    return jax.lax.dot_general(row, ones, (((1,), (1,)), ((), ())),
                               preferred_element_type=acc,
                               precision=_mxu_precision(row.dtype))


def _row_xt(row, x, acc):
    """(1,BN) @ (BN,D) -> (1,D) contraction on the MXU."""
    return jax.lax.dot_general(row, x, (((1,), (0,)), ((), ())),
                               preferred_element_type=acc,
                               precision=_mxu_precision(x.dtype))


_NACC = 32  # accumulator rows: grid step i adds into row i % _NACC, cutting
# the sequential f32 accumulation chain by 32x (precision), while the output
# block stays whole-array (the only tiling-legal shape for accumulation).


def _slot_mask(i):
    rows = jax.lax.broadcasted_iota(jnp.int32, (_NACC, 1), 0)
    return rows == (i % _NACC).astype(jnp.int32)


def _value_grad_kernel(loss: PointwiseLoss, shift_ref, w_ref, x_ref, yow_ref,
                       val_ref, rsum_ref, grad_ref):
    i = pl.program_id(0)
    x = x_ref[:]  # (BN, D) — the only HBM->VMEM traffic that matters
    acc = _acc_dtype(x.dtype)
    z = _row_margins(w_ref[:], x, acc)  # (1, BN)
    z = z + yow_ref[1:2, :].astype(acc) + shift_ref[0, 0].astype(acc)
    wt = yow_ref[2:3, :].astype(acc)
    z = jnp.where(wt > 0, z, 0.0)  # safe margins: padded rows stay finite
    y = yow_ref[0:1, :].astype(acc)
    l, d1 = loss.loss_and_d1(z, y)
    r = wt * d1
    ones = jnp.ones_like(wt)

    @pl.when(i == 0)
    def _():
        val_ref[:] = jnp.zeros_like(val_ref)
        rsum_ref[:] = jnp.zeros_like(rsum_ref)
        grad_ref[:] = jnp.zeros_like(grad_ref)

    mask = _slot_mask(i)
    zero = jnp.zeros((), acc)
    val_ref[:] += jnp.where(mask, _rowsum(wt * l, ones, acc), zero)
    rsum_ref[:] += jnp.where(mask, _rowsum(r, ones, acc), zero)
    grad_ref[:] += jnp.where(mask, _row_xt(r.astype(x.dtype), x, acc), zero)


def _hvp_kernel(loss: PointwiseLoss, shift_ref, vshift_ref, wv_ref, x_ref,
                yow_ref, hv_ref, qsum_ref):
    i = pl.program_id(0)
    x = x_ref[:]
    acc = _acc_dtype(x.dtype)
    zz = _row_margins(wv_ref[:], x, acc)  # (2, BN): X@w row and X@v row
    z = zz[0:1, :] + yow_ref[1:2, :].astype(acc) + shift_ref[0, 0].astype(acc)
    mv = zz[1:2, :] + vshift_ref[0, 0].astype(acc)
    wt = yow_ref[2:3, :].astype(acc)
    z = jnp.where(wt > 0, z, 0.0)
    q = wt * loss.d2(z, yow_ref[0:1, :].astype(acc)) * mv

    @pl.when(i == 0)
    def _():
        qsum_ref[:] = jnp.zeros_like(qsum_ref)
        hv_ref[:] = jnp.zeros_like(hv_ref)

    mask = _slot_mask(i)
    zero = jnp.zeros((), acc)
    qsum_ref[:] += jnp.where(mask, _rowsum(q, jnp.ones_like(wt), acc), zero)
    hv_ref[:] += jnp.where(mask, _row_xt(q.astype(x.dtype), x, acc), zero)


# -- public entry points -------------------------------------------------------


def storage_narrowing_ok(x_dtype, w_dtype) -> bool:
    """ONE definition of the mixed-precision storage contract, shared by
    GLMObjective._fused_eligible and FixedEffectCoordinate's pre-padding
    decision (two separate copies drifted once — a gate mismatch wastes a
    permanent padded X copy on a path that then never runs fused).

    x may equal the solver dtype, or be a STRICTLY narrower float that
    promotes back to it (bf16/f16 against f32): kernels then take
    storage-width MXU operands with solver-width accumulation, mirroring
    DenseBatch.margins.  Widening storage (f64 x / f32 w) is out — promotion
    would change solver numerics."""
    xd, wd = jnp.dtype(x_dtype), jnp.dtype(w_dtype)
    if xd == wd:
        return True
    return bool(jnp.issubdtype(xd, jnp.floating) and xd.itemsize < wd.itemsize
                and jnp.promote_types(xd, wd) == wd)


def eligible(batch, interpret: bool = False) -> bool:
    """True when the pallas kernel path can run: TPU present, lane-aligned
    dim, and a design row within ``_MAX_ROW_BYTES`` so the X tile,
    coefficient block and accumulators fit VMEM.  Callers (GLMObjective)
    use their plain-XLA path otherwise — the kernels raise rather than
    silently duplicating that math here."""
    if not isinstance(batch, DenseBatch):
        return False
    if interpret:
        return True
    row_bytes = batch.dim * np.dtype(batch.x.dtype).itemsize
    return (has_tpu() and batch.dim % _LANE == 0
            and row_bytes <= _MAX_ROW_BYTES)


def fused_value_and_grad(
    loss: PointwiseLoss,
    w_eff: Array,
    batch: DenseBatch,
    margin_shift: Array | float = 0.0,
    block_rows: Optional[int] = None,
    interpret: bool = False,
    name: str = "fused_glm_value_grad",
) -> Tuple[Array, Array, Array]:
    """(Σ wt·l, X^T r, Σ r) in one pass over X.

    ``w_eff``/``margin_shift``: normalization-effective coefficients and shift
    (GLMObjective.margins semantics).  Callers must gate on ``eligible()`` —
    the equivalent XLA math lives in GLMObjective, not duplicated here.
    """
    if not eligible(batch, interpret):
        raise ValueError("fused_value_and_grad called on an ineligible batch; "
                         "gate on ops.fused_glm.eligible()")
    if batch.x.dtype != w_eff.dtype:
        raise ValueError(
            f"fused_value_and_grad needs one uniform dtype (x {batch.x.dtype} "
            f"vs w {w_eff.dtype}); mixed-precision storage uses the XLA path")

    n, d = batch.x.shape
    bn = block_rows or _pick_block_rows(
        n, d, np.dtype(batch.x.dtype).itemsize)
    batch, blocks, rest = _blocks_in_place(batch, bn)
    acc = _acc_dtype(batch.x.dtype)
    shift = jnp.asarray(margin_shift, acc).reshape(1, 1)

    grid = (blocks,)
    yow = jnp.stack([batch.y, batch.offset, batch.weight])  # (3, n): rows on lanes
    kernel = functools.partial(_value_grad_kernel, loss)
    val, rsum, grad = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),            # margin shift
            pl.BlockSpec((d, 1), lambda i: (0, 0)),            # w_eff
            pl.BlockSpec((bn, d), lambda i: (i, 0)),           # X row block
            pl.BlockSpec((3, bn), lambda i: (0, i)),           # y/offset/weight rows
        ],
        out_specs=[
            pl.BlockSpec((_NACC, 1), lambda i: (0, 0)),
            pl.BlockSpec((_NACC, 1), lambda i: (0, 0)),
            pl.BlockSpec((_NACC, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((_NACC, 1), acc),
            jax.ShapeDtypeStruct((_NACC, 1), acc),
            jax.ShapeDtypeStruct((_NACC, d), acc),
        ],
        interpret=interpret,
        name=name,
    )(shift, w_eff.reshape(-1, 1), batch.x, yow)
    out = jnp.sum(val), jnp.sum(grad, axis=0), jnp.sum(rsum)
    if rest is not None:
        out = tuple(a + b for a, b in zip(out, fused_value_and_grad(
            loss, w_eff, rest, margin_shift, interpret=interpret,
            name="fused_glm_tail_value_grad")))
    return out


def fused_hvp(
    loss: PointwiseLoss,
    w_eff: Array,
    v_eff: Array,
    batch: DenseBatch,
    margin_shift: Array | float = 0.0,
    v_shift: Array | float = 0.0,
    block_rows: Optional[int] = None,
    interpret: bool = False,
    name: str = "fused_glm_hvp",
) -> Tuple[Array, Array]:
    """(X^T q, Σ q) with q = wt·l''(z)·(X@v_eff + v_shift), one pass over X.

    Callers must gate on ``eligible()`` (see fused_value_and_grad).
    """
    if not eligible(batch, interpret):
        raise ValueError("fused_hvp called on an ineligible batch; "
                         "gate on ops.fused_glm.eligible()")
    if batch.x.dtype != w_eff.dtype:
        raise ValueError(
            f"fused_hvp needs one uniform dtype (x {batch.x.dtype} "
            f"vs w {w_eff.dtype}); mixed-precision storage uses the XLA path")

    n, d = batch.x.shape
    bn = block_rows or _pick_block_rows(
        n, d, np.dtype(batch.x.dtype).itemsize)
    batch, blocks, rest = _blocks_in_place(batch, bn)
    acc = _acc_dtype(batch.x.dtype)
    shift = jnp.asarray(margin_shift, acc).reshape(1, 1)
    vshift = jnp.asarray(v_shift, acc).reshape(1, 1)

    yow = jnp.stack([batch.y, batch.offset, batch.weight])
    wv = jnp.stack([w_eff, v_eff], axis=1)  # (d, 2)
    kernel = functools.partial(_hvp_kernel, loss)
    hv, qsum = pl.pallas_call(
        kernel,
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((d, 2), lambda i: (0, 0)),            # [w_eff | v_eff]
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((3, bn), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((_NACC, d), lambda i: (0, 0)),
            pl.BlockSpec((_NACC, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((_NACC, d), acc),
            jax.ShapeDtypeStruct((_NACC, 1), acc),
        ],
        interpret=interpret,
        name=name,
    )(shift, vshift, wv, batch.x, yow)
    out = jnp.sum(hv, axis=0), jnp.sum(qsum)
    if rest is not None:
        out = tuple(a + b for a, b in zip(out, fused_hvp(
            loss, w_eff, v_eff, rest, margin_shift, v_shift,
            interpret=interpret, name="fused_glm_tail_hvp")))
    return out
