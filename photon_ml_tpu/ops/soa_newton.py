"""Pallas TPU kernel for the SoA Newton step: fused per-lane Hessian
assembly + batched small-Cholesky factor/solve.

The XLA path (opt/newton_soa.py) computes the per-iteration Newton step in
two stages: ``_hess`` materializes ``xq = x * q`` as a full ``[cap, d, L]``
HBM array (as large as the design itself) and reads the design again for
every of the d(d+1)/2 weighted column products, then ``_cholesky_solve_soa``
runs the unrolled factorization over ~d^2 separate [L] arrays.  This kernel
does the whole step — margins, curvature weights, Hessian lower triangle,
Cholesky, two triangular solves — while one lane-block of the design is
resident in VMEM, so X streams from HBM exactly once per Newton iteration
and ``xq`` never exists as an array (one column product lives at a time).

Layout: everything lanes-last, exactly the SoA solver's layout — [d, L]
state rows ride the 8-sublane tile, per-lane scalars are (1, L) rows using
all 128 VPU lanes, and there is no dot_general anywhere (d <= 16 is far
below the MXU's useful width; the VPU column products ARE the fast path).

Gating follows ops/fused_glm.py: TPU-only (``eligible``), CPU correctness
via the ``interpret=True`` arguments (tests only — no environment variable
reaches interpret mode).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from photon_ml_tpu.core.losses import PointwiseLoss
from photon_ml_tpu.ops.fused_glm import VMEM_BLOCK_BUDGET_BYTES, has_tpu

Array = jax.Array

_LANE = 128  # TPU lane width: lane blocks must be a multiple
_MAX_DIM = 16  # the Hessian triangle and the Cholesky unroll statically:
# O(d^3) straight-line ops (the SoA solver's own bound, opt/newton_soa.py)

# (the block budget's headroom, measured: compiling an 8192-lane (32, 4) f32
# block for a v5e asked for 28.8 MiB — 24 MiB of blocks + ~5 MiB of
# temporaries)


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _bytes_per_lane(cap: int, d: int, itemsize: int) -> int:
    """Pipelined VMEM bytes one lane of a block costs: every operand block
    twice (double buffering), each with its second-minor axis padded to the
    dtype's sublane tile (8 rows of 32 bits; 16 of bf16) — d=4 pads to 8 in
    f32 and to 16 in bf16, which the logical cap*d*itemsize never showed."""
    sub_x = 8 * max(1, 4 // itemsize)
    x = cap * _ceil_to(d, sub_x) * itemsize          # (cap, d, BL) design
    rows = 3 * _ceil_to(cap, 8) * 4                  # y, offset, weight
    state = 3 * _ceil_to(d, 8) * 4 + 8 * 4           # w, g, out; l2 row
    return 2 * (x + rows + state)


def eligible(d: int, num_lanes: int, cap: int, itemsize: int = 4,
             interpret: bool = False) -> bool:
    """True when the pallas Newton-step kernel can run.  Callers
    (opt/newton_soa.solve_newton_soa) keep the XLA path otherwise — the
    kernel raises rather than duplicating that math here.

    Shape rules: lanes a multiple of 128, 1 <= d <= 16 (static unroll), and
    the smallest (128-lane) block of a (cap, d) design at ``itemsize`` must
    fit the VMEM block budget — cap <= 427 at d=16 in f32, 741 at d=4."""
    if not 1 <= d <= _MAX_DIM or cap < 1:
        return False
    if num_lanes < 1 or num_lanes % _LANE != 0:
        return False
    if _LANE * _bytes_per_lane(cap, d, itemsize) > VMEM_BLOCK_BUDGET_BYTES:
        return False
    return interpret or has_tpu()


def _pick_block_lanes(cap: int, d: int, num_lanes: int, itemsize: int) -> int:
    """Largest 128-multiple block that DIVIDES the lane count and whose
    pipelined operand blocks fit the VMEM budget.  128 always divides (the
    gate), so any lane count the gate admits gets a block — 1152 lanes
    take 1152 or 384, never a non-dividing 1024."""
    units = num_lanes // _LANE
    fit = max(1, VMEM_BLOCK_BUDGET_BYTES // _bytes_per_lane(cap, d, itemsize)
              // _LANE)
    k = next(k for k in range(min(fit, units), 0, -1) if units % k == 0)
    return k * _LANE


def _newton_step_kernel(loss: PointwiseLoss, d: int, eps: float,
                        w_ref, g_ref, x_ref, y_ref, off_ref, wt_ref, l2_ref,
                        out_ref):
    """One lane-block: margins -> q -> Hessian lower triangle -> Cholesky ->
    two triangular solves.  Every array below is (cap, BL) or (1, BL); the
    d loops unroll statically (d <= 16 by the SoA gate)."""
    x = x_ref[:]                                    # (cap, d, BL)
    acc = jnp.promote_types(x.dtype, w_ref.dtype)
    w = w_ref[:].astype(acc)                        # (d, BL)
    # margins: sublane sum over the static d axis, no dot_general — the
    # EXACT op sequence of newton_soa._margins ((x*w).sum(axis=1) + off),
    # so interpret-mode runs are bitwise the XLA path's
    z = jnp.sum(x.astype(acc) * w[None], axis=1) + off_ref[:]
    q = wt_ref[:].astype(acc) * loss.d2(z, y_ref[:])  # (cap, BL)

    # Hessian lower triangle: one xq column product at a time — the [cap,
    # d, L] xq array of the XLA path never exists (newton_soa._hess parity:
    # hh[i][j] = sum_cap x_i x_j q, + l2 on the diagonal)
    l2 = l2_ref[:].astype(acc)                      # (1, BL)
    hh = [[None] * d for _ in range(d)]
    for i in range(d):
        xq_i = x[:, i, :].astype(acc) * q
        for j in range(i + 1):
            hij = jnp.sum(xq_i * x[:, j, :].astype(acc), axis=0,
                          keepdims=True)            # (1, BL)
            if i == j:
                hij = hij + l2
            hh[i][j] = hij

    # scale-relative jitter — newton_soa's exact rule: eps * (max |diag| + 1)
    diag_max = functools.reduce(
        jnp.maximum, (jnp.abs(hh[i][i]) for i in range(d)))
    jitter = eps * (diag_max + 1.0)

    # unrolled Cholesky + forward/back substitution, elementwise over lanes
    # (newton_soa._cholesky_solve_soa parity, including the sqrt floor)
    g = g_ref[:].astype(acc)
    lo = [[None] * d for _ in range(d)]
    for i in range(d):
        s = hh[i][i] + jitter
        for k in range(i):
            s = s - lo[i][k] * lo[i][k]
        lii = jnp.sqrt(jnp.maximum(s, jitter))
        lo[i][i] = lii
        for j in range(i + 1, d):
            s2 = hh[j][i]
            for k in range(i):
                s2 = s2 - lo[j][k] * lo[i][k]
            lo[j][i] = s2 / lii
    zz = [None] * d
    for i in range(d):
        s = g[i:i + 1, :]
        for k in range(i):
            s = s - lo[i][k] * zz[k]
        zz[i] = s / lo[i][i]
    xs = [None] * d
    for i in reversed(range(d)):
        s = zz[i]
        for k in range(i + 1, d):
            s = s - lo[k][i] * xs[k]
        xs[i] = s / lo[i][i]
    out_ref[:] = jnp.concatenate(xs, axis=0).astype(out_ref.dtype)


def newton_step(loss: PointwiseLoss, w: Array, g: Array, x_t: Array,
                y_t: Array, off_t: Array, wt_t: Array, l2: Array,
                block_lanes: Optional[int] = None,
                interpret: bool = False) -> Array:
    """step = (H(w) + jitter I)^-1 g in one pass over the design.

    ``w``/``g``: [d, L]; ``x_t``: [cap, d, L]; ``y/off/wt_t``: [cap, L];
    ``l2``: [L] per-lane regularization.  Returns the [d, L] Newton step —
    bitwise the same algorithm as newton_soa's ``_hess`` +
    ``_cholesky_solve_soa`` chain (parity-tested in interpret mode).
    Callers must gate on ``eligible()``.
    """
    d, num_l = w.shape
    cap = x_t.shape[0]
    itemsize = np.dtype(x_t.dtype).itemsize
    if not eligible(d, num_l, cap, itemsize, interpret):
        raise ValueError("soa_newton.newton_step called on an ineligible "
                         "shape; gate on ops.soa_newton.eligible()")
    bl = block_lanes or _pick_block_lanes(cap, d, num_l, itemsize)
    if num_l % bl != 0:
        raise ValueError(f"block_lanes {bl} must divide num_lanes {num_l}")
    eps = float(np.finfo(np.dtype(w.dtype)).eps)
    kernel = functools.partial(_newton_step_kernel, loss, d, eps)
    return pl.pallas_call(
        kernel,
        grid=(num_l // bl,),
        in_specs=[
            pl.BlockSpec((d, bl), lambda i: (0, i)),        # w
            pl.BlockSpec((d, bl), lambda i: (0, i)),        # g
            pl.BlockSpec((cap, d, bl), lambda i: (0, 0, i)),  # x_t
            pl.BlockSpec((cap, bl), lambda i: (0, i)),      # y_t
            pl.BlockSpec((cap, bl), lambda i: (0, i)),      # off_t
            pl.BlockSpec((cap, bl), lambda i: (0, i)),      # wt_t
            pl.BlockSpec((1, bl), lambda i: (0, i)),        # l2
        ],
        out_specs=pl.BlockSpec((d, bl), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((d, num_l), w.dtype),
        interpret=interpret,
        name="soa_newton_step",
    )(w, g, x_t, y_t, off_t, wt_t,
      jnp.broadcast_to(jnp.asarray(l2), (num_l,)).reshape(1, num_l))
