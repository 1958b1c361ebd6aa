"""Pallas TPU kernel for sparse-features x sparse-model compact scoring.

The XLA path (models/game._score_sparse_compact) binary-searches every
sample feature id into its entity's sorted coefficient columns:
``vmap(searchsorted)`` + two ``take_along_axis`` gathers + masks — five
[n, k]-shaped HBM intermediates per call.  This kernel replaces the search
with a match-dot while one sample block is resident in VMEM:

    score[i] = sum_{f, m} (w_idx[i, m] == f_idx[i, f]) * w_val[i, m] * f_val[i, f]

which is exact because coefficient columns are unique per entity (sorted
``np.nonzero`` output), model padding carries value 0 (inert whatever it
matches), and duplicate FEATURE ids accumulate — the same convention the
searchsorted chain and ``SparseBatch.margins`` implement.

Layout: samples-on-lanes.  [n, k] arrays put k on the 128-lane axis (a
k=8 coefficient row wastes 15/16 of every vector register); the kernel
takes [k, n] transposed operands so every compare/multiply uses all 128
lanes and the k_model reduction is a sublane sum.

Gating follows ops/fused_glm.py: TPU-only (``eligible``), interpret=True
for CPU correctness tests.  The O(k_model * k_feat)
compare-accumulate only beats the O(k_feat log k_model) search while the
product is small — ``_MAX_MATCH_WORK`` bounds it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from photon_ml_tpu.ops.fused_glm import VMEM_BLOCK_BUDGET_BYTES, has_tpu

Array = jax.Array

_LANE = 128
_MAX_BLOCK_LANES = 512
_MAX_MATCH_WORK = 4096  # k_model * k_feat above this: keep the searchsorted
# chain (the match-dot's elementwise work grows with the product while the
# search grows with k_feat * log2(k_model))
_MAX_FEAT_UNROLL = 512  # the k_feat loop unrolls statically: compile time is
# linear in it (9 s at 1024 when compiled for a v5e)


def _bytes_per_lane(k_model: int, k_feat: int, itemsize: int) -> int:
    """Pipelined VMEM bytes per sample lane: an int32 id row and a value row
    per coefficient and per feature slot (sublane axis padded to 8), twice
    for double buffering — a (4096, 512) block pair asked for 32 MiB."""
    rows = (-(-k_model // 8) + -(-k_feat // 8)) * 8
    return 2 * rows * (4 + itemsize)


def eligible(k_model: int, k_feat: int, itemsize: int = 4,
             interpret: bool = False) -> bool:
    """True when the pallas match-dot can replace the searchsorted chain.
    Callers (models/game.score_compact_sparse) keep the XLA path otherwise.

    Shape rules: k_model * k_feat <= 4096 (match work), k_feat <= 512
    (static unroll), and a 128-lane block of both operand pairs within the
    VMEM block budget (k_model + k_feat <= ~4096 rows in f32)."""
    if k_model < 1 or k_feat < 1 or k_model * k_feat > _MAX_MATCH_WORK:
        return False
    if k_feat > _MAX_FEAT_UNROLL:
        return False
    if _LANE * _bytes_per_lane(k_model, k_feat, itemsize) \
            > VMEM_BLOCK_BUDGET_BYTES:
        return False
    return interpret or has_tpu()


def _match_dot_kernel(k_feat: int, w_idx_ref, w_val_ref, f_idx_ref, f_val_ref,
                      out_ref):
    """One sample block: (k_model, BN) coefficient rows vs (k_feat, BN)
    feature rows.  The k_feat loop unrolls statically; every op is
    elementwise over the 128-lane sample axis, the k_model reduction is a
    sublane sum."""
    w_idx = w_idx_ref[:]                       # (k_model, BN) int32
    w_val = w_val_ref[:]                       # (k_model, BN)
    acc = jnp.zeros_like(out_ref)              # (1, BN)
    zero = jnp.zeros((), w_val.dtype)
    for f in range(k_feat):
        fi = f_idx_ref[f:f + 1, :]             # (1, BN), broadcasts below
        wv = jnp.sum(jnp.where(w_idx == fi, w_val, zero),
                     axis=0, keepdims=True)    # (1, BN)
        acc = acc + f_val_ref[f:f + 1, :] * wv
    out_ref[:] = acc


def _pad_lanes(a: Array, n_pad: int) -> Array:
    pad = n_pad - a.shape[-1]
    return a if pad == 0 else jnp.pad(a, ((0, 0), (0, pad)))


def match_dot(rows_idx_t: Array, rows_val_t: Array, f_idx_t: Array,
              f_val_t: Array, block_lanes: Optional[int] = None,
              interpret: bool = False) -> Array:
    """Per-sample compact margins from TRANSPOSED [k, n] operands.

    ``rows_idx_t``/``rows_val_t``: each sample's entity coefficient row
    (already gathered, [k_model, n]); ``f_idx_t``/``f_val_t``: the sample's
    sparse features ([k_feat, n]).  Returns margins [n].  Samples are padded
    to a lane-block multiple internally (zero feature values -> margin 0).
    Callers must gate on ``eligible()``.
    """
    k_model, n = rows_idx_t.shape
    k_feat = f_idx_t.shape[0]
    itemsize = np.dtype(rows_val_t.dtype).itemsize
    if not eligible(k_model, k_feat, itemsize, interpret):
        raise ValueError("compact_score.match_dot called on an ineligible "
                         "shape; gate on ops.compact_score.eligible()")
    fit = VMEM_BLOCK_BUDGET_BYTES // _bytes_per_lane(k_model, k_feat, itemsize)
    bl = block_lanes or min(_MAX_BLOCK_LANES, fit,
                            1 << (max(n - 1, 0)).bit_length())
    bl = max(_LANE, (bl // _LANE) * _LANE)
    n_pad = -(-max(n, 1) // bl) * bl
    args = (_pad_lanes(rows_idx_t, n_pad), _pad_lanes(rows_val_t, n_pad),
            _pad_lanes(f_idx_t, n_pad), _pad_lanes(f_val_t, n_pad))
    kernel = functools.partial(_match_dot_kernel, k_feat)
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // bl,),
        in_specs=[
            pl.BlockSpec((k_model, bl), lambda i: (0, i)),
            pl.BlockSpec((k_model, bl), lambda i: (0, i)),
            pl.BlockSpec((k_feat, bl), lambda i: (0, i)),
            pl.BlockSpec((k_feat, bl), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, bl), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), rows_val_t.dtype),
        interpret=interpret,
        name="compact_match_dot",
    )(*args)
    return out[0, :n]


def score_sparse_compact(w_idx: Array, w_val: Array, slots: Array,
                         f_idx: Array, f_val: Array,
                         interpret: bool = False) -> Array:
    """Drop-in twin of models/game._score_sparse_compact's math on the
    pallas path: gather each sample's entity row (XLA gather — the only
    HBM-efficient way to index [E, k] by slot), transpose to lanes-last,
    match-dot in VMEM, mask missing entities to 0."""
    e = jnp.where(slots >= 0, slots, 0)
    s = match_dot(w_idx[e].T, w_val[e].T, f_idx.T.astype(jnp.int32),
                  f_val.T, interpret=interpret)
    return jnp.where(slots >= 0, s, jnp.zeros((), s.dtype))
