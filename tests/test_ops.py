"""Pallas fused-kernel parity tests (interpret mode on the CPU test mesh).

The kernels must be bit-for-bit the same *math* as GLMObjective's reference
path; tolerances cover f64 summation-order differences only.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.core.batch import DenseBatch
from photon_ml_tpu.core.losses import (logistic_loss, poisson_loss,
                                       smoothed_hinge_loss, squared_loss)
from photon_ml_tpu.core.normalization import NormalizationContext, no_normalization
from photon_ml_tpu.core.objective import GLMObjective
from photon_ml_tpu.core.regularization import Regularization
from photon_ml_tpu.ops.fused_glm import (_pad_rows, _pick_block_rows, fused_hvp,
                                         fused_value_and_grad)

LOSSES = [logistic_loss, squared_loss, poisson_loss, smoothed_hinge_loss]


def _batch(rng, loss, n=100, d=12):
    x = rng.normal(size=(n, d)) * 0.3
    if loss is logistic_loss or loss is smoothed_hinge_loss:
        y = (rng.random(n) < 0.5).astype(np.float64)
    elif loss is poisson_loss:
        y = rng.poisson(2.0, size=n).astype(np.float64)
    else:
        y = rng.normal(size=n)
    weight = rng.uniform(0.5, 2.0, size=n)
    weight[: n // 10] = 0.0  # padded/masked rows
    return DenseBatch(x=jnp.asarray(x), y=jnp.asarray(y),
                      offset=jnp.asarray(rng.normal(size=n) * 0.1),
                      weight=jnp.asarray(weight))


def _norm(rng, d):
    return NormalizationContext(factors=jnp.asarray(rng.uniform(0.5, 2.0, size=d)),
                                shifts=jnp.asarray(rng.normal(size=d) * 0.2))


@pytest.mark.parametrize("loss", LOSSES, ids=lambda l: l.name)
@pytest.mark.parametrize("normed", [False, True], ids=["nonorm", "norm"])
def test_value_grad_parity(rng, loss, normed):
    batch = _batch(rng, loss)
    norm = _norm(rng, batch.dim) if normed else no_normalization()
    obj = GLMObjective(loss=loss, reg=Regularization(l2=0.1), norm=norm)
    w = jnp.asarray(rng.normal(size=batch.dim) * 0.2)

    ref_val, ref_grad = obj.value_and_grad(w, batch)
    w_eff = norm.effective_coefficients(w)
    val, g_raw, r_sum = fused_value_and_grad(loss, w_eff, batch,
                                             margin_shift=norm.margin_shift(w),
                                             block_rows=32, interpret=True)
    got_val = val + obj.l2_term(w)
    got_grad = obj._chain(g_raw, r_sum) + 0.1 * w
    np.testing.assert_allclose(got_val, ref_val, rtol=1e-12)
    np.testing.assert_allclose(got_grad, ref_grad, rtol=1e-10, atol=1e-12)


@pytest.mark.parametrize("loss", [logistic_loss, poisson_loss], ids=lambda l: l.name)
def test_hvp_parity(rng, loss):
    batch = _batch(rng, loss)
    norm = _norm(rng, batch.dim)
    obj = GLMObjective(loss=loss, reg=Regularization(l2=0.05), norm=norm)
    w = jnp.asarray(rng.normal(size=batch.dim) * 0.2)
    v = jnp.asarray(rng.normal(size=batch.dim))

    ref = obj.hvp(w, batch, v)
    hv_raw, q_sum = fused_hvp(loss, norm.effective_coefficients(w),
                              norm.effective_coefficients(v), batch,
                              margin_shift=norm.margin_shift(w),
                              v_shift=norm.margin_shift(v),
                              block_rows=32, interpret=True)
    got = obj._chain(hv_raw, q_sum) + 0.05 * v
    np.testing.assert_allclose(got, ref, rtol=1e-10, atol=1e-12)


def test_row_padding_is_invisible(rng):
    batch = _batch(rng, squared_loss, n=70)  # 70 % 32 != 0 -> padded to 96
    padded = _pad_rows(batch, 32)
    assert padded.num_examples == 96
    assert float(jnp.sum(padded.weight[70:])) == 0.0
    w = jnp.asarray(rng.normal(size=batch.dim))
    obj = GLMObjective(loss=squared_loss)
    v_ref, g_ref = obj.value_and_grad(w, batch)
    v, g, r = fused_value_and_grad(squared_loss, w, batch, block_rows=32, interpret=True)
    np.testing.assert_allclose(v, v_ref, rtol=1e-12)
    np.testing.assert_allclose(g, g_ref, rtol=1e-10)


def test_ineligible_raises(rng):
    batch = _batch(rng, squared_loss, n=16)
    w = jnp.asarray(rng.normal(size=batch.dim))
    with pytest.raises(ValueError, match="eligible"):
        fused_value_and_grad(squared_loss, w, batch)  # CPU, unaligned dim


def test_pick_block_rows():
    assert _pick_block_rows(10_000, 128) % 128 == 0
    assert _pick_block_rows(4, 128) >= 128
    # huge d -> smallest legal block, the lane granule
    assert _pick_block_rows(10_000, 1 << 20) == 128


@pytest.mark.parametrize("n", [4, 100, 2000, 2048, 5000, 131072])
@pytest.mark.parametrize("d", [128, 512, 4096])
def test_pick_block_rows_idempotent_under_padding(n, d):
    """pick(pad(n)) must divide the padded n — otherwise the coordinate's
    one-time pre-pad still re-pads (full X copy) inside every jitted call."""
    bn = _pick_block_rows(n, d)
    n_pad = n + (-n) % bn
    assert _pick_block_rows(n_pad, d) == bn
    assert n_pad % bn == 0


def test_objective_fused_flag_cpu_fallback(rng):
    """fused=True on CPU uses the XLA fallback — same results, still jittable."""
    batch = _batch(rng, logistic_loss)
    w = jnp.asarray(rng.normal(size=batch.dim) * 0.1)
    plain = GLMObjective(loss=logistic_loss, reg=Regularization(l2=0.01))
    fused = GLMObjective(loss=logistic_loss, reg=Regularization(l2=0.01), fused=True)
    v1, g1 = jax.jit(plain.value_and_grad)(w, batch)
    v2, g2 = jax.jit(fused.value_and_grad)(w, batch)
    np.testing.assert_allclose(v1, v2, rtol=1e-12)
    np.testing.assert_allclose(g1, g2, rtol=1e-12)
    np.testing.assert_allclose(plain.hvp(w, batch, g1), fused.hvp(w, batch, g2),
                               rtol=1e-12)


def test_has_tpu_does_not_swallow_a_broken_backend(monkeypatch):
    """A backend that fails to initialise must raise — it used to read as
    "no TPU", and all three kernels then yielded to XLA without a word."""
    from photon_ml_tpu.ops import fused_glm

    def broken():
        raise RuntimeError("TPU backend setup error")

    fused_glm.has_tpu.cache_clear()
    monkeypatch.setattr(fused_glm.jax, "devices", broken)
    try:
        with pytest.raises(RuntimeError, match="backend setup error"):
            fused_glm.has_tpu()
    finally:
        fused_glm.has_tpu.cache_clear()


def test_gate_is_a_vmem_shape_rule(rng, monkeypatch):
    """eligible() on a TPU: lane-aligned dim and a design row of at most
    16 KiB at storage width — d=8192 compiles in bf16 and not in f32 (its
    (d, 1) coefficient block pads to 128 lanes: 23.9 MiB of 16 asked)."""
    from photon_ml_tpu.ops import fused_glm

    monkeypatch.setattr(fused_glm, "has_tpu", lambda: True)

    def b(d, dtype):
        return DenseBatch(x=jnp.zeros((8, d), dtype), y=jnp.zeros(8),
                          offset=jnp.zeros(8), weight=jnp.ones(8))

    assert fused_glm.eligible(b(4096, jnp.float32))
    assert not fused_glm.eligible(b(4096 + 128, jnp.float32))
    assert fused_glm.eligible(b(8192, jnp.bfloat16))
    assert not fused_glm.eligible(b(8192 + 128, jnp.bfloat16))
    assert not fused_glm.eligible(b(500, jnp.float32))  # not lane-aligned


@pytest.mark.parametrize("loss", [logistic_loss, poisson_loss], ids=lambda l: l.name)
def test_bf16_storage_parity_normalized(rng, loss):
    """bf16 storage through the FULL objective fused path WITH a non-trivial
    NormalizationContext — the narrowing cast applies to the norm-scaled
    effective coefficients and the f32 margin_shift rides beside bf16
    operands, exactly where storage width and normalization interact.
    Parity vs the XLA mixed path (fused=False) on identical inputs."""
    n, d = 96, 16
    x32 = (rng.normal(size=(n, d)) * 0.3).astype(np.float32)
    y = ((rng.random(n) < 0.5).astype(np.float32) if loss is logistic_loss
         else rng.poisson(2.0, size=n).astype(np.float32))
    weight = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    weight[: n // 10] = 0.0
    batch = DenseBatch(x=jnp.asarray(x32).astype(jnp.bfloat16),
                       y=jnp.asarray(y),
                       offset=jnp.asarray((rng.normal(size=n) * 0.1)
                                          .astype(np.float32)),
                       weight=jnp.asarray(weight))
    norm = NormalizationContext(
        factors=jnp.asarray(rng.uniform(0.5, 2.0, size=d).astype(np.float32)),
        shifts=jnp.asarray((rng.normal(size=d) * 0.2).astype(np.float32)))
    w = jnp.asarray((rng.normal(size=d) * 0.2).astype(np.float32))
    v = jnp.asarray(rng.normal(size=d).astype(np.float32))

    plain = GLMObjective(loss=loss, reg=Regularization(l2=0.05), norm=norm)
    eff = norm.effective_coefficients(w).astype(jnp.bfloat16)
    val, g_raw, r_sum = fused_value_and_grad(
        loss, eff, batch, margin_shift=norm.margin_shift(w),
        block_rows=32, interpret=True)
    got_val = val + plain.l2_term(w)
    got_grad = plain._chain(g_raw, r_sum) + 0.05 * w
    ref_val, ref_grad = plain.value_and_grad(w, batch)
    np.testing.assert_allclose(np.asarray(got_val), np.asarray(ref_val),
                               rtol=2e-2)
    np.testing.assert_allclose(np.asarray(got_grad), np.asarray(ref_grad),
                               rtol=6e-2, atol=6e-2)

    eff_v = norm.effective_coefficients(v).astype(jnp.bfloat16)
    hv_raw, q_sum = fused_hvp(loss, eff, eff_v, batch,
                              margin_shift=norm.margin_shift(w),
                              v_shift=norm.margin_shift(v),
                              block_rows=32, interpret=True)
    got_hvp = plain._chain(hv_raw, q_sum) + 0.05 * v
    np.testing.assert_allclose(np.asarray(got_hvp),
                               np.asarray(plain.hvp(w, batch, v)),
                               rtol=6e-2, atol=6e-2)


@pytest.mark.parametrize("loss", [logistic_loss, poisson_loss], ids=lambda l: l.name)
def test_bf16_storage_parity_with_xla_mixed_path(rng, loss):
    """Narrow (bf16) storage now keeps the pallas path: kernels take
    storage-width MXU operands with f32 accumulation — the same contract as
    DenseBatch.margins / _xt_dot on the XLA mixed path.  Parity here is
    against that XLA mixed path (fused=False), tolerances at bf16 scale."""
    n, d = 96, 16
    x32 = (rng.normal(size=(n, d)) * 0.3).astype(np.float32)
    y = ((rng.random(n) < 0.5).astype(np.float32) if loss is logistic_loss
         else rng.poisson(2.0, size=n).astype(np.float32))
    weight = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    weight[: n // 10] = 0.0
    offset = (rng.normal(size=n) * 0.1).astype(np.float32)
    batch = DenseBatch(x=jnp.asarray(x32).astype(jnp.bfloat16),
                       y=jnp.asarray(y), offset=jnp.asarray(offset),
                       weight=jnp.asarray(weight))
    w = jnp.asarray((rng.normal(size=d) * 0.2).astype(np.float32))
    v = jnp.asarray(rng.normal(size=d).astype(np.float32))

    plain = GLMObjective(loss=loss, reg=Regularization(l2=0.05))

    ref_val, ref_grad = plain.value_and_grad(w, batch)
    val, g_raw, r_sum = fused_value_and_grad(
        loss, w.astype(jnp.bfloat16), batch, block_rows=32, interpret=True)
    got_val = val + plain.l2_term(w)
    got_grad = g_raw + 0.05 * w
    np.testing.assert_allclose(np.asarray(got_val), np.asarray(ref_val),
                               rtol=2e-2)
    np.testing.assert_allclose(np.asarray(got_grad), np.asarray(ref_grad),
                               rtol=5e-2, atol=5e-2)

    ref_hvp = plain.hvp(w, batch, v)
    hv_raw, q_sum = fused_hvp(loss, w.astype(jnp.bfloat16),
                              v.astype(jnp.bfloat16), batch,
                              block_rows=32, interpret=True)
    got_hvp = hv_raw + 0.05 * v
    np.testing.assert_allclose(np.asarray(got_hvp), np.asarray(ref_hvp),
                               rtol=5e-2, atol=5e-2)


def test_bf16_block_rows_doubled():
    """bf16 X tiles carry 2x the rows in the same VMEM budget."""
    assert _pick_block_rows(1 << 20, 256, 2) == 2 * _pick_block_rows(
        1 << 20, 256, 4)


def test_fused_eligible_dtype_gate(rng, monkeypatch):
    """Isolate the dtype guard: with kernel eligibility stubbed true,
    narrow float storage (bf16 x / f32 w) passes, widening mixes (f64 x /
    f32 w) stay on the XLA path (promotion would change solver numerics)."""
    from photon_ml_tpu.ops import fused_glm

    monkeypatch.setattr(fused_glm, "eligible", lambda b, interpret=False: True)
    batch64 = _batch(rng, squared_loss)  # f64 x on the f64 test mesh
    w32 = jnp.asarray(rng.normal(size=batch64.dim).astype(np.float32))
    assert not GLMObjective._fused_eligible(batch64, w32)
    batch16 = DenseBatch(x=batch64.x.astype(jnp.bfloat16),
                         y=batch64.y.astype(jnp.float32),
                         offset=batch64.offset.astype(jnp.float32),
                         weight=batch64.weight.astype(jnp.float32))
    assert GLMObjective._fused_eligible(batch16, w32)
    # uniform dtypes always pass the guard
    assert GLMObjective._fused_eligible(batch64, batch64.x[0])
