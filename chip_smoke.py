"""chip_smoke.py — does the system still start on the chip?

Drives the main path once through the entry points a user calls, on ONE
process, with data and weights made from a seed:

  kernel   each pallas kernel called directly, compiled by Mosaic, at the
           largest shape its ``eligible()`` admits and at this run's shape,
           against the XLA path it replaces
  train    ``GameEstimator.fit(fused=True)`` with a validation suite: the
           two-coordinate logistic GLMix at glmix_chip's widths (fixed d=512
           in bf16 storage, per-user d=4 with active cap 32); rows cut to
           16,384 users x 64
  serve    ``save_game_model`` -> ``cli.serve.build_server`` ->
           ``ThreadedFrontend`` -> JSON lines over a loopback socket
  compact  the same model as a ``CompactRandomEffectModel`` behind the same
           frontend, and a compact model batch-scored over a sparse shard
           (the one production caller of ``ops/compact_score``)
  mesh     with four or more devices: the train leg under ``make_mesh`` and
           the serve leg with ``mesh_shards=4``, each against its one-device
           result, and each device holding only its share

It fails unless ``jax.devices()[0].platform`` is ``tpu``.  Every failed
check is collected and reported, and any of them makes the exit code 1.
The last line of standard output is the result object.

``--dry-run`` is for debugging this script where there is no chip: tiny
sizes, the CPU backend, kernels through their ``interpret=`` arguments.
Its result says ``"dry_run": true`` and is not a chip result.  For the mesh
legs give it virtual devices:
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import socket
import sys
import tempfile
import time

# glmix_chip's widths (fixed d = 512, per-user d = 4, active cap 32): none cut.
# users x per_user is cut from 131072 x 64; 16,384 users keep the lane count
# past the point where both block pickers reach the blocks they pick at
# full size (fused_glm: 1024 rows; soa_newton: 2048 lanes).
_CHIP = dict(users=16384, per_user=64, d_g=512, d_u=4, d_sig=16, cap=32,
             val_stride=8, requests=96, solver_iters=30,
             sparse_rows=8192, sparse_entities=1024, sparse_dim=4096,
             sparse_k=64)
# per_user keeps the 4-parameter per-user fit out of overfitting territory;
# the serving and compact paths need no size at all
_DRY = dict(users=128, per_user=48, d_g=128, d_u=4, d_sig=16, cap=32,
            val_stride=4, requests=24, solver_iters=15,
            sparse_rows=300, sparse_entities=40, sparse_dim=60, sparse_k=6)

SEED = 20260926
AUC_BAND = (0.70, 0.92)  # glmix_chip's own band: Bayes AUC ~0.8
# A reduction computed twice in f32 in two block orders differs by a few
# ulp of the sum of its ABSOLUTE terms (not of the result, which cancels).
# 5e-6 is applied on that scale.
KERNEL_TOL = 5e-6
# bf16 storage: the residual row is ROUNDED to bf16 before it meets X again,
# in the kernel and in XLA alike.  Where the two f32 residuals differ in
# their last bits (X@v cancels, so by ~1e-5 of themselves) a rounding can
# fall the other way and move that term by 2^-8 of itself: the expected
# error is the residuals' own relative difference, ~1e-5 of the absolute
# terms (9e-6 measured on a v5e).  The 2026-08-02 record allowed 2e-2.
KERNEL_TOL_BF16 = 1e-4


class Checks:
    """Collects every check's outcome; nothing here lets a failure pass."""

    def __init__(self):
        self.failed = []
        self.legs = {}
        self._leg = None

    @contextlib.contextmanager
    def leg(self, name):
        self._leg = name
        t0 = time.perf_counter()
        before = len(self.failed)
        print(f"== {name}", flush=True)
        try:
            yield
        except Exception as e:  # a crashed leg is a failed leg, reported
            import traceback

            traceback.print_exc()
            self.failed.append(f"{name}: crashed: {type(e).__name__}: {e}")
        dt = time.perf_counter() - t0
        ok = len(self.failed) == before
        self.legs[name] = {"ok": ok, "seconds": round(dt, 1)}
        print(f"== {name}: {'ok' if ok else 'FAILED'} in {dt:.1f}s", flush=True)

    def check(self, cond, what, detail=""):
        line = f"{'ok  ' if cond else 'FAIL'} {what}" + (
            f"  [{detail}]" if detail else "")
        print("   " + line, flush=True)
        if not cond:
            self.failed.append(f"{self._leg}: {what} [{detail}]")

    def close(self, got, want, scale, tol, what):
        """max|got - want| <= tol * scale, elementwise scale allowed."""
        import numpy as np

        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        scale = np.maximum(np.asarray(scale, np.float64), 1e-30)
        finite = bool(np.isfinite(got).all())
        err = float(np.max(np.abs(got - want) / scale)) if finite \
            else float("inf")
        self.check(finite and err <= tol, what, f"err {err:.2e} <= {tol:.0e}")


# --------------------------------------------------------------------------
# data, from a seed
# --------------------------------------------------------------------------

def make_data(sz):
    """Host arrays for the GLMix: design [n, d_g] f32 whose first d_sig
    columns carry the fixed signal, per-user features [n, d_u], labels from
    the generative logit (std ~1.4: label noise is real, AUC lands in the
    gate's band).  The design is filled by 8 seeded streams on 8 threads —
    the same bytes on any machine, in seconds instead of most of a minute."""
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    users, per_user = sz["users"], sz["per_user"]
    n = users * per_user
    xg = np.empty((n, sz["d_g"]), np.float32)
    streams = np.random.SeedSequence(SEED).spawn(8)
    bounds = np.linspace(0, n, len(streams) + 1).astype(int)
    with ThreadPoolExecutor(len(streams)) as pool:
        list(pool.map(
            lambda k: np.random.default_rng(streams[k]).standard_normal(
                dtype=np.float32, out=xg[bounds[k]:bounds[k + 1]]),
            range(len(streams))))
    rng = np.random.default_rng(SEED)
    xu = rng.standard_normal((n, sz["d_u"]), dtype=np.float32)
    # users' rows interleaved, as real logs are
    uids = rng.permutation(np.repeat(np.arange(users, dtype=np.int64),
                                     per_user))
    wg = rng.normal(size=sz["d_sig"]) * 0.3
    wu = rng.normal(size=(users, sz["d_u"])) * 0.35
    logits = xg[:, :sz["d_sig"]].astype(np.float64) @ wg + np.einsum(
        "nd,nd->n", xu.astype(np.float64), wu[uids])
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logits))).astype(np.float32)
    return {"xg": xg, "xu": xu, "uids": uids, "y": y}


def game_data(d, rows=slice(None)):
    from photon_ml_tpu.game import GameData

    return GameData(y=d["y"][rows],
                    features={"g": d["xg"][rows], "u": d["xu"][rows]},
                    id_tags={"userId": d["uids"][rows]})


def game_config(sz):
    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.game import FixedEffectConfig, RandomEffectConfig
    from photon_ml_tpu.game.config import GameConfig
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.types import TaskType

    solver = SolverConfig(max_iters=sz["solver_iters"], tolerance=1e-7)
    return GameConfig(
        task=TaskType.LOGISTIC_REGRESSION, num_outer_iterations=2,
        coordinates={
            "fixed": FixedEffectConfig(
                feature_shard="g", solver=solver, reg=Regularization(l2=1.0),
                storage_dtype="bfloat16"),
            "per-user": RandomEffectConfig(
                random_effect_type="userId", feature_shard="u",
                solver=solver, reg=Regularization(l2=1.0),
                active_cap=sz["cap"], storage_dtype="bfloat16"),
        })


# --------------------------------------------------------------------------
# train leg
# --------------------------------------------------------------------------

def fit(sz, data, mesh=None):
    """``GameEstimator.fit(fused=True)`` with a held-in validation subset.
    Returns the fit result plus what ``FusedSweep.run_validated`` saw and
    returned — the estimator keeps neither the sweep nor the loss matrix."""
    from photon_ml_tpu.evaluation import EvaluationSuite
    from photon_ml_tpu.game import GameEstimator
    from photon_ml_tpu.game.fused import FusedSweep

    seen = {}
    run_validated = FusedSweep.run_validated

    @functools.wraps(run_validated)
    def recording(self, plan, **kw):
        out = run_validated(self, plan, **kw)
        seen.update(sweep=self, plan=plan, losses=out[3], kw=kw)
        return out

    est = GameEstimator(mesh=mesh, fused=True,
                        validation_suite=EvaluationSuite.from_specs(["auc"]))
    val = game_data(data, slice(None, None, sz["val_stride"]))
    FusedSweep.run_validated = recording
    try:
        result = est.fit(game_data(data), [game_config(sz)],
                         validation_data=val)[0]
    finally:
        FusedSweep.run_validated = run_validated
    return result, seen


def lowered_sweep_text(seen):
    """StableHLO of the validated program for the arguments it just ran
    with: a pallas kernel that went to Mosaic is a ``tpu_custom_call``
    carrying the kernel's name; one that was interpreted or replaced by
    XLA is not there."""
    sweep, plan = seen["sweep"], seen["plan"]
    _key, program = sweep._validated_program(plan)
    return program.lower(*sweep._validated_args(plan)).as_text()


def check_fit(ck, sz, data, result, seen, on_chip):
    import numpy as np

    ck.check("sweep" in seen, "FusedSweep.run_validated was the program "
             "that ran (not the host loop)")
    if "sweep" not in seen:
        return
    fixed = seen["sweep"].coordinates["fixed"]
    n = len(data["y"])

    # anchor 1: the logistic objective at w=0 is n*log(2), through the
    # coordinate's own objective and padded device batch (on the chip:
    # the fused kernel at this run's shape)
    import jax
    import jax.numpy as jnp

    v0, _ = jax.jit(fixed._objective.value_and_grad)(
        jnp.zeros(fixed.dim, jnp.float32), fixed._batch)
    ck.close(v0, n * np.log(2.0), n * np.log(2.0), 1e-5,
             "objective at w=0 equals n*log(2)")

    # anchor 2: per-update loss matrix [iterations, coordinates]
    losses = np.asarray(seen["losses"], np.float64)
    flat = losses.reshape(-1)
    ck.check(losses.shape == (2, 2) and bool(np.isfinite(flat).all()),
             "per-update loss matrix is finite [2, 2]", f"{flat.round(5)}")
    # (rows beyond a user's active cap are held OUT of the per-user fit, so
    # on this subset a late per-user update may give back a little)
    best = np.minimum.accumulate(flat)
    ck.check(bool((flat[1:] <= 1.005 * best[:-1]).all())
             and flat[-1] < flat[0] < np.log(2.0),
             "per-update losses start below log(2) and decrease (each "
             "within 0.5% of the best before it)")

    # anchor 3: held-in AUC inside glmix_chip's band, and the
    # fit's mass on the signal columns
    auc = float(result.evaluation.primary)
    ck.check(AUC_BAND[0] <= auc <= AUC_BAND[1], "held-in AUC in the "
             f"glmix_chip band {AUC_BAND}", f"auc {auc:.4f}")
    w = np.abs(np.asarray(result.model["fixed"].coefficients.means))
    sig, noise = w[:sz["d_sig"]].mean(), w[sz["d_sig"]:].mean()
    ck.check(sig > 5 * noise, "fixed coefficients sit on the signal columns",
             f"signal {sig:.4f} noise {noise:.5f}")

    # block pickers: the blocks glmix_chip's 131k users would get
    re = seen["sweep"].coordinates["per-user"]
    ck.check(re._use_soa and len(re._dev) == 1,
             "per-user coordinate takes the SoA Newton solver, one bucket",
             f"buckets {[tuple(b['x'].shape) for b in re._dev]}")
    if on_chip:
        from photon_ml_tpu.ops import fused_glm, soa_newton

        lanes, cap, d = re._dev[0]["x"].shape
        n_dev = 1 if fixed.mesh is None else fixed.mesh.size
        ck.check(fused_glm._pick_block_rows(fixed._padded_n // n_dev,
                                            fixed.dim, 2)
                 == fused_glm._pick_block_rows(131072 * 64, 512, 2),
                 "fused_glm block rows as at 131k users")
        ck.check(soa_newton._pick_block_lanes(cap, d, lanes // n_dev, 2)
                 == soa_newton._pick_block_lanes(32, 4, 131072, 2),
                 "soa_newton block lanes as at 131k users")
        text = lowered_sweep_text(seen)
        for kernel in ("fused_glm_value_grad", "soa_newton_step"):
            ck.check("tpu_custom_call" in text and kernel in text,
                     f"{kernel} is a Mosaic custom call in the lowered sweep")


# --------------------------------------------------------------------------
# kernel leg
# --------------------------------------------------------------------------

def _glm_case(ck, label, loss, batch, w, v, interpret):
    """fused_value_and_grad + fused_hvp against plain XLA at matching
    operand widths (storage-width MXU operands, f32 accumulation)."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.ops.fused_glm import fused_hvp, fused_value_and_grad

    f32 = jnp.float32
    w_s, v_s = w.astype(batch.x.dtype), v.astype(batch.x.dtype)

    @jax.jit
    def kernels(w_s, v_s, b):
        return (fused_value_and_grad(loss, w_s, b, interpret=interpret)
                + fused_hvp(loss, w_s, v_s, b, interpret=interpret))

    @jax.jit
    def xla(w_s, v_s, b):
        with jax.default_matmul_precision("highest"):
            mm = functools.partial(jnp.matmul, preferred_element_type=f32)
            z = mm(b.x, w_s) + b.offset
            z = jnp.where(b.weight > 0, z, 0.0)
            l, d1 = loss.loss_and_d1(z, b.y)
            r = b.weight * d1
            q = b.weight * loss.d2(z, b.y) * mm(b.x, v_s)
            ax = jnp.abs(b.x)
            rs, qs = r.astype(b.x.dtype), q.astype(b.x.dtype)
            return ((jnp.sum(b.weight * l), mm(rs, b.x), jnp.sum(r),
                     mm(qs, b.x), jnp.sum(q)),
                    # the scale of each reduction: its absolute terms
                    (jnp.sum(jnp.abs(b.weight * l)), mm(jnp.abs(rs), ax),
                     jnp.sum(jnp.abs(r)), mm(jnp.abs(qs), ax),
                     jnp.sum(jnp.abs(q))))

    got = kernels(w_s, v_s, batch)
    want, scale = xla(w_s, v_s, batch)
    tol = KERNEL_TOL if batch.x.dtype.itemsize >= 4 else KERNEL_TOL_BF16
    for name, g, t, s in zip(("value", "grad", "rsum", "hv", "qsum"),
                             got, want, scale):
        ck.close(g, t, s, tol, f"fused_glm {label} {name}")
    return kernels.lower(w_s, v_s, batch).as_text()


def _glm_batch(rng, n, d, dtype, poisson=False):
    import jax.numpy as jnp
    import numpy as np

    from photon_ml_tpu.core.batch import dense_batch

    x = rng.standard_normal((n, d), dtype=np.float32) * (8.0 / d) ** 0.5
    y = (rng.poisson(1.5, size=n) if poisson
         else rng.random(n) > 0.5).astype(np.float32)
    wt = rng.random(n).astype(np.float32) + 0.5
    wt[: n // 16] = 0.0  # padded / masked rows
    b = dense_batch(x, y, offset=rng.normal(size=n).astype(np.float32) * 0.1,
                    weight=wt)
    return b.replace(x=b.x.astype(dtype)), jnp.asarray(
        rng.normal(size=d).astype(np.float32) * 0.3), jnp.asarray(
        rng.normal(size=d).astype(np.float32))


def _soa_case(ck, label, loss, w, x_t, y, off, wt, l2, interpret):
    """The pallas Newton step against newton_soa's _hess + Cholesky chain.
    Scale: each lane's own step size — a Newton step is a solve, so its
    error grows with the lane's conditioning; l2 >= 1 keeps that bounded."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from photon_ml_tpu.ops import soa_newton
    from photon_ml_tpu.opt.newton_soa import (_cholesky_solve_soa, _hess,
                                              _value_grad)

    d = w.shape[0]

    @jax.jit
    def kernel(w, x_t, y, off, wt, l2):
        _, g = _value_grad(loss, w, x_t, y, off, wt, l2)
        return soa_newton.newton_step(loss, w, g, x_t, y, off, wt, l2,
                                      interpret=interpret)

    @jax.jit
    def xla(w, x_t, y, off, wt, l2):
        _, g = _value_grad(loss, w, x_t, y, off, wt, l2)
        hh = _hess(loss, w, x_t, y, off, wt, l2)
        eps = jnp.asarray(np.finfo(np.float32).eps, w.dtype)
        jitter = eps * (jnp.abs(jnp.stack(
            [hh[i][i] for i in range(d)])).max(0) + 1.0)
        return _cholesky_solve_soa(hh, g, jitter)

    args = (w, x_t, y, off, wt, l2)
    got, want = kernel(*args), xla(*args)
    lane_scale = np.abs(np.asarray(want)).max(axis=0, keepdims=True)
    ck.close(got, want, np.maximum(lane_scale, 1e-3), 2e-5,
             f"soa_newton {label} step")
    return kernel.lower(*args).as_text()


def _soa_problem(rng, cap, d, lanes, dtype):
    import jax.numpy as jnp
    import numpy as np

    f = np.float32
    x = jnp.asarray(rng.standard_normal((cap, d, lanes), dtype=f)).astype(dtype)
    wt = rng.uniform(0.5, 2.0, size=(cap, lanes)).astype(f)
    wt[:, : lanes // 8] = 0.0     # weightless lanes (H = l2 I)
    wt[cap - cap // 4:, lanes // 2:] = 0.0  # padded sample slots
    return (jnp.asarray(rng.normal(size=(d, lanes)).astype(f) * 0.1), x,
            jnp.asarray((rng.random((cap, lanes)) < 0.5).astype(f)),
            jnp.asarray(rng.normal(size=(cap, lanes)).astype(f) * 0.1),
            jnp.asarray(wt),
            jnp.asarray(rng.uniform(1.0, 2.0, size=lanes).astype(f)))


def _compact_case(ck, label, rng, k_model, k_feat, n, entities, dim,
                  interpret):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from photon_ml_tpu.models.game import score_compact_sparse_xla
    from photon_ml_tpu.ops import compact_score

    w_idx, w_val = _compact_rows(rng, entities, k_model, dim)
    slots = rng.integers(-1, entities, size=n).astype(np.int32)
    f_idx = rng.integers(0, dim, size=(n, k_feat)).astype(np.int32)
    if k_feat > 3:
        f_idx[:, 3] = f_idx[:, 2]  # duplicate feature ids accumulate
    f_val = rng.normal(size=(n, k_feat)).astype(np.float32)
    f_val[:, -1] = 0.0             # padded COO slots carry value 0
    args = tuple(map(jnp.asarray, (w_idx, w_val, slots, f_idx, f_val)))
    kernel = jax.jit(functools.partial(compact_score.score_sparse_compact,
                                       interpret=interpret))
    got = kernel(*args)
    want = jax.jit(score_compact_sparse_xla)(*args)
    scale = jax.jit(score_compact_sparse_xla)(
        args[0], jnp.abs(args[1]), args[2], args[3], jnp.abs(args[4]))
    ck.close(got, want, np.maximum(np.asarray(scale), 1.0), KERNEL_TOL,
             f"compact_score {label} margins")
    return kernel.lower(*args).as_text()


def _compact_rows(rng, entities, k_model, dim):
    """Sorted unique coefficient columns per entity, dim-padded tails."""
    import numpy as np

    w_idx = np.full((entities, k_model), dim, np.int32)
    w_val = np.zeros((entities, k_model), np.float32)
    for e in range(entities):
        nn = int(rng.integers(max(1, k_model // 2), k_model + 1))
        w_idx[e, :nn] = np.sort(rng.choice(dim, size=nn, replace=False))
        w_val[e, :nn] = rng.normal(size=nn)
    return w_idx, w_val


def kernel_leg(ck, sz, on_chip, seen=None):
    """Every kernel, compiled (or, in a dry run, interpreted), against the
    XLA path it replaces.  ``seen``: the train leg's sweep — its device
    arrays are "this run's shape"."""
    import jax.numpy as jnp
    import numpy as np

    from photon_ml_tpu.core.losses import (logistic_loss, poisson_loss,
                                           squared_loss)
    from photon_ml_tpu.ops import compact_score, fused_glm, soa_newton

    interpret = not on_chip
    rng = np.random.default_rng(SEED + 1)
    f32, bf16 = jnp.float32, jnp.bfloat16
    lowered = []  # (kernel names, StableHLO text) per compiled case
    glm_kernels = ("fused_glm_value_grad", "fused_glm_hvp")

    # fused_glm: the shapes of the 2026-08-02 record, then the gate's bound
    # (a 16 KiB design row: d=4096 in f32, 8192 in bf16)
    n0, d0 = (4096, 256) if on_chip else (256, 128)
    cases = [("logistic", logistic_loss, n0, d0, f32),
             ("squared", squared_loss, n0, d0, f32),
             ("poisson", poisson_loss, n0, d0, f32),
             ("logistic bf16", logistic_loss, n0, d0, bf16)]
    if on_chip:
        row = fused_glm._MAX_ROW_BYTES
        cases += [(f"gate bound d={row // 4} f32", logistic_loss, 16384,
                   row // 4, f32),
                  (f"gate bound d={row // 2} bf16", logistic_loss, 16384,
                   row // 2, bf16)]
    for label, loss, n, d, dt in cases:
        b, w, v = _glm_batch(rng, n, d, dt, poisson=loss is poisson_loss)
        ck.check(fused_glm.eligible(b, interpret), f"fused_glm {label} "
                 "is inside the gate")
        lowered.append((glm_kernels,
                        _glm_case(ck, label, loss, b, w, v, interpret)))

    # soa_newton: the gate's cap bound at d=16 (what online/trainer.py can
    # reach) and at d=4 in bf16, and a lane count that is no power of two
    if on_chip:
        cap16 = max(c for c in range(1, 1024)
                    if soa_newton.eligible(16, 128, c, 4))
        cap4 = max(c for c in range(1, 1024)
                   if soa_newton.eligible(4, 128, c, 2))
        soa_cases = [(f"gate bound d=16 cap={cap16} f32", cap16, 16, 128, f32),
                     (f"gate bound d=4 cap={cap4} bf16", cap4, 4, 128, bf16),
                     ("1152 lanes f32", 32, 4, 1152, f32)]
    else:
        soa_cases = [("d=5 f32", 12, 5, 256, f32),
                     ("1152 lanes bf16", 8, 4, 1152, bf16)]
    for label, cap, d, lanes, dt in soa_cases:
        ck.check(soa_newton.eligible(d, lanes, cap, jnp.dtype(dt).itemsize,
                                     interpret),
                 f"soa_newton {label} is inside the gate")
        lowered.append((("soa_newton_step",), _soa_case(
            ck, label, logistic_loss, *_soa_problem(rng, cap, d, lanes, dt),
            interpret)))

    # compact_score: the corners of its gate
    if on_chip:
        cc = [("64x64", 64, 64, 8192, 1024, 4096),
              ("8x512", 8, 512, 4096, 256, 4096),
              ("2048x2", 2048, 2, 4096, 64, 8192),
              ("6x9, 300 rows", 6, 9, 300, 40, 60)]
    else:
        cc = [("6x9, 300 rows", 6, 9, 300, 40, 60)]
    for label, km, kf, n, ent, dim in cc:
        ck.check(compact_score.eligible(km, kf, 4, interpret),
                 f"compact_score {label} is inside the gate")
        lowered.append((("compact_match_dot",), _compact_case(
            ck, label, rng, km, kf, n, ent, dim, interpret)))

    # this run's shapes: the trained coordinates' own device arrays
    if seen and "sweep" in seen:
        fixed = seen["sweep"].coordinates["fixed"]
        re = seen["sweep"].coordinates["per-user"]
        if fixed.mesh is None:
            d = fixed.dim
            w = jnp.asarray(rng.normal(size=d).astype(np.float32) * 0.05)
            v = jnp.asarray(rng.normal(size=d).astype(np.float32))
            lowered.append((glm_kernels, _glm_case(
                ck, f"run shape {tuple(fixed._batch.x.shape)} "
                f"{fixed._batch.x.dtype}", logistic_loss, fixed._batch, w, v,
                interpret)))
            dev = re._dev[0]
            lanes, cap, d_u = dev["x"].shape
            lowered.append((("soa_newton_step",), _soa_case(
                ck, f"run shape cap={cap} d={d_u} lanes={lanes} "
                f"{dev['x'].dtype}", logistic_loss,
                jnp.asarray(rng.normal(size=(d_u, lanes)).astype(np.float32)
                            * 0.1),
                jnp.transpose(dev["x"], (1, 2, 0)), jnp.transpose(dev["y"]),
                jnp.zeros((cap, lanes), f32), jnp.transpose(dev["w"]),
                jnp.ones((lanes,), f32), interpret)))

    if on_chip:
        for kernel in glm_kernels + ("soa_newton_step", "compact_match_dot"):
            texts = [t for names, t in lowered if kernel in names]
            ck.check(all("tpu_custom_call" in t and kernel in t
                         for t in texts),
                     f"{kernel}: every case lowered to a Mosaic custom call",
                     f"{len(texts)} case(s)")


# --------------------------------------------------------------------------
# serve and compact legs
# --------------------------------------------------------------------------

def _names(sz):
    return ([f"g{j}" for j in range(sz["d_g"])],
            [f"u{j}" for j in range(sz["d_u"])])


def save_model(sz, model, out_dir):
    """The model directory ``cli.serve`` loads: coefficients, one index map
    per feature shard, the entity index."""
    from photon_ml_tpu.data.index_map import IndexMap, feature_key
    from photon_ml_tpu.data.reader import EntityIndex
    from photon_ml_tpu.storage.model_io import save_game_model
    from photon_ml_tpu.types import TaskType

    g_names, u_names = _names(sz)
    imaps = {"g": IndexMap({feature_key(n): j for j, n in enumerate(g_names)}),
             "u": IndexMap({feature_key(n): j for j, n in enumerate(u_names)})}
    eidx = EntityIndex()
    for i in range(sz["users"]):
        eidx.get_or_add(f"user{i}")
    save_game_model(model, out_dir, imaps, entity_indexes={"userId": eidx},
                    task=TaskType.LOGISTIC_REGRESSION)
    for shard, m in imaps.items():
        m.save(os.path.join(out_dir, f"{shard}.idx"))
    eidx.save(os.path.join(out_dir, "userId.entities.json"))
    return imaps, eidx


def make_requests(sz):
    """Wire requests, ~10% for users the model has never seen; the first is
    pinned to user3, the delta's target."""
    import numpy as np

    rng = np.random.default_rng(SEED + 2)
    g_names, u_names = _names(sz)
    names = g_names + u_names
    out = []
    for i in range(sz["requests"]):
        vals = rng.standard_normal(len(names)).astype(np.float32)
        user = (f"ghost{i}" if i % 10 == 9
                else f"user{int(rng.integers(0, sz['users']))}")
        out.append({"uid": i, "ids": {"userId": "user3" if i == 0 else user},
                    "features": [[n, float(v)] for n, v in zip(names, vals)]})
    return out


def socket_scores(ck, engine, swapper, wires, what):
    """Start a ThreadedFrontend, send every request over one loopback
    connection, return scores by uid.  Every reply must carry a score: the
    serving loop turns a device error into an {"error": ...} line and
    carries on, so the reply is the only place a failure shows."""
    import numpy as np

    from photon_ml_tpu.serving.frontend import (AdmissionConfig,
                                                FrontendConfig,
                                                ThreadedFrontend)

    front = ThreadedFrontend(engine, swapper, FrontendConfig(
        admission=AdmissionConfig(budget_s=60.0),
        batcher_deadline_s=0.002)).start()
    replies = []
    try:
        with socket.create_connection(("127.0.0.1", front.port),
                                      timeout=120) as sock:
            f = sock.makefile("rw", encoding="utf-8", newline="\n")
            for w in wires:
                f.write(json.dumps(w) + "\n")
            f.write("\n")  # blank line: flush the batcher
            f.flush()
            for _ in wires:
                line = f.readline()
                if not line:
                    break
                replies.append(json.loads(line))
    finally:
        front.stop()
    scored = {r["uid"]: r["score"] for r in replies if "score" in r}
    ck.check(len(scored) == len(wires),
             f"{what}: every request got a score over the socket",
             f"{len(scored)}/{len(wires)}; first bad reply: "
             f"{next((r for r in replies if 'score' not in r), None)}")
    return np.asarray([scored.get(w["uid"], np.nan) for w in wires])


def reference_scores(sz, model, imaps, eidx, wires):
    """(GameModel.score on the same rows, a plain jax.numpy float32 forward
    at highest matmul precision, the bf16-product error bound per row)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from photon_ml_tpu.game import GameData
    from photon_ml_tpu.serving.batcher import (densify_features,
                                               request_from_json)

    reqs = [request_from_json(w) for w in wires]
    xs = densify_features(reqs, imaps, len(reqs))
    ids = np.asarray([eidx.get(r.ids["userId"]) for r in reqs], np.int64)
    gd = GameData(y=np.zeros(len(reqs), np.float32), features=xs,
                  id_tags={"userId": ids})
    batch = np.asarray(model.score(gd))

    w_g = jnp.asarray(model["fixed"].coefficients.means, jnp.float32)
    re = model["per-user"]
    slots = re.slots_for(gd)  # -1: a user the model has never seen
    w_u = jnp.asarray(np.where(slots[:, None] >= 0,
                               re.w_stack[np.maximum(slots, 0)], 0.0),
                      jnp.float32)
    xg, xu = jnp.asarray(xs["g"]), jnp.asarray(xs["u"])
    with jax.default_matmul_precision("highest"):
        plain = xg @ w_g + jnp.sum(xu * w_u, axis=1)
        # The chip multiplies f32 matmul operands as bf16 at default
        # precision (8 significant bits each): every product is off by at
        # most 2^-8 of itself, so a row is off by at most 2^-8 * sum|x||w|.
        bound = 2.0 ** -8 * (jnp.abs(xg) @ jnp.abs(w_g)
                             + jnp.sum(jnp.abs(xu * w_u), axis=1))
    return batch, np.asarray(plain), np.asarray(bound)


def serve_leg(ck, sz, model, model_dir, wires, mesh_shards=0):
    """build_server -> frontend -> socket; parity; delta + rebalance with
    no recompile.  Returns the pre-delta socket scores."""
    import numpy as np

    from photon_ml_tpu.cli.serve import build_server
    from photon_ml_tpu.storage.model_io import load_model_bundle

    engine, swapper = build_server(model_dir, mesh_shards=mesh_shards)
    warm = engine.compile_count
    ck.check(warm > 0, "build_server warmed the bucket ladder",
             f"{warm} executable(s)")
    bundle = load_model_bundle(model_dir)
    imaps, eidx = bundle.index_maps, bundle.entity_indexes["userId"]
    got = socket_scores(ck, engine, swapper, wires, "dense")
    batch, plain, bound = reference_scores(sz, bundle.model, imaps, eidx,
                                           wires)
    top = np.abs(batch).max()
    ck.close(got, batch, top, 1e-5, "socket scores == GameModel.score")
    ck.close(got, plain, bound + 1e-6 * top, 1.0, "socket scores within "
             "the bf16-product bound of the float32 highest-precision "
             "forward")
    unknown = sum(eidx.get(w["ids"]["userId"]) < 0 for w in wires)
    ck.check(unknown >= len(wires) // 10, "about a tenth of the requests "
             "name a user the model has never seen", f"{unknown}")

    store = engine.store
    row = np.asarray([0.5, -0.25, 0.125, 1.0][: sz["d_u"]], np.float32)
    ck.check(store.apply_delta("per-user", "user3", row),
             "apply_delta accepted for user3")
    after = socket_scores(ck, engine, swapper, wires, "dense after delta")
    hit = np.asarray([w["ids"]["userId"] == "user3" for w in wires])
    ck.check(abs(after[0] - got[0]) > 1e-6
             and bool(np.allclose(after[~hit], got[~hit], rtol=1e-6,
                                  atol=1e-6)),
             "the delta moved user3's score and no other user's",
             f"{got[0]:.5f} -> {after[0]:.5f}")
    store.rebalance()
    again = socket_scores(ck, engine, swapper, wires, "dense after rebalance")
    ck.close(again, after, top, 1e-6, "rebalance leaves scores where "
             "they were")
    ck.check(engine.compile_count == warm, "no recompile across delta and "
             "rebalance", f"compile_count {engine.compile_count} == {warm}")
    if mesh_shards:
        table = store.coordinates["per-user"].table
        shards = table.addressable_shards
        ck.check(len({s.device for s in shards}) == mesh_shards
                 and all(s.data.shape[0] * mesh_shards == table.shape[0]
                         for s in shards),
                 f"per-user table: {mesh_shards} devices hold 1/"
                 f"{mesh_shards} of the rows each",
                 f"{[tuple(s.data.shape) for s in shards]}")
    return got


def compact_leg(ck, sz, model, imaps, eidx, wires, dense_scores, on_chip):
    import numpy as np

    from photon_ml_tpu.game import GameData
    from photon_ml_tpu.game.data import SparseShard
    from photon_ml_tpu.models.game import (CompactRandomEffectModel,
                                           GameModel, _score_sparse_compact,
                                           score_compact_sparse_xla)
    from photon_ml_tpu.serving.batcher import BucketedBatcher
    from photon_ml_tpu.serving.coefficient_store import (
        CoefficientStore, CompactRandomCoordinate)
    from photon_ml_tpu.serving.engine import ScoringEngine
    from photon_ml_tpu.serving.swap import HotSwapper
    from photon_ml_tpu.types import TaskType

    task = TaskType.LOGISTIC_REGRESSION
    compact = GameModel(models={"fixed": model["fixed"],
                                "per-user": model["per-user"].to_compact()})
    store = CoefficientStore.from_model(compact, task, {"userId": eidx},
                                        imaps)
    ck.check(isinstance(store.coordinates["per-user"],
                        CompactRandomCoordinate),
             "the store serves the compact coordinate natively")
    engine = ScoringEngine(store, BucketedBatcher(64))
    warm = engine.warm()
    swapper = HotSwapper(engine)
    got = socket_scores(ck, engine, swapper, wires, "compact")
    ck.close(got, dense_scores, np.abs(dense_scores).max(), 1e-6,
             "compact socket scores == dense socket scores")
    row = np.asarray([0.5, -0.25, 0.125, 1.0][: sz["d_u"]], np.float32)
    ck.check(store.apply_delta("per-user", "user3", row),
             "compact apply_delta accepted for user3")
    after = socket_scores(ck, engine, swapper, wires, "compact after delta")
    ck.check(abs(after[0] - got[0]) > 1e-6, "the delta moved user3's score")
    ck.check(engine.compile_count == warm, "no recompile across the delta",
             f"compile_count {engine.compile_count} == {warm}")

    # batch scoring of a compact model over a SPARSE per-user shard — the
    # cli.score path, and compact_score's one production caller
    rng = np.random.default_rng(SEED + 3)
    n, ent = sz["sparse_rows"], sz["sparse_entities"]
    k, dim = sz["sparse_k"], sz["sparse_dim"]
    w_idx, w_val = _compact_rows(rng, ent, k, dim)
    cm = CompactRandomEffectModel(
        indices=w_idx, values=w_val, dim=dim,
        slot_of={i: i for i in range(ent)}, random_effect_type="userId",
        feature_shard="s", task=task)
    f_idx = rng.integers(0, dim, size=(n, k)).astype(np.int32)
    f_val = rng.normal(size=(n, k)).astype(np.float32)
    ids = rng.integers(-1, ent + ent // 8, size=n)  # some unknown
    gd = GameData(y=np.zeros(n, np.float32),
                  features={"s": SparseShard(f_idx, f_val, dim)},
                  id_tags={"userId": ids})
    got = np.asarray(GameModel(models={"c": cm}).score(gd))
    args = (w_idx, w_val, cm.slots_for(gd).astype(np.int32), f_idx, f_val)
    want = np.asarray(score_compact_sparse_xla(*args))
    scale = np.asarray(score_compact_sparse_xla(
        w_idx, np.abs(w_val), args[2], f_idx, np.abs(f_val)))
    ck.close(got, want, np.maximum(scale, 1.0), KERNEL_TOL,
             f"GameModel.score over a sparse shard ({k}x{k}) == the "
             "searchsorted path")
    if on_chip:
        text = _score_sparse_compact.lower(*args).as_text()
        ck.check("tpu_custom_call" in text and "compact_match_dot" in text,
                 "the sparse batch score is a Mosaic custom call")


# --------------------------------------------------------------------------
# mesh legs
# --------------------------------------------------------------------------

def mesh_train_leg(ck, sz, data, on_chip):
    """The train leg over four devices.  Run FIRST, and the fixed
    coordinate built alone before anything else, so that each device's
    peak memory speaks for the placement of the design and nothing else:
    staged whole on the first chip, it would show there."""
    import jax

    from photon_ml_tpu.game.coordinate import build_coordinate
    from photon_ml_tpu.parallel.mesh import make_mesh

    devices = jax.devices()[:4]
    mesh = make_mesh(devices=devices)
    cfg = game_config(sz)
    probe = build_coordinate("fixed", game_data(data),
                             cfg.coordinates["fixed"], cfg.task, mesh)
    design = probe._batch.x.nbytes
    stats = [d.memory_stats() for d in devices]
    if all(s and "peak_bytes_in_use" in s for s in stats):
        peaks = [s["peak_bytes_in_use"] for s in stats]
        ck.check(max(peaks) < 0.5 * design,
                 "placing the design: no device ever held even half of it",
                 f"design {design / 2**20:.0f} MiB, per-device peaks "
                 f"{[round(p / 2**20) for p in peaks]} MiB")
    else:
        ck.check(not on_chip, "per-device memory_stats (none on this "
                 "backend: the peak check is skipped)")
    del probe

    result, seen = fit(sz, data, mesh=mesh)
    check_fit(ck, sz, data, result, seen, on_chip)
    if "sweep" in seen:
        fixed = seen["sweep"].coordinates["fixed"]
        re = seen["sweep"].coordinates["per-user"]
        for what, arr in (("fixed design", fixed._batch.x),
                          ("per-user bucket", re._dev[0]["x"])):
            shards = arr.addressable_shards
            ck.check(len({s.device for s in shards}) == 4 and all(
                s.data.shape[0] * 4 == arr.shape[0] for s in shards),
                f"{what}: four devices hold a quarter of the rows each",
                f"{tuple(arr.shape)} -> "
                f"{[tuple(s.data.shape) for s in shards]}")
    return result


def check_mesh_parity(ck, one, four):
    import numpy as np

    w1 = np.asarray(one.model["fixed"].coefficients.means)
    w4 = np.asarray(four.model["fixed"].coefficients.means)
    # the same solve with its reductions split four ways: f32 sums in
    # another order, carried through 2 x 30 solver iterations
    ck.close(w4, w1, np.abs(w1).max(), 2e-3,
             "fixed coefficients: four devices == one device")
    u1, u4 = one.model["per-user"], four.model["per-user"]
    ck.check(u1.slot_of == u4.slot_of, "per-user slots agree")
    ck.close(u4.w_stack, u1.w_stack, np.abs(u1.w_stack).max(), 5e-3,
             "per-user coefficients: four devices == one device")
    ck.close(four.evaluation.primary, one.evaluation.primary, 1.0, 1e-3,
             "held-in AUC: four devices == one device")


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny sizes on the CPU backend, kernels "
                         "interpreted; for debugging this script — NOT a "
                         "chip result")
    args = ap.parse_args(argv)
    if args.dry_run:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before jax is imported

    import jax

    from photon_ml_tpu.utils.runtime import init_runtime

    cache = {"cache_hits": 0, "cache_misses": 0}

    def count(event, **_):
        key = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and key in cache:
            cache[key] += 1

    jax.monitoring.register_event_listener(count)
    t_start = time.perf_counter()
    device = init_runtime()
    print(f"platform: {device['platform']}  device_kind: {device['kind']}  "
          f"devices: {device['count']}", flush=True)
    on_chip = device["platform"] == "tpu"
    if not on_chip and not args.dry_run:
        print("chip_smoke: no TPU — jax.devices()[0].platform is "
              f"{device['platform']!r}.  This is not a chip run and no "
              "result is printed.  (--dry-run debugs the script on the "
              "CPU.)", file=sys.stderr)
        return 1
    if args.dry_run:
        print("DRY RUN: CPU backend, tiny sizes, interpreted kernels — "
              "not a chip run", flush=True)
    sz = _DRY if args.dry_run else _CHIP
    ck = Checks()
    t0 = time.perf_counter()
    data = make_data(sz)
    print(f"data: {len(data['y'])} rows x {sz['d_g']} + {sz['d_u']}, "
          f"{sz['users']} users, {time.perf_counter() - t0:.1f}s", flush=True)
    wires = make_requests(sz)
    four = None
    if jax.device_count() >= 4:
        with ck.leg("mesh train (4 devices)"):
            four = mesh_train_leg(ck, sz, data, on_chip)
    one = seen = None
    with ck.leg("train"):
        one, seen = fit(sz, data)
        check_fit(ck, sz, data, one, seen, on_chip)
        if four is not None:
            check_mesh_parity(ck, one, four)
    with ck.leg("kernel"):
        kernel_leg(ck, sz, on_chip, seen)
    seen = None  # lets the training arrays go before serving starts
    if one is not None:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            model_dir = os.path.join(tmp, "model")
            imaps, eidx = save_model(sz, one.model, model_dir)
            dense = None
            with ck.leg("serve"):
                dense = serve_leg(ck, sz, one.model, model_dir, wires)
            if dense is not None:
                with ck.leg("compact"):
                    compact_leg(ck, sz, one.model, imaps, eidx, wires,
                                dense, on_chip)
                if jax.device_count() >= 4:
                    with ck.leg("mesh serve (4 shards)"):
                        sharded = serve_leg(ck, sz, one.model, model_dir,
                                            wires, mesh_shards=4)
                        ck.close(sharded, dense, abs(dense).max(), 1e-6,
                                 "sharded socket scores == one-device "
                                 "socket scores")

    total = time.perf_counter() - t_start
    for line in ck.failed:
        print("FAILED " + line, flush=True)
    print(f"{len(ck.failed)} failed check(s); {total:.1f}s in all "
          f"(compilation included); compile cache "
          f"{os.environ.get('JAX_COMPILATION_CACHE_DIR') or jax.config.jax_compilation_cache_dir}"
          f": {cache['cache_hits']} hit(s), {cache['cache_misses']} miss(es)",
          flush=True)
    print("legs: " + json.dumps(ck.legs), flush=True)
    out = {"ok": not ck.failed, "device": device}
    if args.dry_run:
        out["dry_run"] = True
    print(json.dumps(out), flush=True)
    return 0 if not ck.failed else 1


if __name__ == "__main__":
    sys.exit(main())
