"""Traffic kind ``train_fits``: back-to-back GLMix fits for the window.

A fit is one ``FusedSweep.run_device()`` from zero coefficients over the
configuration's coordinates (``sweeps`` outer iterations, the config's
solver and L2), ended by ``block_until_ready`` on the published
coefficients and the scores.  ``train_examples_per_s`` is rows x sweeps of
every fit that FINISHED inside the window, over the time from the first
fit's start to the last counted fit's end, over chips.

Set-up: the recipe makes the data from the seed (the fixed design on the
device), ``build_coordinate`` buckets and uploads it, one fit compiles and
warms the one program the window runs.  After the window, outside it, the
last fit is held to the cell's gates (``checks``): AUC band, loss gate, the
configuration's own quality rule, and the published coefficients of a
seeded sample of entities of the coordinate updated last against the plain
Newton reference (reference/newton_solve.py).
"""

from __future__ import annotations

import statistics
import time

import numpy as np

SLICE_FITS = 3  # fits inside the traced slice


def rank_auc(y: np.ndarray, s: np.ndarray) -> float:
    """Rank AUC with average ranks on ties (a copy of ``bench._np_auc``'s
    arithmetic, numpy only)."""
    y = np.asarray(y, bool)
    order = np.argsort(s, kind="stable")
    s_sorted = np.asarray(s)[order]
    ranks = np.empty(len(s), np.float64)
    ranks[order] = np.arange(1, len(s) + 1, dtype=np.float64)
    # average the ranks inside each run of equal scores
    starts = np.flatnonzero(np.r_[True, s_sorted[1:] != s_sorted[:-1]])
    if len(starts) != len(s):
        ends = np.r_[starts[1:], len(s)]
        mean_rank = (starts + 1 + ends) / 2.0
        ranks[order] = np.repeat(mean_rank, ends - starts)
    n1 = int(y.sum())
    n0 = len(y) - n1
    if n1 == 0 or n0 == 0:
        return float("nan")
    return float((ranks[y].sum() - n1 * (n1 + 1) / 2) / (n1 * n0))


def build_coordinates(cfg: dict, data: dict, mesh) -> dict:
    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.game import (FixedEffectConfig, GameData,
                                    RandomEffectConfig)
    from photon_ml_tpu.game.coordinate import build_coordinate
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.types import TaskType

    gd = GameData(y=data["y"], features=data["features"],
                  id_tags=data["id_tags"])
    reg = Regularization(l2=float(cfg["l2"]))
    coords = {}
    for c in cfg["coordinates"]:
        spec = c.get("solver", cfg["solver"])  # a coordinate may have its own
        solver = SolverConfig(max_iters=int(spec["max_iters"]),
                              tolerance=float(spec["tolerance"]))
        if c["kind"] == "fixed":
            conf = FixedEffectConfig(
                feature_shard=c["feature_shard"], solver=solver, reg=reg,
                storage_dtype=c.get("storage_dtype"))
        else:
            conf = RandomEffectConfig(
                random_effect_type=c["entity"],
                feature_shard=c["feature_shard"], solver=solver, reg=reg,
                active_cap=c.get("active_cap"),
                storage_dtype=c.get("storage_dtype"))
        coords[c["id"]] = build_coordinate(
            c["id"], gd, conf, TaskType.LOGISTIC_REGRESSION, mesh)
    return coords


def training_loss(y, total, published, l2: float) -> float:
    """The regularised training loss per row, float64 on the host: mean
    logistic loss of the summed scores + l2/2 of every published
    coefficient, over n."""
    z = np.asarray(total, np.float64)
    data_term = np.sum(np.logaddexp(0.0, z) - np.asarray(y, np.float64) * z)
    reg_term = 0.5 * l2 * sum(
        float(np.sum(np.asarray(p, np.float64) ** 2)) for p in published)
    return float((data_term + reg_term) / len(z))


def newton_parity(ctx, cfg, data, coords, published, scores_host) -> dict:
    """The coordinate updated last, on a seeded sample of its entities:
    published coefficients vs the plain Newton reference on the same rows,
    weights and offsets (the program's own scores of the other
    coordinates).  Returns {"err", "entities"}; err is the largest absolute
    difference over the largest reference coefficient of the sample."""
    spec = cfg["coordinates"][-1]
    if spec["kind"] != "random":
        return {}
    ref_solve = ctx.catalog.module("reference", "newton_solve")
    coord = coords[spec["id"]]
    last = len(cfg["coordinates"]) - 1
    model = coord.export_model(np.asarray(published[last], np.float32))
    ids = data["id_tags"][spec["entity"]]
    counts = np.bincount(ids)
    kept = np.asarray(sorted(model.slot_of), np.int64)
    rng = np.random.default_rng([ctx.seed, 3])
    sample = rng.choice(kept, size=min(int(ctx.traffic["parity_entities"]),
                                       len(kept)), replace=False)
    # the active rows are the PROBLEM, not its answer: read them from the
    # program's buckets (its reservoir draw under an active cap)
    active = []
    for e in sample:
        bi, lane = coord.buckets.lane_of[int(e)]
        rows = coord.buckets.buckets[bi].rows[lane]
        active.append(rows[rows >= 0])
    s_max = max(len(r) for r in active)
    x_host = data["features"][spec["feature_shard"]]
    d = x_host.shape[1]
    x = np.zeros((len(sample), s_max, d), np.float32)
    y = np.zeros((len(sample), s_max), np.float32)
    off = np.zeros_like(y)
    wt = np.zeros_like(y)
    others = sum(s for j, s in enumerate(scores_host) if j != last)
    for k, (e, rows) in enumerate(zip(sample, active)):
        x[k, :len(rows)] = x_host[rows]
        y[k, :len(rows)] = data["y"][rows]
        off[k, :len(rows)] = others[rows]
        # rows capped out of the active set are made up for by weight
        wt[k, :len(rows)] = counts[e] / len(rows)
    if spec.get("storage_dtype"):
        import jax.numpy as jnp

        # the problem as stored: features rounded to the storage width
        x = np.asarray(jnp.asarray(x).astype(spec["storage_dtype"])
                       .astype(jnp.float32))
    ref = np.asarray(ref_solve.solve(x, y, off, wt, float(cfg["l2"])))
    got = model.w_stack[[model.slot_of[int(e)] for e in sample]]
    err = float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))
    return {"err": err, "entities": int(len(sample))}


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.game.fused import FusedSweep

    cfg, gates = ctx.config, ctx.workload.get("gates", {})
    recipe = ctx.catalog.module("recipes", cfg["recipe"])
    mesh = ctx.mesh()
    with ctx.span("data_make"):
        data = recipe.make_training(cfg, ctx.seed, mesh)
    n = len(data["y"])
    with ctx.span("coord_build"):
        coords = build_coordinates(cfg, data, mesh)
    sweeps = int(cfg["sweeps"])
    sweep = FusedSweep(coords, num_iterations=sweeps)

    @jax.jit
    def finite(published, scores):
        return (jnp.all(jnp.asarray([jnp.all(jnp.isfinite(p))
                                     for p in published]))
                & jnp.isfinite(sum(jnp.sum(s) for s in scores)))

    def fit():
        published, scores, _, _ = sweep.run_device()
        jax.block_until_ready((published, scores))
        return published, scores

    with ctx.span("warm_fit"):  # compiles (or loads) the one program
        published, scores = fit()
        bool(finite(published, scores))
    est = 0.0  # how long the last fit took: no fit starts that cannot end

    t_win = ctx.window_start()
    deadline = t_win + ctx.seconds
    fits, flags, attempted, raised = [], [], 0, 0
    profiling, sliced, slice_fits = None, False, 0
    while True:
        t0 = time.perf_counter()
        if fits and t0 + est > deadline:
            break
        # every fit starts from the same heap: the last fit's outputs are
        # dropped, and nothing that reads them is still in flight (the
        # flag below is fetched, not left on the device).  Where a fit's
        # buffers land decides how fast its gather fusions run (12% of a
        # glmix_chip fit; PERF.md section 6), and a release that raced the
        # next allocation made that a draw
        published = scores = None
        if ctx.trace and not sliced and attempted == 1:
            sliced = True
            profiling = ctx.profile_slice()
            profiling.__enter__()
            slice_end = attempted + SLICE_FITS
        attempted += 1
        try:
            with ctx.span("fit"):
                published, scores = fit()
        except Exception:  # a fit that raises is a failed fit, reported
            import traceback

            traceback.print_exc()
            raised += 1
            if raised >= 3:
                break
            continue
        t1 = time.perf_counter()
        slice_fits += profiling is not None
        flags.append(bool(finite(published, scores)))
        if t1 <= deadline or not fits:
            fits.append((t0, t1))
        est = t1 - t0
        if profiling is not None and attempted >= slice_end:
            profiling.__exit__(None, None, None)
            profiling = None
        if t1 >= deadline:
            break
    if profiling is not None:
        profiling.__exit__(None, None, None)
    ctx.window_end()
    if scores is None:
        raise RuntimeError("the last fit raised: nothing to hold to the gates")

    nonfinite = flags.count(False)
    rate = (n * sweeps * len(fits) / (fits[-1][1] - fits[0][0]) / ctx.chips
            if fits else None)
    durations = [b - a for a, b in fits]

    # -- outside the window: is the last fit right? ------------------------
    scores_host = [np.asarray(s, np.float32)[:n] for s in scores]
    total = sum(scores_host)
    pub_host = [np.asarray(p, np.float32) for p in published]
    auc = rank_auc(data["y"], total)
    loss = training_loss(data["y"], total, pub_host, float(cfg["l2"]))
    quality = cfg.get("quality", {})
    checks = {"fits_finished": bool(fits) and raised == 0,
              "losses_finite": nonfinite == 0 and np.isfinite(loss)}
    detail = {"setup_spans_s": ctx.span_seconds(("data_make", "coord_build",
                                                 "warm_fit")),
              "fits_in_window": len(fits), "rows": n, "sweeps": sweeps,
              "auc": auc, "loss_per_row": loss,
              "fit_s_min": min(durations, default=None),
              "fit_s_max": max(durations, default=None),
              "fit_s_each": [round(d, 6) for d in durations]}
    band = gates.get("auc_band") or quality.get("auc_band")
    if band and not ctx.dry_run:
        checks["auc_in_band"] = bool(band[0] <= auc <= band[1])
    if gates.get("loss_gate") is not None and not ctx.dry_run:
        checks["loss_under_gate"] = bool(loss <= gates["loss_gate"])
    if "signal_noise_ratio" in quality:
        w = np.abs(pub_host[0])
        d_sig = int(cfg["signal_columns"])
        ratio = float(w[:d_sig].mean() / max(w[d_sig:].mean(), 1e-30))
        detail["signal_noise_ratio"] = ratio
        if not ctx.dry_run:
            checks["signal_over_noise"] = ratio > quality["signal_noise_ratio"]
    parity = newton_parity(ctx, cfg, data, coords, published, scores_host)
    if parity:
        detail["newton_parity_err"] = parity["err"]
        if gates.get("newton_tol") is not None:
            checks["newton_parity"] = parity["err"] <= gates["newton_tol"]

    return {
        "attempted": attempted, "failed": raised + nonfinite,
        "checks": checks, "detail": detail,
        "end_to_end": {"train_examples_per_s": rate},
        "layer_values": {
            "fit_s": statistics.median(durations) if durations else None,
            "coord_build_s": detail["setup_spans_s"].get("coord_build"),
            "slice_fits": slice_fits,
        },
    }
