"""Traffic kind ``serve_slates``: open-loop slates against the served model.

Set-up: coefficients of the configuration's shape drawn from the seed (no
fit), written with ``save_game_model`` and loaded through
``cli.serve.build_server`` (which warms the bucket ladder) behind
``ThreadedFrontend``, with the values of ``cli/serve.py --listen``'s own
defaults copied into the mix file.  The generator is a child process that
never imports JAX (loadgen_child.py); it encodes its lines while the server
is built, scores a seeded check sample over the socket, and waits for GO.
The window starts at GO.

``correct``: the check sample against the plain float32 forward at
``highest`` precision within the bf16-product bound
(reference/glmix_forward.py); every reply of the window a score or
``overloaded``; no compilation in the window; in a cell below the knee
(``lag_guard``), the generator's p99 lag below the run's own p50 — a starved
generator is not a fast server.  A shed slate is a ``failed`` one and does
not make the run incorrect.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PROGRAM_SPANS = ("store.resolve", "serve.execute", "serve.flush")


def save_model_dir(cfg: dict, coefs: dict, out_dir: str, fmt: str) -> None:
    """The model directory ``build_server`` loads: coefficients, one index
    map per feature shard, one entity index per random effect.  Entity e of
    a coordinate is named ``<prefix><e>`` (``user12``, ``item3``)."""
    from photon_ml_tpu.data.index_map import IndexMap, feature_key
    from photon_ml_tpu.data.reader import EntityIndex
    from photon_ml_tpu.models.game import (FixedEffectModel, GameModel,
                                           RandomEffectModel)
    from photon_ml_tpu.models.glm import Coefficients
    from photon_ml_tpu.storage.model_io import save_game_model
    from photon_ml_tpu.types import TaskType

    task = TaskType.LOGISTIC_REGRESSION
    models, imaps, eidxs = {}, {}, {}
    for c in cfg["coordinates"]:
        shard, w = c["feature_shard"], coefs[c["id"]]
        imaps[shard] = IndexMap({feature_key(f"{shard}{j}"): j
                                 for j in range(int(c["dim"]))})
        if c["kind"] == "fixed":
            models[c["id"]] = FixedEffectModel(
                coefficients=Coefficients(means=w), feature_shard=shard,
                task=task)
            continue
        eidx = EntityIndex()
        prefix = c["entity"][:-2]  # userId -> user
        for e in range(len(w)):
            eidx.get_or_add(f"{prefix}{e}")
        eidxs[c["entity"]] = eidx
        models[c["id"]] = RandomEffectModel(
            w_stack=w, slot_of={e: e for e in range(len(w))},
            random_effect_type=c["entity"], feature_shard=shard, task=task)
    save_game_model(GameModel(models=models), out_dir, imaps,
                    entity_indexes=eidxs, task=task, fmt=fmt)
    for shard, m in imaps.items():
        m.save(os.path.join(out_dir, f"{shard}.idx"))
    for tag, eidx in eidxs.items():
        eidx.save(os.path.join(out_dir, f"{tag}.entities.json"))


def check_against_reference(ctx, cfg, coefs, dims, mix, ready) -> dict:
    """The check sample's socket scores vs the plain forward."""
    import loadgen_child

    forward = ctx.catalog.module("reference", "glmix_forward")
    values, _ = loadgen_child.make_pool(ctx.seed, dims, int(mix["pool"]))
    check = ready["check"]
    got = np.asarray([c["reply"].get("score", np.nan) for c in check],
                     np.float64)
    x = values[[c["body"] for c in check]].astype(np.float32)
    col, fixed, randoms = 0, None, []
    for c in cfg["coordinates"]:
        xs = x[:, col:col + int(c["dim"])]
        col += int(c["dim"])
        if c["kind"] == "fixed":
            fixed = (xs, coefs[c["id"]])
        else:
            key = "user" if c["entity"] == "userId" else "item"
            randoms.append((xs, coefs[c["id"]],
                            np.asarray([k[key] for k in check])))
    want, bound = forward.scores(fixed[0], fixed[1], randoms)
    scale = bound + 1e-6 * np.max(np.abs(want))
    finite = bool(np.isfinite(got).all())
    err = float(np.max(np.abs(got - want) / scale)) if finite else float("inf")
    return {"ok": finite and err <= 1.0, "err_over_bound": err,
            "lines": len(check),
            "unknown_users": int(sum(c["user"] < 0 for c in check))}


def run(ctx) -> dict:
    cfg, mix = ctx.config, ctx.traffic
    recipe = ctx.catalog.module("recipes", cfg["recipe"])
    dims = [(c["feature_shard"], int(c["dim"])) for c in cfg["coordinates"]]
    sizes = recipe.sizes(cfg)
    spec = {
        "seed": ctx.seed, "seconds": ctx.seconds,
        "slates_per_s": float(mix["slates_per_s"]),
        "connections": int(mix["connections"]),
        "slate_sizes": mix["slate_sizes"],
        "slate_weights": mix["slate_weights"],
        "users": sizes["users"], "items": sizes.get("items", 1),
        "user_zipf": float(mix["user_zipf"]),
        "item_zipf": float(mix["item_zipf"]),
        "unknown_user_share": float(mix["unknown_user_share"]),
        "pool": int(mix["pool"]), "check_lines": int(mix["check_lines"]),
        "dims": dims,
    }
    spec_path = os.path.join(ctx.tmp, "loadgen_spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    # the child never touches JAX or the chip; it prepares its lines while
    # the server is built
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "loadgen_child.py"), spec_path],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    front = None
    try:
        from photon_ml_tpu.cli.serve import build_server
        from photon_ml_tpu.serving.frontend import (AdmissionConfig,
                                                    FrontendConfig,
                                                    ThreadedFrontend)

        with ctx.span("model_make"):
            coefs = recipe.draw_model(cfg, ctx.seed)
            model_dir = os.path.join(ctx.tmp, "model")
            save_model_dir(cfg, coefs, model_dir, mix["model_format"])
        with ctx.span("build_server"):
            engine, swapper = build_server(
                model_dir, max_batch=int(mix["max_batch"]),
                mesh_shards=ctx.chips if ctx.chips > 1 else 0)
        front = ThreadedFrontend(engine, swapper, FrontendConfig(
            admission=AdmissionConfig(
                budget_s=float(mix["admission_budget_ms"]) * 1e-3),
            batcher_deadline_s=float(mix["batcher_deadline_us"]) * 1e-6,
        )).start()
        child.stdin.write(f"PORT {front.port}\n")
        child.stdin.flush()
        with ctx.span("check_sample"):
            ready = _expect(child, "READY")
        reference = check_against_reference(ctx, cfg, coefs, dims, mix,
                                            ready)

        compiles0 = engine.compile_count
        before = {k: engine.metrics.counter(k)
                  for k in ("batches", "scored_samples", "requests")}
        t_go = time.perf_counter_ns()
        ctx.window_start()
        child.stdin.write("GO\n")
        child.stdin.flush()
        if ctx.trace:
            time.sleep(min(float(mix["slice_start_s"]), ctx.seconds / 3))
            with ctx.profile_slice():
                time.sleep(min(float(mix["slice_s"]), ctx.seconds / 3))
        out = _expect(child, "RESULT")
        ctx.window_end()
        child.wait(timeout=30)
        counters = {k: engine.metrics.counter(k) - v
                    for k, v in before.items()}
        engine_compiles = engine.compile_count - compiles0
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        if front is not None:
            front.stop()

    obs_spans = []
    if ctx.trace:
        from photon_ml_tpu import obs

        t_end = t_go + int(ctx.seconds * 1e9)
        obs_spans = [r for r in obs.get_tracer().records()
                     if r["name"] in _PROGRAM_SPANS
                     and t_go <= r["ts_ns"] <= t_end]

    due = out["slates_due"]
    failed = out["slates_shed"] + out["slates_error"] + out["slates_lost"]
    checks = {
        "check_sample_within_bound": reference["ok"],
        "replies_score_or_overloaded": (out["lines"]["other"] == 0
                                        and out["lines"]["unknown_uid"] == 0),
        "engine_compiled_nothing": engine_compiles == 0,
    }
    if mix.get("lag_guard") and not ctx.dry_run:
        checks["generator_kept_up"] = bool(
            out["gen_lag_p99_ms"] < out["p50_ms"])
    if mix.get("min_slates") and not ctx.dry_run:
        checks["enough_slates"] = due >= int(mix["min_slates"])
    scores_per_s = out["lines"]["scored_in_window"] / out["window_s"]
    return {
        "attempted": due, "failed": failed, "checks": checks,
        "detail": {"reference": reference, "generator": out,
                   "counters": counters, "setup_spans_s": ctx.span_seconds()},
        "end_to_end": {"serve_p50_ms": out["p50_ms"],
                       "serve_scores_per_s": scores_per_s},
        "layer_values": {
            "shed_share": 100.0 * out["slates_shed"] / max(due, 1),
            "gen_lag_p99_ms": out["gen_lag_p99_ms"],
            "over_p50_ms": out["p50_ms"], "over_p99_ms": out["p99_ms"],
            "steady_p90_ms": out["p90_ms"], "steady_p99_ms": out["p99_ms"],
        },
        "counters": counters, "obs_spans": obs_spans,
    }


def _expect(child, tag: str) -> dict:
    """The child's next line, which must be ``<tag> <json>``."""
    line = child.stdout.readline()
    if not line.startswith(tag + " "):
        raise RuntimeError(f"generator: expected {tag}, got {line[:200]!r} "
                           f"(exit code {child.poll()})")
    return json.loads(line[len(tag) + 1:])
