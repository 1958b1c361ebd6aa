"""Model diagnostics driver: bootstrap CIs, learning curve, calibration,
feature importance, residual independence -> HTML + text report.

Reference: the legacy Driver's DIAGNOSED stage (photon-client Driver.scala:431,
photon-diagnostics **) — bootstrap training, fitting diagnostic,
Hosmer-Lemeshow, feature importance, Kendall-tau, rendered via the reporting
tree (diagnostics/reporting/**).  Operates on a trained model dir (the
training driver's output) plus the data it was trained on.

Usage:
  python -m photon_ml_tpu.cli.diagnose \\
    --data train.avro --holdout val.avro --model-dir out \\
    --coordinate fixed --output-dir out/diagnostics
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.core.batch import dense_batch
from photon_ml_tpu.core.losses import loss_for_task
from photon_ml_tpu.core.objective import GLMObjective
from photon_ml_tpu.core.regularization import Regularization
from photon_ml_tpu.data.index_map import load_index
from photon_ml_tpu.data.reader import EntityIndex, read_game_data_avro
from photon_ml_tpu.diagnostics import (bootstrap_training, expected_magnitude_importance,
                                       fitting_diagnostic, hosmer_lemeshow,
                                       kendall_tau_analysis, render_html, render_text,
                                       variance_importance)
from photon_ml_tpu.diagnostics.reporting import (Bars, Bullets, Document,
                                                 NumberedList, Plot,
                                                 Reference, Scatter, Table,
                                                 Text)
from photon_ml_tpu.models.glm import Coefficients, GLMModel
from photon_ml_tpu.opt.solve import make_solver
from photon_ml_tpu.storage.model_io import load_game_model
from photon_ml_tpu.types import TaskType

logger = logging.getLogger("photon_ml_tpu.diagnose")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="photon-tpu-diagnose",
                                description="Diagnose a trained GAME model")
    p.add_argument("--data", nargs="+", required=True, help="training data (Avro)")
    p.add_argument("--holdout", nargs="*", default=[],
                   help="holdout data for the fitting diagnostic")
    p.add_argument("--model-dir", required=True,
                   help="training driver output dir (best/, *.idx, ...)")
    p.add_argument("--coordinate", default=None,
                   help="fixed-effect coordinate to diagnose (default: the only one)")
    p.add_argument("--output-dir", required=True)
    p.add_argument("--bootstrap-replicates", type=int, default=16)
    p.add_argument("--l2", type=float, default=1.0,
                   help="L2 weight for the diagnostic re-trains")
    p.add_argument("--compare-l2", default="",
                   help="comma list of L2 weights: adds a regularization-"
                        "path comparison chapter (one nested subsection per "
                        "weight, like the legacy driver's per-lambda report "
                        "chapters)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--top-k", type=int, default=20)
    p.add_argument("--input-columns", default="",
                   help="remap reserved input columns (see train driver)")
    return p


def _load_dir(model_dir):
    index_maps, entity_indexes = {}, {}
    for name in os.listdir(model_dir):
        if name.endswith(".idx") or name.endswith(".phidx"):
            index_maps[name.rsplit(".", 1)[0]] = load_index(os.path.join(model_dir, name))
        elif name.endswith(".entities.json"):
            entity_indexes[name[: -len(".entities.json")]] = EntityIndex.load(
                os.path.join(model_dir, name))
    model, task = load_game_model(os.path.join(model_dir, "best"),
                                  index_maps, entity_indexes)
    return model, task, index_maps, entity_indexes


def run(argv: List[str]) -> int:
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s %(message)s")
    args = build_parser().parse_args(argv)

    # parse --compare-l2 BEFORE any model/data load (the repo's
    # early-failure rule: a bad flag value must not cost the whole read);
    # weights must be positive finite — the comparison plot is log-axis
    try:
        compare_weights = [float(v) for v in args.compare_l2.split(",") if v]
    except ValueError as e:
        logger.error("--compare-l2: %s", e)
        return 1
    if any(not (w > 0 and np.isfinite(w)) for w in compare_weights):
        logger.error("--compare-l2 weights must be positive finite (the "
                     "comparison plot is on a log axis); got %s",
                     args.compare_l2)
        return 1

    from photon_ml_tpu.utils.runtime import init_runtime

    init_runtime(logger)
    model, task, index_maps, entity_indexes = _load_dir(args.model_dir)

    from photon_ml_tpu.models.game import (CompactRandomEffectModel,
                                           FixedEffectModel,
                                           RandomEffectModel)

    fixed = {cid: m for cid, m in model.models.items()
             if isinstance(m, FixedEffectModel)}
    random_effects = {cid: m for cid, m in model.models.items()
                      if isinstance(m, (RandomEffectModel,
                                        CompactRandomEffectModel))}
    if not fixed:
        logger.error("no fixed-effect coordinate in the model")
        return 1
    if args.coordinate:
        if args.coordinate not in model.models:
            logger.error("coordinate %r not found (have: %s)",
                         args.coordinate, sorted(model.models))
            return 1
        # restrict the per-coordinate chapters to the selection (full-model
        # calibration/residual chapters still cover the whole model)
        fixed = {k: v for k, v in fixed.items() if k == args.coordinate}
        random_effects = {k: v for k, v in random_effects.items()
                          if k == args.coordinate}
    loss = loss_for_task(task)

    id_tags = sorted(entity_indexes)
    from photon_ml_tpu.data.reader import parse_input_columns

    try:
        input_columns = parse_input_columns(args.input_columns)
    except ValueError as e:
        logger.error("%s", e)
        return 1
    data, _ = read_game_data_avro(args.data, index_maps, id_tag_names=id_tags,
                                  input_columns=input_columns,
                                  entity_indexes=entity_indexes)
    holdout_data = None
    if args.holdout:
        holdout_data, _ = read_game_data_avro(args.holdout, index_maps,
                                              input_columns=input_columns,
                                              id_tag_names=id_tags,
                                              entity_indexes=entity_indexes)
    logger.info("diagnosing %d fixed + %d random coordinate(s) on %d samples",
                len(fixed), len(random_effects), data.num_samples)

    obj = GLMObjective(loss=loss, reg=Regularization(l2=args.l2))
    solve = jax.jit(make_solver(obj))

    def train_fn(b):
        res = solve(jnp.zeros(b.dim, b.x.dtype), b)
        return GLMModel(coefficients=Coefficients(means=np.asarray(res.w)), task=task)

    def point_metric(m, b):
        z = np.asarray(m.coefficients.score(b.x)) + np.asarray(b.offset)
        w = np.asarray(b.weight)
        l = np.asarray(loss.loss(jnp.asarray(z), b.y))
        return float((w * l).sum() / max(w.sum(), 1e-12))

    doc = Document(f"Model diagnostics ({task.value})")
    summary: dict = {"task": task.value, "coordinates": {}}

    # per-coordinate raw scores on the training data — each coordinate is
    # diagnosed against the RESIDUAL of the others (the descent's partial
    # score, CoordinateDescent.scala:197-204), and calibration/residual
    # chapters use the FULL model
    coord_scores = {cid: np.asarray(m.score(data), np.float64)
                    for cid, m in model.models.items()}
    total_score = np.sum(list(coord_scores.values()), axis=0)
    base_offset = np.asarray(data.offset, np.float64)
    holdout_scores = ({cid: np.asarray(m.score(holdout_data), np.float64)
                       for cid, m in model.models.items()}
                      if holdout_data is not None else None)

    # ---- chapter: model summary (index + inventory) ----
    ch = doc.chapter("Model summary")
    inventory = []
    for mcid, m in model.models.items():
        if isinstance(m, FixedEffectModel):
            inventory.append(
                f"{mcid}: fixed effect on shard {m.feature_shard!r}, "
                f"{len(m.coefficients.means)} coefficients")
        else:
            width = (m.w_stack.shape[1] if hasattr(m, "w_stack") else m.dim)
            inventory.append(
                f"{mcid}: random effect per {m.random_effect_type!r} on shard "
                f"{m.feature_shard!r}, {m.num_entities} entities x "
                f"{width} coefficients")
    ch.section("Coordinates").add(Bullets(inventory))
    ch.section("Data").add(Bullets([
        f"training samples: {data.num_samples}",
        f"holdout samples: {holdout_data.num_samples if holdout_data else 0}",
        f"diagnostic re-train L2: {args.l2}",
    ]))

    # ---- per-fixed-coordinate chapters ----
    compare_results: dict = {}
    for cid, fe in fixed.items():
        shard = fe.feature_shard
        imap = index_maps[shard]
        residual = total_score - coord_scores[cid]
        batch = dense_batch(data.features[shard], data.y,
                            base_offset + residual, data.weight,
                            dtype=np.float64)

        def _label(j: int) -> str:
            nm = imap.get_feature_name(int(j))
            return f"{nm[0]}:{nm[1]}" if nm else str(j)

        names = [_label(j) for j in range(batch.dim)]
        if compare_weights:
            # per-weight solves run HERE so the dense float64 batch stays
            # transient (one coordinate's at a time); only the small tables
            # and losses are buffered for the comparison chapter below
            published = np.asarray(fe.coefficients.means, np.float64)
            per_weight = []
            for w in compare_weights:
                res = solve(jnp.zeros(batch.dim, batch.x.dtype), batch,
                            objective=obj.with_reg(Regularization(l2=w)))
                m = GLMModel(coefficients=Coefficients(
                    means=np.asarray(res.w)), task=task)
                wv = np.asarray(res.w, np.float64)
                move = np.abs(wv - published[: len(wv)])
                order = np.argsort(-move)[: min(args.top_k, len(move))]
                per_weight.append({
                    "w": w,
                    "rows": [[names[j], f"{wv[j]:.5g}",
                              f"{published[j]:.5g}", f"{move[j]:.5g}"]
                             for j in order],
                    "train_loss": point_metric(m, batch),
                    "norm": float(np.linalg.norm(wv)),
                })
            compare_results[cid] = per_weight
        ch = doc.chapter(f"Coordinate {cid!r} (fixed effect)",
                         label=f"coord:{cid}")
        cs: dict = {}

        # 1. bootstrap confidence intervals (BootstrapTraining.scala:29-181)
        report = bootstrap_training(
            train_fn, batch, num_replicates=args.bootstrap_replicates,
            metrics={"mean_loss": lambda m: point_metric(m, batch)},
            seed=args.seed)
        sec = ch.section(f"Bootstrap 95% coefficient intervals "
                         f"({args.bootstrap_replicates} replicates)")
        order = np.argsort(-np.abs(report.coefficient_means))[: args.top_k]
        sec.add(Table(["feature", "mean", "lo", "hi"],
                      [[names[j], f"{report.coefficient_means[j]:.5g}",
                        f"{report.coefficient_intervals[j][0]:.5g}",
                        f"{report.coefficient_intervals[j][1]:.5g}"]
                       for j in order]))
        sec.add(Plot("coefficient mean and 95% interval by |mean| rank",
                     list(range(len(order))),
                     {"mean": [float(report.coefficient_means[j]) for j in order],
                      "lo": [float(report.coefficient_intervals[j][0]) for j in order],
                      "hi": [float(report.coefficient_intervals[j][1]) for j in order]},
                     x_label="rank"))
        mean, std = report.metric_summary()["mean_loss"]
        sec.add(Text(f"bootstrap mean loss: {mean:.6g} ± {std:.3g}"))
        cs["bootstrap"] = {"replicates": report.num_replicates,
                           "mean_loss": [mean, std]}

        # 2. learning curve (FittingDiagnostic.scala:33-131)
        if holdout_data is not None:
            h_residual = np.sum([s for ocid, s in holdout_scores.items()
                                 if ocid != cid], axis=0) \
                if len(holdout_scores) > 1 else \
                np.zeros(holdout_data.num_samples, np.float64)
            hbatch = dense_batch(holdout_data.features[shard], holdout_data.y,
                                 np.asarray(holdout_data.offset, np.float64)
                                 + h_residual,
                                 holdout_data.weight, dtype=np.float64)
            fit = fitting_diagnostic(train_fn, {"mean_loss": point_metric},
                                     batch, hbatch, seed=args.seed)
            ch.section("Learning curve (train vs holdout)").add(
                Plot("mean loss vs training fraction", list(fit.fractions),
                     {"train": list(fit.train_metrics["mean_loss"]),
                      "holdout": list(fit.holdout_metrics["mean_loss"])},
                     x_label="fraction"))
            cs["fitting"] = {"fractions": fit.fractions.tolist(),
                             "train": fit.train_metrics["mean_loss"].tolist(),
                             "holdout": fit.holdout_metrics["mean_loss"].tolist()}

        # 3. feature importance (featureimportance/*)
        x_np = np.asarray(batch.x)
        em = expected_magnitude_importance(np.asarray(fe.coefficients.means),
                                           np.abs(x_np).mean(0), names, args.top_k)
        vi = variance_importance(np.asarray(fe.coefficients.means),
                                 x_np.var(0), names, args.top_k)
        sec = ch.section("Feature importance")
        sec.add(Bars("expected magnitude |w|*E|x|",
                     [n for n, _ in em.ranked], [v for _, v in em.ranked]))
        sec.add(Table(["feature", "importance"],
                      [[n, f"{v:.5g}"] for n, v in em.ranked]))
        sec.add(Bars("variance w^2*Var[x]",
                     [n for n, _ in vi.ranked], [v for _, v in vi.ranked]))
        sec.add(Table(["feature", "importance"],
                      [[n, f"{v:.5g}"] for n, v in vi.ranked]))
        summary["coordinates"][cid] = cs

    # ---- regularization-path comparison chapter (legacy Driver trains a
    # per-lambda path and its diagnostic report carries per-lambda chapters;
    # photon-diagnostics reporting/** nests them as sections) ----
    if compare_weights:
        ch = doc.chapter("Regularization path comparison", label="regpath")
        ch.section("Weights compared").add(NumberedList(
            [f"l2 = {w:g}" for w in compare_weights]))
        for cid, per_weight in compare_results.items():
            sec = ch.section(f"Coordinate {cid!r}")
            sec.add(Reference(f"coord:{cid}",
                              "full diagnostics for this coordinate"))
            for entry in per_weight:
                ss = sec.subsection(f"l2 = {entry['w']:g}")
                ss.add(Table(["feature", "w(l2)", "published", "|shift|"],
                             entry["rows"]))
                ss.add(Text(f"train mean loss: {entry['train_loss']:.6g}; "
                            f"coefficient norm: {entry['norm']:.5g}"))
            xs = [float(np.log10(w)) for w in compare_weights]
            sec.add(Plot("mean loss vs log10(l2)", xs,
                         {"train": [e["train_loss"] for e in per_weight]},
                         x_label="log10(l2)", y_label="mean loss"))
        summary["regularization_path"] = {
            "weights": compare_weights,
        }

    # ---- per-random-coordinate chapters ----
    for cid, re_model in random_effects.items():
        ch = doc.chapter(f"Coordinate {cid!r} (random effect)")
        # either container: the compact model's value rows are 0-padded, so
        # their norms equal the dense rows'
        stack = (re_model.w_stack if hasattr(re_model, "w_stack")
                 else re_model.values)
        norms = np.linalg.norm(np.asarray(stack, np.float64), axis=1)
        qs = np.quantile(norms, [0.0, 0.25, 0.5, 0.75, 1.0]) if len(norms) else [0] * 5
        ch.section("Per-entity coefficient norms").add(Table(
            ["entities", "min", "p25", "median", "p75", "max"],
            [[str(len(norms))] + [f"{q:.5g}" for q in qs]]))
        hist, edges = np.histogram(norms, bins=min(16, max(4, len(norms) // 4 or 4)))
        ch.sections[-1].add(Bars(
            "entity count by ||w|| bin",
            [f"[{edges[i]:.3g},{edges[i+1]:.3g})" for i in range(len(hist))],
            hist.tolist()))
        top = np.argsort(-norms)[: args.top_k]
        inv = {v: k for k, v in re_model.slot_of.items()}
        ch.section("Largest entities by ||w||").add(Table(
            ["entity", "||w||"],
            [[str(inv.get(int(j), int(j))), f"{norms[j]:.5g}"] for j in top]))
        summary["coordinates"][cid] = {
            "entities": int(len(norms)),
            "norm_quantiles": [float(q) for q in qs],
        }

    # ---- full-model chapters: calibration + residual independence ----
    margins = total_score + base_offset
    preds = np.asarray(loss.mean(jnp.asarray(margins)))
    y = np.asarray(data.y, np.float64)

    if task == TaskType.LOGISTIC_REGRESSION:
        try:
            hl = hosmer_lemeshow(preds, y, np.asarray(data.weight))
            sec = doc.chapter("Calibration (full model)").section("Hosmer-Lemeshow")
            sec.add(Text(f"chi2={hl.chi_square:.4f} df={hl.degrees_of_freedom} "
                         f"p={hl.p_value:.4g}"))
            sec.add(Table(["bin_lo", "bin_hi", "total", "obs+", "exp+"],
                          [[f"{hl.bin_edges[i]:.3f}", f"{hl.bin_edges[i+1]:.3f}",
                            f"{hl.totals[i]:.1f}", f"{hl.observed_pos[i]:.1f}",
                            f"{hl.expected_pos[i]:.1f}"]
                           for i in range(len(hl.totals))]))
            centers = [(hl.bin_edges[i] + hl.bin_edges[i + 1]) / 2
                       for i in range(len(hl.totals))]
            safe_tot = np.maximum(np.asarray(hl.totals), 1e-12)
            sec.add(Plot("observed vs expected positive rate per bin", centers,
                         {"observed": (np.asarray(hl.observed_pos) / safe_tot).tolist(),
                          "expected": (np.asarray(hl.expected_pos) / safe_tot).tolist()},
                         x_label="predicted probability bin"))
            summary["hosmer_lemeshow"] = {"chi_square": hl.chi_square,
                                          "df": hl.degrees_of_freedom,
                                          "p_value": hl.p_value}
        except ValueError as e:
            logger.warning("Hosmer-Lemeshow skipped: %s", e)

    kt = kendall_tau_analysis(preds, y, seed=args.seed)
    sec = doc.chapter("Residuals (full model)").section(
        "Kendall tau (prediction vs error)")
    sec.add(Text(kt.summary()))
    sub = np.random.default_rng(args.seed).permutation(len(preds))[:2000]
    sec.add(Scatter("prediction vs residual", preds[sub].tolist(),
                    (y - preds)[sub].tolist(),
                    x_label="prediction", y_label="residual"))
    summary["kendall_tau"] = {"tau": kt.tau, "p_value": kt.p_value}

    os.makedirs(args.output_dir, exist_ok=True)
    with open(os.path.join(args.output_dir, "report.html"), "w") as f:
        f.write(render_html(doc))
    with open(os.path.join(args.output_dir, "report.txt"), "w") as f:
        f.write(render_text(doc))
    with open(os.path.join(args.output_dir, "diagnostics.json"), "w") as f:
        json.dump(summary, f, indent=2)
    logger.info("report -> %s", os.path.join(args.output_dir, "report.html"))
    return 0


def main() -> None:
    sys.exit(run(sys.argv[1:]))


if __name__ == "__main__":
    main()
