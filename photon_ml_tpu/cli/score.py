"""GAME scoring driver.

Reference: photon-client .../cli/game/scoring/GameScoringDriver.scala:39-263 —
load model -> read data -> GameTransformer -> write ScoringResultAvro ->
optional evaluation.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
from typing import List

import numpy as np

from photon_ml_tpu.data import avro as avro_io
from photon_ml_tpu.data.reader import read_game_data_avro
from photon_ml_tpu.data.schemas import SCORING_RESULT
from photon_ml_tpu.evaluation.evaluator import EvaluationSuite
from photon_ml_tpu.storage.model_io import load_model_bundle

logger = logging.getLogger("photon_ml_tpu.score")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="photon-tpu-score",
                                description="Score data with a trained GAME model")
    p.add_argument("--data", nargs="+", required=True)
    p.add_argument("--model-dir", required=True,
                   help="directory produced by the training driver (contains "
                        "best/, *.idx, *.entities.json)")
    p.add_argument("--output-dir", required=True)
    p.add_argument("--evaluators", default="")
    p.add_argument("--model-id", default="", help="stamped into score metadata")
    p.add_argument("--model-format", default="native",
                   choices=["native", "reference"],
                   help="'reference' imports a model saved by LinkedIn "
                        "Photon ML itself (ModelProcessingUtils on-disk "
                        "layout: model-metadata.json + fixed-effect/ + "
                        "random-effect/) — the migration path; index maps "
                        "are rebuilt from the stored feature names")
    p.add_argument("--predict-mean", action="store_true",
                   help="write inverse-link means instead of raw scores")
    p.add_argument("--input-date-range", default=None,
                   help="yyyyMMdd-yyyyMMdd daily-partition range (reference "
                        "IOUtils.getInputPathsWithinDateRange:113-153)")
    p.add_argument("--input-days-range", default=None,
                   help="START-END days ago (reference DaysRange.scala:28-48)")
    p.add_argument("--error-on-missing-date", action="store_true")
    p.add_argument("--input-columns", default="",
                   help="remap reserved input columns (see train driver)")
    p.add_argument("--log-data-and-model-stats", action="store_true",
                   help="log summaries of the model and scoring data "
                        "(reference GameScoringDriver logDataAndModelStats)")
    return p


def run(argv: List[str]) -> int:
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s %(message)s")
    args = build_parser().parse_args(argv)

    from photon_ml_tpu.utils.runtime import init_runtime

    init_runtime(logger)

    from photon_ml_tpu.utils.dates import input_paths_within_date_range, resolve_range

    date_range = resolve_range(args.input_date_range, args.input_days_range)
    if date_range is not None:
        args.data = input_paths_within_date_range(
            args.data, date_range, args.error_on_missing_date)

    if args.model_format == "reference":
        from photon_ml_tpu.storage.model_io import import_reference_game_model

        try:
            model, task, index_maps, entity_indexes = \
                import_reference_game_model(args.model_dir)
        except (FileNotFoundError, KeyError, ValueError) as e:
            # ValueError covers json.JSONDecodeError (corrupt metadata)
            logger.error("--model-dir (reference format): %s", e)
            return 1
        logger.info("imported reference-format model: %d coordinate(s)",
                    len(model.models))
    else:
        from photon_ml_tpu.storage.model_io import ModelLoadError

        try:
            bundle = load_model_bundle(args.model_dir)
        except ModelLoadError as e:
            logger.error("--model-dir: %s", e)
            return 1
        model, task = bundle.model, bundle.task
        index_maps, entity_indexes = bundle.index_maps, bundle.entity_indexes
    id_tags = sorted(entity_indexes)
    from photon_ml_tpu.data.reader import parse_input_columns

    try:
        input_columns = parse_input_columns(args.input_columns)
    except ValueError as e:
        logger.error("%s", e)
        return 1
    data, _ = read_game_data_avro(args.data, index_maps, id_tag_names=id_tags,
                                  entity_indexes=entity_indexes,
                                  input_columns=input_columns)
    logger.info("scoring %d samples", data.num_samples)
    if args.log_data_and_model_stats:
        # reference logDataAndModelStats: toSummaryString dumps of the model
        # and the prepared dataset
        for cid, m in model.models.items():
            if hasattr(m, "slot_of"):  # either random-effect container
                width = (m.w_stack.shape[1] if hasattr(m, "w_stack")
                         else m.dim)
                logger.info("model %s: random effect %s, %d entities x %d "
                            "features", cid, m.random_effect_type,
                            m.num_entities, width)
            else:
                logger.info("model %s: fixed effect, %d features", cid,
                            len(m.coefficients.means))
        y = np.asarray(data.y, float)
        logger.info("data: %d samples, mean response %.6f, %d feature "
                    "shard(s)", data.num_samples, float(y.mean()),
                    len(data.features))
        for tag, ids in data.id_tags.items():
            known = int((np.asarray(ids) >= 0).sum())
            logger.info("data: id tag %s covers %d/%d samples", tag, known,
                        data.num_samples)

    from photon_ml_tpu.game.scoring import output_scores, raw_scores

    # One scoring pass; the inverse-link mean is a pointwise function of the
    # raw margin, so --predict-mean never re-scores (game/scoring.py — the
    # same composition the serving engine and GameTransformer use).
    raw = raw_scores(model, data)
    scores = output_scores(raw, task, predict_mean=args.predict_mean)

    os.makedirs(args.output_dir, exist_ok=True)
    out_path = os.path.join(args.output_dir, "scores.avro")
    meta = {"modelId": args.model_id} if args.model_id else None
    uids = data.uids if data.uids is not None else range(data.num_samples)
    records = (
        {"uid": (int(u) if isinstance(u, (int, np.integer)) else u),
         "predictionScore": float(scores[i]),
         "label": float(data.y[i]), "metadataMap": meta}
        for i, u in enumerate(uids)
    )
    n = avro_io.write_container(out_path, SCORING_RESULT, records)
    logger.info("wrote %d scores -> %s", n, out_path)

    if args.evaluators:
        # evaluators expect RAW margins regardless of the output format flag
        suite = EvaluationSuite.from_specs(args.evaluators.split(","))
        res = suite.evaluate(raw, data.y, data.weight, group_ids=data.id_tags)
        logger.info("metrics: %s", res.values)
        with open(os.path.join(args.output_dir, "metrics.json"), "w") as f:
            json.dump(res.values, f, indent=2)
    return 0


def main() -> None:
    sys.exit(run(sys.argv[1:]))


if __name__ == "__main__":
    main()
