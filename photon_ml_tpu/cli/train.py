"""GAME training driver.

Reference: photon-client .../cli/game/training/GameTrainingDriver.scala:55-855 —
pipeline: feature maps -> data read -> validate -> normalization ->
reg-weight grid expansion -> GameEstimator.fit (warm-started across the grid)
-> optional hyperparameter tuning -> model selection -> save.

Usage:
  python -m photon_ml_tpu.cli.train \\
    --train-data /path/train.avro --validation-data /path/val.avro \\
    --feature-shards global,per_user \\
    --coordinate "name=fixed,feature.shard=global,reg.weights=0.1|1|10" \\
    --coordinate "name=user,random.effect.type=userId,feature.shard=per_user,reg.weights=1" \\
    --id-tags userId --task LOGISTIC_REGRESSION --evaluators auc \\
    --output-dir /path/out
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time
from typing import Dict, List

import numpy as np

from photon_ml_tpu.cli.config_grammar import expand_game_configs, parse_coordinate_spec
from photon_ml_tpu.data.index_map import IndexMap
from photon_ml_tpu.data.reader import EntityIndex, read_game_data_avro
from photon_ml_tpu.data.validation import DataValidationType, validate_game_data
from photon_ml_tpu.evaluation.evaluator import EvaluationSuite
from photon_ml_tpu.game.estimator import GameEstimator
from photon_ml_tpu.storage.model_io import save_game_model
from photon_ml_tpu.types import TaskType

logger = logging.getLogger("photon_ml_tpu.train")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="photon-tpu-train",
                                description="Train a GAME (GLMix) model on TPU")
    p.add_argument("--train-data", nargs="+", required=True,
                   help="Avro files/dirs of TrainingExampleAvro records")
    p.add_argument("--validation-data", nargs="*", default=[])
    p.add_argument("--input-date-range", default=None,
                   help="yyyyMMdd-yyyyMMdd: treat --train-data entries as base "
                        "dirs of daily <base>/yyyy/MM/dd partitions and read "
                        "the days in range (reference DateRange + "
                        "IOUtils.getInputPathsWithinDateRange:113-153)")
    p.add_argument("--input-days-range", default=None,
                   help="START-END in days ago, e.g. 90-1 (reference "
                        "DaysRange.scala:28-48); mutually exclusive with "
                        "--input-date-range")
    p.add_argument("--error-on-missing-date", action="store_true",
                   help="fail if any day in range has no data dir")
    p.add_argument("--input-columns", default="",
                   help="remap reserved input columns, e.g. "
                        "'response=clicked,weight=sampleWeight' (reference "
                        "InputColumnsNames: uid,response,offset,weight,"
                        "metadataMap,features)")
    p.add_argument("--feature-shards", required=True,
                   help="comma-separated feature shard names")
    p.add_argument("--coordinate", action="append", required=True, dest="coordinates",
                   help="coordinate spec (repeatable; see config grammar)")
    p.add_argument("--id-tags", default="", help="comma-separated id tag columns")
    p.add_argument("--task", default="LOGISTIC_REGRESSION",
                   choices=[t.name for t in TaskType if t != TaskType.NONE])
    p.add_argument("--evaluators", default="",
                   help="comma-separated evaluator specs (first = primary)")
    p.add_argument("--coordinate-descent-iterations", type=int, default=1)
    p.add_argument("--output-dir", required=True)
    p.add_argument("--index-map-dir", default=None,
                   help="load prebuilt index maps instead of scanning data")
    p.add_argument("--no-intercept", action="store_true")
    p.add_argument("--data-validation", default="VALIDATE_FULL",
                   choices=[v.name for v in DataValidationType])
    p.add_argument("--stream", action="store_true",
                   help="out-of-core streaming ingest (photonstream): decode "
                        "Avro chunks on a bounded background pool and "
                        "assemble design matrices ON DEVICE in fixed-shape "
                        "double-buffered batches — peak host memory stays "
                        "bounded by the pipeline window instead of the "
                        "dataset size; coefficients match the eager reader "
                        "bitwise")
    p.add_argument("--stream-batch-rows", type=int, default=4096,
                   help="device-feed batch rows (power of two; the one "
                        "upload shape the stream compiles)")
    p.add_argument("--stream-workers", type=int, default=2,
                   help="background decode threads")
    p.add_argument("--stream-on-error", default="raise",
                   choices=["raise", "skip"],
                   help="malformed-chunk policy: 'raise' fails the job at "
                        "the first corrupt/torn chunk; 'skip' keeps going — "
                        "lost rows stay allocated with weight 0 (inert) and "
                        "are counted in stream_chunk_errors_total / "
                        "stream_skipped_rows_total, never a silent short "
                        "epoch")
    p.add_argument("--sparse-threshold", type=int, default=0,
                   help="shards with >= this many features load as row-padded "
                        "sparse layouts (0 = always dense); the huge-vocabulary "
                        "path (reference scale story, SURVEY §2.7)")
    p.add_argument("--normalization", default="NONE",
                   choices=["NONE", "SCALE_WITH_MAX_MAGNITUDE",
                            "SCALE_WITH_STANDARD_DEVIATION", "STANDARDIZATION"],
                   help="feature normalization built from training stats "
                        "(reference NormalizationType.scala:42); models are "
                        "saved in original space")
    p.add_argument("--tuning-iterations", type=int, default=0,
                   help="GP hyperparameter tuning iterations (0 = off)")
    p.add_argument("--tuning-mode", default="bayesian", choices=["bayesian", "random"])
    p.add_argument("--tuner", default="BUILTIN",
                   help="DUMMY (no-op), BUILTIN, or module.path:ClassName "
                        "loaded reflectively (reference "
                        "HyperparameterTunerFactory.scala:20-48)")
    p.add_argument("--tuning-config", default=None,
                   help="JSON file in the reference HyperparameterSerialization "
                        "format ({tuning_mode, variables:{name:{transform,min,"
                        "max}}}); overrides --tuning-mode and the default L2 "
                        "search ranges (dims in unlocked-coordinate order)")
    p.add_argument("--tuning-priors", default=None,
                   help="JSON file of prior observations ({records:[{param:"
                        "value,...,evaluationValue:v}]}) seeded into the "
                        "search (reference priorFromJson)")
    p.add_argument("--tuning-shrink-radius", type=float, default=None,
                   help="with --tuning-priors: shrink the search domain to a "
                        "box of this radius (in rescaled [0,1] space) around "
                        "the GP-predicted best prior point (reference "
                        "ShrinkSearchRange.getBounds:40-100)")
    p.add_argument("--model-save-format", default="avro",
                   choices=["avro", "columnar"],
                   help="'avro' (default): name-keyed NTV triples, index-map-"
                        "independent and reference-portable; 'columnar': raw "
                        "coefficient arrays bound to this run's index maps — "
                        "seconds instead of minutes at 1e7+ features")
    p.add_argument("--model-output-mode", default="BEST",
                   choices=["NONE", "BEST", "EXPLICIT", "TUNED", "ALL"],
                   help="which trained models to save (reference "
                        "ModelOutputMode.scala: NONE = logs only; BEST = best "
                        "only; EXPLICIT = best + the reg-weight grid models; "
                        "TUNED = best + tuner-explored models; ALL = best + "
                        "everything)")
    p.add_argument("--output-models-limit", type=int, default=None,
                   help="cap on the number of extra models saved under models/ "
                        "(reference outputFilesLimit)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--model-input-dir", default=None,
                   help="existing model dir for warm start "
                        "(reference GameTrainingDriver modelInputDirectory)")
    p.add_argument("--model-input-format", default="native",
                   choices=["native", "reference"],
                   help="'reference' warm-starts from a model saved by "
                        "LinkedIn Photon ML itself (ModelProcessingUtils "
                        "layout; coordinate names must match this run's "
                        "--coordinate names) — the migration path")
    p.add_argument("--lock-coordinates", default="",
                   help="comma-separated coordinate ids kept from the input "
                        "model and only re-scored (partial retraining, "
                        "reference partialRetrainLockedCoordinates)")
    p.add_argument("--export-reference-model", default=None,
                   help="ALSO write the best model in the reference's "
                        "ModelProcessingUtils on-disk layout to this dir so "
                        "Spark-side Photon ML can load it (bidirectional "
                        "migration)")
    p.add_argument("--fused", default="auto", choices=["auto", "on", "off"],
                   help="descent engine: 'auto' (default) runs each fit as "
                        "ONE jitted program — validated fits included "
                        "(held-out scoring + per-update losses fused into "
                        "the scanned program, FusedSweep.run_validated) — "
                        "whenever no per-update host work (checkpoints, "
                        "locked coordinates, resume) is configured; 'off' "
                        "forces the host-paced CoordinateDescent (per-update "
                        "spans + history); 'on' requires the fused path and "
                        "errors where it cannot run")
    p.add_argument("--mesh", default=None,
                   help="device mesh spec 'data=4,entity=2,feature=1' — axes "
                        "default to 1, 'data' defaults to the remaining "
                        "devices; omit for single-device training")
    p.add_argument("--event-listener", action="append", default=[], dest="event_listeners",
                   help="'module.path:ClassName' lifecycle EventListener (repeatable)")
    p.add_argument("--checkpoint-dir", default=None,
                   help="flush descent state after every coordinate update and "
                        "auto-resume from it if present (preemption recovery; "
                        "mid-job checkpointing the reference lacks, SURVEY §5)")
    p.add_argument("--trace-out", default=None,
                   help="enable the photonscope tracer and write the Chrome "
                        "trace JSON (Perfetto-loadable; per-(iteration, "
                        "coordinate) descent spans with nested solve/score/"
                        "validate children) here at exit")
    p.add_argument("--trace-buffer", type=int, default=16384,
                   help="with --trace-out: tracer ring-buffer capacity "
                        "(newest spans win)")
    p.add_argument("--metrics-out", default=None,
                   help="write the unified metrics registry snapshot "
                        "(descent update counters/timings, compile + "
                        "transfer accounting) as JSON here at exit")
    return p


def run(argv: List[str]) -> int:
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s %(message)s")
    args = build_parser().parse_args(argv)

    from photon_ml_tpu.utils.runtime import init_runtime

    init_runtime(logger)
    if args.trace_out:
        from photon_ml_tpu import obs

        obs.enable_tracing(capacity=args.trace_buffer)
        logger.info("tracing enabled (ring capacity %d)", args.trace_buffer)
    t_start = time.time()
    task = TaskType[args.task]

    # Job log next to the outputs + lifecycle events
    # (reference PhotonLogger @ GameTrainingDriver.scala:840-841; EventEmitter).
    from photon_ml_tpu.utils import EventEmitter, PhotonLogger

    os.makedirs(args.output_dir, exist_ok=True)
    # handler on the PACKAGE logger: descent/coordinate/etc records propagate
    # up the 'photon_ml_tpu.*' hierarchy into the job log
    job_log = PhotonLogger(os.path.join(args.output_dir, "log-message.txt"),
                           name="photon_ml_tpu")
    emitter = EventEmitter()
    for spec in args.event_listeners:
        emitter.register(spec)
    emitter.emit("training_start", task=args.task, output_dir=args.output_dir)
    try:
        return _run(args, task, t_start, emitter)
    finally:
        emitter.close_listeners()
        job_log.close()
        if args.trace_out:
            from photon_ml_tpu import obs

            obs.get_tracer().export_chrome_trace(args.trace_out)
            logger.info("trace -> %s", args.trace_out)
        if args.metrics_out:
            from photon_ml_tpu import obs

            obs.get_registry().export(args.metrics_out)
            logger.info("metrics -> %s", args.metrics_out)


def _run(args, task, t_start, emitter) -> int:
    from photon_ml_tpu.game.config import FixedEffectConfig
    from photon_ml_tpu.utils.dates import input_paths_within_date_range, resolve_range

    date_range = resolve_range(args.input_date_range, args.input_days_range)
    if date_range is not None:
        args.train_data = input_paths_within_date_range(
            args.train_data, date_range, args.error_on_missing_date)
        logging.getLogger(__name__).info(
            "date range %s -> %d daily input dirs", date_range, len(args.train_data))

    shards = [s for s in args.feature_shards.split(",") if s]
    id_tags = [s for s in args.id_tags.split(",") if s]
    try:
        specs = [parse_coordinate_spec(s) for s in args.coordinates]
    except ValueError as e:
        logger.error("--coordinate: %s", e)
        return 1

    # per-entity L2 multiplier files: validate and parse NOW — a bad path or
    # value must fail before hours of data loading (same early-failure rule
    # as the tuner resolution above)
    mult_by_spec = {}
    for i, spec in enumerate(specs):
        if spec.per_entity_l2_file is None:
            continue
        try:
            with open(spec.per_entity_l2_file) as f:
                raw = json.load(f)
            if not isinstance(raw, dict):
                raise ValueError(
                    f"expected a JSON object of entity -> multiplier, got "
                    f"{type(raw).__name__}")
            parsed = {}
            for name, m in raw.items():
                m = float(m)
                if not (m >= 0.0) or not np.isfinite(m):
                    raise ValueError(
                        f"entity {name!r}: multiplier {m} must be finite "
                        "and >= 0 (negative L2 is unbounded)")
                parsed[str(name)] = m
            mult_by_spec[i] = parsed
        except (OSError, ValueError, TypeError, json.JSONDecodeError) as e:
            logger.error("coordinate %s per-entity multipliers (%s): %s",
                         spec.name, spec.per_entity_l2_file, e)
            return 1

    # constraint files (reference constraint-string grammar): parse + shape-
    # check NOW; name->index resolution waits for the index maps
    constraint_entries_by_spec = {}
    for i, spec in enumerate(specs):
        if spec.constraints_file is None:
            continue
        try:
            with open(spec.constraints_file) as f:
                raw = json.load(f)
            if not isinstance(raw, list) or not all(
                    isinstance(e, dict) for e in raw):
                raise ValueError("expected a JSON array of constraint objects")
            constraint_entries_by_spec[i] = raw
        except (OSError, ValueError, TypeError, json.JSONDecodeError) as e:
            logger.error("coordinate %s constraints (%s): %s",
                         spec.name, spec.constraints_file, e)
            return 1

    # 1. index maps + training data.  Native loader (native/avro_loader.cpp):
    # columnar decode, no per-record Python objects — index maps and design
    # matrices both come from interned columnar buffers.  Python fallback:
    # decode ONCE, reuse the records for both steps.
    from photon_ml_tpu.data.avro import list_avro_files
    from photon_ml_tpu.data.index_map import (build_index_maps_from_avro,
                                              build_index_maps_from_records)
    from photon_ml_tpu.data.native_avro import schema_eligible

    from photon_ml_tpu.data.reader import parse_input_columns

    try:
        input_columns = parse_input_columns(args.input_columns)
    except ValueError as e:
        logger.error("%s", e)
        return 1
    if args.tuning_iterations > 0:
        # resolve the tuner NOW: a bad --tuner must fail before hours of
        # grid fitting, not after
        from photon_ml_tpu.tune.factory import tuner_factory

        try:
            tuner = tuner_factory(args.tuner)
        except ValueError as e:
            logger.error("%s", e)
            return 1

    if args.stream and "features" in input_columns and not args.index_map_dir:
        # the streaming index scan reads the default features column; a
        # remapped one needs prebuilt maps (eager record decode would defeat
        # out-of-core ingest)
        logger.error("--stream with a remapped features column requires "
                     "--index-map-dir")
        return 1

    # native columnar path only when EVERY file qualifies (and reads the
    # default reserved column names) — otherwise decode once through the
    # Python codec and reuse the records for both steps.  Streaming never
    # materializes the record list: index maps come from --index-map-dir or
    # the memory-bounded scan below.
    use_native = not args.stream and not input_columns and all(
        schema_eligible(f) for p in args.train_data
        for f in list_avro_files(p))
    train_records = None
    if not use_native and not args.stream:
        from photon_ml_tpu.data.avro import read_directory

        train_records = []
        for path in args.train_data:
            train_records.extend(read_directory(path))
    if args.index_map_dir:
        from photon_ml_tpu.data.index_map import load_index

        def _resolve(s):
            for ext in (".idx", ".phidx"):
                p = os.path.join(args.index_map_dir, s + ext)
                if os.path.exists(p):
                    return load_index(p)
            raise FileNotFoundError(f"no index map for shard {s!r} in {args.index_map_dir}")

        index_maps = {s: _resolve(s) for s in shards}
    elif train_records is None and args.stream:
        # the stream's malformed-block policy must govern this pre-pass too:
        # under --stream-on-error=skip a corrupt block costs its rows, not
        # the whole run (the eager scan would raise before the epoch's
        # policy ever applied)
        logger.info("building index maps from training data (streamed scan)")
        from photon_ml_tpu.stream.chunks import AvroStreamSource
        from photon_ml_tpu.stream.pipeline import ChunkPipeline

        def _stream_records():
            pipe = ChunkPipeline(AvroStreamSource(args.train_data),
                                 workers=args.stream_workers,
                                 on_error=args.stream_on_error)
            for _chunk, records, err in pipe:
                if err is None:
                    yield from records

        index_maps = build_index_maps_from_records(
            _stream_records(), shards, add_intercept=not args.no_intercept)
    elif train_records is None:
        logger.info("building index maps from training data (native scan)")
        index_maps = build_index_maps_from_avro(
            args.train_data, {s: [] for s in shards},
            add_intercept=not args.no_intercept)
    else:
        logger.info("building index maps from training data")
        index_maps = build_index_maps_from_records(
            train_records, shards, add_intercept=not args.no_intercept,
            features_col=input_columns.get("features", "features"))
    for s in shards:
        logger.info("shard %s: %d features", s, index_maps[s].size)

    sparse_shards = set()
    if args.sparse_threshold > 0:
        sparse_shards = {s for s in shards
                         if index_maps[s].size >= args.sparse_threshold}
        # random-effect coordinates train from sparse shards directly
        # (compact observed-column buckets, bucket_by_entity_sparse) EXCEPT
        # the ONE combination the sparse path still refuses loudly — those
        # shards stay dense so the run succeeds.  (Round 4 closed the other
        # carve-outs: RANDOM projection, FULL variances, box constraints and
        # shift normalization all run on sparse shards now.)
        from photon_ml_tpu.types import VarianceComputationType

        needs_dense = {
            spec.template.feature_shard for spec in specs
            if not isinstance(spec.template, FixedEffectConfig)
            # variances under compaction + per-entity normalization
            # contexts are refused together (game/coordinate._bind_solver)
            and (spec.template.variance != VarianceComputationType.NONE
                 and args.normalization != "NONE")}
        forced_dense = sparse_shards & needs_dense
        if forced_dense:
            logger.warning("shards %s stay dense: variance-computing "
                           "random-effect coordinates under normalization "
                           "need dense shards", sorted(forced_dense))
            sparse_shards -= forced_dense
        if sparse_shards:
            logger.info("sparse shards: %s", sorted(sparse_shards))

    # 2. assemble GameData (columnar fast path inside when native is up;
    # --stream assembles design matrices on device from the chunk pipeline)
    if args.stream:
        if sparse_shards:
            logger.error("--stream does not support sparse shards yet "
                         "(ROADMAP item 5 follow-on); drop "
                         "--sparse-threshold or the --stream flag")
            return 1
        from photon_ml_tpu.stream import stream_game_data

        # per-tag reservoir caps so EntityStats accumulates the capped
        # selection in O(entities * cap); tags whose coordinates disagree on
        # the cap accumulate full row lists (any cap answerable later)
        active_caps = {}
        seen_caps: Dict[str, set] = {}
        for spec in specs:
            t = spec.template
            if isinstance(t, FixedEffectConfig):
                continue
            seen_caps.setdefault(t.random_effect_type, set()).add(t.active_cap)
        for tag, caps in seen_caps.items():
            if len(caps) == 1 and (cap := next(iter(caps))) is not None:
                active_caps[tag] = cap
        data, entity_indexes = stream_game_data(
            args.train_data, index_maps, id_tag_names=id_tags,
            input_columns=input_columns,
            batch_rows=args.stream_batch_rows,
            workers=args.stream_workers, on_error=args.stream_on_error,
            active_caps=active_caps, seed=args.seed,
            validate=args.data_validation != "VALIDATE_DISABLED")
    else:
        data, entity_indexes = read_game_data_avro(
            args.train_data, index_maps, id_tag_names=id_tags,
            records=train_records, sparse_shards=sparse_shards,
            input_columns=input_columns)
    del train_records
    logger.info("train: %d samples", data.num_samples)
    val_data = None
    if args.validation_data:
        val_data, _ = read_game_data_avro(args.validation_data, index_maps,
                                          id_tag_names=id_tags,
                                          entity_indexes=entity_indexes,
                                          sparse_shards=sparse_shards,
                                          input_columns=input_columns)
        logger.info("validation: %d samples", val_data.num_samples)
    from photon_ml_tpu.data.native_avro import clear_columnar_cache

    clear_columnar_cache()  # decoded columns are folded into GameData now

    # 3. validate (reference DataValidators)
    errors = validate_game_data(
        data, task, DataValidationType[args.data_validation],
        allow_zero_weight=args.stream and args.stream_on_error == "skip")
    if errors:
        for e in errors:
            logger.error("validation: %s", e)
        return 1

    # 4. normalization from training stats (reference GameTrainingDriver
    # :430-436 FeatureDataStatistics + NormalizationContext per shard)
    normalization = None
    feature_stats = {}
    if args.normalization != "NONE":
        import dataclasses as _dc

        import jax.numpy as jnp

        from photon_ml_tpu.core.normalization import (build_normalization,
                                                      compute_feature_stats)
        from photon_ml_tpu.types import NormalizationType

        kind = NormalizationType[args.normalization]
        # normalization applies to EVERY coordinate on the shard, random
        # effects included (reference NormalizationContextRDD via
        # GameEstimator.prepareNormalizationContextWrappers:646-680); sparse
        # shards compute their stats straight from the COO arrays.  Shift
        # normalization (STANDARDIZATION) under per-entity compaction is
        # SUPPORTED since round 4 (the context is projected per entity and
        # the per-lane intercept position absorbs the margin shift —
        # game/coordinate.py); the intercept id is auto-filled from the
        # index maps below.  The one remaining shift refusal: a
        # feature-SHARDED sparse fixed effect (ShardSparseObjective is
        # scaling-only — shifts would densify sparse margins).
        norm_shards = {spec.template.feature_shard for spec in specs}
        if kind == NormalizationType.STANDARDIZATION:
            for spec in specs:
                t = spec.template
                if (isinstance(t, FixedEffectConfig)
                        and getattr(t, "feature_sharded", False)
                        and t.feature_shard in sparse_shards):
                    logger.error(
                        "coordinate %s: STANDARDIZATION shifts are not "
                        "supported on a feature-sharded sparse fixed effect "
                        "(shifts densify sparse margins) — use a factor-only "
                        "normalization", spec.name)
                    return 1
        normalization = {}
        for s in sorted(norm_shards):
            ii = index_maps[s].intercept_index
            shard_data = data.features[s]
            if s in sparse_shards:
                from photon_ml_tpu.core.normalization import \
                    compute_feature_stats_sparse

                stats = compute_feature_stats_sparse(
                    shard_data.indices, shard_data.values, shard_data.dim,
                    weight=data.weight, intercept_index=ii)
            else:
                stats = compute_feature_stats(jnp.asarray(shard_data),
                                              jnp.asarray(data.weight),
                                              intercept_index=ii)
            normalization[s] = build_normalization(kind, stats)
            if s in sparse_shards:
                # a huge-vocabulary shard must not dump dim-length JSON
                # lists (or loop the avro summary over millions of columns)
                # — record OBSERVED columns only, with their ids
                nnz = np.asarray(stats.num_nonzeros)
                keep = np.nonzero(nnz > 0)[0]
                if ii is not None and ii not in keep:
                    keep = np.sort(np.append(keep, ii))
                feature_stats[s] = {
                    "indices": keep.tolist(),
                    "mean": np.asarray(stats.mean)[keep].tolist(),
                    "variance": np.asarray(stats.variance)[keep].tolist(),
                    "abs_max": np.asarray(stats.abs_max)[keep].tolist(),
                    "intercept_index": ii,
                }
            else:
                feature_stats[s] = {
                    "mean": np.asarray(stats.mean).tolist(),
                    "variance": np.asarray(stats.variance).tolist(),
                    "abs_max": np.asarray(stats.abs_max).tolist(),
                    "intercept_index": ii,
                }
        logger.info("normalization %s over %d shard(s)", kind.name, len(normalization))

    # per-entity L2 multipliers: entity NAMES in the JSON file resolve
    # through the entity index built from the data (beyond-reference
    # feature; RandomEffectOptimizationProblem.scala:42 only envisioned
    # per-entity lambdas)
    import dataclasses as _dc

    for i, spec in enumerate(specs):
        if i not in mult_by_spec:
            continue
        re_type = spec.template.random_effect_type
        eidx = entity_indexes.get(re_type)
        if eidx is None:
            logger.error("per-entity multipliers for %r need id tag %r in "
                         "--id-tags", spec.name, re_type)
            return 1
        mult = {}
        missing = 0
        for name, m in mult_by_spec[i].items():
            eid = eidx.get(name)
            if eid < 0:
                missing += 1
                continue
            mult[eid] = m
        if missing:
            logger.warning("coordinate %s: %d multiplier entities not in "
                           "training data (ignored)", spec.name, missing)
        specs[i] = _dc.replace(spec, template=_dc.replace(
            spec.template, per_entity_l2_multipliers=mult))
        logger.info("coordinate %s: per-entity L2 multipliers for %d "
                    "entities", spec.name, len(mult))

    # constraint resolution: reference grammar names/terms -> this run's
    # feature indices (GLMSuite.createConstraintFeatureMap semantics)
    for i, entries in constraint_entries_by_spec.items():
        spec = specs[i]
        from photon_ml_tpu.cli.config_grammar import resolve_constraints

        try:
            resolved = resolve_constraints(
                entries, index_maps[spec.template.feature_shard])
            # bound validation (lo < hi, not both infinite) fires in the
            # config's __post_init__ — keep it inside the CLI error contract
            specs[i] = _dc.replace(spec, template=_dc.replace(
                spec.template, constraints=resolved))
        except ValueError as e:
            logger.error("coordinate %s constraints: %s", spec.name, e)
            return 1
        logger.info("coordinate %s: box constraints on %d feature(s)",
                    spec.name, len(resolved))

    # 5. config grid (reference prepareGameOptConfigs) + fit
    configs = expand_game_configs(specs, task, args.coordinate_descent_iterations)
    if normalization:
        # shift-normalized solves need the intercept column id (conversion
        # between model and transformed space, NormalizationContext.scala);
        # random effects also need it for the RANDOM projector's intercept
        # pass-through — fill from the index map unless the user set it
        configs = [
            _dc.replace(cfg, coordinates={
                cid: (_dc.replace(c, intercept_index=index_maps[c.feature_shard].intercept_index)
                      if c.intercept_index is None else c)
                for cid, c in cfg.coordinates.items()})
            for cfg in configs
        ]
    logger.info("fitting %d configuration(s)", len(configs))
    suite = (EvaluationSuite.from_specs(args.evaluators.split(","))
             if args.evaluators else None)
    mesh = None
    if args.mesh:
        from photon_ml_tpu.parallel.mesh import make_mesh

        axes = {}
        for part in args.mesh.split(","):
            k, _, v = part.partition("=")
            try:
                size = int(v)
            except ValueError:
                size = 0
            if k.strip() not in ("data", "entity", "feature") or size < 1:
                raise SystemExit(f"bad --mesh fragment {part!r} "
                                 "(expected data=N,entity=N,feature=N, N >= 1)")
            axes[k.strip()] = size
        mesh = make_mesh(n_data=axes.get("data"),
                         n_entity=axes.get("entity", 1),
                         n_feature=axes.get("feature", 1))
        logger.info("device mesh: %s", dict(mesh.shape))
    est = GameEstimator(mesh=mesh, validation_suite=suite,
                        normalization=normalization,
                        fused={"auto": "auto", "on": True,
                               "off": False}[args.fused])

    # Warm start / partial retraining (reference GameTrainingDriver.scala:370-379
    # -> GameEstimator initialModel + partial retraining :106-112).
    initial_model = None
    locked = {c for c in args.lock_coordinates.split(",") if c} or None
    if locked:
        known = {cid for cfg in configs for cid in cfg.coordinates}
        bad = locked - known
        if bad:
            logger.error("--lock-coordinates %s not among configured coordinates %s",
                         sorted(bad), sorted(known))
            return 1
    if args.model_input_dir and args.model_input_format == "reference":
        # Warm start / partial retraining FROM a model the reference itself
        # saved (migration): stored (name, term) coefficients remap into THIS
        # run's index maps; imported coordinate ids must match the training
        # coordinate names for warm start to engage.
        from photon_ml_tpu.storage.model_io import import_reference_game_model

        shard_by_cid = {s.name: s.template.feature_shard for s in specs}
        try:
            # subset migration: only coordinates named in this run's
            # --coordinate specs import; others are skipped, not errors
            initial_model, loaded_task, _, entity_indexes = \
                import_reference_game_model(
                    args.model_input_dir, entity_indexes=entity_indexes,
                    index_maps=index_maps, shard_of=shard_by_cid,
                    only=set(shard_by_cid))
        except (KeyError, FileNotFoundError, ValueError) as e:
            logger.error("--model-input-dir (reference format): %s", e)
            return 1
        if loaded_task != task:
            logger.error("input model task %s != --task %s", loaded_task, task)
            return 1
        # The imported per-entity coefficients are keyed by the model's
        # randomEffectType; if a same-named training coordinate uses a
        # DIFFERENT id tag, entity ids would silently misalign — refuse.
        re_type_by_cid = {
            s.name: s.template.random_effect_type for s in specs
            if not isinstance(s.template, FixedEffectConfig)}
        for cid, m in initial_model.models.items():
            want = re_type_by_cid.get(cid)
            got = getattr(m, "random_effect_type", None)
            if want is not None and got is not None and want != got:
                logger.error(
                    "imported coordinate %r has randomEffectType %r but this "
                    "run's coordinate uses random.effect.type=%r — entity "
                    "ids would misalign", cid, got, want)
                return 1
        logger.info("imported reference-format warm-start model "
                    "(%d coordinates%s)", len(initial_model.models),
                    f", locked: {sorted(locked)}" if locked else "")
    elif args.model_input_dir:
        from photon_ml_tpu.storage.model_io import load_game_model

        # accept either the training output dir (contains best/) or a model
        # dir itself (contains metadata.json)
        mdir = args.model_input_dir
        if not os.path.exists(os.path.join(mdir, "metadata.json")):
            mdir = os.path.join(mdir, "best")
        if not os.path.exists(os.path.join(mdir, "metadata.json")):
            logger.error("--model-input-dir %s: no model found (missing metadata.json)",
                         args.model_input_dir)
            return 1
        initial_model, loaded_task = load_game_model(mdir, index_maps, entity_indexes)
        if loaded_task != task:
            logger.error("input model task %s != --task %s", loaded_task, task)
            return 1
        logger.info("warm start from %s (%d coordinates%s)", args.model_input_dir,
                    len(initial_model.models),
                    f", locked: {sorted(locked)}" if locked else "")
    elif locked:
        logger.error("--lock-coordinates requires --model-input-dir")
        return 1

    # Checkpoint/resume (storage/checkpoint.py): resume wins over
    # --model-input-dir because it includes everything that dir did plus the
    # mid-job progress.
    checkpoint_hook = None
    resume_cursor = None
    resume_best = None
    if args.checkpoint_dir:
        import hashlib

        from photon_ml_tpu.storage.checkpoint import (has_checkpoint,
                                                       load_checkpoint,
                                                       save_checkpoint)

        # Fingerprint of everything the positional cursor and best-model
        # tracking depend on: a rerun with ANY of these changed must NOT
        # silently resume (wrong grid indices, skipped-but-never-ran locked
        # updates, best-metric comparisons across different primaries, or a
        # cursor applied to different data).
        fp_src = json.dumps({"coordinates": args.coordinates, "task": args.task,
                             "per_entity_multipliers": {
                                 str(i): sorted(d.items())
                                 for i, d in mult_by_spec.items()},
                             "iterations": args.coordinate_descent_iterations,
                             "seed": args.seed,
                             "train_data": sorted(args.train_data),
                             "validation_data": sorted(args.validation_data),
                             "evaluators": args.evaluators,
                             "lock": args.lock_coordinates,
                             "model_input": args.model_input_dir,
                             "model_input_format": args.model_input_format,
                             "normalization": args.normalization,
                             "sparse_threshold": args.sparse_threshold,
                             "feature_shards": args.feature_shards,
                             "id_tags": args.id_tags,
                             "no_intercept": args.no_intercept,
                             "index_map_dir": args.index_map_dir}, sort_keys=True)
        fingerprint = hashlib.sha256(fp_src.encode()).hexdigest()[:16]

        # Discriminator is the POINTER, not an exception type: a present
        # pointer names an atomically-written version, so ANY load failure
        # there (missing files included) is external damage and must refuse
        # loudly rather than silently retrain from scratch.
        if has_checkpoint(args.checkpoint_dir):
            try:
                initial_model, ck_task, resume_cursor, resume_best = load_checkpoint(
                    args.checkpoint_dir, index_maps, entity_indexes)
            except Exception as e:
                logger.error(
                    "checkpoint in %s is unreadable (%s); clear the dir to "
                    "start fresh or restore it to resume", args.checkpoint_dir, e)
                return 1
            if ck_task != task:
                logger.error("checkpoint task %s != --task %s", ck_task, task)
                return 1
            saved_fp = resume_cursor.pop("fingerprint", None)
            if saved_fp != fingerprint:
                logger.error(
                    "checkpoint in %s was written by a DIFFERENT configuration "
                    "(fingerprint %s != %s); refusing to resume — clear the "
                    "checkpoint dir or rerun with the original flags",
                    args.checkpoint_dir, saved_fp, fingerprint)
                return 1
            logger.info("resuming from checkpoint %s at %s", args.checkpoint_dir,
                        resume_cursor)

        def checkpoint_hook(model, cursor, updated=None, best=None, best_changed=True):
            save_checkpoint(args.checkpoint_dir, model, index_maps, cursor,
                            entity_indexes, task, updated_coordinate=updated,
                            best=best, best_changed=best_changed,
                            fingerprint=fingerprint,
                            fmt=args.model_save_format)

    # Always fit the explicit reg-weight grid; tuning then explores FROM the
    # best grid point (reference: grid first, tuner after, :643-674).
    emitter.emit("fit_start", configs=len(configs))
    try:
        results = est.fit(data, configs, validation_data=val_data, seed=args.seed,
                          initial_model=initial_model, locked_coordinates=locked,
                          checkpoint_hook=checkpoint_hook, resume_cursor=resume_cursor,
                          resume_best=resume_best)
    except (ValueError, NotImplementedError) as e:
        # config-shaped refusals raised at coordinate build/bind time (e.g.
        # box constraints under shift normalization, normalization under a
        # RANDOM projector) get the same error contract as every other
        # config validation failure — with the traceback preserved in the log
        logger.exception("configuration rejected during fit: %s", e)
        return 1
    best = est.best(results)
    tuned_results = []
    if args.tuning_iterations > 0:
        if val_data is None or suite is None:
            logger.error("tuning requires --validation-data and --evaluators")
            return 1
        tuning_mode, search_domain, prior_obs = args.tuning_mode, None, None
        unlocked = [c for c in best.config.coordinates if c not in (locked or ())]
        if args.tuning_config:
            from photon_ml_tpu.tune.serialization import config_from_json

            with open(args.tuning_config) as f:
                mode_str, search_domain = config_from_json(f.read())
            tuning_mode = mode_str.lower()
        if args.tuning_priors:
            from photon_ml_tpu.tune.serialization import (game_prior_default,
                                                          prior_from_json)

            names = ([d.name for d in search_domain.dims] if search_domain
                     else [f"l2:{c}" for c in unlocked])
            defaults = game_prior_default(unlocked)
            defaults.update({n: "0.0" for n in names})
            with open(args.tuning_priors) as f:
                prior_obs = prior_from_json(f.read(), defaults, names)
        # tuners without a search domain (DUMMY and kin) skip the prep work
        tuner_uses_domain = getattr(tuner, "uses_search_domain", True)
        if args.tuning_shrink_radius is not None and not tuner_uses_domain:
            logger.info("skipping search-range shrink: tuner ignores the "
                        "search domain")
        elif args.tuning_shrink_radius is not None:
            if not prior_obs:
                logger.error("--tuning-shrink-radius needs --tuning-priors")
                return 1
            from photon_ml_tpu.tune.shrink import shrink_search_range

            if search_domain is None:
                from photon_ml_tpu.tune.game_tuning import default_l2_domain

                search_domain = default_l2_domain(unlocked)
            minimize = not suite.primary.larger_is_better
            search_domain = shrink_search_range(
                search_domain, prior_obs, radius=args.tuning_shrink_radius,
                minimize=minimize, seed=args.seed)
            logger.info("shrunk tuning domain: %s",
                        [(d.name, round(d.low, 6), round(d.high, 6))
                         for d in search_domain.dims])

        _tuned, _search, tuned_results = tuner.tune(
            est, best.config, data, val_data,
            n_iterations=args.tuning_iterations,
            mode=tuning_mode, seed=args.seed,
            initial_model=initial_model,
            locked_coordinates=locked,
            search_domain=search_domain,
            prior_observations=prior_obs)
        if tuned_results:
            best = est.best(results + tuned_results)

    if best.evaluation is not None:
        logger.info("best model validation: %s", best.evaluation.values)

    # 6. save (reference saveModelToHDFS / ModelProcessingUtils /
    # selectModels:683-701 — output mode picks which extra models go under
    # models/<i>/ alongside best/)
    os.makedirs(args.output_dir, exist_ok=True)
    extra_models = {
        "NONE": [], "BEST": [],
        "EXPLICIT": results,
        "TUNED": tuned_results,
        "ALL": results + tuned_results,
    }[args.model_output_mode]
    if args.output_models_limit is not None:
        extra_models = extra_models[: args.output_models_limit]

    def _config_spec(cfg):
        """Per-coordinate optimization spec (reference
        IOUtils.writeOptimizationConfigToHDFS:195)."""
        spec = {}
        for cid, c in cfg.coordinates.items():
            spec[cid] = {"l1": c.reg.l1, "l2": c.reg.l2,
                         "optimizer": c.optimizer.name}
        return spec

    if args.export_reference_model:
        # independent of --model-output-mode: an explicitly requested
        # Spark-consumable artifact is written even under NONE
        from photon_ml_tpu.storage.model_io import export_reference_game_model

        export_reference_game_model(best.model, args.export_reference_model,
                                    index_maps, entity_indexes, task)
        logger.info("exported best model in reference layout -> %s",
                    args.export_reference_model)
    if args.model_output_mode != "NONE":
        save_game_model(best.model, os.path.join(args.output_dir, "best"),
                        index_maps, entity_indexes, task,
                        fmt=args.model_save_format)
        with open(os.path.join(args.output_dir, "best",
                               "model-spec.json"), "w") as f:
            json.dump(_config_spec(best.config), f, indent=2)
        for i, res in enumerate(extra_models):
            mdir = os.path.join(args.output_dir, "models", str(i))
            save_game_model(res.model, mdir, index_maps, entity_indexes, task,
                            fmt=args.model_save_format)
            with open(os.path.join(mdir, "model-spec.json"), "w") as f:
                json.dump({"config": _config_spec(res.config),
                           "validation": res.evaluation.values
                           if res.evaluation else None}, f, indent=2)
        for s in shards:
            from photon_ml_tpu.data.native_index import StoreIndexMap

            ext = ".phidx" if isinstance(index_maps[s], StoreIndexMap) else ".idx"
            index_maps[s].save(os.path.join(args.output_dir, f"{s}{ext}"))
        for tag, eidx in entity_indexes.items():
            eidx.save(os.path.join(args.output_dir, f"{tag}.entities.json"))
    if feature_stats:
        # reference ModelProcessingUtils.writeBasicStatistics:516 — JSON for
        # humans plus the reference's FeatureSummarizationResultAvro records
        # (per-feature metric map) for tool compatibility
        with open(os.path.join(args.output_dir, "feature-stats.json"), "w") as f:
            json.dump(feature_stats, f)
        from photon_ml_tpu.data import avro as avro_io
        from photon_ml_tpu.data.schemas import FEATURE_SUMMARY

        for s, st in feature_stats.items():
            imap = index_maps[s]

            def records(st=st, imap=imap):
                # sparse shards carry an explicit observed-column id list;
                # dense shards are positionally indexed
                cols = st.get("indices") or range(len(st["mean"]))
                for pos, j in enumerate(cols):
                    name_term = imap.get_feature_name(int(j))
                    if name_term is None:
                        continue
                    name, term = name_term
                    yield {"name": name, "term": term, "metrics": {
                        "mean": st["mean"][pos],
                        "variance": st["variance"][pos],
                        "absMax": st["abs_max"][pos],
                    }}

            avro_io.write_container(
                os.path.join(args.output_dir, f"{s}.feature-summary.avro"),
                FEATURE_SUMMARY, records())
    summary = {
        "task": task.value,
        "train_samples": int(data.num_samples),
        "configs": len(configs),
        "validation": best.evaluation.values if best.evaluation else None,
        "seconds": round(time.time() - t_start, 2),
    }
    with open(os.path.join(args.output_dir, "training-summary.json"), "w") as f:
        json.dump(summary, f, indent=2)
    emitter.emit("training_end", seconds=summary["seconds"],
                 validation=summary["validation"])
    logger.info("done in %.1fs -> %s", summary["seconds"], args.output_dir)
    return 0


def main() -> None:
    sys.exit(run(sys.argv[1:]))


if __name__ == "__main__":
    main()
