"""photonlearn driver — labeled JSON-lines in, refit reports out.

Photon ML reference counterpart: none.  The reference retrains random
effects offline and republishes stores; this driver closes the loop the
paper leaves open: it loads the SAME training output ``cli/serve.py``
serves, then streams fresh labeled examples through
``online.IncrementalTrainer`` — warm-started batched per-entity Newton
refits whose updated rows publish through ``serving.HotSwapper`` into the
live store AND append to the durable ``online.DeltaLog`` under one
``(generation, delta_version)`` identity.  A serving replica started with
``serve.py --delta-log DIR`` on the same directory converges to these
rows with no other coordination.

Wire protocol (one JSON object per line on stdin / ``--examples`` file):

  example   the serving request format plus a label:
            {"uid": 7, "features": [["f0", 0.3], ...],
             "ids": {"userId": "user3"}, "offset": 0.0,
             "label": 1.0, "weight": 2.0}
            ("response" is accepted for "label" — the TrainingExampleAvro
            field name — and weight defaults to 1)
  flush     a blank line — refit the buffered mini-batch now (otherwise
            batches flush at ``--batch-size`` and at EOF)

Each flushed batch emits ONE report line on stdout
(``RefitReport.to_json``): entities refit, rows solved, publish identity
range, solve/publish timings.  ``--format avro`` reads
TrainingExampleAvro container files (``data/avro.read_container``)
instead of JSON lines — the batch pipeline's own output format, so
yesterday's scoring traffic can be replayed as today's fresh examples.

Restart safety: the delta log is opened BEFORE the coefficient store is
built, and the store's generation counter is advanced past the newest
logged generation (``advance_generation_floor``) — a restarted trainer
resumes with a strictly newer identity instead of colliding with rows it
logged in its previous life.
"""

from __future__ import annotations

import argparse
import json
import logging
import sys
from typing import IO, Iterator, List, Optional

from photon_ml_tpu.storage.model_io import ModelLoadError

logger = logging.getLogger("photon_ml_tpu.learn")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="photon-tpu-learn",
                                description="Incremental per-entity refit "
                                            "of a trained GAME model from "
                                            "streamed labeled examples")
    p.add_argument("--model-dir", required=True,
                   help="training output dir (the same one serve.py loads)")
    p.add_argument("--examples", default="-",
                   help="labeled examples: JSON-lines file ('-' = stdin) "
                        "or an Avro container with --format avro")
    p.add_argument("--format", choices=("json", "avro"), default="json",
                   help="examples input format (avro = TrainingExampleAvro "
                        "container, the batch pipeline's own output)")
    p.add_argument("--batch-size", type=int, default=64,
                   help="mini-batch size: buffered examples refit together "
                        "when this many accumulate (blank line / EOF also "
                        "flush)")
    p.add_argument("--coordinates", default="",
                   help="comma list of random-effect coordinates to refit "
                        "(default: every SoA-eligible one)")
    p.add_argument("--l2", type=float, default=1.0,
                   help="per-entity ridge strength for the refits")
    p.add_argument("--max-iters", type=int, default=20,
                   help="Newton iteration cap per refit")
    p.add_argument("--min-rows", type=int, default=1,
                   help="entities with fewer fresh rows in a batch wait "
                        "for more data instead of refitting on noise")
    p.add_argument("--delta-log", default="",
                   help="durable delta log directory (online/delta_log.py); "
                        "this process OWNS it: every published row appends "
                        "here and hot swaps compact it.  Empty = publish "
                        "in-process only (nothing for a replica to follow)")
    p.add_argument("--fsync", choices=("always", "rotate", "never"),
                   default="always",
                   help="delta-log durability: fsync every append, only at "
                        "segment rotation, or never (test only)")
    p.add_argument("--warm", action="store_true",
                   help="AOT-warm the scoring bucket ladder too (only "
                        "useful when this process also answers scores)")
    p.add_argument("--repl-listen", default="",
                   help="host:port for the photonrepl log server "
                        "(online/replication): replicas subscribe here for "
                        "snapshot bootstrap + live delta shipping instead "
                        "of sharing the --delta-log directory.  Requires "
                        "--delta-log.  Port 0 = ephemeral (logged)")
    p.add_argument("--auth-token", default=None,
                   help="shared secret replication subscribers must "
                        "present (constant-time compare; one error frame, "
                        "then close).  Default: $PHOTON_AUTH_TOKEN")
    p.add_argument("--metrics-json", default="",
                   help="write the final metrics snapshot here at exit")
    p.add_argument("--trace", action="store_true",
                   help="enable the photonscope tracer (refit/publish "
                        "spans; publish waves mint photonpulse trace "
                        "contexts that ride the replication wire)")
    p.add_argument("--trace-buffer", type=int, default=8192,
                   help="tracer ring-buffer capacity (newest spans win)")
    p.add_argument("--trace-out", default="",
                   help="write the Chrome trace JSON here at exit "
                        "(implies --trace)")
    p.add_argument("--trace-label", default="owner",
                   help="photonpulse process label stamped on trace "
                        "exports and replication clock replies")
    p.add_argument("--flight-dir", default="",
                   help="photonpulse flight recorder spool: degradation "
                        "transitions dump the tracer ring here")
    p.add_argument("--flight-max-bytes", type=int, default=16 << 20,
                   help="on-disk byte bound for the flight spool")
    p.add_argument("--metrics-port", type=int, default=0,
                   help="expose GET /metrics, /metrics.json and the "
                        "photonwatch /watchz federation pull on this "
                        "localhost port via a sidecar thread (0 = off)")
    p.add_argument("--watch", action="store_true",
                   help="accepted and ignored: GET /watchz on "
                        "--metrics-port is always on, and --slo runs the "
                        "burn-rate sidecar")
    p.add_argument("--slo", default="", metavar="FILE",
                   help="photonwatch SLO objectives (JSON list, "
                        "obs/watch/slo.py) evaluated against this "
                        "process's registry on a background thread")
    p.add_argument("--slo-interval", type=float, default=1.0,
                   help="seconds between --slo evaluation passes")
    return p


def _parse_hostport(value: str) -> tuple:
    host, sep, port = value.rpartition(":")
    if not sep or not host:
        raise ValueError(f"expected HOST:PORT, got {value!r}")
    return host, int(port)


def _avro_examples(path: str) -> Iterator[dict]:
    """TrainingExampleAvro records -> the trainer's wire-JSON dicts."""
    from photon_ml_tpu.data.avro import read_container

    for rec in read_container(path):
        yield {"uid": rec.get("uid"),
               "features": rec.get("features") or (),
               "ids": rec.get("metadataMap") or {},
               "offset": rec.get("offset") or 0.0,
               "label": rec.get("response", rec.get("label")),
               "weight": (1.0 if rec.get("weight") is None
                          else rec.get("weight"))}


def _learn_stream(trainer, lines: IO, out: IO, batch_size: int) -> int:
    """JSON-lines driver: buffer examples, refit on blank line /
    ``batch_size`` / EOF, emit one report line per flushed batch."""
    batch: List[dict] = []

    def flush() -> None:
        if not batch:
            return
        report = trainer.consume(batch)
        out.write(json.dumps(report.to_json()) + "\n")
        out.flush()
        batch.clear()

    for line in lines:
        line = line.strip()
        if not line:
            flush()
            continue
        try:
            obj = json.loads(line)
            if not isinstance(obj, dict):
                raise ValueError(f"expected a JSON object, got "
                                 f"{type(obj).__name__}")
        except ValueError as e:
            logger.error("bad example line: %s", e)
            out.write(json.dumps({"error": str(e)}) + "\n")
            out.flush()
            continue
        batch.append(obj)
        if len(batch) >= batch_size:
            flush()
    flush()
    return 0


def _learn_batches(trainer, examples: Iterator[dict], out: IO,
                   batch_size: int) -> int:
    """Avro driver: fixed-size mini-batches over a record iterator."""
    batch: List[dict] = []
    for obj in examples:
        batch.append(obj)
        if len(batch) >= batch_size:
            report = trainer.consume(batch)
            out.write(json.dumps(report.to_json()) + "\n")
            out.flush()
            batch.clear()
    if batch:
        report = trainer.consume(batch)
        out.write(json.dumps(report.to_json()) + "\n")
        out.flush()
    return 0


def run(argv: List[str]) -> int:
    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="%(asctime)s %(name)s %(message)s")
    args = build_parser().parse_args(argv)
    if args.batch_size < 1:
        logger.error("--batch-size must be >= 1, got %d", args.batch_size)
        return 1

    from photon_ml_tpu.utils.runtime import init_runtime

    init_runtime(logger)

    if args.trace or args.trace_out:
        from photon_ml_tpu import obs

        obs.enable_tracing(capacity=args.trace_buffer)
        logger.info("tracing enabled (ring capacity %d)", args.trace_buffer)

    from photon_ml_tpu.obs import pulse

    pulse.configure(args.trace_label)
    if args.flight_dir:
        pulse.set_flight(pulse.FlightRecorder(
            args.flight_dir, max_bytes=args.flight_max_bytes))
        logger.info("flight recorder spooling to %s (cap %d bytes)",
                    args.flight_dir, args.flight_max_bytes)

    from photon_ml_tpu.cli.serve import build_server
    from photon_ml_tpu.online.trainer import IncrementalTrainer, TrainerConfig

    delta_log = None
    if args.delta_log:
        from photon_ml_tpu.online.delta_log import DeltaLog
        from photon_ml_tpu.serving.coefficient_store import \
            advance_generation_floor

        delta_log = DeltaLog(args.delta_log, fsync=args.fsync)
        last = delta_log.last_identity()
        if last is not None:
            # restart safety: resume with a strictly newer generation than
            # anything already logged, BEFORE the store mints one
            advance_generation_floor(last[0] + 1)
            logger.info("delta log %s resumes past identity %s",
                        args.delta_log, last)

    coords = tuple(c.strip() for c in args.coordinates.split(",")
                   if c.strip()) or None
    try:
        engine, swapper = build_server(args.model_dir, warm=args.warm,
                                       delta_log=delta_log, log_owner=True)
        trainer = IncrementalTrainer(
            swapper,
            TrainerConfig(coordinates=coords, l2=args.l2,
                          max_iters=args.max_iters,
                          min_rows_per_entity=args.min_rows))
    except (ModelLoadError, ValueError) as e:
        logger.error("%s", e)
        return 1
    logger.info("learning on generation %d (version %r), task %s, "
                "coordinates %s", engine.store.generation,
                engine.store.version, engine.store.task.value,
                coords or "auto")

    # photonwatch: identity gauges always; SLO eval / federation pull
    # opt-in
    from photon_ml_tpu.obs.registry import export_build_info

    export_build_info(engine.metrics.registry, role="owner")
    slo_thread = None
    if args.slo:
        from photon_ml_tpu.obs.watch import SLOEngine, SLOEvalThread, load_slos

        try:
            slos = load_slos(args.slo)
        except (OSError, ValueError) as e:
            logger.error("--slo: %s", e)
            return 1
        slo_thread = SLOEvalThread(SLOEngine(slos),
                                   lambda: engine.metrics.registry,
                                   interval_s=args.slo_interval).start()
        logger.info("photonwatch: evaluating %d SLO(s) every %.3fs",
                    len(slos), args.slo_interval)
    metrics_sidecar = None
    if args.metrics_port:
        from photon_ml_tpu.serving.frontend.metrics_http import \
            ThreadedMetricsEndpoint

        metrics_sidecar = ThreadedMetricsEndpoint(
            engine.metrics, port=args.metrics_port).start()
        logger.info("metrics scrape on http://127.0.0.1:%d/metrics "
                    "(+ /watchz)", metrics_sidecar.port)

    repl = None
    if args.repl_listen:
        if delta_log is None:
            logger.error("--repl-listen needs --delta-log (the log is "
                         "what gets replicated)")
            return 1
        import os as _os

        from photon_ml_tpu.online.replication import (ReplicationConfig,
                                                      attach_replication)

        try:
            host, port = _parse_hostport(args.repl_listen)
        except ValueError as e:
            logger.error("%s", e)
            return 1
        token = args.auth_token if args.auth_token is not None \
            else _os.environ.get("PHOTON_AUTH_TOKEN") or None
        repl = attach_replication(
            swapper, ReplicationConfig(host=host, port=port,
                                       auth_token=token),
            registry=engine.metrics.registry)
        logger.info("photonrepl serving the delta log on %s:%d%s", host,
                    repl.port, " (auth required)" if token else "")

    try:
        if args.format == "avro":
            if args.examples == "-":
                logger.error("--format avro needs --examples FILE "
                             "(containers are not streamable from stdin)")
                return 1
            rc = _learn_batches(trainer, _avro_examples(args.examples),
                                sys.stdout, args.batch_size)
        else:
            lines = sys.stdin if args.examples == "-" \
                else open(args.examples)
            try:
                rc = _learn_stream(trainer, lines, sys.stdout,
                                   args.batch_size)
            finally:
                if lines is not sys.stdin:
                    lines.close()
    finally:
        if slo_thread is not None:
            slo_thread.stop()
        if metrics_sidecar is not None:
            metrics_sidecar.stop()
        if repl is not None:
            repl.stop()
        if delta_log is not None:
            delta_log.close()
        if args.metrics_json:
            engine.metrics.export(args.metrics_json)
            logger.info("metrics -> %s", args.metrics_json)
        if args.trace_out:
            from photon_ml_tpu import obs

            obs.get_tracer().export_chrome_trace(args.trace_out)
            logger.info("trace -> %s", args.trace_out)
    return rc


def main() -> None:
    sys.exit(run(sys.argv[1:]))


if __name__ == "__main__":
    main()
