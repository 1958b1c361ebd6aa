"""Multi-process GLMix training driver: every host runs THIS SAME program
under ``jax.distributed`` (reference analog: the Spark cluster executing
GameTrainingDriver — driver loop + executors; here there is no driver
process, SURVEY §5 "Distributed communication backend").

    # on every host h of N (shared filesystem for --output-dir):
    python -m photon_ml_tpu.cli.train_multihost \
        --train-data data.avro --feature-shards g,u --id-tags userId \
        --fixed  "name=fixed,feature.shard=g,reg.weights=0.1" \
        --random "name=user,random.effect.type=userId,feature.shard=u,reg.weights=1" \
        --coordinator-address host0:1234 --num-processes N --process-id h \
        --output-dir out

Layout (parallel/multihost.py): the fixed effect trains on globally
row-sharded data (each host keeps its row range; the one DCN all-reduce),
random effects train on entity-sharded buckets (each host owns the
entities ``process_entity_assignment`` hashes to it, bucketing with GLOBAL
row ids so reservoir decisions are topology-invariant), and
``multihost_glmix_sweep`` runs the residual descent with global score
vectors.  Model output is the reference's executor-partitioned layout:
every host writes its entities as ``part-{pid:05d}.avro`` into the shared
model directory (process 0 adds the fixed effect + metadata); the standard
loader merges the directory.

Multihost v1 contract (see ``multihost_glmix_sweep``): ONE fixed + ONE
random-effect coordinate, dense fixed shard; the random-effect shard may
be dense or sparse (compact observed-column buckets).  Shared-context
normalization (``--normalization``) is supported on dense shards: solves
run transformed, the published models are original-space — the same
semantics as the single-process driver; compact buckets would need
per-lane projected contexts and stay identity-normalized.  Each host
currently scans the full input and keeps its share — a per-host
pre-partitioned read (the reference's partitioned-HDFS layout) drops in
through the same ``row_ids`` contract.
"""

from __future__ import annotations

import argparse
import logging
import sys
from typing import List, Optional

import numpy as np

logger = logging.getLogger("photon_ml_tpu.train_multihost")


def _parse_mesh(spec: str):
    out = {"entity": 1, "feature": 1}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        k, v = part.split("=", 1)
        if k not in out:
            raise ValueError(f"--mesh key {k!r} (multihost meshes take "
                             "entity=/feature=; data fills the rest)")
        out[k] = int(v)
    return out


def run(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="photon-tpu-train-multihost",
        description="Multi-process GLMix training (one fixed + one "
                    "random-effect coordinate) under jax.distributed")
    ap.add_argument("--train-data", nargs="+", required=True)
    ap.add_argument("--feature-shards", required=True)
    ap.add_argument("--id-tags", required=True)
    ap.add_argument("--fixed", required=True,
                    help="fixed-effect coordinate spec (config grammar; "
                         "single reg weight)")
    ap.add_argument("--random", required=True,
                    help="random-effect coordinate spec (config grammar; "
                         "single reg weight)")
    ap.add_argument("--task", default="LOGISTIC_REGRESSION")
    ap.add_argument("--iterations", type=int, default=2)
    ap.add_argument("--coordinator-address", default=None)
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--expected-processes", type=int, default=None)
    ap.add_argument("--mesh", default="entity=1,feature=1",
                    help="entity=E,feature=F axes INSIDE each process "
                         "(ICI); the data axis strides processes (DCN)")
    ap.add_argument("--sparse-threshold", type=int, default=100_000,
                    help="random-effect shards at least this wide read as "
                         "row-sparse and train in compact buckets")
    ap.add_argument("--normalization", default="NONE",
                    choices=["NONE", "SCALE_WITH_MAX_MAGNITUDE",
                             "SCALE_WITH_STANDARD_DEVIATION",
                             "STANDARDIZATION"],
                    help="shared per-shard contexts from training stats; "
                         "solves run transformed, published models are "
                         "original-space (dense shards only)")
    ap.add_argument("--index-map-dir", default=None)
    ap.add_argument("--no-intercept", action="store_true")
    ap.add_argument("--output-dir", required=True)
    ap.add_argument("--checkpoint-dir", default=None,
                    help="per-host npz checkpoint after every iteration; "
                         "rerunning the same command resumes at the cursor "
                         "(requires the same process count and inputs)")
    ap.add_argument("--stop-after-iteration", type=int, default=None,
                    help="exit cleanly right after checkpointing this "
                         "iteration (preemption drills / tests)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.stop_after_iteration is not None and not args.checkpoint_dir:
        raise SystemExit("--stop-after-iteration needs --checkpoint-dir")

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")

    from photon_ml_tpu.cli.config_grammar import parse_coordinate_spec
    from photon_ml_tpu.game.config import FixedEffectConfig, RandomEffectConfig
    from photon_ml_tpu.types import TaskType

    task = TaskType[args.task]
    fixed_spec = parse_coordinate_spec(args.fixed)
    re_spec = parse_coordinate_spec(args.random)
    if not isinstance(fixed_spec.template, FixedEffectConfig):
        raise SystemExit("--fixed must be a fixed-effect coordinate spec")
    if not isinstance(re_spec.template, RandomEffectConfig):
        raise SystemExit("--random must be a random-effect spec "
                         "(random.effect.type=...)")
    if len(fixed_spec.reg_weights) != 1 or len(re_spec.reg_weights) != 1:
        raise SystemExit("multihost training takes ONE reg weight per "
                         "coordinate (grid/tuning runs are the "
                         "single-process driver's job)")
    fixed_cfg = fixed_spec.with_weight(fixed_spec.reg_weights[0])
    re_cfg = re_spec.with_weight(re_spec.reg_weights[0])

    # 1. cluster up FIRST (jax.distributed before any device use)
    import os

    import jax

    from photon_ml_tpu.parallel import multihost as mh

    mh.initialize(coordinator_address=args.coordinator_address,
                  num_processes=args.num_processes,
                  process_id=args.process_id,
                  expected_processes=args.expected_processes)
    pid, nproc = jax.process_index(), jax.process_count()
    axes = _parse_mesh(args.mesh)
    mesh = mh.global_mesh(n_entity=axes["entity"], n_feature=axes["feature"])
    logger.info("process %d/%d, global mesh %s", pid, nproc, dict(mesh.shape))

    # 2. index maps + data (every host scans the same input -> identical
    # maps and EntityIndex numbering, no exchange needed)
    from photon_ml_tpu.data.index_map import build_index_maps_from_avro
    from photon_ml_tpu.data.reader import read_game_data_avro

    shards = [s.strip() for s in args.feature_shards.split(",") if s.strip()]
    id_tags = [t.strip() for t in args.id_tags.split(",") if t.strip()]
    if args.index_map_dir:
        import os

        from photon_ml_tpu.data.index_map import load_index

        index_maps = {}
        for s in shards:
            for name in (f"{s}.idx", f"{s}.phidx"):
                p = os.path.join(args.index_map_dir, name)
                if os.path.exists(p):
                    index_maps[s] = load_index(p)
                    break
            else:
                raise SystemExit(f"no index map for shard {s!r}")
    else:
        index_maps = build_index_maps_from_avro(
            args.train_data, {s: [] for s in shards},
            add_intercept=not args.no_intercept)
    re_shard = re_cfg.feature_shard
    sparse_shards = ({re_shard}
                     if index_maps[re_shard].size >= args.sparse_threshold
                     else set())
    data, entity_indexes = read_game_data_avro(
        args.train_data, index_maps, id_tag_names=id_tags,
        sparse_shards=sparse_shards)
    n = data.num_samples
    logger.info("%d samples; shards %s%s", n,
                {s: index_maps[s].size for s in shards},
                f" (sparse: {sorted(sparse_shards)})" if sparse_shards else "")

    from photon_ml_tpu.game.data import SparseShard

    fixed_x = data.features[fixed_cfg.feature_shard]
    if isinstance(fixed_x, SparseShard):
        raise SystemExit(
            "multihost v1 trains a DENSE fixed shard — raise "
            "--sparse-threshold past its width; note that maps built from "
            "the data are SHARED by every shard (one vocabulary), so a "
            "sparse random-effect shard with a dense fixed shard needs "
            "distinct per-shard maps via --index-map-dir")

    # shared normalization contexts from training stats (every host scans
    # the same data -> identical contexts; same semantics as the
    # single-process driver's prepareNormalizationContext analog)
    from photon_ml_tpu.core.normalization import (NormalizationType,
                                                  build_normalization,
                                                  compute_feature_stats,
                                                  no_normalization)

    norm_kind = NormalizationType[args.normalization]
    norms = {}
    if norm_kind != NormalizationType.NONE:
        if isinstance(data.features[re_cfg.feature_shard], SparseShard):
            raise SystemExit(
                "multihost --normalization needs DENSE shards (compact "
                "buckets would need per-lane projected contexts — the "
                "single-process driver's domain)")
        import jax.numpy as jnp

        for s in {fixed_cfg.feature_shard, re_cfg.feature_shard}:
            stats = compute_feature_stats(
                jnp.asarray(np.asarray(data.features[s])),
                jnp.asarray(data.weight),
                intercept_index=index_maps[s].intercept_index)
            norms[s] = build_normalization(norm_kind, stats)
    fixed_norm = norms.get(fixed_cfg.feature_shard, no_normalization())
    re_norm = norms.get(re_cfg.feature_shard, no_normalization())
    fixed_ii = index_maps[fixed_cfg.feature_shard].intercept_index
    re_ii = index_maps[re_cfg.feature_shard].intercept_index

    # 3. fixed side: this host's row range, padded, assembled globally
    from photon_ml_tpu.core.batch import DenseBatch

    start, stop = mh.process_row_range(n)
    rows_per = mh.padded_per_host_rows(n, mesh)
    blk = mh.pad_local_rows(
        dict(x=np.asarray(fixed_x[start:stop]), y=data.y[start:stop],
             offset=data.offset[start:stop], weight=data.weight[start:stop]),
        rows_per)
    g = mh.global_batch_from_local(blk, mesh)
    fixed_batch = DenseBatch(x=g["x"], y=g["y"], offset=g["offset"],
                             weight=g["weight"])

    # 4. random-effect side: entity-hash ownership, host-local bucketing
    # with GLOBAL row ids
    from photon_ml_tpu.parallel.bucketing import (bucket_by_entity,
                                                  bucket_by_entity_sparse)

    re_type = re_cfg.random_effect_type
    if re_type not in data.id_tags:
        raise SystemExit(f"id tag {re_type!r} not in --id-tags")
    uids = data.id_tags[re_type]
    rid = mh.local_entity_rows(uids, seed=args.seed)
    logger.info("host owns %d rows across its entities", len(rid))
    n_glob = rows_per * nproc
    xu = data.features[re_shard]
    common = dict(active_cap=re_cfg.active_cap,
                  min_active_samples=re_cfg.min_active_samples,
                  seed=args.seed, row_ids=rid, num_samples=n_glob)
    padded_projs = None
    if isinstance(xu, SparseShard):
        if re_cfg.active_cap is not None:
            raise SystemExit(
                "multihost v1: reservoir caps need the passive scoring "
                "path, which doesn't compose with compact buckets — drop "
                "active.data.upper.bound or densify the shard")
        local, projs = bucket_by_entity_sparse(
            uids[rid], xu.indices[rid], xu.values[rid], xu.dim, data.y[rid],
            offset=data.offset[rid], weight=data.weight[rid], **common)
        gb, padded_projs = mh.global_entity_buckets(local, mesh,
                                                    projections=projs)
    else:
        local = bucket_by_entity(
            uids[rid], np.asarray(xu)[rid], data.y[rid],
            offset=data.offset[rid], weight=data.weight[rid], **common)
        gb = mh.global_entity_buckets(local, mesh)
    scoring = None
    if re_cfg.active_cap is not None:
        ls = bucket_by_entity(
            uids[rid], np.asarray(xu)[rid], data.y[rid],
            offset=data.offset[rid], weight=data.weight[rid],
            min_active_samples=re_cfg.min_active_samples,
            seed=args.seed, row_ids=rid, num_samples=n_glob)
        scoring = mh.build_re_scoring(gb, ls, mesh)

    # 5. the sweep (+ per-iteration checkpointing: every host writes ITS
    # lane blocks, process 0 advances the cursor AFTER a barrier — a rerun
    # of the same command resumes at the cursor with recomputed scores)
    import json
    import os

    from jax.experimental import multihost_utils

    from photon_ml_tpu.core.losses import loss_for_task
    from photon_ml_tpu.core.objective import GLMObjective

    initial, start_it = None, 0
    ck = args.checkpoint_dir
    if ck:
        os.makedirs(ck, exist_ok=True)
        cursor_p = os.path.join(ck, "cursor.json")
        host_p = os.path.join(ck, f"host-{pid:05d}.npz")
        if os.path.exists(cursor_p):
            with open(cursor_p) as f:
                cur = json.load(f)
            if cur["num_processes"] != nproc:
                raise SystemExit(
                    f"checkpoint was written by {cur['num_processes']} "
                    f"processes; this run has {nproc} (lane blocks are "
                    "per-host — resume with the same topology)")
            if not os.path.exists(host_p):
                raise SystemExit(
                    f"checkpoint cursor exists but {host_p} is missing — "
                    "every host's npz must be present (lane blocks are "
                    "per-host; copy the whole checkpoint dir)")
            z = np.load(host_p)
            start_it = int(cur["next_iteration"])
            if int(z["iteration"]) != start_it - 1:
                # a preemption between the block write and the cursor
                # commit leaves blocks/cursor from different iterations —
                # resuming would warm-start a state on NO point of the
                # uninterrupted trajectory
                raise SystemExit(
                    f"checkpoint inconsistent: {host_p} holds iteration "
                    f"{int(z['iteration'])} but cursor expects "
                    f"{start_it - 1} — restart from scratch or restore a "
                    "consistent checkpoint dir")
            initial = (z["w_fixed"],
                       [z[f"b{i}"] for i in range(int(z["n_buckets"]))])
            logger.info("resuming at iteration %d from %s", start_it, ck)
        # every host must enter the sweep with the SAME trip count — a
        # stale cursor view (NFS attribute caching, partial mounts) would
        # otherwise deadlock the first collective
        from jax.experimental import multihost_utils as _mhu

        views = np.asarray(_mhu.process_allgather(
            np.asarray([start_it], np.int64)))
        if len(set(views.ravel().tolist())) != 1:
            raise SystemExit(
                f"hosts disagree on the resume iteration ({views.ravel()}) "
                "— the checkpoint dir is not uniformly visible")

    def on_iteration(it, wf, coeffs):
        if not ck:
            return
        blocks = mh.host_lane_blocks(coeffs)
        arrays = {f"b{i}": b for i, b in enumerate(blocks)}
        arrays["w_fixed"] = np.asarray(wf)
        arrays["n_buckets"] = np.asarray(len(blocks))
        arrays["iteration"] = np.asarray(it)
        tmp = host_p + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **arrays)
        os.replace(tmp, host_p)
        multihost_utils.sync_global_devices(f"ckpt blocks {it}")
        if pid == 0:
            tmp = cursor_p + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"next_iteration": it + 1,
                           "num_processes": nproc}, f)
            os.replace(tmp, cursor_p)
        multihost_utils.sync_global_devices(f"ckpt cursor {it}")
        if args.stop_after_iteration is not None \
                and it >= args.stop_after_iteration:
            logger.info("stopping after iteration %d (checkpointed)", it)
            raise SystemExit(0)

    obj_f = GLMObjective(loss=loss_for_task(task), reg=fixed_cfg.reg,
                         norm=fixed_norm)
    obj_re = GLMObjective(loss=loss_for_task(task), reg=re_cfg.reg,
                          norm=re_norm)
    wf, rec, _ = mh.multihost_glmix_sweep(
        mesh, fixed_batch, gb, obj_f, obj_re,
        num_iterations=args.iterations,
        optimizer=fixed_cfg.optimizer, config=fixed_cfg.solver,
        re_scoring=scoring, num_samples=n,
        on_iteration=on_iteration, initial=initial,
        start_iteration=start_it)
    exported = mh.export_local_random_effects(
        rec, gb, mesh, projections=padded_projs,
        norm=None if re_norm.is_identity else re_norm,
        intercept_index=re_ii)
    logger.info("trained: fixed[%d], %d local entities",
                len(np.asarray(wf)), len(exported))

    # 6. executor-partitioned model write (shared --output-dir): every host
    # writes its entities as part-{pid}; process 0 adds fixed + metadata
    from photon_ml_tpu.models.game import FixedEffectModel, RandomEffectModel
    from photon_ml_tpu.models.glm import Coefficients
    from photon_ml_tpu.storage.model_io import (FORMAT_VERSION,
                                                save_coordinate,
                                                save_random_effect_part)

    os.makedirs(args.output_dir, exist_ok=True)
    eids = sorted(exported)
    w_stack = (np.stack([exported[e] for e in eids]) if eids
               else np.zeros((0, index_maps[re_shard].size), np.float32))
    re_model = RandomEffectModel(
        w_stack=w_stack, slot_of={e: i for i, e in enumerate(eids)},
        random_effect_type=re_type, feature_shard=re_shard, task=task)
    re_info = save_random_effect_part(
        re_spec.name, re_model, args.output_dir, index_maps[re_shard],
        entity_indexes.get(re_type), part=pid)
    # metadata.json is the completion signal readers poll for — it must not
    # appear while a peer is still writing its part file
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices("model parts written")
    if pid == 0:
        fixed_model = FixedEffectModel(
            coefficients=Coefficients(means=np.asarray(
                wf if fixed_norm.is_identity
                else fixed_norm.model_to_original_space(wf, fixed_ii))),
            feature_shard=fixed_cfg.feature_shard, task=task)
        fixed_info = save_coordinate(fixed_spec.name, fixed_model,
                                     args.output_dir, index_maps)
        meta = {"version": FORMAT_VERSION, "task": task.value,
                "coordinates": {fixed_spec.name: fixed_info,
                                re_spec.name: re_info}}
        with open(os.path.join(args.output_dir, "metadata.json"), "w") as f:
            json.dump(meta, f, indent=2)
        from photon_ml_tpu.data.native_index import StoreIndexMap

        for s2 in shards:
            ext = (".phidx" if isinstance(index_maps[s2], StoreIndexMap)
                   else ".idx")
            index_maps[s2].save(os.path.join(args.output_dir, f"{s2}{ext}"))
        for tag, eidx in entity_indexes.items():
            eidx.save(os.path.join(args.output_dir,
                                   f"{tag}.entities.json"))
    logger.info("process %d wrote its model part -> %s", pid,
                args.output_dir)
    return 0


def main() -> None:
    sys.exit(run())


if __name__ == "__main__":
    main()
