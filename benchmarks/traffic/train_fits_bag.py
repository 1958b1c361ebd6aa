"""Traffic kind ``train_fits_bag``: ``train_fits`` over a configuration
whose per-entity effect lies on a ROW-SPARSE feature bag, and the last fit
held to the plain projected reference (``reference/projected_solve.py``).

``train_fits`` builds every random effect over a dense shard under the
IDENTITY projector.  This kind loads a copy of ``train_fits`` of its own
(``train_fits_passive.own_copy``: the catalog's, which other kinds share,
is left as it is), replaces ``build_coordinates`` in the copy so that a
coordinate's ``projector``, ``features_to_samples_ratio`` and
``intercept_index`` reach ``RandomEffectConfig`` and a recipe's
``{"indices", "values", "dim"}`` becomes the program's ``SparseShard``, and
wraps ``newton_parity``, the one call the last fit's outputs are handed to.

The checks it adds, all on the SPARSE coordinates (``"sparse": true``),
outside the window:

**The compact solves** (``solve_tol``): ``parity_entities`` sampled
entities, class by class so that every capacity class is reached.  ``w`` is
the coordinate's own solve (``Coordinate.update``: the same vmapped solve
on the same device buckets as the fit's program unrolls) on the last fit's
scores of the other coordinates, since the fit no longer holds the offsets
it solved on; ``w_ref`` the reference's solve of the entity's own active
rows from the raw pairs (its own observed columns, its own Pearson
ranking, plain Newton, weight count / kept).  Read at quantiles of
``|w - w_ref| / |w_ref|`` over the sample.

**The kept sets** (``zero_off_support``): the rows the FIT published for
those entities are exactly 0 outside the reference's kept columns.  Where
the reference reports a tie (``projected_solve.TIE``) it is handed the
fit's own support and takes it if it is a valid choice; ``tie_share`` says
how many of the sample were ties (``tie_share_max``).

**Every row** (``score_tol``, ``passive_tol``): ``passive_entities`` sampled
CAPPED entities, every row of theirs, passive ones included: the fit's
score against the reference's forward pass over the coefficients the fit
PUBLISHED, in float64 (the sparse rescore alone, exact to float32
rounding), and the coordinate's score under its own solve above against
the forward pass over ``w_ref`` (solve, back-projection and rescore).  Each
is the largest absolute difference over the largest reference score.

``reference_dtype`` (``float32`` in every cell) is the control that sets
the limits: the reference on values rounded to that type.
"""

from __future__ import annotations

import numpy as np

QUANTILES = {"p10": 0.1, "p50": 0.5, "p90": 0.9, "max": 1.0}


def build_coordinates(cfg: dict, data: dict, mesh) -> dict:
    """``train_fits.build_coordinates`` with what a compact coordinate
    needs: the projector fields, and the sparse shard."""
    from photon_ml_tpu.core.regularization import Regularization
    from photon_ml_tpu.game import (FixedEffectConfig, GameData,
                                    RandomEffectConfig)
    from photon_ml_tpu.game.coordinate import build_coordinate
    from photon_ml_tpu.game.data import SparseShard
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.types import ProjectorType, TaskType

    features = {
        name: SparseShard(indices=x["indices"], values=x["values"],
                          dim=int(x["dim"])) if isinstance(x, dict) else x
        for name, x in data["features"].items()}
    gd = GameData(y=data["y"], features=features, id_tags=data["id_tags"])
    reg = Regularization(l2=float(cfg["l2"]))
    coords = {}
    for c in cfg["coordinates"]:
        spec = c.get("solver", cfg["solver"])
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
                projector=ProjectorType[c.get("projector", "IDENTITY")],
                features_to_samples_ratio=c.get("features_to_samples_ratio"),
                intercept_index=c.get("intercept_index"),
                storage_dtype=c.get("storage_dtype"))
        coords[c["id"]] = build_coordinate(
            c["id"], gd, conf, TaskType.LOGISTIC_REGRESSION, mesh)
    return coords


def sample_by_class(buckets, rng, size: int) -> np.ndarray:
    """``size`` entities, each capacity class with its share of them and at
    least one: every class is reached."""
    classes = [b.entity_lanes[b.entity_lanes >= 0] for b in buckets.buckets]
    total = sum(len(c) for c in classes)
    picked = []
    for lanes in classes:
        take = min(len(lanes), max(1, round(size * len(lanes) / total)))
        picked.append(rng.choice(lanes, size=take, replace=False))
    return np.sort(np.concatenate(picked))


def bag_checks(ctx, cfg, data, coords, published, scores_host) -> dict:
    """{coordinate id: what the three checks read} over the sparse
    coordinates of the configuration."""
    ref = ctx.catalog.module("reference", "projected_solve")
    l2 = float(cfg["l2"])
    out = {}
    for j, spec in enumerate(cfg["coordinates"]):
        if not spec.get("sparse"):
            continue
        coord, shard = coords[spec["id"]], data["features"][spec["feature_shard"]]
        dim, ids = int(shard["dim"]), data["id_tags"][spec["entity"]]
        # the values as the reference reads them: as they are, or rounded
        # to the mix's ``reference_dtype`` (the control)
        indices, values = shard["indices"], ctx.catalog.module(
            "traffic", "train_fits_passive").reference_features(
                ctx, shard["values"])
        ratio, pin = spec.get("features_to_samples_ratio"), spec.get(
            "intercept_index")
        counts = np.bincount(ids)
        others = sum(s for k, s in enumerate(scores_host) if k != j)
        own, _ = coord.update(others, seed=0)   # its solve on these offsets
        fit_rows = np.asarray(published[j], np.float32)
        buckets = coord.buckets

        def active_rows(e):
            bi, lane = buckets.lane_of[int(e)]
            rows = buckets.buckets[bi].rows[lane]
            return bi, rows[rows >= 0]

        def solved(e):
            _, act = active_rows(e)
            support = np.flatnonzero(fit_rows[own.slot_of[int(e)]])
            return ref.solve_entity(
                indices[act], values[act], data["y"][act], others[act],
                np.full(len(act), counts[e] / len(act)), dim, l2, ratio, pin,
                program_kept=support)

        rng = np.random.default_rng([ctx.seed, 6, j])
        sample = sample_by_class(buckets, rng,
                                 int(ctx.traffic["parity_entities"]))
        far, ties, off_support, classes = [], 0, 0, set()
        for e in sample:
            got = solved(e)
            slot = own.slot_of[int(e)]
            w = np.asarray(own.w_stack[slot], np.float64)
            far.append(np.linalg.norm(w - got["w"])
                       / max(np.linalg.norm(got["w"]), 1e-30))
            ties += got["tie"]
            outside = np.ones(dim, bool)
            outside[got["kept"]] = False
            off_support += int(np.count_nonzero(fit_rows[slot][outside])
                               + np.count_nonzero(own.w_stack[slot][outside]))
            classes.add(active_rows(e)[0])
        read = {name: float(np.quantile(far, q))
                for name, q in QUANTILES.items()}
        read.update(entities=int(len(sample)), classes_reached=len(classes),
                    classes=len(buckets.buckets),
                    tie_share=ties / len(sample), off_support=off_support)

        cap = spec.get("active_cap")
        capped = (np.flatnonzero(counts > int(cap)) if cap
                  else np.empty(0, np.int64))
        capped = capped[[int(e) in buckets.lane_of for e in capped]]
        if len(capped):
            rng = np.random.default_rng([ctx.seed, 7, j])
            some = rng.choice(capped, size=min(
                int(ctx.traffic["passive_entities"]), len(capped)),
                replace=False)
            own_scores = coord.score(own)
            worst = {"score_err": 0.0, "passive_err": 0.0}
            largest, n_rows, n_active = 0.0, 0, 0
            for e in some:
                rows = np.flatnonzero(ids == e)
                slot = own.slot_of[int(e)]
                by_fit = ref.forward(fit_rows[slot], indices[rows],
                                     values[rows])
                by_ref = ref.forward(solved(e)["w"], indices[rows],
                                     values[rows])
                worst["score_err"] = max(worst["score_err"], float(np.max(
                    np.abs(scores_host[j][rows] - by_fit))))
                worst["passive_err"] = max(worst["passive_err"], float(np.max(
                    np.abs(own_scores[rows] - by_ref))))
                largest = max(largest, float(np.max(np.abs(by_ref))))
                n_rows += len(rows)
                n_active += len(active_rows(e)[1])
            read.update(
                score_err=worst["score_err"] / max(largest, 1e-30),
                passive_err=worst["passive_err"] / max(largest, 1e-30),
                passive_entities=int(len(some)),
                passive_rows=int(n_rows - n_active))
        out[spec["id"]] = read
    return out


def refuse_a_program_that_cannot_hold_it(ctx) -> None:
    """End at once (exit code 2, no result line) where the program keeps a
    sparse shard's pairs ``[n, k]`` on the device over the padded-footprint
    line: there an array of k = 16 takes 128 / 16 times its bytes, 4.86 GB
    each of the two at this configuration's rows, and the parent of the PR
    that brought this cell ran out of device memory in its compile (17.62 GB
    of 15.75: PERF.md section 6, PR 36) after 171 s of set-up.  The same
    verdict, sooner and in words."""
    import sys

    from photon_ml_tpu.parallel import bucketing

    if ctx.dry_run or hasattr(bucketing, "score_samples_sparse_blocks"):
        return
    cfg = ctx.config
    rows = ctx.catalog.module("recipes", cfg["recipe"]).sizes(cfg)["n"]
    for c in cfg["coordinates"]:
        if c.get("sparse") and bucketing.use_transposed_scoring(
                rows, int(c["row_width"]), 4):
            print(f"benchmarks/traffic/train_fits_bag.py: this program keeps "
                  f"the pairs of coordinate {c['id']!r} [{rows}, "
                  f"{c['row_width']}] on the device, padded to 128 lanes: "
                  f"{2 * rows * 128 * 4 / 1e9:.2f} GB beside the rest of the "
                  "configuration; it cannot hold it", file=sys.stderr)
            raise SystemExit(2)


def run(ctx) -> dict:
    refuse_a_program_that_cannot_hold_it(ctx)
    base = ctx.catalog.module("traffic", "train_fits_passive").own_copy(
        ctx.catalog, "traffic", "train_fits")
    newton_parity, seen = base.newton_parity, {}

    def parity_and_more(ctx, cfg, data, coords, published, scores_host):
        args = (ctx, cfg, data, coords, published, scores_host)
        seen["bags"] = bag_checks(*args)
        return newton_parity(*args)

    base.build_coordinates = build_coordinates
    base.newton_parity = parity_and_more
    result = base.run(ctx)
    gates, bags = ctx.workload.get("gates", {}), seen["bags"]
    result["detail"]["bag_checks"] = bags
    result["detail"]["reference_dtype"] = ctx.traffic.get("reference_dtype",
                                                          "float32")
    result["layer_values"]["rows"] = result["detail"]["rows"]
    checks = result["checks"]
    checks["every_class_reached"] = all(
        b["classes_reached"] == b["classes"] for b in bags.values())
    checks["zero_off_support"] = all(b["off_support"] == 0
                                     for b in bags.values())
    # how often the cut is a tie is the data's: at dry-run sizes another
    if gates.get("tie_share_max") is not None and not ctx.dry_run:
        checks["ties_few"] = all(b["tie_share"] <= gates["tie_share_max"]
                                 for b in bags.values())
    if gates.get("solve_tol") is not None:
        # {coordinate id: {quantile's name: limit}}
        checks["solves_precise"] = all(
            cid in bags and bags[cid][name] <= limit
            for cid, limits in gates["solve_tol"].items()
            for name, limit in limits.items())
    if gates.get("passive_tol") is not None:
        checks["passive_rows_scored"] = all(
            "passive_err" in b and b["passive_err"] <= gates["passive_tol"]
            and b["score_err"] <= gates["score_tol"] for b in bags.values())
    return result
