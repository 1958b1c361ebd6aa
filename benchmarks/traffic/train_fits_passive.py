"""Traffic kind ``train_fits_passive``: ``train_fits``, and the last fit held
to the plain reference where ``train_fits`` cannot see.

``train_fits`` holds the published coefficients of sampled entities of the
coordinate updated last to the Newton reference by the LARGEST difference
of the sample (``newton_tol``): what the program's solver leaves undone in
its worst entity, which is as large as what a precision lost would add
everywhere.  This kind runs ``train_fits`` as it stands (found through the
catalog) and adds two checks to the last fit.

**The solves' precision** (``solve_precision``, gate ``solve_tol``), for
EVERY random-effect coordinate, on a seeded sample of ``parity_entities``
of its entities: the relative distance ``|w - w_ref| / |w_ref|`` of each
entity's coefficients from the reference's solve of the entity's own
active rows (``reference/glmix_descent.solve_entities``; weight count /
kept), read at quantiles of the sample.  The solver's remainder is large
in few entities and next to nothing in most; a precision lost (a solve or
buckets in bfloat16) moves every entity, so a low quantile tells the two
apart where the largest difference cannot.  For the coordinate updated
last, ``w`` is what the fit published and the offsets are the fit's other
scores.  A coordinate updated earlier was solved on offsets the fit no
longer holds: ``w`` is the coordinate's own solve (``Coordinate.update``:
the same vmapped solve on the same device buckets as the fit's program
unrolls) on the last fit's scores of the other coordinates, outside the
window.

**The passive rows** (``passive_rows_check``, gates ``passive_tol`` and
``score_tol``), on a seeded sample of the CAPPED entities of the
coordinate updated last: the program's score of EVERY row of the entity,
active and passive, against ``x . w_ref`` (the whole path) and against
``x . w_published`` in float64 on the host (the scoring alone, exact to
float32 rounding).  Each is the largest absolute difference over the
largest reference score of the sample.

``reference_dtype`` (a parameter of the mix, ``float32`` in every cell) is
the control that sets the limits: the reference computed on features
rounded to that type, the nearest precision below the configuration's,
has to come out as not ``correct``.

The last fit's outputs exist inside ``train_fits.run`` only, and the one
call it hands them to is ``newton_parity``.  This kind loads a copy of
``train_fits`` of its own (the catalog's, which other kinds share, is left
as it is) and wraps that name in the copy.
"""

from __future__ import annotations

import importlib.util

import numpy as np

QUANTILES = {"p10": 0.1, "p50": 0.5, "p90": 0.9, "max": 1.0}


def reference_features(ctx, x: np.ndarray) -> np.ndarray:
    """The features as the reference reads them: as they are, or rounded
    to the mix's ``reference_dtype`` (the control)."""
    dtype = ctx.traffic.get("reference_dtype", "float32")
    if dtype == "float32":
        return x
    import jax.numpy as jnp

    return np.asarray(jnp.asarray(x).astype(dtype).astype(jnp.float32))


def sampled_problems(buckets, ids: np.ndarray, sample, entity_rows) -> tuple:
    """({entity: all its rows}, {entity: its active rows}) of the sampled
    entities.  The active rows are the PROBLEM, not its answer: read from
    the program's buckets (its reservoir draw under an active cap)."""
    theirs = np.flatnonzero(np.isin(ids, sample))
    rows_of = {e: theirs[r] for e, r in entity_rows(ids[theirs]).items()}
    active_of = {}
    for e in rows_of:
        bi, lane = buckets.lane_of[e]
        rows = buckets.buckets[bi].rows[lane]
        active_of[e] = rows[rows >= 0]
    return rows_of, active_of


def solve_precision(ctx, cfg, data, coords, published, scores_host) -> dict:
    """{coordinate id: {"p10", "p50", "p90", "max", "entities"}}: quantiles
    over a seeded sample of the coordinate's entities of the relative
    distance of the program's coefficients from the reference's."""
    descent = ctx.catalog.module("reference", "glmix_descent")
    solve = ctx.catalog.module("reference", "newton_solve").solve
    last = len(cfg["coordinates"]) - 1
    out = {}
    for j, spec in enumerate(cfg["coordinates"]):
        if spec["kind"] != "random":
            continue
        coord = coords[spec["id"]]
        others = sum(s for k, s in enumerate(scores_host) if k != j)
        if j == last:
            model = coord.export_model(np.asarray(published[j], np.float32))
        else:
            model, _ = coord.update(others, seed=0)
        kept = np.asarray(sorted(model.slot_of), np.int64)
        rng = np.random.default_rng([ctx.seed, 5, j])
        sample = rng.choice(kept, size=min(int(ctx.traffic["parity_entities"]),
                                           len(kept)), replace=False)
        rows_of, active_of = sampled_problems(
            coord.buckets, data["id_tags"][spec["entity"]], sample,
            descent.entity_rows)
        x = reference_features(ctx, data["features"][spec["feature_shard"]])
        w_ref = descent.solve_entities(solve, x, data["y"], others, rows_of,
                                       active_of, float(cfg["l2"]))
        entities = sorted(rows_of)
        ref = np.stack([w_ref[e] for e in entities]).astype(np.float64)
        got = model.w_stack[[model.slot_of[e] for e in entities]]
        far = (np.linalg.norm(got - ref, axis=1)
               / np.maximum(np.linalg.norm(ref, axis=1), 1e-30))
        out[spec["id"]] = {name: float(np.quantile(far, q))
                           for name, q in QUANTILES.items()}
        out[spec["id"]]["entities"] = len(entities)
    return out


def passive_rows_check(ctx, cfg, data, coords, published, scores_host) -> dict:
    """{"err", "score_err", "entities", "passive_rows"} over a seeded sample
    of the capped entities of the last coordinate; {} where it has none."""
    spec = cfg["coordinates"][-1]
    last = len(cfg["coordinates"]) - 1
    if spec["kind"] != "random" or not spec.get("active_cap"):
        return {}
    descent = ctx.catalog.module("reference", "glmix_descent")
    solve = ctx.catalog.module("reference", "newton_solve").solve
    coord = coords[spec["id"]]
    buckets = coord.buckets
    ids = data["id_tags"][spec["entity"]]
    counts = np.bincount(ids)
    capped = np.flatnonzero(counts > int(spec["active_cap"]))
    capped = capped[[int(e) in buckets.lane_of for e in capped]]
    if not len(capped):
        return {}
    rng = np.random.default_rng([ctx.seed, 4])
    sample = rng.choice(capped, size=min(int(ctx.traffic["passive_entities"]),
                                         len(capped)), replace=False)
    rows_of, active_of = sampled_problems(buckets, ids, sample,
                                          descent.entity_rows)
    x = reference_features(ctx, data["features"][spec["feature_shard"]])
    others = sum(s for j, s in enumerate(scores_host) if j != last)
    w_ref = descent.solve_entities(solve, x, data["y"], others, rows_of,
                                   active_of, float(cfg["l2"]))
    model = coord.export_model(np.asarray(published[last], np.float32))
    worst = {"err": 0.0, "score_err": 0.0}
    largest = 0.0
    for e, rows in rows_of.items():
        got = scores_host[last][rows]
        ref = x[rows] @ w_ref[e]
        own = x[rows].astype(np.float64) @ model.w_stack[model.slot_of[e]]
        worst["err"] = max(worst["err"], float(np.max(np.abs(got - ref))))
        worst["score_err"] = max(worst["score_err"],
                                 float(np.max(np.abs(got - own))))
        largest = max(largest, float(np.max(np.abs(ref))))
    n_rows = sum(len(r) for r in rows_of.values())
    return {"err": worst["err"] / max(largest, 1e-30),
            "score_err": worst["score_err"] / max(largest, 1e-30),
            "entities": int(len(sample)),
            "passive_rows": int(n_rows - sum(len(a) for a in
                                             active_of.values()))}


def own_copy(catalog, group: str, name: str):
    """A module object of this run's own from the catalog's file: what is
    set on it, no other user of the catalog sees."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{group}_{name}_copy", catalog.find(group, name, ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run(ctx) -> dict:
    base = own_copy(ctx.catalog, "traffic", "train_fits")
    newton_parity, seen = base.newton_parity, {}

    def parity_and_more(ctx, cfg, data, coords, published, scores_host):
        args = (ctx, cfg, data, coords, published, scores_host)
        seen["passive"] = passive_rows_check(*args)
        seen["solves"] = solve_precision(*args)
        return newton_parity(*args)

    base.newton_parity = parity_and_more
    result = base.run(ctx)
    gates = ctx.workload.get("gates", {})
    passive, solves = seen["passive"], seen["solves"]
    result["detail"]["solve_precision"] = solves
    result["detail"]["reference_dtype"] = ctx.traffic.get("reference_dtype",
                                                          "float32")
    if passive:
        result["detail"].update(
            passive_rows_err=passive["err"],
            passive_score_err=passive["score_err"],
            passive_entities=passive["entities"],
            passive_rows_checked=passive["passive_rows"])
    if gates.get("passive_tol") is not None:
        result["checks"]["passive_rows_scored"] = bool(
            passive and passive["err"] <= gates["passive_tol"]
            and passive["score_err"] <= gates["score_tol"])
    if gates.get("solve_tol") is not None:
        # {coordinate id: {quantile's name: limit}}
        result["checks"]["solves_precise"] = all(
            cid in solves and solves[cid][name] <= limit
            for cid, limits in gates["solve_tol"].items()
            for name, limit in limits.items())
    return result
