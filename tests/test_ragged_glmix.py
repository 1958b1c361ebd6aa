"""A GLMix with heavy-tailed rows per entity (ISSUE 25): the benchmark
configuration ``glmix_ml20m`` at its dry-run sizes, on the CPU.

(a) the fused sweep against the plain reference
    (benchmarks/reference/glmix_descent.py): every entity's coefficients
    and every row's score, passive rows included;
(b) the capacity-class rule (parallel/bucketing._capacity_classes), which
    this configuration measured and left as it was;
(c) the recipe (benchmarks/recipes/ml20m_counts.py): counts that no seed
    moves, the source's marginals, rows grouped by user;
(d) the four per-layer readers the configuration's cell adds;
(e) the fixed effect's kernels on a design whose rows do not divide into
    their blocks (13,017,636 rows do not): in place, never padded, where
    the design has many blocks; a small one is padded as it always was;
(f) the cell's own comparison: the reference on features rounded to
    bfloat16 comes out as not correct.
"""

from __future__ import annotations

import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
sys.path[:0] = [BENCH, REPO]

import run as harness  # noqa: E402

from photon_ml_tpu import obs  # noqa: E402
from photon_ml_tpu.game.fused import FusedSweep  # noqa: E402
from photon_ml_tpu.obs.trace import Tracer, set_tracer  # noqa: E402
from photon_ml_tpu.parallel import bucketing  # noqa: E402
from photon_ml_tpu.types import TaskType  # noqa: E402

CATALOG = harness.Catalog()
SEEDS = (5, 3000000019)


def config(name="glmix_ml20m", dry_run=True):
    return harness.sized(CATALOG.json("configs", name), dry_run)


def recipe_of(cfg):
    return CATALOG.module("recipes", cfg["recipe"])


@pytest.fixture(scope="module")
def built():
    """{seed: (cfg, data, coordinates, bucket spans)} at the dry-run sizes."""
    out = {}
    # solved as far as float32 goes (the cell's own 1e-5 stops sooner, for
    # steady timing): the comparison with the reference has to tell
    # float32 from bfloat16, and the solver's remainder would hide it
    cfg = dict(config(), solver={"max_iters": 30, "tolerance": 1e-7})
    train_fits = CATALOG.module("traffic", "train_fits")
    for seed in SEEDS:
        data = recipe_of(cfg).make_training(cfg, seed)
        prev = set_tracer(Tracer(capacity=4096, enabled=True))
        try:
            coords = train_fits.build_coordinates(cfg, data, None)
            spans = {r["attrs"]["coordinate"]: r["attrs"]
                     for r in obs.get_tracer().records()
                     if r["name"] == "coord.bucket" and "classes" in r["attrs"]}
        finally:
            set_tracer(prev)
        out[seed] = (cfg, data, coords, spans)
    return out


@pytest.fixture(scope="module")
def fitted(built):
    """The fused sweep's model and scores, and the plain reference's, on the
    first seed's data and the program's own active sets."""
    cfg, data, coords, _ = built[SEEDS[0]]
    sweep = FusedSweep(coords, num_iterations=int(cfg["sweeps"]))
    model, scores = sweep.run()
    active = {}
    for c in cfg["coordinates"][1:]:
        b = coords[c["id"]].buckets
        active[c["id"]] = {
            e: (rows := b.buckets[bi].rows[lane])[rows >= 0]
            for e, (bi, lane) in b.lane_of.items()}
    x = {k: np.asarray(v) for k, v in data["features"].items()}
    descent = CATALOG.module("reference", "glmix_descent")

    def reference(features):
        # the reference states float32 and is not written for conftest's x64
        jax.config.update("jax_enable_x64", False)
        try:
            return descent.descend(data["y"], features, data["id_tags"],
                                   cfg["coordinates"], int(cfg["sweeps"]),
                                   float(cfg["l2"]), active)
        finally:
            jax.config.update("jax_enable_x64", True)

    return cfg, data, coords, model, scores, x, reference, active


# -- (a) the fused sweep against the plain reference --------------------------

# The program's float32 L-BFGS stops at 30 iterations or its plateau, the
# reference is a converged float32 Newton solve: on this seed 5.2e-4 to
# 9.4e-4 of the largest coefficient or score apart, over the three
# coordinates.  The same reference on features rounded to bfloat16 is
# 2.6e-3 to 3.1e-3 away (test_a_bf16_solve_fails): the limit lies between.
COEF_TOL = 1.6e-3
SCORE_TOL = 1.6e-3


def relative(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def stacked(ws: dict, model) -> np.ndarray:
    """The reference's {entity: w} in the published model's slot order."""
    out = np.zeros_like(np.asarray(model.w_stack, np.float32))
    for e, w in ws.items():
        out[model.slot_of[e]] = w
    return out


def test_the_data_has_the_shape_the_issue_asks_for(built):
    cfg, data, coords, spans = built[SEEDS[0]]
    assert list(coords) == ["fixed", "per-user", "per-item"]
    for c in cfg["coordinates"][1:]:
        counts = np.bincount(data["id_tags"][c["entity"]])
        cap = c["active_cap"]
        assert counts.min() == 1 and counts.max() > 2 * cap
        a = spans[c["id"]]
        assert 0 < a["capped_entities"] < len(counts)  # both kinds, one coordinate
        assert a["capped_entities"] == int((counts > cap).sum())
        assert a["passive_rows"] == int(np.maximum(counts - cap, 0).sum()) > 0
        assert a["active_rows"] + a["passive_rows"] == len(data["y"])
        assert a["classes"] == len(a["capacities"]) == len(a["lanes"]) >= 4
        assert a["slots"] == int(np.dot(a["capacities"], a["lanes"]))


@pytest.mark.parametrize("cid", ["fixed", "per-user", "per-item"])
def test_fused_sweep_matches_the_plain_reference(fitted, cid):
    cfg, data, coords, model, scores, x, reference, _ = fitted
    ref_w, ref_scores = reference(x)
    if cid == "fixed":
        got, want = np.asarray(model[cid].coefficients.means), ref_w[cid]
    else:
        m = model[cid]
        assert sorted(m.slot_of) == sorted(ref_w[cid])  # EVERY entity
        got, want = np.asarray(m.w_stack), stacked(ref_w[cid], m)
    assert relative(got, want) <= COEF_TOL
    # every row, the passive ones too
    assert scores[cid].shape == ref_scores[cid].shape == data["y"].shape
    assert relative(scores[cid], ref_scores[cid]) <= SCORE_TOL


def test_passive_rows_are_scored_and_never_trained_on(fitted):
    cfg, data, coords, model, scores, x, reference, active = fitted
    spec = cfg["coordinates"][-1]
    ids = data["id_tags"][spec["entity"]]
    trained = np.zeros(len(ids), bool)
    for rows in active[spec["id"]].values():
        trained[rows] = True
    passive = ~trained
    assert passive.sum() == coords[spec["id"]].buckets.passive_rows
    m = model[spec["id"]]
    slots = np.asarray([m.slot_of[int(e)] for e in ids])
    want = np.einsum("nd,nd->n", x[spec["feature_shard"]],
                     np.asarray(m.w_stack)[slots])
    np.testing.assert_allclose(scores[spec["id"]][passive], want[passive],
                               rtol=1e-4, atol=1e-6)
    assert np.abs(want[passive]).max() > 0.1


def test_a_bf16_solve_fails(fitted):
    """The tolerance tells precisions apart: the same reference computed on
    features rounded to bfloat16, the nearest precision below the
    configuration's float32, is outside it."""
    import ml_dtypes

    cfg, data, coords, model, scores, x, reference, _ = fitted
    rounded = {k: v.astype(ml_dtypes.bfloat16).astype(np.float32)
               for k, v in x.items()}
    low_w, low_scores = reference(rounded)
    for cid in ("per-user", "per-item"):
        m = model[cid]
        assert relative(np.asarray(m.w_stack),
                        stacked(low_w[cid], m)) > COEF_TOL
        assert relative(scores[cid], low_scores[cid]) > SCORE_TOL
    assert relative(np.asarray(model["fixed"].coefficients.means),
                    low_w["fixed"]) > COEF_TOL


def test_solver_iterations_leave_the_program(built):
    cfg, _, coords, spans = built[SEEDS[0]]
    sweep = FusedSweep(coords, num_iterations=int(cfg["sweeps"]))
    assert sweep.solve_iterations is None
    out = sweep.run_device()
    assert len(out) == 4
    its = [np.asarray(a) for a in sweep.solve_iterations]
    solves = [1] + [spans[c]["classes"] for c in ("per-user", "per-item")]
    assert [a.shape for a in its] == [(int(cfg["sweeps"]), s, 4)
                                      for s in solves]
    assert all(a.dtype == np.int32 for a in its)
    for a, cid in zip(its[1:], ("per-user", "per-item")):
        total, most, trials, most_trials = np.moveaxis(a, -1, 0)
        lanes = np.asarray(spans[cid]["lanes"])
        assert (most >= 1).all() and (most <= 30).all()
        assert (total <= most * lanes).all() and (total >= most).all()
        # a trip makes 1 to max_linesearch trials in the lane that makes most
        assert (most_trials >= most).all() and (most_trials <= 25 * most).all()
        assert (trials <= most_trials * lanes).all()
        assert (trials >= total).all()
    assert (its[0][..., 0] == its[0][..., 1]).all()  # one problem
    assert (its[0][..., 2] == its[0][..., 3]).all()
    # the dry run's CPU takes no fused kernel: all three search the margins
    assert [coords[c].line_search for c in ("fixed", "per-user", "per-item")
            ] == ["margins"] * 3
    assert [spans[c]["line_search"] for c in ("per-user", "per-item")
            ] == ["margins"] * 2


def test_a_traced_fit_reports_trials_and_the_line_search(built):
    """The span ``descent.solve_iterations`` of a traced fit: what
    ``solve_lane_waste_share`` reads, unchanged, and beside it the line
    search's trials and how each coordinate's search evaluates one."""
    cfg, _, coords, _ = built[SEEDS[0]]
    sweep = FusedSweep(coords, num_iterations=int(cfg["sweeps"]))
    prev = set_tracer(Tracer(capacity=4096, enabled=True))
    try:
        sweep.run_device()
        (span,) = [r["attrs"] for r in obs.get_tracer().records()
                   if r["name"] == "descent.solve_iterations"]
    finally:
        set_tracer(prev)
    assert list(span) == ["coordinates", "lane_iterations", "trips",
                          "lane_trials", "trial_trips", "line_search"]
    assert span["coordinates"] == ["fixed", "per-user", "per-item"]
    assert span["line_search"] == [coords[c].line_search
                                   for c in span["coordinates"]]
    for k, name in enumerate(["lane_iterations", "trips", "lane_trials",
                              "trial_trips"]):
        assert span[name] == [np.asarray(a)[..., k].tolist()
                              for a in sweep.solve_iterations]


# -- (b) the class rule --------------------------------------------------------

def pow2(counts):
    return np.asarray([max(1, 1 << (int(k) - 1).bit_length()) for k in counts])


def classes_of(counts, cap=None):
    active = np.asarray(counts) if cap is None else np.minimum(counts, cap)
    return bucketing._capacity_classes([range(int(k)) for k in active])


@pytest.mark.parametrize("dry_run", [True, False])
def test_every_class_holds_its_entities_within_the_bound(dry_run):
    """The next power of two of the active count: under a cap of c a
    coordinate gets at most log2(c) + 1 classes, 11 at the full size's
    1,024 (per-item's 1 to 1,024) and 7 at the dry run's 64."""
    cfg = config(dry_run=dry_run)
    found = []
    for counts, c in zip(recipe_of(cfg).row_counts(cfg),
                         cfg["coordinates"][1:]):
        cap = c["active_cap"]
        active = np.minimum(counts, cap)
        got = classes_of(counts, cap)
        assert (got >= active).all() and (got < 2 * active).all()
        assert (got & (got - 1) == 0).all()
        found.append(sorted(set(got.tolist())))
        assert len(found[-1]) <= cap.bit_length() and found[-1][-1] == cap
    if dry_run:
        assert [len(f) for f in found] == [7, 7]
    else:
        assert found == [[32, 64, 128, 256, 512, 1024],
                         [1 << k for k in range(11)]]


@pytest.mark.parametrize("name, dry_run, want", [
    ("glmix_chip", False, [{32}]),
    ("glmix_chip", True, [{32}]),
    ("glmix3_wide", False, [{128}, {256, 512}]),
    ("glmix3_wide", True, [{32}, {128, 256}]),
])
def test_the_existing_coordinates_keep_their_classes(name, dry_run, want):
    cfg = config(name, dry_run)
    users, per_user = int(cfg["users"]), int(cfg["rows_per_user"])
    counts = [np.full(users, per_user)]
    if name == "glmix3_wide":
        counts.append(recipe_of(cfg).item_row_counts(cfg))
    for k, c, classes in zip(counts, cfg["coordinates"][1:], want):
        got = classes_of(k, c.get("active_cap"))
        assert set(got.tolist()) == classes
        assert (got == pow2(np.minimum(k, c.get("active_cap") or k))).all()


# -- (c) the recipe ------------------------------------------------------------

def test_no_seed_moves_the_counts_the_classes_or_the_lanes(built):
    (cfg, a, coords_a, spans_a), (_, b, coords_b, spans_b) = (
        built[s] for s in SEEDS)
    for tag in ("userId", "itemId"):
        assert (np.bincount(a["id_tags"][tag])
                == np.bincount(b["id_tags"][tag])).all()
    assert (a["id_tags"]["userId"] == b["id_tags"]["userId"]).all()
    assert (a["id_tags"]["itemId"] != b["id_tags"]["itemId"]).any()
    assert not np.array_equal(a["y"], b["y"])
    for cid in ("per-user", "per-item"):
        for k in ("classes", "capacities", "lanes", "slots", "active_rows",
                  "capped_entities", "passive_rows"):
            assert spans_a[cid][k] == spans_b[cid][k], (cid, k)
    # one program: the two sweeps' programs have the same arguments' shapes
    shapes = [[(x.shape, x.dtype) for x in jax.tree.leaves(
        FusedSweep(c, num_iterations=2)._program_args(None, None, 0, None)[0])
        if hasattr(x, "shape")] for c in (coords_a, coords_b)]
    assert shapes[0] == shapes[1]


def test_the_population_meets_the_sources_marginals():
    cfg = config(dry_run=False)
    recipe = recipe_of(cfg)
    for key, n in (("user_rows", cfg["source_users"]),
                   ("item_rows", cfg["source_items"])):
        m = cfg[key]
        counts = recipe.lognormal_counts(n, m["min"], m["median"], m["max"],
                                         cfg["source_rows"])
        assert len(counts) == n and counts.sum() == cfg["source_rows"]
        assert counts.min() == m["min"]
        assert np.median(counts) == m["median"]
        assert 0.95 * m["max"] <= counts.max() <= m["max"]
    # the source's means, 144 a user and 748 a movie, follow from its sums
    assert round(cfg["source_rows"] / cfg["source_users"]) == 144
    assert round(cfg["source_rows"] / cfg["source_items"]) == 748


def test_the_cut_keeps_whole_users_and_every_movie():
    cfg = config(dry_run=False)
    per_user, per_item = recipe_of(cfg).row_counts(cfg)
    assert len(per_user) == cfg["users"] == 90112
    assert len(per_item) == cfg["source_items"] == 26744
    assert per_user.sum() == per_item.sum() == 13017636
    assert per_user.min() == 20 and np.median(per_user) == 68
    assert 140 < per_user.mean() < 149
    share = per_user.sum() / cfg["source_rows"]
    assert per_item.min() == 1
    assert abs(per_item.max() - 67001 * share) < 2
    again = recipe_of(cfg).row_counts(dict(cfg, truth_seed=99))[0]
    assert not np.array_equal(again, per_user)  # truth_seed's choice of users


def test_rows_arrive_grouped_by_user_and_a_movies_lie_anywhere(built):
    _, data, coords, _ = built[SEEDS[0]]
    uids, iids = data["id_tags"]["userId"], data["id_tags"]["itemId"]
    assert (np.diff(uids) >= 0).all() and (np.diff(iids) < 0).any()
    assert bucketing.entity_runs(uids)[2] is None  # recognised as grouped
    assert bucketing.entity_runs(iids)[2] is not None


# -- (d) the four readers ------------------------------------------------------

U = "jit(program)/while/body/closed_call/photon.update.per_user/"
I = "jit(program)/while/body/closed_call/photon.update.per_item/"
TABLE = {
    "fusion.1": U + "photon.entity_solve.b0/jit(_vsolve)/while/body/mul",
    "fusion.2": U + "photon.entity_solve.b1/jit(_vsolve)/while/body/mul",
    "fusion.3": I + "photon.entity_solve.b0/jit(_vsolve)/while/body/mul",
    "fusion.4": I + "photon.entity_solve.b1/jit(_vsolve)/while/body/mul",
    "fusion.5": I + "photon.rescore/gather",
}
OPS_SELF = {"fusion.1": [10e9, 60], "fusion.2": [30e9, 60],
            "fusion.3": [5e9, 60], "fusion.4": [15e9, 60],
            "fusion.5": [40e9, 6]}


def readings(profile=True):
    busy = sum(v[0] for v in OPS_SELF.values()) * 1e-9
    return {"profile": {"ops_self": OPS_SELF, "busy_s": busy, "chips": 1,
                        "window_s": busy} if profile else None,
            "spans": [], "measured": {}, "config": {"sweeps": 2},
            "obs_spans": [], "counters": {}}


def reader(name):
    return CATALOG.module("layer_metrics", name)


NEW = ["solve_classes", "solve_slot_fill", "solve_lane_waste_share",
       "tail_solve_busy_share"]


@pytest.fixture
def traced():
    prev = set_tracer(Tracer(capacity=256, enabled=True))
    try:
        yield obs.get_tracer()
    finally:
        set_tracer(prev)


def test_readers_on_a_synthetic_reading(traced):
    traced.record_device_table("jit_program", TABLE)
    # per-user as the program records it since PR 28 (one span, with how
    # the line search evaluates), per-item as its parent did (two spans)
    traced.complete("coord.bucket", 0, 10, coordinate="per-user", classes=2,
                    line_search="margins",
                    capacities=[64, 256], lanes=[10, 4], slots=1664,
                    active_rows=1000, capped_entities=1, passive_rows=7)
    traced.complete("coord.bucket", 0, 10, coordinate="per-item", classes=2,
                    capacities=[16, 1024], lanes=[6, 2], slots=2144,
                    active_rows=904, capped_entities=2, passive_rows=9)
    traced.complete("coord.bucket", 0, 10, coordinate="per-item")  # projection
    for fit in range(2):  # two traced fits of two updates
        since_pr28 = dict(
            lane_trials=[[[9], [7]], [[80, 31], [66, 20]],
                         [[41, 12], [30, 13]]],
            trial_trips=[[[9], [7]], [[22, 9], [19, 7]], [[11, 6], [9, 6]]],
            line_search=["passes", "margins", "margins"]) if fit else {}
        traced.complete(
            "descent.solve_iterations", 0, 10,
            coordinates=["fixed", "per-user", "per-item"],
            lane_iterations=[[[5], [4]], [[50, 20], [40, 16]],
                             [[30, 10], [24, 10]]],
            trips=[[[5], [4]], [[10, 5], [10, 4]], [[6, 5], [6, 5]]],
            **since_pr28)
    r = readings()
    assert reader("solve_classes").read(r) == 4
    assert reader("solve_slot_fill").read(r) == pytest.approx(
        100 * 1904 / 3808)
    needed = 64 * 90 + 256 * 36 + 16 * 54 + 1024 * 20
    run = 64 * 10 * 20 + 256 * 4 * 9 + 16 * 6 * 12 + 1024 * 2 * 10
    assert reader("solve_lane_waste_share").read(r) == pytest.approx(
        100 * (1 - needed / run))
    # capacity >= 256: per_user b1 (30 s) and per_item b1 (15 s) of 100 s
    assert reader("tail_solve_busy_share").read(r) == pytest.approx(45.0)


@pytest.mark.parametrize("name", NEW)
def test_reader_with_nothing_to_read_returns_none(traced, name):
    """A program that records no class attributes and no iteration span
    (the parent of PR 25), and a run with no device trace."""
    traced.complete("coord.bucket", 0, 10, coordinate="per-user")
    traced.record_device_table("jit_program", TABLE)
    assert reader(name).read(readings()) is None
    assert reader(name).read(readings(profile=False)) is None


@pytest.mark.parametrize("name", NEW)
def test_the_new_metrics_are_appended_and_well_formed(name):
    import json

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    entry = CATALOG.json("layer_metrics", name)
    names = [p["name"] for p in m["per_layer"]]
    assert names.index(name) > names.index("soa_newton_busy_share")
    listed = next(p for p in m["per_layer"] if p["name"] == name)
    # the cell that brought them; later cells (glmix_ml25m.train_x4) append
    assert listed["workloads"][0] == "glmix_ml20m.train"
    assert listed["layer"] == entry["layer"] \
        == "per-entity solve (ops/soa_newton.py)"
    cell = next(w for w in m["workloads"] if w["name"] == "glmix_ml20m.train")
    assert m["workloads"].index(cell) == 2 and cell["chips"] == 1
    cfg = next(c for c in m["configs"] if c["name"] == "glmix_ml20m")
    assert m["configs"].index(cfg) == 2 and cfg["reduced"] == ["users"]


# -- (e) a design whose rows do not divide into the kernels' blocks -----------

@pytest.mark.parametrize("rows, block, want", [
    (13017636, 2048, True),   # glmix_ml20m: 6,356 blocks and 548 rows
    (8388608, 2048, False),   # glmix3_wide: the rows divide
    (8388608, 1024, False),   # glmix_chip
    (6144, 4096, False),      # glmix_chip's dry run: one block and a half
    (64 * 2048 + 1, 2048, True),
    (63 * 2048 + 5, 2048, False),
])
def test_only_a_design_of_many_blocks_runs_in_place(rows, block, want):
    from photon_ml_tpu.ops import fused_glm

    assert fused_glm.runs_in_place(rows, block) is want


def test_an_undivisible_design_runs_in_place(monkeypatch):
    import jax.numpy as jnp

    from photon_ml_tpu.core.batch import DenseBatch
    from photon_ml_tpu.core.losses import loss_for_task
    from photon_ml_tpu.ops import fused_glm

    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    d, bn = 128, 128
    many = fused_glm._IN_PLACE_BLOCKS
    n = many * bn + 77
    rng = np.random.default_rng(0)
    batch = DenseBatch(
        x=jnp.asarray(rng.standard_normal((n, d)), jnp.float32),
        y=jnp.asarray(rng.random(n) < 0.5, jnp.float32),
        offset=jnp.asarray(0.1 * rng.standard_normal(n), jnp.float32),
        weight=jnp.asarray(rng.random(n), jnp.float32))
    w, v = (jnp.asarray(0.1 * rng.standard_normal(d), jnp.float32)
            for _ in range(2))
    run, blocks, rest = fused_glm._blocks_in_place(batch, bn)
    assert run.x is batch.x and blocks == many and rest.num_examples == 77
    padded = fused_glm._pad_rows(batch, bn)
    assert fused_glm._blocks_in_place(padded, bn)[1:] == (many + 1, None)
    # a design of a few blocks is padded, as it always was: the programs of
    # the cells that have such designs (glmix_chip's dry run: 6,144 rows,
    # one block of 4,096 and a remainder) stay as they were
    few_blocks = fused_glm._blocks_in_place(DenseBatch(
        x=batch.x[:bn + 77], y=batch.y[:bn + 77],
        offset=batch.offset[:bn + 77], weight=batch.weight[:bn + 77]), bn)
    assert few_blocks[0].num_examples == 2 * bn and few_blocks[1:] == (2, None)
    small = fused_glm._blocks_in_place(rest, bn)  # one block: padded, cheap
    assert small[0].num_examples == bn and small[1:] == (1, None)
    for fn, args in ((fused_glm.fused_value_and_grad, (w,)),
                     (fused_glm.fused_hvp, (w, v))):
        got = fn(loss, *args, batch, block_rows=bn, interpret=True)
        want = fn(loss, *args, padded, block_rows=bn, interpret=True)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-4)
    # the coordinate leaves such a design as it is (kernels eligible: a TPU)
    from photon_ml_tpu.game.coordinate import build_coordinate
    from photon_ml_tpu.game import FixedEffectConfig, GameData

    monkeypatch.setattr(fused_glm, "has_tpu", lambda: True)
    n = many * 2048 + 100
    data = GameData(y=np.zeros(n, np.float32),
                    features={"g": jnp.zeros((n, d), jnp.float32)},
                    id_tags={})
    coord = build_coordinate("fixed", data, FixedEffectConfig(
        feature_shard="g"), TaskType.LOGISTIC_REGRESSION)
    assert coord._padded_n == n and coord._batch.x.shape == (n, d)
    few = GameData(y=np.zeros(100, np.float32),
                   features={"g": jnp.zeros((100, d), jnp.float32)},
                   id_tags={})
    assert build_coordinate("fixed", few, FixedEffectConfig(
        feature_shard="g"), TaskType.LOGISTIC_REGRESSION)._padded_n == 128
    some = GameData(y=np.zeros(2 * 2048 + 100, np.float32),
                    features={"g": jnp.zeros((2 * 2048 + 100, d),
                                             jnp.float32)}, id_tags={})
    assert build_coordinate("fixed", some, FixedEffectConfig(
        feature_shard="g"), TaskType.LOGISTIC_REGRESSION)._padded_n == 3 * 2048



# -- (f) the comparison that decides ``correct`` -------------------------------

def _dry_run_line(tmp_path, reference_dtype):
    """The result line of a CPU dry run of the cell, its mix's
    ``reference_dtype`` set through a manifest with one more path."""
    import json
    import subprocess

    more = tmp_path / "more"
    (more / "workloads").mkdir(parents=True)
    cell = dict(CATALOG.json("workloads", "glmix_ml20m.train"))
    cell["name"] = "glmix_ml20m.control"
    cell["traffic_params"] = dict(cell["traffic_params"],
                                  reference_dtype=reference_dtype)
    (more / "workloads" / "glmix_ml20m.control.json").write_text(
        json.dumps(cell))
    manifest = dict(CATALOG.manifest, paths=[BENCH, str(more)])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         str(tmp_path / "BENCHMARK.json"), "--workload",
         "glmix_ml20m.control", "--seed", "3000000021", "--seconds", "1",
         "--dry-run"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=600,
        env={**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla")})
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_the_reference_in_bfloat16_is_not_correct(tmp_path):
    """The nearest precision below the configuration's float32, through
    the cell's own comparison and gates: the solves' precision and the
    scores fail; the largest coefficient difference (``newton_tol``), which
    reads the solver's remainder, cannot tell."""
    sound = _dry_run_line(tmp_path / "f32", "float32")
    assert sound["correct"], sound["checks"]
    low = _dry_run_line(tmp_path / "bf16", "bfloat16")
    assert not low["correct"]
    failed = {k for k, v in low["checks"].items() if not v}
    assert failed == {"solves_precise", "passive_rows_scored"}
    for cid in ("per-user", "per-item"):
        a = sound["detail"]["solve_precision"][cid]
        b = low["detail"]["solve_precision"][cid]
        assert a["entities"] == b["entities"] == 64
        assert 5 * a["p10"] < b["p10"]
        assert a["max"] > 0.5 * b["p10"]  # the remainder, as large as the loss


def test_the_kind_wraps_a_copy_of_train_fits_of_its_own():
    kind = CATALOG.module("traffic", "train_fits_passive")
    shared = CATALOG.module("traffic", "train_fits")
    copy = kind.own_copy(CATALOG, "traffic", "train_fits")
    assert copy is not shared and copy.__file__ == shared.__file__
    copy.newton_parity = None
    assert callable(shared.newton_parity)
    assert CATALOG.module("traffic", "train_fits") is shared
