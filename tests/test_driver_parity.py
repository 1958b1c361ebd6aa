"""The two descent drivers on one problem, over what the shape rules choose.

``FusedSweep`` (one jitted scan program) must reproduce the host-paced
``CoordinateDescent``: same residual semantics, same warm starts across
outer iterations, same final model.  The benchmark's cells sit on ONE
full-sample layout (entity-major) and reach each side of the solver rule
with the logistic loss only, so this file is what holds the other sides:
every loss x the per-entity solver the rule picks x the full-sample layout
the coordinate takes, each on both drivers.

A side is reached the way the rule reaches it, from the data's shape (and,
for the padded-footprint line, by moving the line as
tests/test_parallel.py::test_rescore_layout_span_says_what_engaged does):
nothing here selects a path.
"""

import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.core.regularization import Regularization
from photon_ml_tpu.game.data import SparseShard
from photon_ml_tpu.game import (CoordinateDescent, FixedEffectConfig, GameData,
                                RandomEffectConfig, build_coordinate)
from photon_ml_tpu.game.fused import FusedSweep
from photon_ml_tpu.obs.trace import Tracer, set_tracer
from photon_ml_tpu.opt.types import SolverConfig
from photon_ml_tpu.parallel import bucketing
from photon_ml_tpu.types import TaskType

TASKS = {
    "logistic": TaskType.LOGISTIC_REGRESSION,
    "linear": TaskType.LINEAR_REGRESSION,
    "poisson": TaskType.POISSON_REGRESSION,
    "smoothed_hinge": TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
}
# the per-entity solver is a rule over (capacity x d^2, d, loss): a side is
# (d, rows of a full entity); the capacity class is their next power of two
SIDES = {"soa": (4, 32), "lbfgs": (16, 128)}
N_ENTITIES = 12
LAYOUTS = ("row_major", "transposed", "entity_major", "entity_major_pos",
           "sparse")


def _rows_per_entity(layout, full):
    """Row counts that send ``entity_major_chunk`` where the case wants it.
    The largest entity keeps ``full`` rows (the side's capacity class) in
    every case."""
    if layout == "transposed":
        # nine rows pad to 16 at the shortest chunk: no chunk length keeps
        # the total within 1.3x, whatever the one full entity adds
        return [full] + [9] * (N_ENTITIES - 1) if full > 32 \
            else [17] * N_ENTITIES
    return [full] * N_ENTITIES


def _problem(rng, loss, side, layout):
    d_user, full = SIDES[side]
    counts = _rows_per_entity(layout, full)
    uid = np.repeat(np.arange(N_ENTITIES) * 3 + 11, counts)
    if layout in ("entity_major_pos", "sparse"):
        uid = rng.permutation(uid)  # an entity's rows lie anywhere
    n, d_global = len(uid), 5
    xg = rng.normal(size=(n, d_global))
    xu = rng.normal(size=(n, d_user))
    wg = rng.normal(size=d_global) * 0.5
    wu = rng.normal(size=(N_ENTITIES, d_user)) * 0.5
    margin = xg @ wg + np.einsum("nd,nd->n", xu, wu[(uid - 11) // 3])
    if loss == "linear":
        y = margin + rng.normal(size=n) * 0.3
    elif loss == "poisson":
        y = rng.poisson(np.exp(np.clip(0.3 * margin, -2.0, 2.0))).astype(float)
    else:
        y = (rng.random(n) < 1.0 / (1.0 + np.exp(-margin))).astype(float)
    shard = xu
    if layout == "sparse":
        idx = np.tile(np.arange(d_user, dtype=np.int32), (n, 1))
        shard = SparseShard(indices=idx, values=xu.astype(np.float32),
                            dim=d_user)
    return GameData(y=y, features={"global": xg, "per_user": shard},
                    id_tags={"userId": uid}), xu


def _cases():
    for side in SIDES:
        for loss in TASKS:
            if side == "soa" and loss == "smoothed_hinge":
                continue  # soa_eligible refuses it: the case is lbfgs's
            for layout in LAYOUTS:
                yield pytest.param(loss, side, layout,
                                   id=f"{loss}-{side}-{layout}")


@pytest.mark.parametrize("loss, side, layout", list(_cases()))
def test_fused_sweep_matches_host_descent(rng, monkeypatch, loss, side, layout):
    if layout not in ("row_major", "sparse"):
        monkeypatch.setattr(bucketing, "NARROW_SCORE_PAD_BYTES_MIN", 1)
    data, xu = _problem(rng, loss, side, layout)
    solver = SolverConfig(max_iters=100, tolerance=1e-8)
    configs = {
        "fixed": FixedEffectConfig(feature_shard="global", solver=solver,
                                   reg=Regularization(l2=1.0)),
        "per-user": RandomEffectConfig(random_effect_type="userId",
                                       feature_shard="per_user", solver=solver,
                                       reg=Regularization(l2=1.0)),
    }
    prev = set_tracer(Tracer(capacity=256, enabled=True))
    try:
        coords = {cid: build_coordinate(cid, data, c, TASKS[loss])
                  for cid, c in configs.items()}
        spans = [r["attrs"] for r in obs.get_tracer().records()
                 if r["name"] == "coord.rescore_layout"]
    finally:
        set_tracer(prev)

    # the rules chose what the case is named for
    re = coords["per-user"]
    assert len(spans) == 1 and spans[0]["layout"] == layout.removesuffix("_pos")
    if spans[0]["layout"] == "entity_major":
        assert (spans[0]["back"] == "identity") == (layout == "entity_major")
        assert (re._full["way_back"] is None) == (layout == "entity_major")
    assert re._use_soa == (side == "soa")
    assert max(b.capacity for b in re.buckets.buckets) == SIDES[side][1]

    host_model, _, _ = CoordinateDescent(coords, num_iterations=2).run()
    fused_model, fused_scores = FusedSweep(coords, num_iterations=2).run()

    np.testing.assert_allclose(fused_model["fixed"].coefficients.means,
                               host_model["fixed"].coefficients.means,
                               rtol=2e-3, atol=2e-3)
    re_h, re_f = host_model["per-user"], fused_model["per-user"]
    assert re_h.slot_of == re_f.slot_of
    np.testing.assert_allclose(re_f.w_stack, re_h.w_stack,
                               rtol=2e-3, atol=2e-3)

    # each driver's scores are its own model's, by plain numpy: the layout
    # the rescore went through changes nothing (with the coefficients equal
    # above, the two drivers' scores are then equal too)
    def plain(model):
        slots = np.asarray([model.slot_of[u] for u in data.id_tags["userId"]])
        return np.einsum("nd,nd->n", xu, np.asarray(model.w_stack)[slots])

    np.testing.assert_allclose(fused_scores["per-user"], plain(re_f),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(re.score(re_h)), plain(re_h),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        fused_scores["fixed"],
        data.features["global"] @ fused_model["fixed"].coefficients.means,
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("d, rows, loss, extra, soa", [
    # capacity x d^2 <= 2560, d <= 16, a smooth loss, no per-lane extras
    (4, 32, "logistic", {}, True),
    (4, 128, "logistic", {}, True),        # 128 x 16 = 2048
    (4, 129, "logistic", {}, False),       # class 256: 4096
    (16, 8, "poisson", {}, True),          # 8 x 256 = 2048
    (16, 9, "poisson", {}, False),         # class 16: 4096
    (17, 2, "linear", {}, False),          # past the Cholesky unroll
    (4, 32, "smoothed_hinge", {}, False),  # no second derivative to use
    (4, 32, "logistic", {"reg": Regularization(l1=0.1, l2=1.0)}, False),
    (4, 32, "logistic", {"constraints": ((0, -0.1, 0.1),)}, False),
])
def test_solver_rule_follows_the_worst_bucket(rng, d, rows, loss, extra, soa):
    """``_bind_solver``: SoA Newton where the WORST capacity class keeps
    capacity x d^2 within 2560 (and the rest of the rule holds), the
    vmapped solve otherwise.  One entity of ``rows`` rows among short ones:
    the longest decides for all."""
    counts = [rows] + [2] * 5
    uid = np.repeat(np.arange(len(counts)), counts)
    n = len(uid)
    y = (rng.random(n) < 0.5).astype(float)
    data = GameData(y=y, features={"u": rng.normal(size=(n, d))},
                    id_tags={"userId": uid})
    cfg = RandomEffectConfig(**{"random_effect_type": "userId",
                                "feature_shard": "u",
                                "reg": Regularization(l2=1.0), **extra})
    coord = build_coordinate("user", data, cfg, TASKS[loss])
    assert coord._use_soa == soa


ROW_GATHER = "slice_sizes = array<i64: 1, %d>" % bucketing.EM_ROW


@pytest.mark.parametrize("rows_lie, cap, run_classes, window_lanes", [
    ("by_user", None, 3, 0),   # the control: classes of 64 to 256, all runs
    ("by_user", 128, 2, 1),    # 150 rows capped to a reservoir beside runs
    ("anywhere", None, 0, 4),  # the ids shuffled: rows that lie anywhere,
                               # which at 682 samples is inside 8 x 128
    ("by_user", 32, 0, 8),     # every user over the cap: reservoirs only,
                               # each inside a window of 8 x 32 samples
    ("by_user", 8, 0, 4),      # reservoirs out of 40 to 64 rows, and four
                               # out of over 8 x their capacity
    ("short", None, 0, 0),     # runs, in classes under RUN_CAPACITY_MIN
])
def test_run_rule_reads_the_rows(rng, rows_lie, cap, run_classes,
                                 window_lanes):
    """``bucketing._class_lanes``: a lane is addressed by its start where
    its rows are one run of samples, by its window's start where they lie
    inside ``WINDOW_SPAN_MAX`` x its capacity samples (ISSUE 35), and its
    class holds at least ``RUN_CAPACITY_MIN`` rows; every other lane, and
    with it the program of a coordinate that has none, keeps one index a
    slot."""
    counts = ([1, 2, 1, 2] * 4 if rows_lie == "short"
              else [40, 50, 60, 64, 100, 128, 150, 90])
    uid = np.repeat(np.arange(len(counts)), counts)
    if rows_lie == "anywhere":
        uid = rng.permutation(uid)
    n = len(uid)
    data = GameData(y=(rng.random(n) < 0.5).astype(float),
                    features={"u": rng.normal(size=(n, 4))},
                    id_tags={"userId": uid})
    cfg = RandomEffectConfig(random_effect_type="userId", feature_shard="u",
                             reg=Regularization(l2=1.0), active_cap=cap,
                             solver=SolverConfig(max_iters=5))
    prev = set_tracer(Tracer(capacity=256, enabled=True))
    try:
        coord = build_coordinate("user", data, cfg, TASKS["logistic"])
        (span,) = [r["attrs"] for r in obs.get_tracer().records()
                   if r["name"] == "coord.bucket"]
    finally:
        set_tracer(prev)
    classes = coord.buckets.buckets
    assert sum(b.run_lanes > 0 for b in classes) == run_classes
    assert ["run_start" in dev for dev in coord._dev] == [
        b.run_lanes > 0 for b in classes]
    assert span["run_lanes"] == [b.run_lanes for b in classes]
    assert span["run_slots"] == sum(b.run_lanes * b.capacity
                                    for b in classes)
    # ISSUE 35: the lanes addressed by their window's start, a class's
    # widest window, the passes of its pick; a slot is counted once
    assert span["window_lanes"] == [b.window_lanes for b in classes]
    assert sum(span["window_lanes"]) == window_lanes
    assert ["windows" in dev for dev in coord._dev] == [
        b.window_lanes > 0 for b in classes]
    for b, dev, w, stages in zip(classes, coord._dev, span["window"],
                                 span["pick_stages"]):
        if not b.window_lanes:
            assert (w, stages) == (0, 0)
            continue
        head = slice(b.run_lanes, b.run_lanes + b.window_lanes)
        spans = b.rows[head].max(axis=1) - b.rows[head, 0] + 1
        assert w == max(b.capacity, spans.max())
        assert spans.max() <= bucketing.WINDOW_SPAN_MAX * b.capacity
        assert stages == int((spans - b.counts[head]).max()).bit_length()
        assert dev["windows"].pull.shape == (b.window_lanes, w)
    assert span["window_slots"] == sum(b.window_lanes * b.capacity
                                       for b in classes)
    assert (span["run_slots"] + span["window_slots"] + span["index_slots"]
            == span["slots"])
    if cap == 128:  # the capped user's lane stands behind the runs
        b = classes[-1]
        assert (b.capacity, b.num_lanes, b.run_lanes) == (128, 4, 3)
        assert b.counts[-1] == 128 and np.any(np.diff(b.rows[-1]) > 1)
    sweep = FusedSweep({"user": coord}, num_iterations=1)
    args, _ = sweep._program_args(None, None, 0, None)
    text = sweep._program.lower(*args).as_text()
    assert (ROW_GATHER in text) == (run_classes + window_lanes > 0)
    # either way the lanes are what one index a slot gives
    offsets = rng.normal(size=n)
    gather = coord._offsets_into_lanes(np.asarray(offsets), coord._dev)
    for bi, b in enumerate(classes):
        want = np.where(b.rows >= 0, offsets[np.maximum(b.rows, 0)], 0.0)
        assert np.array_equal(np.asarray(gather(bi)), want)
