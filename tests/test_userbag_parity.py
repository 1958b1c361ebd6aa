"""A per-user effect on the ITEM's sparse feature bag (ISSUE 36): the fused
fit of a three-coordinate GLMix whose per-user shard is a ``SparseShard``
under the INDEX_MAP projector, held to ``benchmarks/reference/
projected_solve.py`` (no import from ``photon_ml_tpu``): coefficient by
coefficient in every capacity class, with and without the
features-to-samples bound; the kept sets; exact zeros off them; every row's
score, passive rows included; compact = full vocabulary without the bound;
bfloat16 features as the control that has to fail; and the dense
coordinates' programs left as they were.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.core.regularization import Regularization
from photon_ml_tpu.game import (FixedEffectConfig, GameData,
                                RandomEffectConfig)
from photon_ml_tpu.game.coordinate import build_coordinate
from photon_ml_tpu.game.data import SparseShard
from photon_ml_tpu.game.fused import FusedSweep
from photon_ml_tpu.opt.types import SolverConfig
from photon_ml_tpu.parallel import bucketing
from photon_ml_tpu.types import ProjectorType, TaskType

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
DIM, WIDTH, CAP, L2 = 1149, 16, 64, 1.0
TOL = 2e-5        # |w - w_ref| <= TOL (1 + |w_ref|): float64, L-BFGS to 1e-12 of the loss
SCORE_TOL = 1e-5  # of the largest reference score


def _reference():
    spec = importlib.util.spec_from_file_location(
        "bench_projected_solve",
        os.path.join(BENCH, "reference", "projected_solve.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", "projected_solve.py")) as f:
        assert "photon_ml_tpu" not in f.read().split('"""', 2)[2]


def problem(seed=0, users=36, items=12):
    """Rows grouped by user, 3 to 200 a user (classes 4 to 64 under the cap
    of 64, nine users capped), each row a bag of 6 to 15 columns out of
    1,149 with values that vary row by row.  Columns a user holds in one
    row only tie by Pearson score whatever their values (a small user has
    many): the reference is handed the program's kept set there and takes
    it where it is a valid choice."""
    rng = np.random.default_rng(seed)
    counts = np.r_[rng.integers(3, 60, users - 9),
                   rng.integers(70, 200, 9)]
    rng.shuffle(counts)
    uids = np.repeat(np.arange(users), counts)
    n = len(uids)
    iids = rng.integers(0, items, n)
    idx = np.zeros((n, WIDTH), np.int32)
    val = np.zeros((n, WIDTH), np.float32)
    val[:, 0] = 1.0                                   # the intercept
    # a user draws from its own 40 columns, so that columns recur
    own = np.stack([rng.choice(np.arange(1, DIM), 40, replace=False)
                    for _ in range(users)])
    for i in range(n):
        k = rng.integers(5, WIDTH - 1)
        idx[i, 1:1 + k] = np.sort(rng.choice(own[uids[i]], k, replace=False))
        val[i, 1:1 + k] = rng.uniform(0.5, 1.0, k)
    xg = rng.normal(size=(n, 8)).astype(np.float32)
    xi = rng.normal(size=(n, 4)).astype(np.float32)
    wu = np.zeros((users, DIM))
    wu[:, 0] = rng.normal(0, 0.4, users)
    for u in range(users):
        wu[u, own[u][:8]] = rng.normal(0, 1.0, 8)
    logit = (xg @ rng.normal(0, 0.3, 8) + xi[np.arange(n)] @ rng.normal(
        0, 0.2, 4) + np.einsum("nk,nk->n", wu[uids[:, None], idx], val))
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    return dict(y=y, uids=uids, iids=iids, idx=idx, val=val, xg=xg, xi=xi)


def fit(p, ratio, values=None, dtype=np.float64, **per_user):
    """The fused fit, the sparse per-user coordinate updated LAST, so that
    the offsets of its solves are the fit's own final scores of the two
    others.  Returns (its coordinate, its published table, every
    coordinate's scores).  ``per_user``: fields of that coordinate's
    configuration in the place of the INDEX_MAP ones."""
    data = GameData(
        y=p["y"],
        features={"g": p["xg"], "i": p["xi"], "u": SparseShard(
            indices=p["idx"], values=p["val"] if values is None else values,
            dim=DIM)},
        id_tags={"userId": p["uids"], "itemId": p["iids"]})
    solver = SolverConfig(max_iters=200, tolerance=1e-12)
    reg = Regularization(l2=L2)
    confs = {
        "fixed": FixedEffectConfig(feature_shard="g", solver=solver, reg=reg),
        "per-item": RandomEffectConfig(
            random_effect_type="itemId", feature_shard="i", solver=solver,
            reg=reg),
        "per-user": RandomEffectConfig(
            random_effect_type="userId", feature_shard="u", solver=solver,
            reg=reg, active_cap=CAP, **(per_user or dict(
                projector=ProjectorType.INDEX_MAP,
                features_to_samples_ratio=ratio, intercept_index=0)))}
    coords = {cid: build_coordinate(cid, data, conf,
                                    TaskType.LOGISTIC_REGRESSION, dtype=dtype)
              for cid, conf in confs.items()}
    published, scores, _, _ = FusedSweep(coords,
                                         num_iterations=2).run_device()
    return (coords["per-user"], np.asarray(published[2]),
            [np.asarray(s) for s in scores])


def held_to_reference(p, coord, table, scores, ratio, values=None):
    """Every user against the reference's solve of its own active rows on
    the fit's own offsets: {"far": the largest |w - w_ref| / (1 + |w_ref|),
    "score": the largest score difference over the largest score,
    "sets_differ", "off_support", "ties", "classes"}."""
    val = p["val"] if values is None else values
    others = (scores[0] + scores[1]).astype(np.float64)
    out = dict(far=0.0, score=0.0, sets_differ=0, off_support=0, ties=0,
               classes=set(), largest=0.0)
    for e, (bi, lane) in coord.buckets.lane_of.items():
        rows = coord.buckets.buckets[bi].rows[lane]
        act = rows[rows >= 0]
        mine = np.flatnonzero(p["uids"] == e)
        maps = coord._proj.projections[bi].indices[lane]
        theirs = np.sort(maps[maps >= 0])
        got = ref.solve_entity(
            p["idx"][act], val[act], p["y"][act], others[act],
            np.full(len(act), len(mine) / len(act)), DIM, L2, ratio, 0,
            program_kept=theirs)
        w = table[coord._slot_of[e]]
        out["classes"].add(bi)
        out["ties"] += got["tie"]
        out["sets_differ"] += not np.array_equal(theirs, got["kept"])
        outside = np.ones(DIM, bool)
        outside[got["kept"]] = False
        out["off_support"] += int(np.count_nonzero(w[outside]))
        out["far"] = max(out["far"], float(np.max(
            np.abs(w - got["w"]) / (1.0 + np.abs(got["w"])))))
        # every row of the user, the passive ones too
        want = ref.forward(got["w"], p["idx"][mine], val[mine])
        out["score"] = max(out["score"],
                           float(np.max(np.abs(scores[2][mine] - want))))
        out["largest"] = max(out["largest"], float(np.max(np.abs(want))))
    out["score"] /= out["largest"]
    return out


@pytest.fixture(scope="module")
def data():
    return problem()


def _fitted(data, ratio):
    coord, table, scores = fit(data, ratio)
    return ratio, coord, table, scores, held_to_reference(
        data, coord, table, scores, ratio)


@pytest.fixture(scope="module")
def fitted_with_bound(data):
    return _fitted(data, 0.25)


@pytest.fixture(scope="module")
def fitted_without_bound(data):
    return _fitted(data, None)


@pytest.fixture(params=["with_bound", "without_bound"])
def fitted(request):
    return request.getfixturevalue("fitted_" + request.param)


def test_every_capacity_class_holds_a_user(fitted):
    _, coord, _, _, held = fitted
    caps = [b.capacity for b in coord.buckets.buckets]
    assert caps == [4, 8, 16, 32, 64]
    assert held["classes"] == set(range(len(caps)))
    assert coord.buckets.capped_entities == 9
    assert coord.buckets.passive_rows > 0


def test_the_fused_fit_is_the_reference_coefficient_by_coefficient(fitted):
    """(a): in every capacity class, with and without the bound."""
    assert fitted[4]["far"] <= TOL


def test_the_kept_sets_are_the_reference_s(fitted):
    """(b): where the data decide them; where they tie, a valid choice."""
    ratio, coord, _, _, held = fitted
    assert held["sets_differ"] == 0
    assert held["ties"] < len(coord.buckets.lane_of) // 2
    widths = [p.d_proj for p in coord._proj.projections]
    if ratio is None:
        assert held["ties"] == 0 and all(16 <= w <= 64 for w in widths)
    else:  # at most a quarter as many columns as active rows
        assert widths == [1, 2, 4, 8, 16]


def test_unobserved_and_dropped_columns_are_exactly_zero(fitted):
    """(c): in the published table."""
    _, coord, table, _, held = fitted
    assert held["off_support"] == 0
    kept = sum(int(np.count_nonzero(p.indices >= 0))
               for p in coord._proj.projections)
    assert np.count_nonzero(table) == kept
    assert np.all(table[:, 0] != 0.0)            # the intercept always stays


def test_every_row_is_scored_by_the_published_coefficients(fitted, data):
    """(d): passive rows included, against the reference's forward pass
    over its own solve and, bit for bit but float rounding, over the table."""
    _, coord, table, scores, held = fitted
    assert held["score"] <= SCORE_TOL
    slots = np.asarray([coord._slot_of[int(e)] for e in data["uids"]])
    own = np.einsum("nk,nk->n", table[slots[:, None], data["idx"]],
                    data["val"].astype(np.float64))
    np.testing.assert_allclose(scores[2], own, rtol=0, atol=1e-12)


def test_without_the_bound_the_compact_solve_is_the_full_vocabulary_one(
        fitted_without_bound, data):
    """(e): a plain Newton solve over all 1,149 columns of the same rows:
    a column the user never observed has no gradient but the penalty's."""
    _, coord, table, scores, _ = fitted_without_bound
    others = (scores[0] + scores[1]).astype(np.float64)
    for bi, bucket in enumerate(coord.buckets.buckets):
        e = int(bucket.entity_lanes[bucket.entity_lanes >= 0][0])
        rows = bucket.rows[coord.buckets.lane_of[e][1]]
        act = rows[rows >= 0]
        full = ref.newton(
            ref.densify(data["idx"][act], data["val"][act], DIM),
            data["y"][act].astype(np.float64), others[act],
            np.full(len(act), np.sum(data["uids"] == e) / len(act)), L2)
        np.testing.assert_allclose(table[coord._slot_of[e]], full, rtol=0,
                                   atol=TOL)


def test_bfloat16_features_fail_the_stated_tolerance(data):
    """(f): the nearest precision below the configuration's cannot pass."""
    rounded = np.asarray(jnp.asarray(data["val"]).astype(jnp.bfloat16)
                         .astype(jnp.float32))
    assert np.any(rounded != data["val"])
    coord, table, scores = fit(data, 0.25, values=rounded)
    held = held_to_reference(data, coord, table, scores, 0.25)
    assert held["far"] > 10 * TOL or held["score"] > 10 * SCORE_TOL
    # the same program held to the reference on ITS features passes
    own = held_to_reference(data, coord, table, scores, 0.25, values=rounded)
    assert own["far"] <= TOL and own["score"] <= SCORE_TOL


def test_pairs_by_blocks_score_as_row_major_ones(data):
    """Over the padded-footprint line the pairs keep the samples on the
    lanes, by blocks of samples: the same sums, one gather a pair out of
    the block's own rows of the table laid flat; any slots score right."""
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(36, DIM)), jnp.float32)
    n = len(data["uids"])
    grouped = np.where(rng.random(n) < 0.1, -1, data["uids"]).astype(np.int32)
    anywhere = rng.permutation(grouped)
    for slots, blocks in ((grouped, 4), (grouped, 1), (anywhere, 8)):
        rows = bucketing.score_samples_sparse(
            w, jnp.asarray(slots), jnp.asarray(data["idx"]),
            jnp.asarray(data["val"]))
        by_block, first, table_rows = bucketing.block_slots(slots, blocks)
        assert table_rows <= 36 and by_block.shape[0] == blocks
        lanes = bucketing.score_samples_sparse_blocks(
            w, jnp.asarray(by_block), jnp.asarray(first),
            jnp.asarray(bucketing.block_pairs(data["idx"], blocks)),
            jnp.asarray(bucketing.block_pairs(data["val"], blocks)),
            table_rows)
        np.testing.assert_allclose(lanes[:n], rows, rtol=1e-6, atol=1e-6)
        assert np.all(np.asarray(lanes)[:n][slots < 0] == 0.0)
        assert not np.asarray(lanes)[n:].any()
    # grouped rows narrow what a block reads, rows that lie anywhere do not
    assert bucketing.block_slots(grouped, 4)[2] < 20
    assert bucketing.block_slots(anywhere, 4)[2] == 36


def test_the_blocks_follow_the_slots(monkeypatch):
    """The fewest blocks whose samples span at most the table bytes a
    gather should read; one block where halving never narrows."""
    slots = np.repeat(np.arange(1024), 8)
    monkeypatch.setattr(bucketing, "SPARSE_TABLE_BYTES_MAX", 64 * 400)
    assert bucketing.sample_blocks(slots, 400) == 16
    assert bucketing.sample_blocks(slots, 25) == 1
    shuffled = np.random.default_rng(0).permutation(slots)
    assert bucketing.sample_blocks(shuffled, 400) == 1


def own_scores(p, coord, table):
    """Every row's score by the published table, row by row."""
    slots = np.asarray([coord._slot_of.get(int(e), -1) for e in p["uids"]])
    got = np.einsum("nk,nk->n", table[np.maximum(slots, 0)[:, None],
                                      p["idx"]],
                    p["val"].astype(np.float64))
    return np.where(slots >= 0, got, 0.0)


def turned(coord, table):
    """The coordinate's model, and the same model under another slot map."""
    import dataclasses

    model = coord.export_model(table)
    last = len(model.slot_of) - 1
    return model, dataclasses.replace(
        model, w_stack=model.w_stack[::-1].copy(),
        slot_of={e: last - s for e, s in model.slot_of.items()})


def test_a_coordinate_over_the_footprint_line_scores_its_pairs_entity_major(
        data, monkeypatch):
    """Over the line a compact coordinate stores its pairs entity-major and
    scores them from its compact lanes: the fit is the blocked gather's."""
    from photon_ml_tpu.game.coordinate import RandomEffectCoordinate

    monkeypatch.setattr(bucketing, "NARROW_SCORE_PAD_BYTES_MIN", 1 << 16)
    monkeypatch.setattr(bucketing, "SPARSE_TABLE_BYTES_MAX", 12 * DIM * 8)
    coord, table, scores = fit(data, 0.25)
    em = coord._em
    assert coord._pick_columns == 16 and coord._pair_blocks == 0
    assert em is not None and em.back == "unpad"
    rows = em.lanes * em.chunk // bucketing.EM_ROW
    block = bucketing.pick_block(em, 16, 8)
    assert block == -(-rows // 8) * 8       # the layout's rows, one block
    assert coord._full["x_word"].shape == (1, WIDTH, block,
                                           bucketing.EM_ROW)
    assert coord._full["chunk_row"].shape == em.chunk_entity.shape
    # the parent's path on the same data: the pairs by blocks of samples
    monkeypatch.setattr(RandomEffectCoordinate, "_compact_pick_columns",
                        lambda self: 0)
    blocks, table_0, scores_0 = fit(data, 0.25)
    assert blocks._pair_blocks == 4 and blocks._pick_columns == 0
    np.testing.assert_allclose(table, table_0, rtol=0, atol=1e-9)
    for got, want in zip(scores, scores_0):
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-8)
    np.testing.assert_allclose(scores[2], own_scores(data, coord, table),
                               rtol=0, atol=1e-12)
    # a full-width table, under its own slot map or another, off the path
    model, other = turned(coord, table)
    np.testing.assert_allclose(coord.score(model), scores_0[2], rtol=0,
                               atol=1e-8)
    np.testing.assert_allclose(coord.score(other), coord.score(model),
                               rtol=0, atol=1e-12)
    np.testing.assert_allclose(blocks.score(other), coord.score(model),
                               rtol=0, atol=1e-8)


def _few_rows(p, most=4):
    """The problem cut to at most ``most`` rows a user: no chunk length
    pads within ``EM_PAD_MAX``."""
    rank = np.arange(len(p["uids"])) - np.searchsorted(p["uids"], p["uids"])
    keep = rank < most
    return {key: v[keep] for key, v in p.items()}


@pytest.mark.parametrize("case", ["random_projector", "box_fill",
                                  "no_chunk"])
def test_what_cannot_be_picked_keeps_the_blocks(data, monkeypatch, case):
    """A RANDOM projection, a box whose fill publishes off the compact
    columns, rows with no chunk length: the pairs by blocks of samples,
    every row scored by the published table."""
    monkeypatch.setattr(bucketing, "NARROW_SCORE_PAD_BYTES_MIN", 1 << 12)
    p, per_user = data, {}
    if case == "random_projector":
        per_user = dict(projector=ProjectorType.RANDOM, projected_dim=8,
                        intercept_index=0)
    elif case == "box_fill":
        per_user = dict(projector=ProjectorType.INDEX_MAP,
                        features_to_samples_ratio=0.25, intercept_index=0,
                        constraints=((5, 0.1, 1.0),))
    else:
        p = _few_rows(data)
    coord, table, scores = fit(p, 0.25, **per_user)
    assert coord._pick_columns == 0 and coord._em is None
    assert coord._pair_blocks >= 1
    if case == "box_fill":
        assert np.all(table[:, 5] >= 0.1)   # published off the columns
    np.testing.assert_allclose(scores[2], own_scores(p, coord, table),
                               rtol=0, atol=1e-9)


def compact_lanes(p, rng, widths=(1, 2, 4, 8, 16)):
    """Compact lanes as a coordinate lays them side by side: classes of the
    given widths, each entity keeping a random part of its columns, one
    entity with no lane.  Returns (lane_entity, lane_columns, lanes, the
    published table [users, DIM])."""
    users = int(p["uids"].max()) + 1
    width = max(widths)
    lane_entity, lane_columns, lanes = [], [], []
    table = np.zeros((users, DIM), np.float32)
    for e in range(users):
        if e == 7:
            continue
        seen = np.unique(p["idx"][p["uids"] == e])
        d = widths[e % len(widths)]
        kept = rng.choice(seen, min(d, len(seen)), replace=False)
        cols = np.full(width, -1, np.int32)
        cols[:len(kept)] = kept
        w = np.zeros(width, np.float32)
        w[:len(kept)] = rng.normal(size=len(kept))
        table[e, kept] = w[:len(kept)]
        lane_entity.append(e)
        lane_columns.append(cols)
        lanes.append(w)
    order = rng.permutation(len(lanes))        # classes, not entities
    return (np.asarray(lane_entity)[order], np.stack(lane_columns)[order],
            np.stack(lanes)[order], table)


@pytest.mark.parametrize("rows", ["grouped", "shuffled", "whole_chunks"])
def test_pairs_entity_major_score_as_row_major_ones(data, rows):
    """``score_pairs_em`` from compact lanes and ``score_pairs_full`` from
    a full-width table, against ``score_samples_sparse``: pairs of columns
    the entity did not keep, an entity with no model, padding pairs and
    padding slots, every class width, users of one chunk and of many."""
    rng = np.random.default_rng(11)
    p = data
    if rows == "whole_chunks":   # 64 rows a user: the chunks ARE the order
        take = np.concatenate([np.resize(np.flatnonzero(data["uids"] == e),
                                         64) for e in range(36)])
        p = {key: v[take] for key, v in data.items()}
    ids = p["uids"] * 3
    if rows == "shuffled":
        ids = rng.permutation(ids)
    runs = bucketing.entity_runs(ids)
    em = bucketing.entity_major_layout(runs)
    assert em.back == {"grouped": "unpad", "shuffled": "gather",
                       "whole_chunks": "identity"}[rows]
    pp = dict(p, uids=ids // 3)
    lane_entity, lane_columns, lanes, table = compact_lanes(pp, rng)
    words, kept = bucketing.pair_words(pp["idx"], runs, lane_entity,
                                       lane_columns, pp["val"])
    want_kept = sum(int(np.count_nonzero(
        np.isin(pp["idx"][pp["uids"] == e], c[c >= 0])
        & (pp["val"][pp["uids"] == e] != 0)))
        for e, c in zip(lane_entity, lane_columns))
    assert kept == want_kept
    n, k = pp["idx"].shape
    values = bucketing.pair_planes(pp["val"], np.float32)
    np.testing.assert_array_equal(values, np.c_[pp["val"].T, np.zeros(k)])
    way_back = jax.tree.map(jnp.asarray, em.way_back())
    row_of = np.full(len(em.entities), -1, np.int32)
    row_of[lane_entity] = np.arange(len(lane_entity))
    slots = np.where(np.isin(pp["uids"], lane_entity), pp["uids"], -1)
    want = np.asarray(bucketing.score_samples_sparse(
        jnp.asarray(table), jnp.asarray(slots), jnp.asarray(pp["idx"]),
        jnp.asarray(pp["val"])))
    # a full-width table under another slot map
    order = rng.permutation(36)
    slot_of = np.where(np.arange(36) == 7, -1, order).astype(np.int32)
    foreign = np.zeros_like(table)
    foreign[order] = table
    # one block and the rows' tail, or blocks of 8 rows and a padded tail
    for block in (bucketing.pick_block(em, 16, 4), 8):
        x_word = bucketing.entity_major_pairs(em, jnp.asarray(words), block)
        x_val = bucketing.entity_major_pairs(em, jnp.asarray(values), block)
        assert x_word.shape[1:] == (k, block, bucketing.EM_ROW)
        got = np.asarray(jax.jit(bucketing.score_pairs_em)(
            jnp.asarray(lanes), jnp.asarray(em.lane_slots(row_of)),
            x_word, x_val, way_back))[:n]
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        assert np.all(got[slots < 0] == 0.0)
        stream = np.asarray(bucketing.score_pairs_full(
            jnp.asarray(foreign), jnp.asarray(em.lane_slots(slot_of)),
            x_word, x_val, lane_columns.shape[1].bit_length()))
        assert stream.shape == (em.lanes * em.chunk,)
        full = stream if em.pos is None else stream[em.pos]
        np.testing.assert_allclose(full[:n], want, rtol=1e-6, atol=1e-6)


def test_pair_words_by_passes_are_each_pairs_place(data, monkeypatch):
    """The places looked up ``PAIR_ROWS`` samples at a time are each
    pair's place among its entity's compact columns, pair by pair."""
    rng = np.random.default_rng(2)
    runs = bucketing.entity_runs(data["uids"])
    lane_entity, lane_columns = compact_lanes(data, rng)[:2]
    args = (data["idx"], runs, lane_entity, lane_columns, data["val"])
    whole = bucketing.pair_words(*args)
    monkeypatch.setattr(bucketing, "PAIR_ROWS", 37)
    parts = bucketing.pair_words(*args)
    assert parts[1] == whole[1]
    np.testing.assert_array_equal(parts[0], whole[0])
    assert np.all(whole[0][:, -1] == 16)       # padding: column 0, q = D
    assert np.all(whole[0][:, :-1] >> 5 == data["idx"].T)
    lane_of = dict(zip(runs[0][lane_entity].tolist(), lane_columns))
    for i, e in enumerate(data["uids"]):
        cols = lane_of.get(int(e), np.empty(0, np.int32))
        for j, c in enumerate(data["idx"][i]):
            q = np.flatnonzero(cols == c)
            assert whole[0][j, i] & 31 == (q[0] if q.size else 16)


# -- the compaction, all lanes at once, against the loop an entity ----------

def parents_compact_lane(rows, indices, values, y, weight, ratio, pin):
    """The parent's ``_compact_lane``: one entity, a dense block."""
    from photon_ml_tpu.parallel.projection import pearson_top_k

    iv, vv = indices[rows], values[rows]
    nz_r, nz_c = np.nonzero(vv != 0)
    obs = np.unique(iv[nz_r, nz_c]) if nz_r.size else np.empty(0, np.int64)
    x = np.zeros((len(rows), len(obs)), values.dtype)
    if nz_r.size:
        np.add.at(x, (nz_r, np.searchsorted(obs, iv[nz_r, nz_c])),
                  vv[nz_r, nz_c])
    if ratio is not None and obs.size:
        keep_n = max(1, int(np.ceil(ratio * len(rows))))
        if obs.size > keep_n:
            top = pearson_top_k(x, y[rows], weight[rows], obs, keep_n, pin)
            obs, x = obs[top], x[:, top]
    return obs.astype(np.int32), x


@pytest.mark.parametrize("ratio", [None, 0.25, 0.5])
@pytest.mark.parametrize("shuffled", [False, True],
                         ids=["sorted_pairs", "duplicate_and_unsorted_pairs"])
def test_the_compaction_of_all_lanes_is_the_parents_lane_by_lane(
        data, ratio, shuffled):
    idx, val = data["idx"].copy(), data["val"].copy()
    if shuffled:  # pairs in any order, a column twice in a row, zeros inside
        rng = np.random.default_rng(5)
        for i in range(len(idx)):
            order = rng.permutation(WIDTH)
            idx[i], val[i] = idx[i, order], val[i, order]
        idx[::3, 2] = idx[::3, 5]
        val[::7, 4] = 0.0
    ents, projections = bucketing.bucket_by_entity_sparse(
        data["uids"], idx, val, DIM, data["y"], active_cap=CAP,
        features_to_samples_ratio=ratio, intercept_index=0)
    weight = np.ones(len(idx), np.float32)
    observed = cut = 0
    for e, (bi, lane) in ents.lane_of.items():
        bucket, proj = ents.buckets[bi], projections[bi]
        rows = bucket.rows[lane]
        rows = rows[rows >= 0]
        obs, x = parents_compact_lane(rows, idx, val, data["y"], weight,
                                      ratio, 0)
        assert np.array_equal(proj.indices[lane][:len(obs)], obs)
        assert np.all(proj.indices[lane][len(obs):] == -1)
        np.testing.assert_allclose(bucket.x[lane, :len(rows), :len(obs)], x,
                                   rtol=0, atol=1e-6)
        assert not bucket.x[lane, len(rows):].any()
        assert not bucket.x[lane, :, len(obs):].any()
        seen = len(np.unique(idx[rows][val[rows] != 0]))
        observed += seen
        cut += seen > len(obs)
    assert ents.observed_columns == observed
    assert ents.filtered_entities == cut
    assert ents.compact


# -- the spans and the scope --------------------------------------------------

def test_the_spans_say_what_the_compaction_made(data):
    from photon_ml_tpu import obs

    obs.enable_tracing(capacity=1 << 12)
    try:
        coord, _, _ = fit(data, 0.25)
        tracer = obs.get_tracer()
        spans = {r["name"]: r["attrs"] for r in tracer.records()
                 if r["ph"] == "X" and r["attrs"].get("coordinate")
                 == "per-user"}
        tables = tracer.device_tables()
    finally:
        obs.disable_tracing()
    bucket, layout = spans["coord.bucket"], spans["coord.rescore_layout"]
    assert bucket["projector"] == "INDEX_MAP" and bucket["d_full"] == DIM
    assert bucket["row_width"] == WIDTH
    assert bucket["d_proj"] == [1, 2, 4, 8, 16]
    assert bucket["compact_columns"] == sum(
        lanes * d for lanes, d in zip(bucket["lanes"], bucket["d_proj"]))
    assert 0 < bucket["kept_columns"] <= bucket["compact_columns"]
    assert bucket["observed_columns"] > bucket["kept_columns"]
    assert bucket["filtered_entities"] == 36
    assert layout["layout"] == "sparse" and layout["row_width"] == WIDTH
    assert layout["nonzeros"] == int(np.count_nonzero(data["val"]))
    assert layout["blocks"] == 0 and layout["table_rows"] is None
    assert layout["pairs"] == "rows"
    paths = set(tables["jit_program"].values())
    assert any("photon.update.per_user/photon.publish/photon.backproject"
               in p for p in paths)
    assert any("photon.update.per_user/photon.rescore" in p for p in paths)
    assert not any("backproject" in p and "per_item" in p for p in paths)


def _layout_span(data, **per_user):
    from photon_ml_tpu import obs

    obs.enable_tracing(capacity=1 << 12)
    try:
        coord, _, _ = fit(data, 0.25, **per_user)
        spans = {r["name"]: r["attrs"] for r in obs.get_tracer().records()
                 if r["ph"] == "X" and r["attrs"].get("coordinate")
                 == "per-user"}
        paths = set(obs.get_tracer().device_tables()["jit_program"].values())
    finally:
        obs.disable_tracing()
    return coord, spans["coord.rescore_layout"], paths


def test_the_span_says_which_pairs_engaged(data, monkeypatch):
    """``pairs = entity_major`` and its fields on the compact coordinate,
    ``pairs = blocks`` on a RANDOM projection; the layout stays ``sparse``
    and says its ``row_width``, as the benchmark's readers find it."""
    monkeypatch.setattr(bucketing, "NARROW_SCORE_PAD_BYTES_MIN", 1 << 16)
    coord, layout, paths = _layout_span(data)
    em = coord._em
    kept = 0
    for e, (bi, lane) in coord.buckets.lane_of.items():
        cols = coord._proj.projections[bi].indices[lane]
        mine = data["uids"] == e
        kept += int(np.count_nonzero(np.isin(data["idx"][mine], cols[cols >= 0])
                                     & (data["val"][mine] != 0)))
    assert layout == dict(
        coordinate="per-user", layout="sparse", pairs="entity_major",
        chunk=em.chunk, lanes=em.lanes, fill=em.fill, coef_indices=em.lanes,
        back="unpad", stages=coord._full["way_back"].stages,
        slots=em.lanes * em.chunk,
        blocks=0, table_rows=None, row_width=WIDTH,
        nonzeros=int(np.count_nonzero(data["val"])), pick_columns=16,
        kept_pairs=kept)
    assert 0 < kept < layout["nonzeros"]
    # the rescore reads no published table: no back-projection in the loop
    assert any("photon.update.per_user/photon.rescore" in p for p in paths)
    assert not any("photon.update.per_user/photon.publish" in p
                   for p in paths)
    _, layout, _ = _layout_span(data, projector=ProjectorType.RANDOM,
                                projected_dim=8, intercept_index=0)
    assert layout["layout"] == "sparse" and layout["pairs"] == "blocks"
    assert layout["row_width"] == WIDTH and layout["blocks"] >= 1
    assert "pick_columns" not in layout


# -- the dense coordinates' programs stay what they were ----------------------

def parents_trace_publish(self, state, data):
    """The parent's ``RandomEffectCoordinate._trace_publish`` for a
    coordinate with no projection and no normalisation."""
    from photon_ml_tpu.parallel.bucketing import stack_bucket_lanes

    return stack_bucket_lanes(state, self._slot_idx_dev,
                              len(self._sorted_ids))


def parents_score_samples_full(self, w_stack, data):
    """The parent's ``_score_samples_full`` on one device, no sparse
    shard."""
    from photon_ml_tpu.parallel.bucketing import (score_samples,
                                                  score_samples_em,
                                                  score_samples_t)

    if self._em is not None:
        return score_samples_em(w_stack, data["lane_slot"], data["x_em"],
                                data["way_back"])
    score = score_samples_t if self._x_full_is_t else score_samples
    return score(w_stack, data["slots"], data["x_full"])


def parents_trace_score_external(self, published, vdata):
    """The parent's ``RandomEffectCoordinate.trace_score_external``."""
    from photon_ml_tpu.parallel.bucketing import (score_samples,
                                                  score_samples_em,
                                                  score_samples_sparse,
                                                  score_samples_t)

    if "x_em" in vdata:
        return score_samples_em(published, vdata["lane_slot"],
                                vdata["x_em"], vdata["way_back"])
    if "x_t" in vdata:
        return score_samples_t(published, vdata["slots"], vdata["x_t"])
    if "x" in vdata:
        return score_samples(published, vdata["slots"], vdata["x"])
    return score_samples_sparse(published, vdata["slots"],
                                vdata["x_idx"], vdata["x_val"])


@pytest.mark.parametrize("narrow", [False, True],
                         ids=["row_major", "entity_major"])
def test_a_dense_random_effect_traces_as_it_did(data, narrow, monkeypatch):
    """(g): the jaxprs of a dense random effect's update and of its
    held-out scoring (``trace_score_external`` over ``external_data``, the
    validated program's) are the parent's."""
    if narrow:
        monkeypatch.setattr(bucketing, "NARROW_SCORE_PAD_BYTES_MIN", 1 << 16)
    game = GameData(y=data["y"], features={"i": data["xi"]},
                    id_tags={"userId": data["uids"]})
    coord = build_coordinate(
        "per-user", game, RandomEffectConfig(
            random_effect_type="userId", feature_shard="i",
            solver=SolverConfig(max_iters=5, tolerance=1e-5),
            reg=Regularization(l2=L2), active_cap=CAP),
        TaskType.LOGISTIC_REGRESSION)
    assert (coord._em is not None) == narrow
    state = coord.init_sweep_state()
    offsets = jnp.zeros(len(data["y"]), jnp.float32)

    def update(state, offsets):
        return coord.trace_update(state, offsets, data=coord.sweep_data())

    # the held-out path of a validated fit: the rows of every other user
    held = np.flatnonzero(data["uids"] % 2 == 0)
    vdata = coord.external_data(GameData(
        y=data["y"][held], features={"i": data["xi"][held]},
        id_tags={"userId": data["uids"][held]}))
    published = coord.trace_publish(state)

    def heldout(published, vdata):
        return coord.trace_score_external(published, vdata)

    ours = jax.make_jaxpr(update)(state, offsets)
    ours_held = jax.make_jaxpr(heldout)(published, vdata)
    monkeypatch.setattr(type(coord), "_trace_publish", parents_trace_publish)
    monkeypatch.setattr(type(coord), "_score_samples_full",
                        parents_score_samples_full)
    monkeypatch.setattr(type(coord), "trace_score_external",
                        parents_trace_score_external)
    parents = jax.make_jaxpr(update)(state, offsets)
    assert str(ours) == str(parents)
    assert str(ours_held) == str(jax.make_jaxpr(heldout)(published, vdata))
    assert ("x_em" in vdata) == narrow
    assert "backproject" not in str(ours)
