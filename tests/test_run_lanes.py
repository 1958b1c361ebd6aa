"""Lanes addressed by the start of their run of samples (ISSUE 31):
``bucketing._class_lanes`` (the rule and the lane order, read off the rows)
and ``bucketing.offsets_into_lanes`` (the gather by rows of 128 and a
shift), against one index a slot, BITWISE: the values are copies.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.parallel import bucketing
from photon_ml_tpu.parallel.bucketing import (EM_ROW, bucket_by_entity,
                                              bucket_by_entity_sparse,
                                              offsets_into_lanes,
                                              stack_bucket_lanes)

CAPACITIES = (8, 32, 64, 128, 256, 1024)


def ragged_runs(rng, n, capacity):
    """(start, count) of lanes that together meet every case the shift
    and the clamp have: every residue of the start mod 128, a run that ends
    at the vector's last sample, full lanes and ``k < capacity``."""
    full = min(capacity, n)
    count = np.minimum(rng.integers(1, capacity + 1, 3 * EM_ROW), n)
    count[:4] = full
    start = rng.integers(0, n - count + 1)
    start[0] = n - full                              # ends at the last sample
    start[1] = n - count[1]
    start[2] = 0
    residues = slice(EM_ROW, 2 * EM_ROW)             # every residue mod 128
    start[residues] = np.arange(EM_ROW) + EM_ROW * rng.integers(
        0, (n - capacity) // EM_ROW, EM_ROW)
    assert set(start % EM_ROW) == set(range(EM_ROW))
    return start.astype(np.int32), count


def slots_of(start, count, capacity):
    slot = np.arange(capacity)[None, :]
    valid = slot < count[:, None]
    return np.where(valid, start[:, None] + slot, 0).astype(np.int32), valid


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n", [5000, 40 * EM_ROW])
@pytest.mark.parametrize("capacity", CAPACITIES)
def test_lanes_by_run_are_bitwise_one_index_a_slot(capacity, n, dtype):
    rng = np.random.default_rng(capacity + n)
    offsets = jnp.asarray(rng.standard_normal(n), dtype)
    start, count = ragged_runs(rng, n, capacity)
    rows, valid = slots_of(start, count, capacity)
    want = np.asarray(jnp.where(valid, offsets[rows], 0.0))
    assert np.count_nonzero(want) > valid.sum() // 2
    runs = len(start) - 40  # the class's last 40 lanes keep their indices
    for lanes in (runs, len(start)):
        got = jax.jit(offsets_into_lanes)(
            offsets, jnp.asarray(rows[lanes:]), jnp.asarray(valid),
            jnp.asarray(start[:lanes]))
        assert got.dtype == offsets.dtype and got.shape == want.shape
        assert np.array_equal(np.asarray(got), want)
    # no run lane: the parent's one expression
    plain = offsets_into_lanes(offsets, jnp.asarray(rows), jnp.asarray(valid))
    assert np.array_equal(np.asarray(plain), want)


def problem(rng, by_user=True):
    """Entities of 1 to 150 rows, six of them far over the cap of 64; rows
    by entity, or anywhere."""
    counts = np.r_[rng.integers(1, 151, 90), [400, 300, 200, 180, 170, 160]]
    ids = np.repeat(rng.permutation(len(counts)), counts)
    if not by_user:
        ids = rng.permutation(ids)
    n = len(ids)
    return ids, rng.normal(size=(n, 3)), (rng.random(n) < 0.5).astype(float)


def lane_runs(b):
    """[lanes] bool: the rows a lane stores are one consecutive run."""
    k = b.counts
    last = b.rows[np.arange(b.num_lanes), np.maximum(k - 1, 0)]
    return (k > 0) & (last - b.rows[:, 0] == k - 1)


@pytest.mark.parametrize("lane_multiple", [1, 4])
@pytest.mark.parametrize("sparse", [False, True])
def test_run_lanes_lead_every_share_of_a_mixed_class(monkeypatch, sparse,
                                                     lane_multiple):
    rng = np.random.default_rng(3)
    ids, x, y = problem(rng)

    def bucket():
        if not sparse:
            return bucket_by_entity(ids, x, y, active_cap=64,
                                    lane_multiple=lane_multiple,
                                    dtype=np.float64)
        indices = np.tile(np.arange(3), (len(ids), 1))
        return bucket_by_entity_sparse(ids, indices, x, 3, y, active_cap=64,
                                       lane_multiple=lane_multiple,
                                       dtype=np.float64)[0]

    ours, floor = bucket(), bucketing.RUN_CAPACITY_MIN
    monkeypatch.setattr(bucketing, "RUN_CAPACITY_MIN", 1 << 30)
    parents = bucket()  # the order the entities came in, padding last
    assert ours.lane_of.keys() == parents.lane_of.keys()
    assert [b.capacity for b in ours.buckets] == [
        b.capacity for b in parents.buckets]
    seen_mixed = False
    for b, p in zip(ours.buckets, parents.buckets):
        assert p.run_lanes == 0 and b.num_lanes == p.num_lanes
        assert b.num_lanes % lane_multiple == 0
        runs = lane_runs(b).reshape(lane_multiple, -1)
        if b.capacity < floor:
            assert b.run_lanes == 0
            assert np.array_equal(b.entity_lanes, p.entity_lanes)
            continue
        # every share: run lanes first, the same number in each; a run
        # that did not fill a round of the deal keeps its indices
        assert runs[:, :b.run_lanes].all()
        assert 0 <= runs[:, b.run_lanes:].sum() < lane_multiple
        assert b.run_lanes == lane_runs(p).sum() // lane_multiple
        seen_mixed |= b.capacity == 64 and 0 < b.run_lanes < runs.shape[1]
        # a lane is its entity's, whatever lane that is
        for lane, e in enumerate(b.entity_lanes):
            if e < 0:
                assert b.counts[lane] == 0 and not b.weight[lane].any()
                continue
            assert ours.lane_of[int(e)][1] == lane
            _, q = parents.lane_of[int(e)]
            for f in ("x", "y", "offset", "weight", "rows", "counts"):
                assert np.array_equal(getattr(b, f)[lane], getattr(p, f)[q])
    assert seen_mixed  # capped reservoirs beside runs in the class of 64
    # published: the same table from either order
    tables = []
    for eb in (ours, parents):
        ids_sorted = sorted(eb.lane_of)
        slot_of = {e: i for i, e in enumerate(ids_sorted)}
        lane_ws, slot_idx = [], []
        for b in eb.buckets:
            e = np.asarray(b.entity_lanes)
            lane_ws.append(jnp.asarray(np.where(
                e[:, None] >= 0, e[:, None] * 10.0 + np.arange(3), -1.0)))
            slot_idx.append(jnp.asarray(np.asarray(
                [slot_of.get(int(i), len(ids_sorted)) for i in e], np.int32)))
        tables.append(np.asarray(stack_bucket_lanes(lane_ws, slot_idx,
                                                    len(ids_sorted))))
    assert np.array_equal(tables[0], tables[1])
    assert np.array_equal(tables[0][:, 0], 10.0 * np.asarray(ids_sorted))


def test_rows_that_lie_anywhere_give_no_run_lane():
    rng = np.random.default_rng(4)
    ids, x, y = problem(rng, by_user=False)
    eb = bucket_by_entity(ids, x, y, active_cap=64)
    assert [b.run_lanes for b in eb.buckets] == [0] * len(eb.buckets)
    # global row ids decide, where a host holds a part of the rows: the
    # same local runs under ids that step by two are no runs
    ids, x, y = problem(rng)
    eb = bucket_by_entity(ids, x, y, active_cap=64,
                          row_ids=2 * np.arange(len(ids)),
                          num_samples=2 * len(ids))
    assert [b.run_lanes for b in eb.buckets] == [0] * len(eb.buckets)
    eb = bucket_by_entity(ids, x, y, active_cap=64,
                          row_ids=7 + np.arange(len(ids)),
                          num_samples=7 + len(ids))
    assert sum(b.run_lanes for b in eb.buckets) > 0
    for b in eb.buckets:
        assert lane_runs(b)[:b.run_lanes].all()


# -- ISSUE 35: window lanes ---------------------------------------------------

def window_rows(rng, start, k, span):
    """``k`` ascending rows from ``start``, the first and the last of a
    window of ``span`` samples among them."""
    inner = rng.choice(np.arange(1, span - 1), k - 2, replace=False)
    return (start + np.sort(np.r_[0, inner, span - 1])).astype(np.int64)


def kinds_class(rng, capacity, runs, windows, far, n=1 << 20):
    """``kept_rows`` of ``runs`` run, ``windows`` window and ``far`` index
    entities of one capacity class, shuffled, and each one's kind."""
    limit = bucketing.WINDOW_SPAN_MAX * capacity
    kept, kind = [], []
    for which, count in (("run", runs), ("window", windows), ("far", far)):
        for i in range(count):
            k = int(rng.integers(capacity // 2 + 1, capacity + 1))
            start = int(rng.integers(0, n - 2 * limit))
            if which == "run":
                rows = start + np.arange(k)
            else:
                # the rule's edge on both sides: a span of exactly the
                # limit is a window, one more is not
                span = (limit + 1 + (i % 2) * limit if which == "far" else
                        (limit, k + 1)[i] if i < 2 else
                        int(rng.integers(k + 1, limit + 1)))
                rows = window_rows(rng, start, k, span)
            kept.append(rows.astype(np.int64))
            kind.append(which)
    order = rng.permutation(len(kept))
    return [kept[i] for i in order], [kind[i] for i in order]


@pytest.mark.parametrize("lane_multiple", [1, 4])
@pytest.mark.parametrize("capacity", [2, 4, 16, 128])
def test_class_lanes_deals_runs_then_windows_then_the_rest(capacity,
                                                           lane_multiple):
    """``_class_lanes`` reads a lane's kind off its rows and gives every
    share the same number of run lanes, then of window lanes; what does
    not fill a round of the deal, and every lane of a class under
    ``RUN_CAPACITY_MIN``, keeps one index a slot."""
    rng = np.random.default_rng(capacity)
    kept, kind = kinds_class(rng, capacity, runs=7, windows=10, far=5)
    idxs = np.arange(len(kept))
    lanes, run_lanes, window_lanes = bucketing._class_lanes(
        idxs, kept, capacity, lane_multiple)
    assert len(lanes) == -(-len(kept) // lane_multiple) * lane_multiple
    assert sorted(lanes[lanes >= 0]) == list(idxs)
    if capacity < bucketing.RUN_CAPACITY_MIN:
        assert (run_lanes, window_lanes) == (0, 0)
        assert np.array_equal(lanes[:len(kept)], idxs)  # the parent's order
        return
    assert run_lanes == 7 // lane_multiple
    assert window_lanes == 10 // lane_multiple
    by_share = lanes.reshape(lane_multiple, -1)
    of = np.vectorize(lambda e: kind[e] if e >= 0 else "padding")
    assert (of(by_share[:, :run_lanes]) == "run").all()
    head = run_lanes + window_lanes
    assert (of(by_share[:, run_lanes:head]) == "window").all()
    rest = of(by_share[:, head:]).ravel()
    assert (rest == "run").sum() == 7 % lane_multiple
    assert (rest == "window").sum() == 10 % lane_multiple
    assert (rest == "far").sum() == 5
    # the rest in the entities' own order, padding lanes last in the class
    tail = by_share[:, head:].ravel()
    assert np.array_equal(tail[tail >= 0], np.sort(tail[tail >= 0]))


def parents_runs_at(offsets, run_start, capacity):
    """PR 31's ``_runs_at`` as it landed, word for word."""
    n = offsets.shape[0]
    whole = -(-n // EM_ROW) * EM_ROW
    table = (offsets if whole == n else jnp.pad(offsets, (0, whole - n))
             ).reshape(-1, EM_ROW)
    take = max(capacity // EM_ROW, 1) + 1
    first = (run_start // EM_ROW)[:, None]
    picked = table[jnp.minimum(first + jnp.arange(take, dtype=first.dtype),
                               table.shape[0] - 1)]
    picked = picked.reshape(picked.shape[0], -1)
    shift = (run_start % EM_ROW)[:, None]
    for bit in range(EM_ROW.bit_length() - 1):
        picked = jnp.where((shift >> bit) & 1 == 1,
                           jnp.roll(picked, -(1 << bit), axis=1), picked)
    return picked[:, :capacity]


def parents_offsets_into_lanes(offsets, rows, valid, run_start=None):
    """PR 31's ``offsets_into_lanes``, word for word."""
    if run_start is None:
        return jnp.where(valid, offsets[rows], 0.0)
    picked = parents_runs_at(offsets, run_start, valid.shape[1])
    if rows.shape[0]:
        picked = jnp.concatenate([picked, offsets[rows]])
    return jnp.where(valid, picked, 0.0)


@pytest.mark.parametrize("run_lanes, index_lanes", [(0, 5), (9, 0), (9, 5)])
@pytest.mark.parametrize("capacity", [4, 64, 128, 1024])
def test_a_class_with_no_window_lane_traces_as_it_did(capacity, run_lanes,
                                                      index_lanes):
    """No window lane: the jaxpr of the class's gather is the parent's,
    with run lanes (``run_start`` only) and without (None)."""
    n = 5000
    offsets = jnp.zeros(n, jnp.float32)
    rows = jnp.zeros((index_lanes, capacity), jnp.int32)
    valid = jnp.ones((run_lanes + index_lanes, capacity), bool)
    start = jnp.zeros(run_lanes, jnp.int32) if run_lanes else None
    ours = jax.make_jaxpr(offsets_into_lanes)(offsets, rows, valid, start)
    parents = jax.make_jaxpr(parents_offsets_into_lanes)(
        offsets, rows, valid, start)
    assert str(ours) == str(parents)
    if run_lanes:
        windowed = jax.make_jaxpr(offsets_into_lanes)(
            offsets, rows, jnp.ones((len(valid) + 1, capacity), bool), start,
            jax.tree.map(jnp.asarray, bucketing.lane_windows(
                np.arange(2 * capacity)[None, ::2])))
        assert str(windowed) != str(ours)


@pytest.mark.parametrize("capacity, lanes", [(8, 50), (64, 21), (256, 9)])
def test_window_lanes_in_blocks_are_the_window_lanes(monkeypatch, capacity,
                                                     lanes):
    """``_windows_at`` reads its lanes block by block (a block's wide view
    ``WINDOW_BLOCK_BYTES`` on the chip): the lanes do not depend on where
    the blocks are cut, a last block that is not whole included."""
    rng = np.random.default_rng(capacity)
    n = 3 * bucketing.WINDOW_SPAN_MAX * capacity + 77
    rows = np.full((lanes, capacity), -1, np.int64)
    for lane in range(lanes):
        k = int(rng.integers(2, capacity + 1))
        span = int(rng.integers(k + 1, bucketing.WINDOW_SPAN_MAX * capacity + 1))
        rows[lane, :k] = window_rows(
            rng, int(rng.integers(0, n - span + 1)), k, span)
    valid = rows >= 0
    offsets = jnp.asarray(rng.standard_normal(n), jnp.float32)
    want = np.asarray(jnp.where(valid, offsets[np.maximum(rows, 0)], 0.0))
    by = jax.tree.map(jnp.asarray, bucketing.lane_windows(rows))
    none = jnp.zeros((0, capacity), jnp.int32)
    whole = offsets_into_lanes(offsets, none, jnp.asarray(valid), None, by)
    monkeypatch.setattr(bucketing, "WINDOW_BLOCK_BYTES", 1)  # 8 lanes a block
    blocks = jax.make_jaxpr(offsets_into_lanes)(
        offsets, none, jnp.asarray(valid), None, by)
    assert str(blocks).count("concatenate") > 0
    cut = offsets_into_lanes(offsets, none, jnp.asarray(valid), None, by)
    assert np.array_equal(np.asarray(whole), want)
    assert np.array_equal(np.asarray(cut), want)
