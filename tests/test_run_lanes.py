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
