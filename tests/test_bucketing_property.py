"""Property tests for deterministic entity bucketing (SURVEY §5 determinism).

The reservoir cap must be a pure function of (rows, seed) — the reference
keys its reservoir on a seeded hash so retries/stragglers resample the SAME
rows (RandomEffectDataset.scala:358-420).  Hypothesis drives random entity
layouts through the grouping core and checks determinism, permutation
stability, cap/rescale accounting, and dense/sparse bucketer agreement.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

pytest.importorskip("hypothesis")  # not in the image; skip, don't error at collection
from hypothesis import given, settings
from hypothesis import strategies as st

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from photon_ml_tpu.parallel import bucketing

_ids = st.lists(st.integers(0, 6), min_size=1, max_size=60).map(
    lambda v: np.asarray(v, np.int64))


@settings(max_examples=60, deadline=None)
@given(ids=_ids, cap=st.integers(1, 8), seed=st.integers(0, 2**31 - 1))
def test_group_rows_deterministic_and_capped(ids, cap, seed):
    a = bucketing._group_rows(ids, cap, 1, seed)
    b = bucketing._group_rows(ids, cap, 1, seed)
    assert all(np.array_equal(x, y) for x, y in zip(a[0], b[0]))
    assert a[1] == b[1] and a[2] == b[2]
    kept_rows, kept_entities, rescale = a
    assert kept_entities == sorted(set(int(i) for i in ids))
    for rows, ent, sc in zip(kept_rows, kept_entities, rescale):
        total = int(np.sum(ids == ent))
        assert len(rows) == min(total, cap)
        # weight rescale preserves total weight: kept * (count/cap) = count
        assert sc * len(rows) == total
        assert np.all(ids[rows] == ent)  # rows really belong to the entity


@settings(max_examples=60, deadline=None)
@given(ids=_ids, cap=st.integers(1, 8),
       s1=st.integers(0, 2**31 - 1), s2=st.integers(0, 2**31 - 1))
def test_group_rows_seed_controls_sample(ids, cap, s1, s2):
    """Same seed -> same sample; the seed is the ONLY stochastic input."""
    a = bucketing._group_rows(ids, cap, 1, s1)
    b = bucketing._group_rows(ids, cap, 1, s2)
    if s1 == s2:
        assert all(np.array_equal(x, y) for x, y in zip(a[0], b[0]))
    # regardless of seed, the kept-entity directory is identical
    assert a[1] == b[1]


@settings(max_examples=40, deadline=None)
@given(ids=_ids, min_active=st.integers(1, 6))
def test_min_active_lower_bound_semantics(ids, min_active):
    """Under-bound entities are dropped ONLY when a prior model covers them
    (reference RandomEffectDataset.scala:322-333); new entities always
    train."""
    covered = frozenset(int(i) for i in np.unique(ids)[::2])
    kept_rows, kept_entities, _ = bucketing._group_rows(
        ids, None, min_active, 0, existing_model_keys=covered)
    for ent in np.unique(ids):
        count = int(np.sum(ids == ent))
        if count >= min_active or int(ent) not in covered:
            assert int(ent) in kept_entities
        else:
            assert int(ent) not in kept_entities


@settings(max_examples=30, deadline=None)
@given(ids=_ids, cap=st.integers(2, 8), seed=st.integers(0, 1000))
def test_dense_and_sparse_bucketers_agree(ids, cap, seed):
    """Same grouping core, same lane metadata: the dense bucketer and the
    row-sparse bucketer must agree on labels/weights/row maps lane by lane
    (their padding/rescale semantics share _pack_lane_meta by design)."""
    rng = np.random.default_rng(seed)
    n, d, k = len(ids), 8, 3
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.normal(size=n).astype(np.float32)
    w = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    off = rng.normal(size=n).astype(np.float32)
    dense = bucketing.bucket_by_entity(ids, x, y, offset=off, weight=w,
                                       active_cap=cap, seed=seed,
                                       dtype=np.float32)
    idx = np.tile(np.arange(k, dtype=np.int32), (n, 1))
    sparse, _projections = bucketing.bucket_by_entity_sparse(
        ids, idx, x[:, :k], d, y, offset=off, weight=w,
        active_cap=cap, seed=seed, dtype=np.float32)
    assert dense.lane_of.keys() == sparse.lane_of.keys()
    d_b = {e: dense.buckets[bi] for e, (bi, _) in dense.lane_of.items()}
    s_b = {e: sparse.buckets[bi] for e, (bi, _) in sparse.lane_of.items()}
    for e in dense.lane_of:
        (dbi, dl), (sbi, sl) = dense.lane_of[e], sparse.lane_of[e]
        db, sb = d_b[e], s_b[e]
        np.testing.assert_array_equal(np.asarray(db.rows)[dl],
                                      np.asarray(sb.rows)[sl])
        np.testing.assert_allclose(np.asarray(db.weight)[dl],
                                   np.asarray(sb.weight)[sl], rtol=1e-6)
        np.testing.assert_allclose(np.asarray(db.y)[dl],
                                   np.asarray(sb.y)[sl], rtol=1e-6)


@settings(max_examples=40, deadline=None)
@given(n=st.integers(1, 300), ne=st.integers(1, 40),
       d=st.integers(1, 24), seed=st.integers(0, 2**31 - 1),
       bf16=st.booleans())
def test_score_samples_t_property(n, ne, d, seed, bf16):
    """[d, n] samples-on-lanes scoring == [n, d] gather scoring for ANY
    shape/slot pattern/storage dtype (the narrow-shard layout swap must be
    a pure layout change — including d=1, all-(-1) slots, and bf16
    storage against f32 coefficients)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=(ne, d)).astype(np.float32)
    slots = rng.integers(-1, ne, size=n).astype(np.int32)
    xa = jnp.asarray(x)
    tol = dict(rtol=1e-5, atol=1e-6)
    if bf16:
        xa = xa.astype(jnp.bfloat16)
        tol = dict(rtol=2e-2, atol=2e-2)
    a = np.asarray(bucketing.score_samples(
        jnp.asarray(w), jnp.asarray(slots), xa), np.float64)
    b = np.asarray(bucketing.score_samples_t(
        jnp.asarray(w), jnp.asarray(slots), xa.T), np.float64)
    np.testing.assert_allclose(a, b, **tol)
    assert (b[slots < 0] == 0).all()


# -- the entity-major full-sample layout (ISSUE 24) ---------------------------

def _row_counts(pattern, ne, rng):
    """Rows of each of ``ne`` entities under a named pattern."""
    if pattern == "equal":
        return np.full(ne, int(rng.choice([8, 16, 48, 64, 128])))
    if pattern == "one_heavy":  # one entity holds most rows
        return np.r_[rng.integers(30, 90, size=ne - 1), 3000]
    if pattern == "single_rows":  # 1-row entities beside a few long ones
        return np.r_[np.ones(ne, np.int64), rng.integers(400, 900, size=3)]
    return rng.integers(40, 300, size=ne)  # "ragged"


@settings(max_examples=30, deadline=None)
@given(pattern=st.sampled_from(["equal", "one_heavy", "single_rows",
                                "ragged"]),
       ne=st.integers(2, 12), d=st.integers(1, 24),
       order=st.sampled_from(["sorted", "shuffled", "descending"]),
       absent=st.floats(0.0, 0.6), seed=st.integers(0, 2**31 - 1),
       bf16=st.booleans())
def test_score_samples_em_property(pattern, ne, d, order, absent, seed, bf16):
    """Entity-major layout + scorer == row-major gather scoring on the same
    rows for ANY row-count pattern, sample order, width and storage dtype,
    bitwise equal to the transposed scorer (the same products in the same
    order), and exactly 0 where the entity has no model."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    counts = _row_counts(pattern, ne, rng)
    ids = np.repeat(rng.choice(10_000, size=len(counts), replace=False) - 7,
                    counts).astype(np.int64)
    ids = {"sorted": np.sort, "shuffled": rng.permutation,
           "descending": lambda v: np.sort(v)[::-1].copy()}[order](ids)
    n = len(ids)
    runs = bucketing.entity_runs(ids)
    layout = bucketing.entity_major_layout(runs)
    assert layout is not None, counts
    assert layout.chunk == bucketing.entity_major_chunk(runs[1])
    # every sample has a place of its own, inside a chunk of its entity
    pos = np.arange(n) if layout.pos is None else layout.pos
    assert len(np.unique(pos)) == n
    k = bucketing.EM_ROW // layout.chunk
    chunk_of = layout.chunk_entity.T.reshape(-1)[pos // layout.chunk]
    np.testing.assert_array_equal(layout.entities[chunk_of], ids)
    assert layout.chunk_entity.shape[0] == k
    assert (layout.pos is None) == np.array_equal(pos, np.arange(n))

    ne_all = len(layout.entities)
    slot_of_entity = rng.permutation(ne_all).astype(np.int32)
    slot_of_entity[rng.random(ne_all) < absent] = -1
    slots = slot_of_entity[np.searchsorted(layout.entities, ids)]
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = jnp.asarray(rng.normal(size=(ne_all, d)).astype(np.float32))
    xa = jnp.asarray(x)
    tol = dict(rtol=1e-5, atol=1e-6)
    if bf16:
        xa = xa.astype(jnp.bfloat16)
        tol = dict(rtol=2e-2, atol=2e-2)
    x_em = bucketing.entity_major_design(layout, xa.T)
    assert x_em.dtype == xa.dtype
    assert x_em.shape == (d, layout.lanes // k, bucketing.EM_ROW)
    got = np.asarray(bucketing.score_samples_em(
        w, jnp.asarray(layout.lane_slots(slot_of_entity)), x_em,
        None if layout.pos is None else jnp.asarray(layout.pos)))[:n]
    want = np.asarray(bucketing.score_samples(w, jnp.asarray(slots), xa),
                      np.float64)
    np.testing.assert_allclose(got.astype(np.float64), want, **tol)
    np.testing.assert_array_equal(got, np.asarray(bucketing.score_samples_t(
        w, jnp.asarray(slots), xa.T)))
    assert (got[slots < 0] == 0).all()


def _cell_entity_ids(config, coordinate, full):
    """The entity column of one of the benchmark's three random-effect
    coordinates as its recipe makes it (benchmarks/recipes), at the full or
    the dry-run size: ids only, no design."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import run as harness

    catalog = harness.Catalog()
    cfg = harness.sized(catalog.json("configs", config), not full)
    recipe = catalog.module("recipes", cfg["recipe"])
    if config == "glmix_chip":  # chip_signal.make_training's own line
        s = recipe.sizes(cfg)
        return np.repeat(np.arange(s["users"], dtype=np.int64), s["per_user"])
    uids, iids = recipe.entity_columns(cfg, 3)
    return uids if coordinate == "per-user" else iids


@pytest.mark.parametrize("config, coordinate, full, chunk, fill, identity", [
    ("glmix_chip", "per-user", True, 64, 1.0, True),
    ("glmix3_wide", "per-user", True, 128, 1.0, False),  # rows shuffled
    ("glmix3_wide", "per-item", True, 128, 0.8, False),  # 240 and 272 rows
    ("glmix_chip", "per-user", False, 16, 1.0, True),    # 48 rows each
    ("glmix3_wide", "per-user", False, 32, 1.0, False),
    ("glmix3_wide", "per-item", False, 64, 0.8, False),  # 120 and 136
])
def test_entity_major_rule_on_the_cells(config, coordinate, full, chunk, fill,
                                        identity):
    """The chunk length is read off the row counts: the benchmark's three
    random-effect coordinates, from their entity columns alone."""
    ids = _cell_entity_ids(config, coordinate, full)
    runs = bucketing.entity_runs(ids)
    assert bucketing.entity_major_chunk(runs[1]) == chunk
    layout = bucketing.entity_major_layout(runs)
    assert layout.chunk == chunk and layout.fill == pytest.approx(fill)
    assert layout.lanes * chunk * fill == pytest.approx(len(ids))
    assert (layout.pos is None) == identity


@pytest.mark.parametrize("counts, chunk", [
    (np.full(1000, 10), None),   # 16 of 10 at C = 8: [d, n] as before
    (np.full(1000, 24), 8),
    (np.full(1000, 31), 32),
    (np.full(10, 129), 32),      # 160 of 129; 256 and 192 are too many
    (np.r_[np.ones(1000, np.int64), 100_000], 16),  # a long entity takes
    # many chunks: only the 1-row entities pad
])
def test_entity_major_chunk_rule(counts, chunk):
    """The largest power of two in [8, 128] within 1.3x padding, or none."""
    assert bucketing.entity_major_chunk(counts) == chunk
    ids = np.repeat(np.arange(len(counts), dtype=np.int64), counts)
    layout = bucketing.entity_major_layout(bucketing.entity_runs(ids))
    assert (layout is None) == (chunk is None)


# -- the way back to sample order (ISSUE 33) ----------------------------------

def _heavy_tail(rng):
    return np.maximum(1, rng.lognormal(3.6, 1.3, 400).astype(np.int64))


# name -> (rows of each entity, the chunk the rule finds, the un-pad's
# stages: the bits of the padding in front of the last entity; None: not
# pinned).  Rows of 0.8 C to C an entity force the chunk C.
_GROUPED = {
    "ragged": (lambda rng: rng.integers(40, 300, size=200), None, None),
    "heavy_tail": (_heavy_tail, None, None),
    "an_entity_of_one_row": (
        lambda rng: np.r_[rng.integers(40, 90, size=30), 1,
                          rng.integers(40, 90, size=30)], None, None),
    "one_row_each": (lambda rng: np.r_[np.ones(300, np.int64), 9000], 8,
                     None),
    "n_not_a_multiple_of_128": (lambda rng: np.r_[np.full(37, 50), 13], None,
                                None),
    "padding_127": (lambda rng: np.full(128, 7), 8, 7),
    "padding_128": (lambda rng: np.full(129, 7), 8, 8),
    "padding_32767": (lambda rng: np.full(32768, 7), 8, 15),
    "padding_32768": (lambda rng: np.full(32769, 7), 8, 16),
    **{f"chunk_{c}": (lambda rng, c=c: rng.integers(c - c // 5, c + 1,
                                                    size=150), c, None)
       for c in (8, 16, 32, 64, 128)},
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_GROUPED))
def test_unpad_is_bitwise_the_position_gather(case, dtype):
    """Rows grouped by entity whose counts do not fill chunks: the scores
    come back to sample order by the un-pad, BITWISE ``acc[pos]``, with no
    gather in the lowered program."""
    import jax
    import jax.numpy as jnp

    make, chunk, stages = _GROUPED[case]
    rng = np.random.default_rng(33)
    counts = np.asarray(make(rng), np.int64)
    ids = np.repeat(np.arange(len(counts), dtype=np.int64) * 3 - 5, counts)
    layout = bucketing.entity_major_layout(bucketing.entity_runs(ids))
    assert layout.grouped and layout.back == "unpad"
    assert chunk is None or layout.chunk == chunk
    back = layout.way_back()
    assert isinstance(back, bucketing.Unpad)
    assert back.num_samples == len(ids)
    assert back.slots == layout.lanes * layout.chunk
    assert stages is None or back.stages == stages
    assert back.pull.dtype == np.int32 and back.pull.shape == (back.slots,)
    assert int(back.pull.max(initial=0)).bit_length() <= back.stages

    acc = jnp.asarray(rng.normal(size=back.slots), dtype)
    fn = jax.jit(bucketing.to_sample_order)
    back = jax.tree.map(jnp.asarray, back)
    got = np.asarray(fn(acc, back))
    want = np.asarray(acc[jnp.asarray(layout.pos)])
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))
    assert "gather" not in fn.lower(acc, back).as_text()


@pytest.mark.parametrize("counts", [np.full(40, 64), np.full(7, 128),
                                    np.full(100, 16), np.r_[np.full(5, 256),
                                                            100]],
                         ids=["64", "128", "16", "last_one_short"])
def test_counts_that_fill_chunks_need_no_way_back(counts):
    """The chunks ARE the sample order: identity, the helper does not
    engage and the scores are handed on as they are."""
    ids = np.repeat(np.arange(len(counts), dtype=np.int64), counts)
    layout = bucketing.entity_major_layout(bucketing.entity_runs(ids))
    assert layout.grouped and layout.pos is None
    assert layout.back == "identity" and layout.way_back() is None
    acc = np.arange(layout.lanes * layout.chunk, dtype=np.float32)
    assert bucketing.to_sample_order(acc, layout.way_back()) is acc


@pytest.mark.parametrize("parts", [1, 4])
@pytest.mark.parametrize("counts", [np.r_[np.full(5, 256), 100],
                                    np.full(37, 64)],
                         ids=["last_one_short", "tail_of_the_last_row"])
def test_cutting_the_tail_pulls_nothing(counts, parts):
    """Chunks that are the sample order but for the zeros behind the last
    sample (``pos == arange(n)``, as ``external_data`` and a mesh whose
    shards are not the chunks' say it): an un-pad of no stage, which reads
    no ``pull`` and uploads none; a part only cuts its own range."""
    import jax
    import jax.numpy as jnp

    ids = np.repeat(np.arange(len(counts), dtype=np.int64), counts)
    n = len(ids)
    layout = bucketing.entity_major_layout(bucketing.entity_runs(ids), parts)
    slots = layout.lanes * layout.chunk
    assert layout.pos is None and slots > n
    layout.pos = np.arange(n, dtype=np.int32)
    back = layout.way_back(parts, slots // parts)
    assert layout.back == "unpad" and back.stages == 0
    assert back.pull.shape == (0,)
    assert back.num_samples == (n if parts == 1 else slots // parts)
    acc = jnp.arange(1, slots + 1, dtype=jnp.float32)
    want = np.where(np.arange(slots) < n, np.asarray(acc), 0)
    fn = jax.jit(bucketing.to_sample_order)
    got = []
    for c in range(parts):
        part = back if parts == 1 else dataclasses.replace(
            back, start=back.start[c:c + 1], live=back.live[c:c + 1])
        part = jax.tree.map(jnp.asarray, part)
        got.append(np.asarray(fn(acc, part)))
        text = fn.lower(acc, part).as_text()
        assert "gather" not in text and "concatenate" not in text  # no roll
    np.testing.assert_array_equal(np.concatenate(got),
                                  want[:parts * back.num_samples])


@pytest.mark.parametrize("order", ["shuffled", "descending", "interleaved"])
def test_rows_not_grouped_take_the_position_gather(order):
    """Rows that lie anywhere keep ``acc[pos]``: ``way_back`` is ``pos``."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(34)
    counts = rng.integers(40, 300, size=60)
    ids = np.repeat(np.arange(len(counts), dtype=np.int64), counts)
    ids = {"shuffled": rng.permutation,
           "descending": lambda v: v[::-1].copy(),
           "interleaved": lambda v: np.r_[v[::2], v[1::2]]}[order](ids)
    layout = bucketing.entity_major_layout(bucketing.entity_runs(ids))
    assert not layout.grouped and layout.back == "gather"
    assert layout.way_back() is layout.pos
    acc = jnp.asarray(rng.normal(size=layout.lanes * layout.chunk),
                      jnp.float32)
    fn = jax.jit(bucketing.to_sample_order)
    pos = jnp.asarray(layout.pos)
    np.testing.assert_array_equal(np.asarray(fn(acc, pos)),
                                  np.asarray(acc)[layout.pos])
    assert "gather" in fn.lower(acc, pos).as_text()


def test_pull_stages_refuses_positions_that_do_not_rise():
    with pytest.raises(ValueError, match="do not rise"):
        bucketing.pull_stages(np.asarray([0, 5, 3, 9], np.int32), 16)


def _window_class(rng, n, capacity, runs, windows, anywhere):
    """Stored rows [lanes, capacity] (-1 behind a lane's rows) of a class
    that mixes run, window and index lanes, in that order."""
    limit = bucketing.WINDOW_SPAN_MAX * capacity
    rows = np.full((runs + windows + anywhere, capacity), -1, np.int64)
    for lane in range(runs):
        k = int(rng.integers(1, capacity + 1))
        start = int(rng.integers(0, n - k + 1))
        rows[lane, :k] = start + np.arange(k)
    for lane in range(runs, runs + windows):
        k = int(rng.integers(2, capacity + 1))
        # spans from one skipped row to the rule's limit, both met
        span = int(rng.choice([k + 1, limit, rng.integers(k + 1, limit + 1)]))
        # a window that ends at the vector's last sample; the class's
        # widest then makes every narrower one near it reach PAST the end
        start = n - span if rng.random() < 0.4 else int(
            rng.integers(0, n - span + 1))
        inner = rng.choice(np.arange(1, span - 1), k - 2, replace=False)
        rows[lane, :k] = start + np.sort(np.r_[0, inner, span - 1])
    for lane in range(runs + windows, len(rows)):
        k = int(rng.integers(1, capacity + 1))
        rows[lane, :k] = np.sort(rng.choice(n, k, replace=False))
    return rows


@pytest.mark.parametrize("capacity", [4, 8, 32, 128, 1024])
@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), runs=st.integers(0, 3),
       windows=st.integers(1, 6), anywhere=st.integers(0, 3),
       tail=st.integers(1, bucketing.EM_ROW - 1), bf16=st.booleans())
def test_window_lanes_are_bitwise_one_index_a_slot(capacity, seed, runs,
                                                   windows, anywhere, tail,
                                                   bf16):
    """ISSUE 35: a class that mixes run, window and index lanes comes out
    of ``offsets_into_lanes`` BITWISE ``where(valid, offsets[rows], 0)``:
    windows that start anywhere (no multiple of 128), that reach past the
    vector's end, of every span the rule admits, ``n`` no multiple of 128."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    limit = bucketing.WINDOW_SPAN_MAX * capacity
    n = (limit // bucketing.EM_ROW + int(rng.integers(1, 4))
         ) * bucketing.EM_ROW + tail
    rows = _window_class(rng, n, capacity, runs, windows, anywhere)
    valid = rows >= 0
    offsets = jnp.asarray(rng.standard_normal(n),
                          jnp.bfloat16 if bf16 else jnp.float32)
    want = np.asarray(jnp.where(valid, offsets[np.maximum(rows, 0)], 0.0))
    by = bucketing.lane_windows(rows[runs:runs + windows])
    assert by.window >= capacity and by.stages >= 1
    assert by.window == max(capacity, int(
        (rows[runs:runs + windows].max(axis=1)
         - rows[runs:runs + windows, 0]).max()) + 1)
    got = jax.jit(bucketing.offsets_into_lanes)(
        offsets, jnp.asarray(np.maximum(rows[runs + windows:], 0), jnp.int32),
        jnp.asarray(valid),
        jnp.asarray(rows[:runs, 0], jnp.int32) if runs else None,
        jax.tree.map(jnp.asarray, by))
    assert got.dtype == offsets.dtype and got.shape == want.shape
    assert np.array_equal(np.asarray(got), want)


@settings(max_examples=40, deadline=None)
@given(ids=_ids, cap=st.integers(1, 8), seed=st.integers(0, 2**31 - 1),
       sort=st.booleans())
def test_group_rows_from_shared_runs(ids, cap, seed, sort):
    """``entity_runs`` handed to the bucketer groups as its own scan does,
    for rows that arrive grouped (no sort, ``order`` None) and anywhere."""
    if sort:
        ids = np.sort(ids)
    runs = bucketing.entity_runs(ids)
    assert (runs[2] is None) == bool(np.all(ids[1:] >= ids[:-1]))
    uniq, counts = np.unique(ids, return_counts=True)
    np.testing.assert_array_equal(runs[0], uniq)
    np.testing.assert_array_equal(runs[1], counts)
    a = bucketing._group_rows(ids, cap, 1, seed)
    b = bucketing._group_rows(ids, cap, 1, seed, runs=runs)
    assert all(np.array_equal(x, y) for x, y in zip(a[0], b[0]))
    assert a[1] == b[1] and a[2] == b[2]
