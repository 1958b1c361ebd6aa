"""Serving hot-path v2 tests: async deadline batching, frequency-ranked
device hot set, streaming coefficient deltas.

The contracts under test (ISSUE 4 / ROADMAP serving follow-ons):
  - AsyncBatcher: thread-safe submit -> future; flushes on a full bucket OR
    the deadline; shutdown drains pending futures; every future's score is
    bitwise the synchronous single-request score (padding parity).
  - Hot set: promotion/demotion tracks EWMA request frequency, is
    deterministic for a fixed trace, never changes a table shape (zero
    recompiles), and never changes a score (hot and cold tiers are
    bitwise-identical by construction).
  - Deltas: apply_delta rewrites one live row (device scatter when hot,
    archive + LRU invalidation always) and serves exactly what a fresh
    store built from the patched model would serve.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from photon_ml_tpu.data.index_map import IndexMap, feature_key
from photon_ml_tpu.data.reader import EntityIndex
from photon_ml_tpu.models.game import (FixedEffectModel, GameModel,
                                       RandomEffectModel)
from photon_ml_tpu.models.glm import Coefficients
from photon_ml_tpu.serving.batcher import AsyncBatcher, BucketedBatcher, Request
from photon_ml_tpu.serving.coefficient_store import (CoefficientStore,
                                                     HotSetManager,
                                                     StoreConfig)
from photon_ml_tpu.serving.engine import ScoringEngine
from photon_ml_tpu.serving.metrics import ServingMetrics
from photon_ml_tpu.serving.swap import HotSwapper
from photon_ml_tpu.types import TaskType

N_ENT = 40
D = 4
NAMES = [f"f{j}" for j in range(D)]


def _model(seed=0):
    rng = np.random.default_rng(seed)
    task = TaskType.LOGISTIC_REGRESSION
    return GameModel(models={
        "fixed": FixedEffectModel(
            coefficients=Coefficients(means=rng.normal(size=D)),
            feature_shard="all", task=task),
        "user": RandomEffectModel(
            w_stack=rng.normal(size=(N_ENT, D)) * 0.5,
            slot_of={i: i for i in range(N_ENT)},
            random_effect_type="userId", feature_shard="all", task=task),
    }), task


def _store(model, task, capacity, lru=16, decay=0.5, metrics=None,
           max_moves=None):
    imap = IndexMap({feature_key(n): j for j, n in enumerate(NAMES)})
    eidx = EntityIndex()
    for i in range(N_ENT):
        eidx.get_or_add(f"user{i}")
    return CoefficientStore.from_model(
        model, task, {"userId": eidx}, {"all": imap},
        config=StoreConfig(device_capacity=capacity, lru_capacity=lru,
                           hot_decay=decay, hot_max_moves=max_moves),
        version="synthetic", metrics=metrics)


def _engine(capacity=None, max_batch=8, seed=0, metrics=None, decay=0.5):
    model, task = _model(seed)
    metrics = metrics or ServingMetrics()
    store = _store(model, task, capacity, metrics=metrics, decay=decay)
    eng = ScoringEngine(store, BucketedBatcher(max_batch), metrics=metrics)
    eng.warm()
    return eng, model, task


def _req(rng, uid=0, user=None):
    feats = [{"name": n, "term": "", "value": float(v)}
             for n, v in zip(NAMES, rng.normal(size=D))]
    user = user if user is not None else int(rng.integers(0, N_ENT))
    return Request(uid=uid, features=feats, ids={"userId": f"user{user}"})


# ---------------------------------------------------------------------------
# async deadline batcher
# ---------------------------------------------------------------------------
class TestAsyncBatcher:
    def test_full_flush_parity(self):
        eng, _, _ = _engine(max_batch=4)
        rng = np.random.default_rng(1)
        reqs = [_req(rng, uid=i) for i in range(8)]
        with eng.async_batcher(deadline_s=10.0) as ab:
            futs = [ab.submit(r) for r in reqs]
            got = [f.result(timeout=30) for f in futs]
        # every future resolves to ITS request's score.  The async batcher
        # may group arrivals into any bucket size, and XLA's reduction
        # order differs by one ulp across bucket shapes — so compare at
        # float tolerance here; the bitwise same-list guarantee is held by
        # tests/test_serving.py's parity property
        for r, s in zip(reqs, got):
            assert s == pytest.approx(float(eng.score_requests([r])[0]),
                                      rel=1e-9, abs=1e-12)
        # 8 submits at threshold 4 with an un-hittable deadline: only full
        # flushes fire
        assert eng.metrics.counter("flushes_full") >= 1
        assert eng.metrics.counter("flushes_deadline") == 0

    def test_deadline_flush_low_qps(self):
        eng, _, _ = _engine(max_batch=8)
        rng = np.random.default_rng(2)
        with eng.async_batcher(deadline_s=0.01) as ab:
            futs = [ab.submit(_req(rng, uid=i)) for i in range(3)]
            got = [f.result(timeout=30) for f in futs]  # no bucket ever fills
        assert all(np.isfinite(got))
        assert eng.metrics.counter("flushes_deadline") >= 1

    def test_concurrent_submits(self):
        eng, _, _ = _engine(max_batch=8)
        per_thread = 25
        results = {}

        def worker(tid):
            rng = np.random.default_rng(100 + tid)
            pairs = []
            with_futs = []
            for i in range(per_thread):
                r = _req(rng, uid=(tid, i))
                with_futs.append((r, ab.submit(r)))
            for r, f in with_futs:
                pairs.append((r, f.result(timeout=60)))
            results[tid] = pairs

        with eng.async_batcher(deadline_s=0.002) as ab:
            threads = [threading.Thread(target=worker, args=(t,))
                       for t in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        assert all(not t.is_alive() for t in threads)
        assert sum(len(v) for v in results.values()) == 4 * per_thread
        # interleaved multi-producer traffic still scores each request
        # correctly (tolerance: bucket-shape reduction-order ulps, see
        # test_full_flush_parity)
        for pairs in results.values():
            for r, s in pairs:
                assert s == pytest.approx(float(eng.score_requests([r])[0]),
                                          rel=1e-9, abs=1e-12)

    def test_flush_forces_pending(self):
        eng, _, _ = _engine(max_batch=8)
        rng = np.random.default_rng(3)
        ab = eng.async_batcher(deadline_s=60.0)
        try:
            futs = [ab.submit(_req(rng, uid=i)) for i in range(3)]
            assert ab.flush() == futs
            for f in futs:
                assert np.isfinite(f.result(timeout=30))
            assert eng.metrics.counter("flushes_forced") >= 1
        finally:
            ab.shutdown()

    def test_shutdown_drains_pending_futures(self):
        eng, _, _ = _engine(max_batch=8)
        rng = np.random.default_rng(4)
        ab = eng.async_batcher(deadline_s=60.0)  # deadline can never fire
        futs = [ab.submit(_req(rng, uid=i)) for i in range(5)]
        ab.shutdown(drain=True)
        assert all(f.done() and not f.cancelled() for f in futs)
        assert all(np.isfinite(f.result()) for f in futs)
        with pytest.raises(RuntimeError):
            ab.submit(_req(rng))
        ab.shutdown()  # idempotent

    def test_shutdown_no_drain_cancels(self):
        eng, _, _ = _engine(max_batch=8)
        rng = np.random.default_rng(5)
        ab = eng.async_batcher(deadline_s=60.0)
        futs = [ab.submit(_req(rng, uid=i)) for i in range(3)]
        ab.shutdown(drain=False)
        assert all(f.cancelled() for f in futs)

    def test_score_error_resolves_futures(self):
        def boom(reqs):
            raise RuntimeError("scorer down")

        ab = AsyncBatcher(boom, flush_threshold=2, deadline_s=0.005)
        try:
            f1 = ab.submit(Request(uid=1))
            f2 = ab.submit(Request(uid=2))
            with pytest.raises(RuntimeError, match="scorer down"):
                f1.result(timeout=30)
            with pytest.raises(RuntimeError, match="scorer down"):
                f2.result(timeout=30)
        finally:
            ab.shutdown()

    def test_validation(self):
        with pytest.raises(ValueError):
            AsyncBatcher(lambda r: [], flush_threshold=0)
        with pytest.raises(ValueError):
            AsyncBatcher(lambda r: [], flush_threshold=1, deadline_s=-1.0)


class TestBatcherIntrospection:
    """pending_count / flush_cost_estimate / queue_wait_estimate — the
    backlog predictor the front end's admission controller reads."""

    @staticmethod
    def _dummy(flush_threshold, deadline_s):
        return AsyncBatcher(lambda reqs: np.zeros(len(reqs)),
                            flush_threshold=flush_threshold,
                            deadline_s=deadline_s)

    def test_estimate_wave_arithmetic_before_any_flush(self):
        # nothing observed yet: the flush-cost EWMA floors at the deadline,
        # so every term of the estimate is exact arithmetic
        ab = self._dummy(flush_threshold=4, deadline_s=10.0)
        try:
            assert ab.pending_count() == 0
            assert ab.flush_cost_estimate() == 10.0
            # empty queue: the arriving request is a non-full tail wave —
            # one flush cost plus the residual deadline wait
            assert ab.queue_wait_estimate() == pytest.approx(20.0)
            # 3 ahead + itself = exactly one full wave: no deadline wait
            assert ab.queue_wait_estimate(extra=3) == pytest.approx(10.0)
            # 8 ahead + itself = 2 full waves + a tail
            assert ab.queue_wait_estimate(extra=8) == pytest.approx(40.0)
        finally:
            ab.shutdown(drain=False)

    def test_pending_count_tracks_submits_and_flush(self):
        ab = self._dummy(flush_threshold=64, deadline_s=10.0)
        try:
            futs = [ab.submit(Request(uid=i, features=[], ids={}))
                    for i in range(3)]
            assert ab.pending_count() == 3
            ab.flush()
            for f in futs:
                f.result(timeout=30)
            assert ab.pending_count() == 0
            # that flush was observed: the EWMA left its deadline floor
            assert ab.flush_cost_estimate() < 10.0
        finally:
            ab.shutdown()

    def test_ewma_converges_to_observed_flush_cost(self):
        delay = 0.005

        def slow_score(reqs):
            time.sleep(delay)
            return np.zeros(len(reqs))

        ab = AsyncBatcher(slow_score, flush_threshold=1, deadline_s=10.0)
        try:
            for i in range(10):
                ab.submit(Request(uid=i, features=[], ids={})) \
                  .result(timeout=30)
            est = ab.flush_cost_estimate()
            assert delay * 0.8 < est < delay * 10
            # empty queue + threshold 1: the next request is one full wave
            wait = ab.queue_wait_estimate()
            assert delay * 0.8 < wait < delay * 10
        finally:
            ab.shutdown()

    def test_shutdown_under_concurrent_submit_never_drops(self):
        """The drain contract under racing producers: every submit either
        raises RuntimeError (batcher closed) or returns a future that
        RESOLVES to a score — no future is ever silently dropped or
        cancelled by shutdown(drain=True)."""
        eng, _, _ = _engine(max_batch=4)
        ab = eng.async_batcher(deadline_s=0.0005)
        accepted = []

        def producer(tid):
            rng = np.random.default_rng(500 + tid)
            mine = []
            for i in range(50):
                try:
                    mine.append(ab.submit(_req(rng, uid=f"{tid}-{i}")))
                except RuntimeError:
                    break  # closed under us: the expected race outcome
            accepted.extend(mine)  # one atomic extend per thread

        threads = [threading.Thread(target=producer, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.005)  # let producers race the drain
        ab.shutdown(drain=True)
        for t in threads:
            t.join(60)
        assert accepted, "no submits landed before shutdown?"
        for f in accepted:
            assert np.isfinite(f.result(timeout=30))


# ---------------------------------------------------------------------------
# frequency-ranked hot set
# ---------------------------------------------------------------------------
def _trace(engine, users, rng, repeats=3):
    """Score a deterministic trace concentrated on ``users``."""
    for _ in range(repeats):
        reqs = [_req(rng, uid=i, user=u) for i, u in enumerate(users)]
        engine.score_requests(reqs)


class TestHotSet:
    def test_promotion_tracks_traffic(self):
        metrics = ServingMetrics()
        model, task = _model()
        store = _store(model, task, capacity=8, metrics=metrics)
        eng = ScoringEngine(store, BucketedBatcher(8), metrics=metrics)
        eng.warm()
        compiles = eng.compile_count
        coord = store.coordinates["user"]
        assert set(coord.hot_slot_of) == set(range(8))  # training-slot order

        hot_users = list(range(30, 38))
        rng = np.random.default_rng(7)
        _trace(eng, hot_users, rng)
        moves = store.rebalance()
        assert moves["user"] == (8, 8)  # full turnover to the traffic
        assert set(coord.hot_slot_of) == set(hot_users)
        assert metrics.counter("hot_promotions") == 8
        assert metrics.counter("rebalances") == 1

        # residency moved; scores must not — and no recompile either
        ref_eng, _, _ = _engine(capacity=None)  # all-hot reference
        rng2 = np.random.default_rng(8)
        reqs = [_req(rng2, uid=i) for i in range(13)]
        np.testing.assert_array_equal(eng.score_requests(reqs),
                                      ref_eng.score_requests(reqs))
        assert eng.compile_count == compiles
        assert store.signature() == _store(model, task, capacity=8).signature()

    def test_hot_set_deterministic_for_fixed_trace(self):
        def run():
            model, task = _model()
            store = _store(model, task, capacity=6)
            eng = ScoringEngine(store, BucketedBatcher(8))
            rng = np.random.default_rng(11)
            _trace(eng, [3, 17, 17, 25, 25, 25, 31, 9, 9, 40 % N_ENT], rng)
            store.rebalance()
            _trace(eng, [17, 25, 31, 31, 31, 2], rng)
            store.rebalance()
            return dict(store.coordinates["user"].hot_slot_of)

        first, second = run(), run()
        assert first == second  # identical entities AND device rows

    def test_ewma_ages_out_stale_entities(self):
        model, task = _model()
        store = _store(model, task, capacity=4, decay=0.5)
        eng = ScoringEngine(store, BucketedBatcher(8))
        coord = store.coordinates["user"]
        rng = np.random.default_rng(13)
        _trace(eng, [20, 21, 22, 23], rng, repeats=2)
        store.rebalance()
        assert set(coord.hot_slot_of) == {20, 21, 22, 23}
        # traffic moves entirely; the old set decays below the new one
        for _ in range(4):
            _trace(eng, [30, 31, 32, 33], rng, repeats=2)
            store.rebalance()
        assert set(coord.hot_slot_of) == {30, 31, 32, 33}

    def test_max_moves_caps_turnover(self):
        model, task = _model()
        store = _store(model, task, capacity=8, max_moves=3)
        eng = ScoringEngine(store, BucketedBatcher(8))
        rng = np.random.default_rng(17)
        _trace(eng, list(range(30, 38)), rng)
        assert store.rebalance()["user"] == (3, 3)

    def test_rebalance_noop_when_all_hot_or_all_cold(self):
        model, task = _model()
        for capacity in (None, 0):
            store = _store(model, task, capacity=capacity)
            eng = ScoringEngine(store, BucketedBatcher(8))
            rng = np.random.default_rng(19)
            _trace(eng, [1, 2, 3], rng)
            assert store.rebalance()["user"] == (0, 0)

    def test_padding_rows_not_counted_as_misses(self):
        metrics = ServingMetrics()
        model, task = _model()
        store = _store(model, task, capacity=None, metrics=metrics)
        eng = ScoringEngine(store, BucketedBatcher(8), metrics=metrics)
        rng = np.random.default_rng(23)
        eng.score_requests([_req(rng, uid=i) for i in range(3)])  # bucket 4
        assert metrics.counter("entity_misses") == 0  # padding row is silent
        assert metrics.counter("hot_hits") == 3
        assert metrics.snapshot()["hot_set_hit_rate"] == 1.0

    def test_hot_set_manager_background(self):
        model, task = _model()
        store = _store(model, task, capacity=4)
        eng = ScoringEngine(store, BucketedBatcher(8))
        rng = np.random.default_rng(29)
        mgr = HotSetManager(lambda: eng.store, interval_s=0.01).start()
        try:
            deadline = time.time() + 20
            while (set(store.coordinates["user"].hot_slot_of) != {30, 31, 32,
                                                                  33}
                   and time.time() < deadline):
                _trace(eng, [30, 31, 32, 33], rng)
        finally:
            mgr.stop(timeout=10)
        assert set(store.coordinates["user"].hot_slot_of) == {30, 31, 32, 33}


@pytest.mark.parametrize("zipf", [0.0, 1.2], ids=["uniform", "zipf"])
def test_warm_engine_compiles_nothing_under_a_stream(zipf):
    """Requests submitted one at a time to the deadline batcher, ~5% of them
    for unknown users, with a frequency rebalance every 16 requests of an
    adaptation epoch, then a measured epoch on a fresh draw: a warm engine
    compiles nothing more.  Under Zipf traffic (ranks shuffled over the
    slots, so the first-slots residency starts uncorrelated with the head)
    the rebalances must move residency."""
    n_requests, every, deadline_s = 48, 16, 1e-4
    metrics = ServingMetrics()
    engine, _, _ = _engine(capacity=8, max_batch=8, metrics=metrics)
    n_compiled = engine.compile_count
    assert n_compiled == 4
    rng = np.random.default_rng(0)
    slot_of_rank = rng.permutation(N_ENT)

    def draw(n):
        if zipf:
            w = (np.arange(N_ENT) + 1.0) ** -zipf
            users = slot_of_rank[rng.choice(N_ENT, size=n, p=w / w.sum())]
        else:
            users = rng.integers(0, N_ENT, size=n)
        users = np.where(rng.random(n) < 0.05, N_ENT + users, users)
        return [_req(rng, uid=i, user=u) for i, u in enumerate(users)]

    batcher = engine.async_batcher(deadline_s=deadline_s)
    try:
        stream = draw(n_requests)
        for start in range(0, n_requests, every):
            for f in [batcher.submit(r) for r in stream[start:start + every]]:
                f.result(timeout=300)
            engine.store.rebalance()
        # arrival gaps over the deadline: deadline flushes
        trickle = []
        for r in draw(16):
            trickle.append(batcher.submit(r))
            time.sleep(2.0 * deadline_s)
        for f in trickle:
            f.result(timeout=300)
        before = metrics.snapshot()
        scores = [f.result(timeout=300)
                  for f in [batcher.submit(r) for r in draw(n_requests)]]
    finally:
        batcher.shutdown(drain=True)
    snap = metrics.snapshot()
    counters = snap["counters"]

    assert engine.compile_count == n_compiled
    assert np.isfinite(scores).all()
    padded = snap["padded_rows_launched"] - before["padded_rows_launched"]
    real = snap["real_rows_launched"] - before["real_rows_launched"]
    assert real == n_requests and 0 <= 1.0 - real / padded < 1
    misses = (counters.get("entity_misses", 0)
              - before["counters"].get("entity_misses", 0))
    assert 0 <= misses / n_requests < 1
    assert counters["rebalances"] >= 3
    assert sum(counters.get(k, 0) for k in
               ("flushes_full", "flushes_deadline", "flushes_forced")) >= 1
    if zipf:
        assert counters["hot_promotions"] >= 1  # skew moved residency


# ---------------------------------------------------------------------------
# streaming coefficient deltas
# ---------------------------------------------------------------------------
class TestDeltas:
    def _patched_reference(self, model, task, user, row, capacity=None):
        """Fresh engine built from the model with ``user``'s row replaced —
        what serving must match after an in-place delta."""
        import dataclasses

        patched, _ = _model()  # same seed -> identical weights
        re_model = patched.models["user"]
        stack = np.array(re_model.w_stack)
        stack[user] = row
        patched = GameModel(models={
            "fixed": patched.models["fixed"],
            "user": dataclasses.replace(re_model, w_stack=stack),
        })
        store = _store(patched, task, capacity)
        eng = ScoringEngine(store, BucketedBatcher(8))
        return eng

    def test_delta_hot_entity_scatters_device_row(self):
        eng, model, task = _engine(capacity=None)
        compiles = eng.compile_count
        rng = np.random.default_rng(31)
        req = _req(rng, uid=1, user=5)
        before = eng.score_requests([req])[0]
        new_row = np.full(D, 0.25, np.float64)
        assert eng.store.apply_delta("user", "user5", new_row) is True
        after = eng.score_requests([req])[0]
        assert after != before
        ref = self._patched_reference(model, task, 5, new_row)
        np.testing.assert_array_equal(eng.score_requests([req]),
                                      ref.score_requests([req]))
        assert eng.compile_count == compiles  # no shape change, no compile

    def test_delta_cold_entity_invalidates_lru(self):
        metrics = ServingMetrics()
        model, task = _model()
        store = _store(model, task, capacity=4, metrics=metrics)
        eng = ScoringEngine(store, BucketedBatcher(8), metrics=metrics)
        rng = np.random.default_rng(37)
        req = _req(rng, uid=1, user=20)  # slot 20 >= capacity 4: cold
        eng.score_requests([req])          # pulls the row into the LRU
        eng.score_requests([req])
        assert metrics.counter("lru_hits") >= 1
        new_row = np.linspace(-1, 1, D)
        assert store.apply_delta("user", "user20", new_row) is True
        ref = self._patched_reference(model, task, 20, new_row, capacity=4)
        np.testing.assert_array_equal(eng.score_requests([req]),
                                      ref.score_requests([req]))
        assert metrics.counter("delta_updates") == 1

    def test_delta_survives_rebalance_both_directions(self):
        """A delta'd row keeps serving its new value through promotion AND
        demotion (archive and device table stay coherent)."""
        model, task = _model()
        store = _store(model, task, capacity=4)
        eng = ScoringEngine(store, BucketedBatcher(8))
        rng = np.random.default_rng(41)
        new_row = np.full(D, -0.5)
        store.apply_delta("user", "user20", new_row)  # cold at apply time
        req = _req(rng, uid=1, user=20)
        ref = self._patched_reference(model, task, 20, new_row, capacity=4)
        want = ref.score_requests([req])
        np.testing.assert_array_equal(eng.score_requests([req]), want)
        # hammer user20 so it promotes, then verify the DEVICE copy is new
        _trace(eng, [20, 20, 20, 20], rng)
        store.rebalance()
        assert 20 in store.coordinates["user"].hot_slot_of
        np.testing.assert_array_equal(eng.score_requests([req]), want)

    def test_delta_rejections(self):
        eng, _, _ = _engine(capacity=None)
        store = eng.store
        assert store.apply_delta("user", "no-such-user", np.zeros(D)) is False
        with pytest.raises(ValueError, match="fixed"):
            store.apply_delta("fixed", "user1", np.zeros(D))
        with pytest.raises(ValueError, match="unknown coordinate"):
            store.apply_delta("nope", "user1", np.zeros(D))
        with pytest.raises(ValueError, match="shape"):
            store.apply_delta("user", "user1", np.zeros(D + 1))

    def test_swapper_delta_version(self):
        eng, _, _ = _engine(capacity=None)
        swapper = HotSwapper(eng)
        assert swapper.delta_version == 0
        assert swapper.apply_delta("user", "user3", np.zeros(D)) is True
        assert swapper.apply_delta("user", "user4", np.ones(D)) is True
        assert swapper.delta_version == 2
        # rejected deltas never bump the version
        assert swapper.apply_delta("user", "ghost", np.zeros(D)) is False
        assert swapper.apply_delta("fixed", "user1", np.zeros(D)) is False
        assert swapper.delta_version == 2
        assert eng.metrics.counter("delta_rejects") == 2
        assert eng.metrics.counter("delta_updates") == 2


# ---------------------------------------------------------------------------
# the async JSON-lines driver end to end
# ---------------------------------------------------------------------------
N_USERS = 6
FEATURES = ["g0", "g1", "g2", "ux"]


def _write_fixture(path, n=250, seed=0):
    from photon_ml_tpu.data import avro as avro_io
    from photon_ml_tpu.data.schemas import TRAINING_EXAMPLE

    rng = np.random.default_rng(seed)
    uw = rng.normal(size=(N_USERS, 1)) * 1.5
    gw = np.asarray([0.8, -1.2, 0.5])
    records = []
    for i in range(n):
        u = int(rng.integers(0, N_USERS))
        xg = rng.normal(size=3)
        xu = rng.normal(size=1)
        logit = xg @ gw + xu @ uw[u]
        y = float(rng.random() < 1.0 / (1.0 + np.exp(-logit)))
        feats = [{"name": f"g{j}", "term": "", "value": float(xg[j])}
                 for j in range(3)]
        feats.append({"name": "ux", "term": "", "value": float(xu[0])})
        records.append({"uid": i, "response": y, "label": None,
                        "features": feats, "weight": None, "offset": None,
                        "metadataMap": {"userId": f"user{u}"}})
    avro_io.write_container(path, TRAINING_EXAMPLE, records)


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    from photon_ml_tpu.cli import train as train_cli

    tmp = tmp_path_factory.mktemp("serving_async")
    data = str(tmp / "train.avro")
    _write_fixture(data, n=250, seed=1)
    out = str(tmp / "model")
    rc = train_cli.run([
        "--train-data", data, "--feature-shards", "all",
        "--coordinate", "name=fixed,feature.shard=all,reg.weights=1",
        "--coordinate",
        "name=user,random.effect.type=userId,feature.shard=all,reg.weights=1",
        "--id-tags", "userId", "--coordinate-descent-iterations", "2",
        "--output-dir", out])
    assert rc == 0
    return out


class TestServeCliAsync:
    def test_async_stream_delta_rebalance(self, model_dir, tmp_path, capsys):
        from photon_ml_tpu.cli import serve as serve_cli

        feats = [[f, 0.5] for f in FEATURES]
        lines = [
            json.dumps({"uid": 0, "features": feats,
                        "ids": {"userId": "user3"}}),
            json.dumps({"uid": 1, "features": feats,
                        "ids": {"userId": "user1"}}),
            "",  # force-flush + drain
            # the trained shard has 5 columns (4 features + intercept)
            json.dumps({"cmd": "delta", "coordinate": "user",
                        "entity": "user3", "row": [2.0, 2.0, 2.0, 2.0, 2.0]}),
            json.dumps({"uid": 2, "features": feats,
                        "ids": {"userId": "user3"}}),
            json.dumps({"cmd": "rebalance"}),
            json.dumps({"cmd": "metrics"}),
            json.dumps({"cmd": "swap", "model_dir": model_dir}),
        ]
        req_file = tmp_path / "requests.jsonl"
        req_file.write_text("\n".join(lines) + "\n")
        metrics_file = str(tmp_path / "metrics.json")

        rc = serve_cli.run(["--model-dir", model_dir, "--max-batch", "8",
                            "--deadline-us", "2000",
                            "--requests", str(req_file),
                            "--metrics-json", metrics_file])
        assert rc == 0
        out = [json.loads(l) for l in
               capsys.readouterr().out.strip().splitlines()]
        scores = {o["uid"]: o["score"] for o in out if "score" in o}
        assert sorted(scores) == [0, 1, 2]
        # uid 2 rescored user3 AFTER the delta rewrote its row
        assert scores[2] != scores[0]
        deltas = [o for o in out if "delta" in o]
        assert deltas == [{"delta": "ok", "delta_version": 1}]
        rebalances = [o for o in out if "rebalance" in o]
        assert len(rebalances) == 1 and "user" in rebalances[0]["rebalance"]
        swaps = [o for o in out if "swap" in o]
        assert swaps[0]["swap"] == "ok"
        assert swaps[0]["delta_version"] == 0  # swap resets the counter
        exported = json.load(open(metrics_file))
        assert exported["counters"]["requests"] == 3
        assert exported["counters"]["delta_updates"] == 1
        assert exported["counters"]["flushes_forced"] >= 1
        assert "bucket_occupancy" in exported
        assert "hot_set_hit_rate" in exported

    def test_sync_batcher_flag_still_works(self, model_dir, tmp_path, capsys):
        from photon_ml_tpu.cli import serve as serve_cli

        lines = [json.dumps({"uid": i, "features": [[f, 0.1] for f in FEATURES],
                             "ids": {"userId": f"user{i}"}})
                 for i in range(3)]
        req_file = tmp_path / "requests.jsonl"
        req_file.write_text("\n".join(lines) + "\n")
        rc = serve_cli.run(["--model-dir", model_dir, "--max-batch", "8",
                            "--sync-batcher",
                            "--requests", str(req_file)])
        assert rc == 0
        out = [json.loads(l) for l in
               capsys.readouterr().out.strip().splitlines()]
        assert [o["uid"] for o in out if "score" in o] == [0, 1, 2]
