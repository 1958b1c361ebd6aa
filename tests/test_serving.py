"""Online serving tests (photon_ml_tpu/serving/*, cli/serve.py).

Reference analog: the batch repo has no serving integ tests to mirror — the
contract here is INTERNAL parity: padded, bucketed, AOT-compiled serving
scores must be bitwise the ``GameTransformer`` batch scores on the same
inputs (property test over random batch sizes / entity mixes / cold-entity
splits), plus the operational guarantees the subsystem exists for: zero
recompiles after warm, atomic hot swap, clean rejection of corrupt model
dirs, metrics accounting.
"""

import json
import os
import shutil
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from photon_ml_tpu.data import avro as avro_io
from photon_ml_tpu.data.schemas import TRAINING_EXAMPLE
from photon_ml_tpu.game.data import GameData
from photon_ml_tpu.game.estimator import GameTransformer
from photon_ml_tpu.serving.batcher import (BucketedBatcher, Request,
                                           densify_features,
                                           pow2_bucket_ladder,
                                           request_from_json)
from photon_ml_tpu.serving.coefficient_store import (CoefficientStore,
                                                     StoreConfig)
from photon_ml_tpu.serving.engine import ScoringEngine
from photon_ml_tpu.serving.metrics import LatencyHistogram, ServingMetrics
from photon_ml_tpu.serving.swap import HotSwapper
from photon_ml_tpu.storage.model_io import (ModelLoadError,
                                            load_model_bundle)

N_USERS = 6
FEATURES = ["g0", "g1", "g2", "ux"]


def _write_fixture(path, n=250, seed=0):
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


def _train(tmp, seed):
    from photon_ml_tpu.cli import train as train_cli

    data = str(tmp / f"train{seed}.avro")
    _write_fixture(data, n=250, seed=seed)
    out = str(tmp / f"model{seed}")
    rc = train_cli.run([
        "--train-data", data, "--feature-shards", "all",
        "--coordinate", "name=fixed,feature.shard=all,reg.weights=1",
        "--coordinate",
        "name=user,random.effect.type=userId,feature.shard=all,reg.weights=1",
        "--id-tags", "userId", "--coordinate-descent-iterations", "2",
        "--output-dir", out])
    assert rc == 0
    return out


@pytest.fixture(scope="module")
def model_dirs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("serving")
    return _train(tmp, seed=1), _train(tmp, seed=2)


def _mk_requests(rng, k, offset=False):
    """Random requests over a mix of trained (user0..5) and unknown users."""
    reqs = []
    for i in range(k):
        feats = [{"name": f, "term": "", "value": float(rng.normal())}
                 for f in FEATURES]
        name = f"user{int(rng.integers(0, N_USERS + 2))}"  # +2 unknown
        reqs.append(Request(uid=i, features=feats, ids={"userId": name},
                            offset=float(rng.normal()) if offset else 0.0))
    return reqs


def _batch_reference(bundle, reqs):
    """Score the same requests through the BATCH path (GameTransformer on a
    GameData built with the same index/entity maps)."""
    x = densify_features(reqs, bundle.index_maps, len(reqs))
    ids = np.asarray([bundle.entity_indexes["userId"].get(r.ids["userId"])
                      for r in reqs], np.int64)
    data = GameData(y=np.zeros(len(reqs)), features=x,
                    id_tags={"userId": ids})
    return np.asarray(GameTransformer(bundle.model, bundle.task).score(data))


# ---------------------------------------------------------------------------
# batcher
# ---------------------------------------------------------------------------
class TestBatcher:
    def test_pow2_ladder(self):
        assert pow2_bucket_ladder(64) == (1, 2, 4, 8, 16, 32, 64)
        assert pow2_bucket_ladder(5) == (1, 2, 4, 8)
        assert pow2_bucket_ladder(1) == (1,)
        with pytest.raises(ValueError):
            pow2_bucket_ladder(0)

    def test_pow2_ladder_min_bucket(self):
        assert pow2_bucket_ladder(64, min_bucket=8) == (8, 16, 32, 64)
        assert pow2_bucket_ladder(16, min_bucket=16) == (16,)
        # min_bucket above the top bucket would yield a ladder that cannot
        # hold the promised max_batch — must raise, not silently shrink
        with pytest.raises(ValueError, match="min_bucket"):
            pow2_bucket_ladder(5, min_bucket=32)

    def test_plan_pads_and_splits(self):
        b = BucketedBatcher(max_batch=8)
        assert [(mb.bucket, mb.real_rows) for mb in b.plan(3)] == [(4, 3)]
        # 21 = 2 full top buckets + padded tail
        plan = b.plan(21)
        assert [(mb.bucket, mb.real_rows) for mb in plan] == \
            [(8, 8), (8, 8), (8, 5)]
        assert b.padding_rows(plan) == 3
        assert b.plan(0) == []

    def test_custom_buckets_and_overflow(self):
        b = BucketedBatcher(bucket_sizes=[4, 16])
        assert b.bucket_for(3) == 4
        assert b.bucket_for(5) == 16
        with pytest.raises(ValueError):
            b.bucket_for(17)

    def test_request_from_json_forms(self):
        r = request_from_json({"uid": 3, "features": [
            {"name": "a", "term": "t", "value": 1.5}, ["b", 2.0],
            ["c", "u", 3.0]], "ids": {"userId": "u1"}, "offset": 0.25})
        assert r.uid == 3 and r.offset == 0.25
        assert r.features[1] == {"name": "b", "term": "", "value": 2.0}
        assert r.features[2] == {"name": "c", "term": "u", "value": 3.0}
        with pytest.raises(ValueError):
            request_from_json({"features": [["only-name"]]})


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
class TestMetrics:
    def test_latency_histogram_percentiles(self):
        h = LatencyHistogram()
        for ms in (1, 1, 1, 1, 1, 1, 1, 1, 1, 100):
            h.record(ms / 1000.0)
        assert h.count == 10
        assert h.percentile(0.5) <= 0.005
        assert h.percentile(0.99) <= h.max == pytest.approx(0.1)
        assert h.snapshot()["p50_s"] < h.snapshot()["p99_s"]

    def test_padding_waste_and_counters(self):
        m = ServingMetrics()
        m.observe_batch(bucket=8, real_rows=5, seconds=0.001)
        m.observe_batch(bucket=4, real_rows=4, seconds=0.002)
        assert m.padding_waste_ratio == pytest.approx(3 / 12)
        m.inc("requests", 9)
        snap = m.snapshot()
        assert snap["counters"]["requests"] == 9
        assert snap["counters"]["batches"] == 2
        assert "bucket_8" in snap["latency"]
        json.loads(m.to_json())  # serializable

    def test_bucket_occupancy_gauges(self):
        m = ServingMetrics()
        m.observe_batch(bucket=8, real_rows=5, seconds=0.001)
        m.observe_batch(bucket=8, real_rows=8, seconds=0.001)
        m.observe_batch(bucket=2, real_rows=1, seconds=0.001)
        occ = m.snapshot()["bucket_occupancy"]
        assert occ["bucket_8"] == pytest.approx(13 / 16)
        assert occ["bucket_2"] == pytest.approx(0.5)

    def test_hot_set_and_miss_rate_gauges(self):
        m = ServingMetrics()
        snap = m.snapshot()
        assert snap["hot_set_hit_rate"] == 0.0  # no lookups yet
        m.inc("hot_hits", 6)
        m.inc("lru_hits", 1)
        m.inc("cold_fetches", 1)
        m.inc("entity_misses", 2)
        snap = m.snapshot()
        assert snap["hot_set_hit_rate"] == pytest.approx(0.6)
        assert snap["entity_miss_rate"] == pytest.approx(0.2)


# ---------------------------------------------------------------------------
# typed model-load errors (satellite: clean failure for the swap path)
# ---------------------------------------------------------------------------
class TestModelLoadErrors:
    def test_missing_dir(self, tmp_path):
        with pytest.raises(ModelLoadError, match="does not exist"):
            load_model_bundle(str(tmp_path / "nope"))

    def test_no_metadata(self, tmp_path):
        d = tmp_path / "empty"
        d.mkdir()
        with pytest.raises(ModelLoadError, match="metadata.json"):
            load_model_bundle(str(d))

    def test_missing_index_map(self, model_dirs, tmp_path):
        broken = str(tmp_path / "no_idx")
        shutil.copytree(model_dirs[0], broken)
        for f in os.listdir(broken):
            if f.endswith((".idx", ".phidx")):
                os.remove(os.path.join(broken, f))
        with pytest.raises(ModelLoadError, match=r"\.idx"):
            load_model_bundle(broken)

    def test_missing_entity_index(self, model_dirs, tmp_path):
        broken = str(tmp_path / "no_entities")
        shutil.copytree(model_dirs[0], broken)
        for f in os.listdir(broken):
            if f.endswith(".entities.json"):
                os.remove(os.path.join(broken, f))
        with pytest.raises(ModelLoadError, match="entities.json"):
            load_model_bundle(broken)

    def test_corrupt_metadata(self, model_dirs, tmp_path):
        broken = str(tmp_path / "corrupt")
        shutil.copytree(model_dirs[0], broken)
        with open(os.path.join(broken, "best", "metadata.json"), "w") as f:
            f.write("{not json")
        with pytest.raises(ModelLoadError, match="corrupt|unreadable"):
            load_model_bundle(broken)

    def test_load_game_model_typed(self, tmp_path):
        from photon_ml_tpu.storage.model_io import load_game_model

        with pytest.raises(ModelLoadError):
            load_game_model(str(tmp_path), {}, {})


# ---------------------------------------------------------------------------
# serving == batch scoring (tentpole property test)
# ---------------------------------------------------------------------------
class TestServingParity:
    @pytest.mark.parametrize("device_capacity,lru_capacity", [
        (None, 4096),  # everything hot
        (3, 2),        # half the users cold, tiny LRU (forces evictions)
        (0, 1),        # everything cold: pure host-fallback scoring
    ])
    def test_bucketed_padded_matches_transformer(self, model_dirs,
                                                 device_capacity,
                                                 lru_capacity):
        """Property: for random batch sizes and entity mixes (trained, cold,
        and unknown entities), padded bucketed AOT scoring is BITWISE equal
        to unpadded GameTransformer batch scoring."""
        bundle = load_model_bundle(model_dirs[0])
        store = CoefficientStore.from_bundle(
            bundle, config=StoreConfig(device_capacity=device_capacity,
                                       lru_capacity=lru_capacity))
        engine = ScoringEngine(store, BucketedBatcher(max_batch=16))
        rng = np.random.default_rng(20260804)
        for trial in range(12):
            k = int(rng.integers(1, 14))
            reqs = _mk_requests(rng, k)
            got = engine.score_requests(reqs)
            want = _batch_reference(bundle, reqs)
            np.testing.assert_array_equal(
                got, want,
                err_msg=f"trial={trial} k={k} cap={device_capacity}")

    def test_offset_and_predict_mean(self, model_dirs):
        bundle = load_model_bundle(model_dirs[0])
        store = CoefficientStore.from_bundle(bundle)
        engine = ScoringEngine(store, BucketedBatcher(max_batch=8))
        rng = np.random.default_rng(5)
        reqs = _mk_requests(rng, 6, offset=True)
        offsets = np.asarray([r.offset for r in reqs])
        raw = engine.score_requests(reqs)
        np.testing.assert_array_equal(raw,
                                      _batch_reference(bundle, reqs) + offsets)
        mean = engine.score_requests(reqs, predict_mean=True)
        assert np.all((mean > 0) & (mean < 1))  # logistic inverse link
        np.testing.assert_allclose(mean, 1.0 / (1.0 + np.exp(-raw)))

    def test_cold_entity_lru_accounting(self, model_dirs):
        bundle = load_model_bundle(model_dirs[0])
        metrics = ServingMetrics()
        store = CoefficientStore.from_bundle(
            bundle, config=StoreConfig(device_capacity=2, lru_capacity=2),
            metrics=metrics)
        engine = ScoringEngine(store, BucketedBatcher(max_batch=8),
                               metrics=metrics)
        rng = np.random.default_rng(9)
        reqs = _mk_requests(rng, 8)
        engine.score_requests(reqs)
        engine.score_requests(reqs)  # repeats hit the LRU
        assert metrics.counter("cold_fetches") > 0
        assert metrics.counter("lru_hits") > 0
        assert metrics.counter("entity_misses") > 0  # the unknown users


# ---------------------------------------------------------------------------
# AOT compilation: warm once, zero recompiles after
# ---------------------------------------------------------------------------
class TestCompilationCache:
    def test_zero_recompiles_after_warm(self, model_dirs):
        bundle = load_model_bundle(model_dirs[0])
        engine = ScoringEngine(CoefficientStore.from_bundle(bundle),
                               BucketedBatcher(max_batch=8))
        n = engine.warm()
        assert n == len(engine.batcher.bucket_sizes) == 4
        rng = np.random.default_rng(3)
        for k in (1, 3, 3, 8, 5, 2, 7, 1):
            engine.score_requests(_mk_requests(rng, k))
        assert engine.compile_count == n  # acceptance: zero recompiles

    def test_lazy_compile_once_per_bucket(self, model_dirs):
        bundle = load_model_bundle(model_dirs[0])
        engine = ScoringEngine(CoefficientStore.from_bundle(bundle),
                               BucketedBatcher(max_batch=8))
        rng = np.random.default_rng(4)
        engine.score_requests(_mk_requests(rng, 3))  # bucket 4
        assert engine.compile_count == 1
        engine.score_requests(_mk_requests(rng, 4))  # same bucket
        assert engine.compile_count == 1
        engine.score_requests(_mk_requests(rng, 5))  # bucket 8
        assert engine.compile_count == 2


# ---------------------------------------------------------------------------
# hot swap
# ---------------------------------------------------------------------------
class TestHotSwap:
    def test_swap_serves_new_model(self, model_dirs):
        dir1, dir2 = model_dirs
        bundle2 = load_model_bundle(dir2)
        engine = ScoringEngine(
            CoefficientStore.from_bundle(load_model_bundle(dir1)),
            BucketedBatcher(max_batch=8))
        engine.warm()
        swapper = HotSwapper(engine)
        rng = np.random.default_rng(11)
        reqs = _mk_requests(rng, 6)
        s1 = engine.score_requests(reqs)
        gen1 = engine.store.generation
        compiles_before = engine.compile_count

        assert swapper.swap(dir2) is True
        assert engine.store.generation != gen1
        s2 = engine.score_requests(reqs)
        assert not np.array_equal(s1, s2)  # different model now serving
        np.testing.assert_array_equal(s2, _batch_reference(bundle2, reqs))
        # both versions have identical shapes -> the swap reused every
        # compiled executable (signature-keyed cache)
        assert engine.compile_count == compiles_before
        assert engine.metrics.counter("swaps") == 1

    def test_corrupt_swap_keeps_old_version(self, model_dirs, tmp_path):
        dir1, _ = model_dirs
        engine = ScoringEngine(
            CoefficientStore.from_bundle(load_model_bundle(dir1)),
            BucketedBatcher(max_batch=8))
        swapper = HotSwapper(engine)
        rng = np.random.default_rng(13)
        reqs = _mk_requests(rng, 5)
        s1 = engine.score_requests(reqs)
        gen1 = engine.store.generation

        # missing dir
        assert swapper.swap(str(tmp_path / "missing")) is False
        # structurally broken dir (metadata.json is garbage)
        broken = str(tmp_path / "broken")
        shutil.copytree(dir1, broken)
        with open(os.path.join(broken, "best", "metadata.json"), "w") as f:
            f.write("not json at all")
        assert swapper.swap(broken) is False

        assert engine.store.generation == gen1  # old version still serving
        np.testing.assert_array_equal(engine.score_requests(reqs), s1)
        assert engine.metrics.counter("swap_failures") == 2
        assert engine.metrics.counter("swaps") == 0

    def test_swap_async(self, model_dirs):
        dir1, dir2 = model_dirs
        engine = ScoringEngine(
            CoefficientStore.from_bundle(load_model_bundle(dir1)),
            BucketedBatcher(max_batch=8))
        swapper = HotSwapper(engine)
        gen1 = engine.store.generation
        t = swapper.swap_async(dir2)
        t.join(timeout=60)
        assert not t.is_alive()
        assert engine.store.generation != gen1


# ---------------------------------------------------------------------------
# the JSON-lines driver
# ---------------------------------------------------------------------------
class TestServeCli:
    def test_stream_with_swap_and_metrics(self, model_dirs, tmp_path, capsys):
        from photon_ml_tpu.cli import serve as serve_cli

        dir1, dir2 = model_dirs
        rng = np.random.default_rng(17)
        lines = []
        for i in range(5):
            feats = [[f, float(rng.normal())] for f in FEATURES]
            lines.append(json.dumps({
                "uid": i, "features": feats,
                "ids": {"userId": f"user{i % N_USERS}"}}))
        lines.append("")  # flush
        lines.append(json.dumps({"cmd": "metrics"}))
        lines.append(json.dumps({"cmd": "swap", "model_dir": dir2}))
        lines.append(json.dumps({
            "uid": 99, "features": [[f, 0.5] for f in FEATURES],
            "ids": {"userId": "user0"}}))
        req_file = tmp_path / "requests.jsonl"
        req_file.write_text("\n".join(lines) + "\n")
        metrics_file = str(tmp_path / "metrics.json")

        rc = serve_cli.run(["--model-dir", dir1, "--max-batch", "8",
                            "--requests", str(req_file),
                            "--metrics-json", metrics_file])
        assert rc == 0
        out = [json.loads(l) for l in
               capsys.readouterr().out.strip().splitlines()]
        scores = [o for o in out if "score" in o]
        assert [o["uid"] for o in scores] == [0, 1, 2, 3, 4, 99]
        assert all(np.isfinite(o["score"]) for o in scores)
        swaps = [o for o in out if "swap" in o]
        assert len(swaps) == 1
        assert swaps[0]["swap"] == "ok"
        assert swaps[0]["version"] == dir2
        assert swaps[0]["delta_version"] == 0  # fresh generation
        metrics_lines = [o for o in out if "counters" in o]
        assert len(metrics_lines) == 1
        exported = json.load(open(metrics_file))
        assert exported["counters"]["requests"] == 6
        assert exported["counters"]["swaps"] == 1

    def test_rejects_broken_model_dir(self, tmp_path, capsys):
        from photon_ml_tpu.cli import serve as serve_cli

        d = tmp_path / "not_a_model"
        d.mkdir()
        assert serve_cli.run(["--model-dir", str(d)]) == 1
