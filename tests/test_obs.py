"""photonscope tests (photon_ml_tpu/obs/*, the ServingMetrics facade, the
Timed/EventEmitter bridges, and the end-to-end traced CLI paths).

The contracts under test (ISSUE 5):
  - Tracer: span nesting/ordering within and across concurrent threads,
    ring-buffer wraparound (newest spans win, no tearing), Chrome
    ``trace_event`` export round-trip (valid JSON, monotonic ts, pid/tid
    present, children contained in parents), instant events, the opt-in
    device fence.
  - MetricsRegistry: counter/gauge/histogram families, label aliasing
    (keyword order never splits a series), Prometheus text exposition
    (golden), JSON snapshot, concurrent increments.
  - JaxRuntimeProbe: compile-counter parity with
    ``ScoringEngine.compile_count``, transfer-byte accounting at the
    ``utils/transfer`` chunk path.
  - ServingMetrics facade: ``snapshot()`` wire format byte-compatible with
    PR 4 (key set + semantics BENCH_SERVING history depends on).
  - One trace through ``CoordinateDescent.run`` (2 coordinates x 2
    iterations, nested solve/score spans) and one through ``cli/serve.py``
    (submit -> flush -> resolve -> execute -> respond), both valid Chrome
    trace JSON.
"""

import io
import json
import os
import sys
import threading

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from photon_ml_tpu import obs
from photon_ml_tpu.obs.registry import MetricsRegistry
from photon_ml_tpu.obs.trace import Tracer
from photon_ml_tpu.serving.metrics import ServingMetrics
from photon_ml_tpu.utils import Event, EventEmitter, Timed


@pytest.fixture
def tracer():
    """A fresh enabled tracer installed as the process default; restored
    (and tracing re-disabled) afterwards so tests never leak spans."""
    t = Tracer(capacity=512, enabled=True)
    prev = obs.set_tracer(t)
    try:
        yield t
    finally:
        obs.set_tracer(prev)


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------
class TestTracer:
    def test_span_nesting_and_ordering(self, tracer):
        with obs.span("outer", who="a"):
            with obs.span("mid"):
                with obs.span("inner"):
                    pass
            with obs.span("mid2"):
                pass
        recs = {r["name"]: r for r in tracer.records()}
        assert set(recs) == {"outer", "mid", "inner", "mid2"}
        assert recs["mid"]["parent"] == recs["outer"]["id"]
        assert recs["inner"]["parent"] == recs["mid"]["id"]
        assert recs["mid2"]["parent"] == recs["outer"]["id"]
        assert recs["outer"]["parent"] == 0
        assert recs["outer"]["attrs"] == {"who": "a"}
        # children record (at exit) before parents; ts says inner started last
        assert recs["inner"]["ts_ns"] >= recs["mid"]["ts_ns"] >= \
            recs["outer"]["ts_ns"]

    def test_disabled_is_silent_noop(self):
        t = Tracer(capacity=16, enabled=False)
        prev = obs.set_tracer(t)
        try:
            with obs.span("nothing", k=1):
                obs.instant("tick")
        finally:
            obs.set_tracer(prev)
        assert t.records() == []

    def test_ring_wraparound_keeps_newest(self, tracer):
        small = Tracer(capacity=8, enabled=True)
        for i in range(20):
            with small.span(f"s{i}"):
                pass
        recs = small.records()
        assert len(recs) == 8  # exactly the ring capacity survives
        assert [r["name"] for r in recs] == [f"s{i}" for i in range(12, 20)]
        # export is still valid JSON with monotonic ts
        trace = json.loads(json.dumps(small.chrome_trace()))
        ts = [e["ts"] for e in trace["traceEvents"]]
        assert ts == sorted(ts)

    def test_concurrent_threads_nest_independently(self, tracer):
        n_threads, n_spans = 8, 30
        barrier = threading.Barrier(n_threads)

        def work(k):
            barrier.wait()
            for i in range(n_spans):
                with obs.span("parent", thread=k, i=i):
                    with obs.span("child", thread=k, i=i):
                        pass

        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        recs = tracer.records()
        assert len(recs) == n_threads * n_spans * 2
        parents = {r["id"]: r for r in recs if r["name"] == "parent"}
        children = [r for r in recs if r["name"] == "child"]
        assert len(children) == n_threads * n_spans
        for c in children:
            p = parents[c["parent"]]  # every child belongs to a parent...
            assert p["tid"] == c["tid"]  # ...on its OWN thread
            assert p["attrs"]["thread"] == c["attrs"]["thread"]
            assert p["attrs"]["i"] == c["attrs"]["i"]
            assert p["ts_ns"] <= c["ts_ns"]
            assert c["ts_ns"] + c["dur_ns"] <= p["ts_ns"] + p["dur_ns"]

    def test_chrome_export_round_trip(self, tracer):
        with obs.span("a", x=1):
            with obs.span("b"):
                pass
        obs.instant("evt", y=2)
        raw = json.dumps(tracer.chrome_trace())
        trace = json.loads(raw)  # valid JSON
        meta = [e for e in trace["traceEvents"] if e["ph"] == "M"]
        events = [e for e in trace["traceEvents"] if e["ph"] != "M"]
        assert len(events) == 3
        pid = os.getpid()
        # photonpulse merge keys on the metadata rows: a process_name for
        # the Perfetto process lane, thread_name per recording thread
        assert any(m["name"] == "process_name" for m in meta)
        assert any(m["name"] == "thread_name" for m in meta)
        for m in meta:
            assert m["pid"] == pid and m["ts"] == 0
        for e in events:
            assert e["pid"] == pid and e["tid"] and "ts" in e
            assert e["ph"] in ("X", "i")
            if e["ph"] == "X":
                assert e["dur"] >= 0
        ts = [e["ts"] for e in trace["traceEvents"]]
        assert ts == sorted(ts)  # metadata (ts 0) first, then monotonic
        by_name = {e["name"]: e for e in events}
        assert by_name["b"]["args"]["parent_id"] == \
            by_name["a"]["args"]["span_id"]
        assert by_name["evt"]["ph"] == "i" and by_name["evt"]["args"]["y"] == 2
        assert trace["otherData"]["pid"] == pid

    def test_device_sync_runs_fence(self, tracer):
        fences = []
        tracer.set_device_fence(lambda: fences.append(1))
        with obs.span("plain"):
            pass
        assert fences == []  # no fence unless asked
        with obs.span("synced", device_sync=True):
            pass
        assert len(fences) == 2  # entry + exit

    def test_clear(self, tracer):
        with obs.span("x"):
            pass
        assert tracer.records()
        tracer.clear()
        assert tracer.records() == []


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_counters_and_label_aliasing(self):
        r = MetricsRegistry()
        r.inc("requests_total", bucket="64", model="a")
        r.inc("requests_total", 2, model="a", bucket="64")  # kwarg order!
        r.inc("requests_total", bucket="32")
        assert r.counter("requests_total", bucket="64", model="a") == 3
        assert r.counter("requests_total", bucket="32") == 1
        assert r.counter("requests_total") == 0  # unlabeled is its own series
        series = r.counter_series("requests_total")
        assert len(series) == 2  # aliased labels collapsed

    def test_gauges_set_and_add(self):
        r = MetricsRegistry()
        r.set_gauge("temp", 3.5, zone="hbm")
        r.set_gauge("temp", 4.0, zone="hbm")
        r.add_gauge("phase_s", 1.0, phase="warm")
        r.add_gauge("phase_s", 0.5, phase="warm")
        assert r.gauge("temp", zone="hbm") == 4.0
        assert r.gauge("phase_s", phase="warm") == pytest.approx(1.5)
        assert r.gauge("missing") is None

    def test_histograms(self):
        r = MetricsRegistry()
        for ms in (1, 2, 3):
            r.observe("lat", ms / 1000.0, key="bucket_8")
        snap = r.histogram_snapshot("lat", key="bucket_8")
        assert snap["count"] == 3
        assert 0 < snap["p50_s"] <= snap["p99_s"] <= snap["max_s"]
        series = r.histogram_series("lat")
        assert list(series) == [(("key", "bucket_8"),)]

    def test_json_snapshot(self):
        r = MetricsRegistry()
        r.inc("c", 2, a="1")
        r.set_gauge("g", 0.5)
        r.observe("h", 0.001)
        snap = json.loads(r.to_json())
        assert snap["counters"] == {'c{a="1"}': 2}
        assert snap["gauges"] == {"g": 0.5}
        assert snap["histograms"]["h"]["count"] == 1

    def test_prometheus_golden(self):
        r = MetricsRegistry()
        r.inc("requests_total", 3, bucket="64")
        r.inc("requests_total", 1, bucket="8")
        r.set_gauge("hot.rate", 0.75)  # "." sanitized to "_"
        text = r.to_prometheus()
        lines = text.splitlines()
        assert lines[0] == "# TYPE requests_total counter"
        assert 'requests_total{bucket="8"} 1' in lines
        assert 'requests_total{bucket="64"} 3' in lines
        assert "# TYPE hot_rate gauge" in lines
        assert "hot_rate 0.75" in lines
        assert text.endswith("\n")

    def test_prometheus_histogram_exposition(self):
        r = MetricsRegistry()
        r.observe("lat_s", 0.001, key="b8")
        text = r.to_prometheus()
        assert "# TYPE lat_s histogram" in text
        assert 'lat_s_bucket{key="b8",le="+Inf"} 1' in text
        assert 'lat_s_count{key="b8"} 1' in text
        assert 'lat_s_sum{key="b8"} 0.001' in text
        # cumulative: the 1.024ms bin already holds the observation
        assert 'lat_s_bucket{key="b8",le="0.001024"} 1' in text
        assert 'lat_s_bucket{key="b8",le="0.000512"} 0' in text

    def test_concurrent_increments_sum(self):
        r = MetricsRegistry()
        n_threads, n_incs = 8, 2000

        def work():
            for _ in range(n_incs):
                r.inc("hits", shard="s")

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert r.counter("hits", shard="s") == n_threads * n_incs


# ---------------------------------------------------------------------------
# jax runtime probe
# ---------------------------------------------------------------------------
@pytest.fixture
def fresh_registry():
    """A fresh process-default registry (the lazily-bound probe target);
    restored afterwards."""
    r = MetricsRegistry()
    prev = obs.set_registry(r)
    try:
        yield r
    finally:
        obs.set_registry(prev)


class TestProbe:
    def test_compile_span_counts_and_times(self, fresh_registry):
        probe = obs.get_probe()
        with probe.compile_span("test.site", bucket=4):
            pass
        assert probe.compile_count("test.site") == 1
        assert probe.compile_count() == 1
        hist = fresh_registry.histogram_snapshot("jax_compile_seconds",
                                                 site="test.site")
        assert hist["count"] == 1

    def test_compile_counter_parity_with_engine(self, fresh_registry):
        from test_serving_async import _engine, _req

        probe = obs.get_probe()
        eng, _, _ = _engine(max_batch=4)
        assert probe.compile_count("serving.engine") == eng.compile_count > 0
        rng = np.random.default_rng(3)
        eng.score_requests([_req(rng, uid=i) for i in range(5)])
        # zero-recompile guarantee holds in BOTH ledgers
        assert probe.compile_count("serving.engine") == eng.compile_count

    def test_transfer_accounting_device_put(self, fresh_registry):
        from photon_ml_tpu.utils.transfer import device_put_counted

        arr = np.ones((64, 128), np.float32)
        out = device_put_counted(arr, "bfloat16")
        np.testing.assert_array_equal(np.asarray(out, np.float32), arr)
        probe = obs.get_probe()
        # counted at the width that crossed: narrowed on the host first
        assert probe.transfer_bytes("h2d") == arr.nbytes // 2
        assert fresh_registry.counter("jax_transfers_total", direction="h2d",
                                      site="device_put") == 1
        device_put_counted(out)  # already resident: nothing crosses
        assert probe.transfer_bytes("h2d") == arr.nbytes // 2

    def test_compile_cache_gauge(self, fresh_registry, monkeypatch, tmp_path):
        import jax

        from photon_ml_tpu.utils import compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compilation_cache() == str(tmp_path)
        assert fresh_registry.gauge("xla_compile_cache_enabled") == 1
        # jax's own switch is the only off switch left
        before = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            assert compile_cache.enable_compilation_cache() is None
        finally:
            jax.config.update("jax_enable_compilation_cache", before)
        assert fresh_registry.gauge("xla_compile_cache_enabled") == 0


# ---------------------------------------------------------------------------
# ServingMetrics facade: PR-4 wire-format regression
# ---------------------------------------------------------------------------
PR4_SNAPSHOT_KEYS = {
    "counters", "qps", "uptime_s", "padding_waste_ratio",
    "padded_rows_launched", "real_rows_launched", "bucket_occupancy",
    "hot_set_hit_rate", "entity_miss_rate", "latency", "phases_s",
}
PR4_HISTOGRAM_KEYS = {"count", "mean_s", "p50_s", "p99_s", "min_s", "max_s"}


class TestServingMetricsFacade:
    def test_snapshot_keys_byte_compatible_with_pr4(self):
        m = ServingMetrics()
        m.inc("requests", 9)
        m.observe_batch(bucket=8, real_rows=5, seconds=0.001)
        m.observe_latency("request", 0.002)
        m.phase("warm", 0.5)
        snap = m.snapshot()
        assert set(snap) == PR4_SNAPSHOT_KEYS
        assert set(snap["latency"]["bucket_8"]) == PR4_HISTOGRAM_KEYS
        assert snap["counters"] == {"requests": 9, "batches": 1,
                                    "scored_samples": 5}
        assert snap["padded_rows_launched"] == 8
        assert snap["real_rows_launched"] == 5
        assert snap["padding_waste_ratio"] == pytest.approx(3 / 8)
        assert snap["bucket_occupancy"] == {"bucket_8": pytest.approx(5 / 8)}
        assert snap["phases_s"] == {"warm": pytest.approx(0.5)}
        json.dumps(snap)  # wire-serializable

    def test_bench_serving_fields_still_derivable(self):
        """The exact counter names BENCH_SERVING diffs across epochs."""
        m = ServingMetrics()
        for name in ("hot_hits", "lru_hits", "cold_fetches", "entity_misses",
                     "batches", "flushes_full", "flushes_deadline",
                     "flushes_forced", "hot_promotions", "hot_demotions",
                     "rebalances"):
            m.inc(name)
        snap = m.snapshot()
        for name in ("hot_hits", "entity_misses", "flushes_full",
                     "hot_promotions", "rebalances"):
            assert snap["counters"][name] == 1
        assert snap["hot_set_hit_rate"] == pytest.approx(0.25)
        assert snap["entity_miss_rate"] == pytest.approx(0.25)

    def test_facade_backed_by_registry(self):
        m = ServingMetrics()
        m.inc("requests", 3)
        m.observe_batch(bucket=4, real_rows=2, seconds=0.001)
        # the SAME data is queryable/scrapable through the registry
        assert m.registry.counter("requests") == 3
        assert m.registry.counter("serving_batches_total", bucket=4) == 1
        prom = m.to_prometheus()
        assert "requests 3" in prom
        assert 'serving_batches_total{bucket="4"} 1' in prom


# ---------------------------------------------------------------------------
# Timed + EventEmitter bridges
# ---------------------------------------------------------------------------
class TestBridges:
    def test_timed_emits_span(self, tracer):
        sunk = {}
        with Timed("my.phase", sink=lambda k, s: sunk.update({k: s})):
            pass
        recs = [r for r in tracer.records() if r["name"] == "my.phase"]
        assert len(recs) == 1 and recs[0]["ph"] == "X"
        assert "my.phase" in sunk  # the sink path still works

    def test_event_emitter_bridges_instants(self, tracer):
        seen = []
        em = EventEmitter()
        em.register(lambda e: seen.append(e))
        em.emit("training_start", task="logistic")
        assert len(seen) == 1 and isinstance(seen[0], Event)
        recs = [r for r in tracer.records() if r["name"] == "training_start"]
        assert len(recs) == 1 and recs[0]["ph"] == "i"
        assert recs[0]["attrs"] == {"task": "logistic"}

    def test_event_emitter_opt_out(self, tracer):
        em = EventEmitter(trace=False)
        em.emit("noisy_tick")
        assert [r for r in tracer.records() if r["name"] == "noisy_tick"] == []


# ---------------------------------------------------------------------------
# descent trace: nested spans through CoordinateDescent.run
# ---------------------------------------------------------------------------
class TestDescentTrace:
    def test_descent_run_traces_nested_updates(self, tracer, tmp_path):
        from test_serving import _write_fixture
        from photon_ml_tpu.cli import train as train_cli

        data = str(tmp_path / "t.avro")
        val = str(tmp_path / "v.avro")
        _write_fixture(data, n=120, seed=5)
        _write_fixture(val, n=60, seed=6)
        trace_path = str(tmp_path / "trace.json")
        metrics_path = str(tmp_path / "metrics.json")
        reg = MetricsRegistry()
        prev = obs.set_registry(reg)
        try:
            # --fused off pins the host-paced CoordinateDescent.run — the
            # per-update span nesting under test lives there (validated
            # fits otherwise run as one fused program with a single span)
            rc = train_cli.run([
                "--fused", "off",
                "--train-data", data, "--validation-data", val,
                "--evaluators", "auc", "--feature-shards", "all",
                "--coordinate", "name=fixed,feature.shard=all,reg.weights=1",
                "--coordinate", "name=user,random.effect.type=userId,"
                                "feature.shard=all,reg.weights=1",
                "--id-tags", "userId", "--coordinate-descent-iterations", "2",
                "--output-dir", str(tmp_path / "out"),
                "--trace-out", trace_path, "--metrics-out", metrics_path])
        finally:
            obs.set_registry(prev)
        assert rc == 0
        trace = json.load(open(trace_path))  # valid JSON on disk
        events = [e for e in trace["traceEvents"] if e["ph"] != "M"]
        pid = os.getpid()
        assert all(e["pid"] == pid and e["tid"] for e in events)
        updates = [e for e in events if e["name"] == "descent.update"]
        # 2 coordinates x 2 iterations
        assert len(updates) == 4
        assert {(e["args"]["iteration"], e["args"]["coordinate"])
                for e in updates} == {(0, "fixed"), (0, "user"),
                                      (1, "fixed"), (1, "user")}
        by_id = {e["args"]["span_id"]: e for e in events}
        for name in ("descent.solve", "descent.score"):
            children = [e for e in events if e["name"] == name]
            assert len(children) == 4
            for c in children:
                p = by_id[c["args"]["parent_id"]]
                assert p["name"] == "descent.update"
                assert p["ts"] <= c["ts"]
                assert c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 0.01
        ts = [e["ts"] for e in events]
        assert ts == sorted(ts)
        # lifecycle instants bridged onto the same timeline
        assert {"training_start", "fit_start", "training_end"} <= \
            {e["name"] for e in events if e["ph"] == "i"}
        # DescentHistory bookkeeping landed in the registry
        snap = json.load(open(metrics_path))
        assert snap["counters"]['descent_updates_total{coordinate="fixed"}'] \
            == 2
        assert snap["counters"]['descent_updates_total{coordinate="user"}'] \
            == 2
        assert snap["histograms"][
            'descent_update_seconds{coordinate="user"}']["count"] == 2


# ---------------------------------------------------------------------------
# serve trace: submit -> flush -> resolve -> execute -> respond
# ---------------------------------------------------------------------------
class TestServeCliTrace:
    def test_serve_stream_trace_and_prometheus(self, tracer, tmp_path):
        from test_serving import _train
        from photon_ml_tpu.cli import serve as serve_cli

        model_dir = _train(tmp_path, seed=7)
        lines = []
        for i in range(6):
            lines.append(json.dumps({
                "uid": i, "features": [["g0", 0.3], ["ux", 0.1]],
                "ids": {"userId": f"user{i % 6}"}}))
        lines.append(json.dumps({"cmd": "trace"}))
        lines.append(json.dumps({"cmd": "metrics", "format": "prometheus"}))
        lines.append(json.dumps({"cmd": "metrics"}))
        req_file = str(tmp_path / "reqs.jsonl")
        with open(req_file, "w") as f:
            f.write("\n".join(lines) + "\n")
        trace_path = str(tmp_path / "serve_trace.json")

        import contextlib

        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = serve_cli.run(["--model-dir", model_dir,
                                "--requests", req_file,
                                "--trace", "--trace-out", trace_path,
                                "--max-batch", "4"])
        assert rc == 0
        out = [json.loads(l) for l in buf.getvalue().splitlines()]
        scores = [o for o in out if "score" in o]
        assert len(scores) == 6 and [o["uid"] for o in scores] == list(range(6))

        trace_line = [o for o in out if "traceEvents" in o]
        assert len(trace_line) == 1
        events = [e for e in trace_line[0]["traceEvents"]
                  if e["ph"] != "M"]
        names = {e["name"] for e in events}
        # the whole request path is on the timeline
        assert {"serve.submit", "serve.flush", "store.resolve",
                "serve.execute", "serve.respond"} <= names
        assert {"jax.compile"} <= names  # warm compiles traced too
        # resolve nests inside the executing micro-batch, which nests
        # inside the batcher flush (on the worker thread)
        by_id = {e["args"]["span_id"]: e for e in events}
        resolves = [e for e in events if e["name"] == "store.resolve"]
        assert resolves
        for r in resolves:
            parent = by_id[r["args"]["parent_id"]]
            assert parent["name"] == "serve.execute"
            gp = by_id[parent["args"]["parent_id"]]
            assert gp["name"] == "serve.flush"
            assert gp["tid"] == parent["tid"] == r["tid"]
        # submits happen on the stream thread, flushes on the worker
        submit_tids = {e["tid"] for e in events if e["name"] == "serve.submit"}
        flush_tids = {e["tid"] for e in events if e["name"] == "serve.flush"}
        assert submit_tids and flush_tids and submit_tids != flush_tids
        ts = [e["ts"] for e in events]
        assert ts == sorted(ts)
        # exported file matches the wire dump's shape
        exported = json.load(open(trace_path))
        assert {e["name"] for e in exported["traceEvents"]} >= names

        prom = [o for o in out if "prometheus" in o]
        assert len(prom) == 1
        assert "# TYPE requests counter" in prom[0]["prometheus"]
        snap = [o for o in out if "counters" in o and "qps" in o]
        assert len(snap) == 1
        assert set(snap[0]) == PR4_SNAPSHOT_KEYS


# ---------------------------------------------------------------------------
# the disabled span guard: every hot-path call site pays it on every request
# ---------------------------------------------------------------------------
def test_disabled_span_is_cheaper_than_a_recording_one():
    """Best of five windows of 2,000 calls each way, also under a bound
    trace context (propagation wired in must not make the guard record)."""
    import time

    from photon_ml_tpu.obs.pulse import context as pulse_ctx

    def per_call_ns(n=2000):
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter_ns()
            for _ in range(n):
                with obs.span("guard.op", bucket=64):
                    pass
            best = min(best, (time.perf_counter_ns() - t0) / n)
        return best

    tracer = Tracer(capacity=4096, enabled=False)
    prev = obs.set_tracer(tracer)
    try:
        disabled_ns = per_call_ns()
        with pulse_ctx.bind(pulse_ctx.mint()):
            disabled_bound_ns = per_call_ns()
        assert not tracer.records()  # the guard recorded nothing
        tracer.enable()
        enabled_ns = per_call_ns()
    finally:
        obs.set_tracer(prev)
    assert 0 < disabled_ns < enabled_ns
    assert 0 < disabled_bound_ns < enabled_ns
