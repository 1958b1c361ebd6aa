"""photonwatch tests (photon_ml_tpu/obs/watch/*, the federation surfaces
on the metrics endpoint, the admission fleet-pressure latch, and the
fleetwatch CLI).

The contracts under test (ISSUE 20):
  - DeltaExporter: frame 1 is the full registry, later frames carry only
    changed series; histogram change detection keys on (count, total).
  - FleetView: counters summed across processes, gauges kept per process
    under an added ``process=`` label, histograms bucket-merged on a
    shared ladder and degraded to per-process series on a mismatch;
    delta-stream sequence gaps drop the frame and mark the source for
    resync; staleness reported per source.
  - SLOEngine: multi-window burn-rate math for availability (counter
    quotient) and latency (histogram ladder above-threshold) objectives,
    cold-start burns are 0.0, alert latch edges (firing then resolved,
    exactly once each), ``fleet_slo_burn_rate`` gauges published, the
    firing edge dumps the flight recorder.
  - ``GET /watchz`` always-full pull and ``GET /fleetz`` on a
    FleetView-wired endpoint (404 without one).
  - AdmissionController ``fleet_burn_budget``: shed with reason
    ``fleet_pressure`` while the published burn gauge is over budget,
    hysteresis release at the resume watermark.
  - ``export_build_info``: ``photon_build_info{version=,role=}`` and
    ``process_start_time_seconds`` in every process registry.
  - tools/fleetwatch.py: ``poll_once`` over live HTTP, ``--once`` snapshot
    to stdout with exit status tied to peer reachability.
"""

import json
import os
import socket
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from photon_ml_tpu.obs import pulse
from photon_ml_tpu.obs.registry import (MetricsRegistry, export_build_info,
                                        process_start_time)
from photon_ml_tpu.obs.watch import (SLO, DeltaExporter, FleetView,
                                     SLOEngine, SLOEvalThread, load_slos)
from photon_ml_tpu.serving.frontend.admission import (SHED_FLEET,
                                                      AdmissionConfig,
                                                      AdmissionController)
from photon_ml_tpu.serving.frontend.metrics_http import \
    ThreadedMetricsEndpoint
from photon_ml_tpu.serving.metrics import ServingMetrics


def _http_get(port, path):
    with socket.create_connection(("127.0.0.1", port), timeout=10) as s:
        s.sendall(f"GET {path} HTTP/1.0\r\n\r\n".encode())
        data = b""
        while True:
            chunk = s.recv(65536)
            if not chunk:
                break
            data += chunk
    status = int(data.split(b" ", 2)[1])
    return status, data.split(b"\r\n\r\n", 1)[1]


# ---------------------------------------------------------------------------
# federation: DeltaExporter
# ---------------------------------------------------------------------------
class TestDeltaExporter:
    def test_first_frame_is_full(self):
        reg = MetricsRegistry()
        reg.inc("a_total", 3)
        reg.set_gauge("depth", 7, queue="q0")
        reg.observe("lat_s", 0.01)
        exp = DeltaExporter(reg, label="p0")
        f = exp.frame()
        assert f["full"] and f["seq"] == 1 and f["label"] == "p0"
        assert [c[0] for c in f["counters"]] == ["a_total"]
        assert f["counters"][0][2] == 3
        assert f["gauges"][0][:2] == ["depth", [["queue", "q0"]]]
        assert f["histograms"][0][0] == "lat_s"
        assert f["histograms"][0][2]["count"] == 1

    def test_delta_frames_carry_only_changes(self):
        reg = MetricsRegistry()
        reg.inc("a_total")
        reg.inc("b_total")
        reg.observe("lat_s", 0.01)
        exp = DeltaExporter(reg)
        exp.frame()
        # nothing moved: empty delta
        f2 = exp.frame()
        assert not f2["full"] and f2["seq"] == 2
        assert f2["counters"] == [] and f2["histograms"] == []
        # one counter and the histogram move; b_total stays out
        reg.inc("a_total")
        reg.observe("lat_s", 0.02)
        f3 = exp.frame()
        assert [c[0] for c in f3["counters"]] == ["a_total"]
        assert f3["counters"][0][2] == 2
        assert [h[0] for h in f3["histograms"]] == ["lat_s"]
        assert f3["histograms"][0][2]["count"] == 2


# ---------------------------------------------------------------------------
# federation: FleetView merge semantics
# ---------------------------------------------------------------------------
class TestFleetView:
    def _frame(self, reg, label):
        return DeltaExporter(reg, label=label).frame()

    def test_counters_sum_across_processes(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.inc("req_total", 2)
        b.inc("req_total", 5)
        view = FleetView()
        assert view.ingest("a", self._frame(a, "a"))
        assert view.ingest("b", self._frame(b, "b"))
        assert sum(view.registry.counter_series("req_total").values()) == 7

    def test_gauges_keep_process_identity(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.set_gauge("queue_depth", 3)
        b.set_gauge("queue_depth", 11)
        view = FleetView()
        view.ingest("a", self._frame(a, "a"))
        view.ingest("b", self._frame(b, "b"))
        series = view.registry.gauge_series("queue_depth")
        by_proc = {dict(lk)["process"]: v for lk, v in series.items()}
        assert by_proc == {"a": 3, "b": 11}

    def test_histograms_bucket_merge_on_shared_ladder(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.observe("lat_s", 0.001)
        a.observe("lat_s", 0.002)
        b.observe("lat_s", 0.004)
        view = FleetView()
        view.ingest("a", self._frame(a, "a"))
        view.ingest("b", self._frame(b, "b"))
        states = view.registry.histogram_state_series("lat_s")
        assert len(states) == 1
        st = next(iter(states.values()))
        assert st["count"] == 3
        assert st["total"] == pytest.approx(0.007)

    def test_ladder_mismatch_degrades_to_per_process(self):
        a = MetricsRegistry()
        a.observe("lat_s", 0.001)
        fa = self._frame(a, "a")
        # hand-craft a peer whose ladder disagrees: merge must NOT guess
        fb = json.loads(json.dumps(fa))
        fb["label"] = "b"
        fb["histograms"][0][2]["bounds"] = \
            [x * 2 for x in fb["histograms"][0][2]["bounds"]]
        view = FleetView()
        view.ingest("a", fa)
        view.ingest("b", fb)
        states = view.registry.histogram_state_series("lat_s")
        assert len(states) == 2
        procs = {dict(lk)["process"] for lk in states}
        assert procs == {"a", "b"}

    def test_seq_gap_drops_frame_and_marks_resync(self):
        reg = MetricsRegistry()
        reg.inc("a_total")
        exp = DeltaExporter(reg, label="p")
        view = FleetView()
        assert view.ingest("p", exp.frame())       # seq 1 (full)
        reg.inc("a_total")
        exp.frame()                                # seq 2 lost in transit
        reg.inc("a_total")
        f3 = exp.frame()                           # seq 3 arrives
        assert view.ingest("p", f3) is False
        snap = view.fleet_snapshot()
        assert snap["sources"]["p"]["resyncs"] == 1
        # merged view still holds the pre-gap value, not a hole
        assert sum(view.registry.counter_series("a_total").values()) == 1

    def test_staleness_reported_per_source(self):
        reg = MetricsRegistry()
        reg.inc("a_total")
        view = FleetView(stale_after_s=0.05)
        view.ingest("fresh", self._frame(reg, "fresh"))
        frame = self._frame(reg, "old")
        frame["at_unix"] = time.time() - 10.0
        view.ingest("old", frame)
        snap = view.fleet_snapshot()
        assert snap["sources"]["old"]["stale"] is True
        assert snap["sources"]["fresh"]["stale"] is False

    def test_watchz_full_pull_is_ingestible(self):
        m = ServingMetrics()
        m.registry.inc("front_requests_total", 4)
        state = m.watch_state()
        assert state["full"] is True
        view = FleetView()
        assert view.ingest("p", state)
        assert sum(view.registry.counter_series(
            "front_requests_total").values()) == 4


# ---------------------------------------------------------------------------
# SLO engine
# ---------------------------------------------------------------------------
def _avail_slo(**kw):
    base = dict(name="avail", objective=0.99, kind="availability",
                total="req_total", bad=("shed_total",),
                fast=(5.0, 20.0), slow=(10.0, 40.0),
                fast_burn=2.0, slow_burn=1.5)
    base.update(kw)
    return SLO(**base)


class TestSLOEngine:
    def test_cold_start_burns_zero(self):
        reg = MetricsRegistry()
        reg.inc("req_total", 100)
        eng = SLOEngine([_avail_slo()])
        assert eng.evaluate(reg, now=100.0) == []
        gauges = eng._publish or reg
        burn = reg.gauge_series("fleet_slo_burn_rate")
        assert list(burn.values()) == [0.0]

    def test_availability_fire_and_resolve_edges(self):
        reg = MetricsRegistry()
        eng = SLOEngine([_avail_slo()])
        now = 100.0
        # healthy traffic long enough to anchor every window
        for _ in range(50):
            reg.inc("req_total", 10)
            eng.evaluate(reg, now=now)
            now += 1.0
        assert eng.events() == []
        # burn: half the traffic shed -> ratio 0.5, burn 50 over every
        # window once the short anchors land
        for _ in range(30):
            reg.inc("req_total", 10)
            reg.inc("shed_total", 5)
            eng.evaluate(reg, now=now)
            now += 1.0
        assert eng.firing() == ["avail"]
        # heal: clean traffic until every window drains
        for _ in range(50):
            reg.inc("req_total", 10)
            eng.evaluate(reg, now=now)
            now += 1.0
        assert eng.firing() == []
        states = [(e["slo"], e["state"]) for e in eng.events()]
        assert states == [("avail", "firing"), ("avail", "resolved")]

    def test_latency_counts_above_threshold_from_ladder(self):
        reg = MetricsRegistry()
        slo = SLO(name="lat", objective=0.9, kind="latency",
                  histogram="lat_s", threshold_s=0.016,
                  fast=(5.0, 20.0), slow=(10.0, 40.0),
                  fast_burn=2.0, slow_burn=1.5)
        eng = SLOEngine([slo])
        now = 100.0
        for _ in range(30):
            reg.observe("lat_s", 0.002)
            eng.evaluate(reg, now=now)
            now += 1.0
        assert eng.events() == []
        for _ in range(30):
            reg.observe("lat_s", 0.05)       # above threshold: bad
            eng.evaluate(reg, now=now)
            now += 1.0
        assert eng.firing() == ["lat"]

    def test_publishes_burn_gauges_into_publish_registry(self):
        source, target = MetricsRegistry(), MetricsRegistry()
        eng = SLOEngine([_avail_slo()], publish=target)
        eng.evaluate(source, now=100.0)
        assert dict(target.gauge_series("fleet_slo_burn_rate"))
        assert source.gauge_series("fleet_slo_burn_rate") == {}

    def test_duplicate_slo_names_rejected(self):
        with pytest.raises(ValueError):
            SLOEngine([_avail_slo(), _avail_slo()])

    def test_firing_edge_dumps_flight_recorder(self, tmp_path):
        prev = pulse.set_flight(pulse.FlightRecorder(str(tmp_path)))
        try:
            reg = MetricsRegistry()
            eng = SLOEngine([_avail_slo()])
            now = 100.0
            for _ in range(50):
                reg.inc("req_total", 10)
                eng.evaluate(reg, now=now)
                now += 1.0
            for _ in range(30):
                reg.inc("req_total", 10)
                reg.inc("shed_total", 8)
                eng.evaluate(reg, now=now)
                now += 1.0
            assert eng.firing() == ["avail"]
            recorder = pulse.get_flight()
            assert any("slo_burn" in d["reason"]
                       for d in recorder.index())
        finally:
            pulse.set_flight(prev)

    def test_on_alert_callback_sees_both_edges(self):
        seen = []
        reg = MetricsRegistry()
        eng = SLOEngine([_avail_slo()], on_alert=seen.append)
        now = 100.0
        for _ in range(50):
            reg.inc("req_total", 10)
            eng.evaluate(reg, now=now)
            now += 1.0
        for _ in range(30):
            reg.inc("req_total", 10)
            reg.inc("shed_total", 8)
            eng.evaluate(reg, now=now)
            now += 1.0
        for _ in range(60):
            reg.inc("req_total", 10)
            eng.evaluate(reg, now=now)
            now += 1.0
        assert [e["state"] for e in seen] == ["firing", "resolved"]

    def test_load_slos_roundtrip(self, tmp_path):
        spec = [{"name": "a", "objective": 0.99, "kind": "availability",
                 "bad": ["shed_total"], "fast": [1.0, 4.0],
                 "slow": [2.0, 8.0]}]
        p = tmp_path / "slos.json"
        p.write_text(json.dumps(spec))
        slos = load_slos(str(p))
        assert len(slos) == 1 and slos[0].name == "a"
        assert slos[0].fast == (1.0, 4.0)

    def test_eval_thread_ticks_engine(self):
        reg = MetricsRegistry()
        reg.inc("req_total")
        eng = SLOEngine([_avail_slo()])
        thread = SLOEvalThread(eng, lambda: reg, interval_s=0.01).start()
        try:
            deadline = time.monotonic() + 5.0
            while not eng._tracks[0].samples:
                assert time.monotonic() < deadline, "eval thread never ran"
                time.sleep(0.01)
        finally:
            thread.stop()


# ---------------------------------------------------------------------------
# build-info contract
# ---------------------------------------------------------------------------
class TestBuildInfo:
    def test_every_process_exports_identity(self):
        reg = MetricsRegistry()
        export_build_info(reg, role="replica")
        info = reg.gauge_series("photon_build_info")
        assert len(info) == 1
        labels = dict(next(iter(info)))
        assert labels["role"] == "replica" and labels["version"]
        assert list(info.values()) == [1]
        start = reg.gauge_series("process_start_time_seconds")
        assert list(start.values()) == [pytest.approx(
            process_start_time())]


# ---------------------------------------------------------------------------
# HTTP surfaces + admission consult + fleetwatch CLI
# ---------------------------------------------------------------------------
class TestWatchHTTP:
    def test_watchz_serves_ingestible_full_state(self):
        m = ServingMetrics()
        m.registry.inc("front_requests_total", 3)
        ep = ThreadedMetricsEndpoint(m, port=0).start()
        try:
            status, body = _http_get(ep.port, "/watchz")
            assert status == 200
            frame = json.loads(body)
            assert frame["full"] is True
            view = FleetView()
            assert view.ingest("p", frame)
            assert sum(view.registry.counter_series(
                "front_requests_total").values()) == 3
        finally:
            ep.stop()

    def test_fleetz_requires_a_fleet_view(self):
        m = ServingMetrics()
        ep = ThreadedMetricsEndpoint(m, port=0).start()
        try:
            status, _ = _http_get(ep.port, "/fleetz")
            assert status == 404
        finally:
            ep.stop()

    def test_fleetz_serves_fleet_snapshot(self):
        src = MetricsRegistry()
        src.inc("req_total", 2)
        view = FleetView()
        view.ingest("p", DeltaExporter(src, label="p").frame())
        ep = ThreadedMetricsEndpoint(ServingMetrics(registry=view.registry),
                                     port=0, fleet_view=view).start()
        try:
            status, body = _http_get(ep.port, "/fleetz")
            assert status == 200
            snap = json.loads(body)
            assert snap["processes"] == 1
            assert "p" in snap["sources"]
        finally:
            ep.stop()


class TestAdmissionFleetPressure:
    def test_shed_and_hysteresis_release(self):
        reg = MetricsRegistry()
        reg.set_gauge("fleet_slo_burn_rate", 10.0, slo="lat")
        adm = AdmissionController(
            AdmissionConfig(budget_s=5.0, fleet_burn_budget=1.0,
                            fleet_burn_poll_s=0.01),
            registry=reg)
        v = adm.decide(0.0)
        assert not v.admitted and v.reason == SHED_FLEET
        assert v.retry_after_ms > 0
        # over the resume watermark: latch holds
        reg.set_gauge("fleet_slo_burn_rate", 0.9, slo="lat")
        time.sleep(0.02)
        assert not adm.decide(0.0).admitted
        # under it: release
        reg.set_gauge("fleet_slo_burn_rate", 0.1, slo="lat")
        time.sleep(0.02)
        assert adm.decide(0.0).admitted

    def test_off_by_default(self):
        reg = MetricsRegistry()
        reg.set_gauge("fleet_slo_burn_rate", 99.0, slo="lat")
        adm = AdmissionController(AdmissionConfig(budget_s=5.0),
                                  registry=reg)
        assert adm.decide(0.0).admitted


class TestFleetwatchCLI:
    def _endpoint(self, counter_value=5):
        m = ServingMetrics()
        m.registry.inc("front_requests_total", counter_value)
        return ThreadedMetricsEndpoint(m, port=0).start()

    def test_poll_once_merges_live_peers(self):
        from tools.fleetwatch import poll_once
        ep = self._endpoint()
        try:
            view = FleetView()
            ok = poll_once(view, [("front", "127.0.0.1", ep.port)])
            assert ok == 1
            assert sum(view.registry.counter_series(
                "front_requests_total").values()) == 5
        finally:
            ep.stop()

    def test_once_mode_writes_snapshot_and_exit_status(self, tmp_path):
        from tools.fleetwatch import run
        ep = self._endpoint()
        out = tmp_path / "snap.json"
        try:
            rc = run([f"front=127.0.0.1:{ep.port}", "--once",
                      "--out", str(out)])
        finally:
            ep.stop()
        assert rc == 0
        snap = json.loads(out.read_text())
        assert snap["processes"] == 1
        # every peer down -> nonzero exit, snapshot still written
        rc = run([f"front=127.0.0.1:{ep.port}", "--once", "--timeout",
                  "0.2", "--out", str(out)])
        assert rc == 1

    def test_peer_spec_validation(self):
        from tools.fleetwatch import run
        assert run(["not-a-peer", "--once"]) == 2
