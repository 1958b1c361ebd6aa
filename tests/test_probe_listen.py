"""The probe LISTENS to JAX (ISSUE 34): while tracing is on every program
JAX builds is a ``jax.trace`` / ``jax.lower`` / ``jax.compile`` span with
its name, cache hit or miss, on the tracer's clock and under the ``obs``
span that was open, and one ``jax_compiles_total{site="jit"}``; with
tracing off nothing is registered; a serving site's compile is counted
once; the five ``setup_*`` readers on a synthetic record list.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
sys.path[:0] = [BENCH, os.path.join(BENCH, "tools"), REPO]

import run as harness  # noqa: E402

from photon_ml_tpu import obs  # noqa: E402
from photon_ml_tpu.obs import MetricsRegistry  # noqa: E402
from photon_ml_tpu.obs.trace import Tracer, set_tracer  # noqa: E402

PHASES = ("jax.trace", "jax.lower", "jax.compile")


@pytest.fixture
def listening():
    """The default probe listening, a fresh default tracer (on) and a fresh
    default registry; both restored afterwards."""
    obs.get_probe().listen()
    prev_tracer = set_tracer(Tracer(capacity=1 << 14, enabled=True))
    registry = MetricsRegistry()
    prev_registry = obs.set_registry(registry)
    try:
        yield obs.get_tracer(), registry
    finally:
        obs.set_registry(prev_registry)
        set_tracer(prev_tracer)


def fresh_fn(tag: str):
    """A function JAX has not seen: its name and its constant are new."""
    def fn(x):
        return jnp.sum(x * float(len(tag)) + 0.5)
    fn.__name__ = fn.__qualname__ = tag
    return fn


def of_program(records, tag):
    return [r for r in records
            if r["attrs"].get("program") in (tag, f"jit({tag})")]


def jit_count(registry, **labels):
    return sum(v for lk, v in registry.counter_series(
        "jax_compiles_total").items()
        if all((k, w) in lk for k, w in dict(labels, site="jit").items()))


# -- one span a phase, named, nested, on the tracer's clock -------------------

@pytest.mark.parametrize("phase", PHASES)
def test_a_fresh_jit_function_is_one_span_a_phase(listening, phase):
    tracer, registry = listening
    tag = "probe_fn_" + phase.split(".")[1]
    f = jax.jit(fresh_fn(tag))
    x = np.ones(7, np.float32)
    with obs.span("outer") as outer:
        f(x)
        f(x)  # jit's own cache: nothing is built
    records = tracer.records()
    mine = [r for r in of_program(records, tag) if r["name"] == phase]
    assert len(mine) == 1, [r["name"] for r in of_program(records, tag)]
    span = mine[0]
    parent = next(r for r in records if r["name"] == "outer")
    assert span["parent"] == parent["id"] == outer._id
    assert parent["ts_ns"] <= span["ts_ns"]
    assert span["ts_ns"] + span["dur_ns"] <= parent["ts_ns"] + parent["dur_ns"]
    assert span["dur_ns"] > 0
    if phase == "jax.compile":
        assert span["attrs"]["cache"] in ("hit", "miss", "off")
        assert jit_count(registry, program=f"jit({tag})") == 1
        hist = registry.histogram_snapshot("jax_compile_seconds", site="jit")
        assert hist["count"] == jit_count(registry) >= 1
    else:
        assert set(span["attrs"]) == {"program"}


def test_an_inner_jit_is_part_of_its_callers_trace(listening):
    """A span a PROGRAM: JAX brackets the trace of every ``jit`` it meets
    while tracing another, thousands of them in a descent program."""
    from photon_ml_tpu.obs import probe as probe_mod

    tracer, _ = listening
    inner = jax.jit(fresh_fn("probe_fn_inner"))

    def outer(x):
        return inner(x) * 2.0 + jnp.where(x > 0, x, 0.0).sum()
    outer.__name__ = outer.__qualname__ = "probe_fn_outer"
    x = np.ones(6, np.float32)
    jax.jit(outer)(x)
    records = tracer.records()
    assert [r["name"] for r in of_program(records, "probe_fn_outer")] == [
        "jax.trace", "jax.lower", "jax.compile"]
    assert of_program(records, "probe_fn_inner") == []
    assert not [r for r in records if r["attrs"].get("program") == "_where"]
    assert probe_mod._thread.phases == 0
    inner(x)  # now a program of its own
    built = [r["name"] for r in of_program(tracer.records(), "probe_fn_inner")]
    assert built[-2:] == ["jax.lower", "jax.compile"]
    assert probe_mod._thread.phases == 0


def test_nothing_is_recorded_while_the_tracer_is_off(listening):
    tracer, registry = listening
    tracer.disable()  # enabled once, disabled later: the listener returns
    jax.jit(fresh_fn("probe_fn_disabled"))(np.ones(3, np.float32))
    assert tracer.records() == [] and jit_count(registry) == 0
    tracer.enable()
    jax.jit(fresh_fn("probe_fn_enabled_again"))(np.ones(3, np.float32))
    assert jit_count(registry, program="jit(probe_fn_enabled_again)") == 1


# -- cache miss, then hit ------------------------------------------------------

def test_cache_miss_then_hit_with_retrieval_seconds(listening,
                                                    persistent_cache):
    tracer, registry = listening
    tag = "probe_fn_cached"
    x = np.ones(5, np.float32)
    jax.jit(fresh_fn(tag))(x)   # compiled, written to the cache
    jax.jit(fresh_fn(tag))(x)   # a new function object: read back
    compiles = [r["attrs"] for r in of_program(tracer.records(), tag)
                if r["name"] == "jax.compile"]
    assert [a["cache"] for a in compiles] == ["miss", "hit"]
    assert set(compiles[0]) == {"program", "cache"}
    assert compiles[1]["retrieval_s"] > 0 and "saved_s" in compiles[1]
    assert jit_count(registry, program=f"jit({tag})", cache="miss") == 1
    assert jit_count(registry, program=f"jit({tag})", cache="hit") == 1


def test_cache_off_is_said_so(listening):
    tracer, _ = listening
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        jax.jit(fresh_fn("probe_fn_no_cache"))(np.ones(2, np.float32))
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
    compiles = [r["attrs"] for r in of_program(tracer.records(),
                                               "probe_fn_no_cache")
                if r["name"] == "jax.compile"]
    assert compiles == [{"program": "jit(probe_fn_no_cache)", "cache": "off"}]


# -- a compile is never counted twice -------------------------------------------

def test_a_site_counts_its_compile_alone(listening):
    tracer, registry = listening
    probe = obs.get_probe()
    tag = "probe_fn_site"
    with probe.compile_span("serving.engine", bucket=8):
        jax.jit(fresh_fn(tag)).lower(np.ones(4, np.float32)).compile()
    assert probe.compile_count("serving.engine") == 1
    assert probe.compile_count() == 1 and jit_count(registry) == 0
    records = tracer.records()
    site = next(r for r in records if r["attrs"].get("site"))
    assert site["name"] == "jax.compile" and site["attrs"]["bucket"] == 8
    inner = of_program(records, tag)
    # the listener's spans of the same compile nest inside the site's
    assert sorted(r["name"] for r in inner) == sorted(PHASES)
    assert all(r["parent"] == site["id"] for r in inner)
    # outside the site the listener counts again
    jax.jit(fresh_fn(tag + "_after"))(np.ones(4, np.float32))
    assert probe.compile_count("serving.engine") == 1
    assert jit_count(registry, program=f"jit({tag}_after)") == 1


# -- tracing never enabled: nothing registered ---------------------------------

OURS = ("[l for l in m.get_event_duration_listeners() + m.get_event_listeners()"
        " + m.get_scalar_listeners()"
        " if type(getattr(l, '__self__', None)).__module__"
        ".startswith('photon_ml_tpu')]")
UNTRACED = {
    "obs_alone": f"""
import jax, numpy as np
from jax._src import monitoring as m
from photon_ml_tpu import obs
jax.jit(lambda x: x + 1)(np.ones(3))
print("OURS", len({OURS}), "RECORDS", len(obs.get_tracer().records()))
""",
    "benchmark_run": f"""
import sys
sys.path.insert(0, {BENCH!r})
import run
rc = run.main(["--workload", "glmix_chip.train", "--seed", "3400000019",
               "--seconds", "0.2", "--trace", "0", "--dry-run"])
from jax._src import monitoring as m
from photon_ml_tpu import obs
print("RC", rc, "THEIRS", len(m.get_event_duration_listeners()))
print("OURS", len({OURS}), "RECORDS", len(obs.get_tracer().records()))
""",
}


@pytest.mark.parametrize("process", sorted(UNTRACED))
def test_an_untraced_process_registers_no_listener(process, tmp_path):
    done = subprocess.run(
        [sys.executable, "-c", UNTRACED[process]], cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla")})
    assert done.returncode == 0, done.stderr[-3000:]
    last = done.stdout.strip().splitlines()[-1]
    assert last == "OURS 0 RECORDS 0", done.stdout[-2000:]
    if process == "benchmark_run":  # its own listener is there: not vacuous
        assert "RC 0 THEIRS 1" in done.stdout


def test_listening_registers_the_probes_listeners_once(listening):
    from jax._src import monitoring as m

    assert obs.get_probe().listen() is False  # a second call registers none
    assert obs.JaxRuntimeProbe().listen() is False
    ours = eval(OURS)
    assert len(ours) == 3  # durations, events, scalars
    assert {l.__self__ for l in ours} == {obs.get_probe()}


# -- the five readers, on a synthetic record list --------------------------------

S = 1_000_000_000


def reader(name):
    return harness.Catalog().module("layer_metrics", name)


def readings(spans=()):
    return {"profile": None, "spans": list(spans), "measured": {},
            "config": {}, "obs_spans": [], "counters": {},
            "catalog": harness.Catalog()}


def synthetic_setup(tracer):
    """A set-up of 40 s and a window from 40 s: ``warm_fit`` 20 to 39."""
    c = tracer.complete
    c("jax.trace", 1 * S, 2 * S, program="generate")
    c("jax.lower", 3 * S, 1 * S, program="jit(generate)")
    c("jax.compile", 4 * S, 6 * S, program="jit(generate)", cache="miss")
    c("jax.compile", 10 * S, S // 2, program="jit(add)", cache="miss")
    c("jax.trace", 20 * S, 8 * S, program="program")  # the main program
    c("jax.lower", 28 * S, 3 * S, program="jit(program)")
    c("jax.compile", 31 * S, 4 * S, program="jit(program)", cache="hit",
      retrieval_s=3.5, saved_s=190.0)
    c("descent.device_table", 20 * S, 16 * S, program="jit_program",
      instructions=5)
    # a serving site's own span carries no ``program``: not the listener's
    c("jax.compile", 12 * S, 5 * S, site="serving.engine")
    # inside the window: a recompile there is no set-up
    c("jax.trace", 41 * S, 1 * S, program="late")
    c("jax.compile", 42 * S, 3 * S, program="jit(late)", cache="miss")
    return [("data_make", 0, 10 * S), ("warm_fit", 20 * S, 39 * S),
            ("fit", 40 * S, 45 * S), ("fit", 45 * S, 50 * S)]


WANT = {"setup_trace_s": 10.0, "setup_lower_s": 4.0, "setup_compile_s": 10.5,
        "setup_cache_misses": 1, "setup_first_run_s": 3.0}


@pytest.mark.parametrize("name", sorted(WANT))
def test_setup_reader_on_a_synthetic_record_list(name):
    prev = set_tracer(Tracer(capacity=256, enabled=True))
    try:
        spans = synthetic_setup(obs.get_tracer())
        value = reader(name).read(readings(spans))
        # trials, as the tuning cell has them, start the window alike
        trials = [(n.replace("fit", "trial") if n == "fit" else n, a, b)
                  for n, a, b in spans]
        assert reader(name).read(readings(trials)) == value
    finally:
        set_tracer(prev)
    assert value == pytest.approx(WANT[name])
    if name == "setup_cache_misses":
        assert isinstance(value, int)


@pytest.mark.parametrize("name", sorted(WANT))
def test_setup_reader_with_nothing_to_read_returns_none(name):
    """The parent's program, or a run with the tracer off: the benchmark's
    spans are there, the program's are not."""
    prev = set_tracer(Tracer(capacity=16, enabled=True))
    try:
        spans = [("warm_fit", 20 * S, 39 * S), ("fit", 40 * S, 45 * S)]
        assert reader(name).read(readings(spans)) is None
    finally:
        set_tracer(prev)


def test_setup_metric_files_are_well_formed():
    import manifest

    catalog = harness.Catalog()
    for name in WANT:
        m = catalog.json("layer_metrics", name)
        assert m["name"] == name and manifest.NAME.match(name)
        assert manifest.UNIT.match(m["unit"]) and m["better"] == "lower"
        assert m["source"] == "program_span" and m["moves"] == "setup_s"
        assert m["layer"] == "descent program (game/fused.py)"
        assert catalog.find("layer_metrics", name, ".py")


# -- the tool: all five cells, one traced dry run through it --------------------

def test_tool_writes_all_five_cells_and_one_runs_traced(tmp_path):
    import layer_cells
    import layer_cells_all

    account = tmp_path / "account"
    path = layer_cells_all.write(str(tmp_path / "cells"), str(account))
    catalog = harness.Catalog(path)
    cells = sorted(layer_cells_all.APPENDED)
    # the five cells the benchmark had when the tool was written: a later
    # cell waits for a ``benchmark`` PR to list it there (PERF.md section 7)
    assert len(cells) == 5 and set(harness.Catalog().names("workloads")) - set(
        cells) == {"glmix_userbag_ml20m.train"}
    for cell in cells:
        listed = catalog.json("workloads", cell + layer_cells_all.SUFFIX)[
            "per_layer"]
        assert listed[:-1] == (harness.Catalog().json("workloads", cell)[
            "per_layer"] + layer_cells_all.APPENDED[cell])
        assert set(layer_cells.EVERY_CELL + layer_cells_all.SETUP) <= set(listed)
    cell = "glmix3_wide.train"
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest", path,
         "--workload", cell + layer_cells_all.SUFFIX, "--seed", "3400000019",
         "--seconds", "1", "--trace", "1", "--dry-run"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=600,
        env={**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla")})
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["checks"]["no_compile_in_window"]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    spans = {n for n in catalog.json("workloads", cell + "_layers")["per_layer"]
             if catalog.json("layer_metrics", n)["source"] == "program_span"}
    assert spans - {layer_cells_all.ACCOUNT} <= set(m)
    assert all(np.isfinite(m[n]) for n in spans - {layer_cells_all.ACCOUNT})
    assert m["setup_cache_misses"] == int(m["setup_cache_misses"]) >= 0
    assert min(m[n] for n in layer_cells_all.SETUP
               if n != "setup_cache_misses") > 0
    # the account the reader left: warm_fit's parts beside its seconds
    (left,) = list(account.iterdir())
    found = json.loads(left.read_text())
    assert found["workload"] == cell + layer_cells_all.SUFFIX
    events = {k: v["events"] for k, v in found["phases"].items()}
    # a span a program and phase: the recipe's, the checks', the main one
    assert events["jax.lower"] == events["jax.compile"] >= 5
    assert events["jax.lower"] <= events["jax.trace"] < 2 * events["jax.lower"]
    assert found["listener_events"] == sum(events.values())
    warm = found["warm_fit"]
    assert set(warm["parts"]) == set(PHASES) | {"first_run"}
    assert 0.5 * warm["seconds"] < warm["sum"] < 1.2 * warm["seconds"]
    (table,) = found["device_tables"]
    assert table["program"] == "jit_program" and table["instructions"] > 10
    assert table["compile"]["program"] == "jit(program)"
