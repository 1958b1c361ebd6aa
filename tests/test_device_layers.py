"""The device half of the tracer (ISSUE 23): ``photon.*`` scopes in the
descent program, the op-to-layer table read off its own executable, host
spans on the profiler's clock, and the benchmark's layer readers that join
the table with a reduced device trace.

The sweeps are the benchmark's two training configurations at their
dry-run sizes (benchmarks/configs/*.json), built with the benchmark's own
recipes: a two-coordinate ``glmix_chip`` and a three-coordinate
``glmix3_wide``.  Everything runs on the CPU backend; what the TPU compiler
names its instructions is tests/test_compile_v5e_layers.py's business.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
sys.path[:0] = [BENCH, os.path.join(BENCH, "tools"), REPO]

import layer_cells  # noqa: E402
import layer_join  # noqa: E402
import manifest  # noqa: E402
import run as harness  # noqa: E402

from photon_ml_tpu import obs  # noqa: E402
from photon_ml_tpu.game.fused import FusedSweep  # noqa: E402
from photon_ml_tpu.obs import trace as obs_trace  # noqa: E402
from photon_ml_tpu.obs.trace import (Tracer, device_scope,  # noqa: E402
                                     hlo_op_table, set_tracer)
from photon_ml_tpu.parallel import bucketing  # noqa: E402

CONFIGS = ["glmix_chip", "glmix3_wide"]
# what each configuration exercises of the vocabulary (PERF.md section 3)
LAYERS = {
    "glmix_chip": {"update.fixed", "update.per_user", "residual",
                   "fixed_solve", "entity_gather", "entity_solve.b0",
                   "publish", "rescore"},
    "glmix3_wide": {"update.fixed", "update.per_user", "update.per_item",
                    "residual", "fixed_solve", "entity_gather",
                    "entity_solve.b0", "entity_solve.b1", "publish",
                    "rescore"},
}


# -- (i) the HLO-text parser --------------------------------------------------

HLO = '''HloModule jit_program, is_scheduled=true, entry_computation_layout={(f32[4096]{0:T(1024)})->f32[4096]{0:T(1024)}}

%fused_computation.clone.clone (param_0.23: f32[4096], param_1.24: s32[4096]) -> f32[4096] {
  %param_0.23 = f32[4096]{0:T(1024)} parameter(0)
  %gather.5 = f32[4096]{0:T(1024)} gather(%param_0.23, %param_1.24), offset_dims={}, metadata={op_name="jit(program)/while/body/closed_call/photon.update.per_user/photon.rescore/gather" stack_frame_id=3}
  ROOT %reshape.11 = f32[4096]{0:T(1024)S(1)} reshape(%gather.5), metadata={op_name="jit(program)/while/body/closed_call/photon.update.per_user/photon.rescore/gather" stack_frame_id=3}
}

%scalar_add_computation (scalar_lhs: f32[], scalar_rhs: f32[]) -> f32[] {
  %scalar_lhs = f32[]{:T(128)} parameter(0)
  ROOT %add.8 = f32[]{:T(128)} add(%scalar_lhs, %scalar_rhs)
}

%wide.region_1.1.sunk (wide.arg_tuple.1: (f32[4096], s32[])) -> (f32[4096], s32[]) {
  %multiply_reduce_fusion.4 = f32[256]{0:T(256)S(1)} fusion(%get-tuple-element.90), kind=kLoop, calls=%fused_computation.1.clone.clone, metadata={op_name="jit(program)/while/body/closed_call/photon.update.fixed/photon.fixed_solve/jit(_solve)/while/body/dot_general" stack_frame_id=4}, backend_config={"flag_configs":[]}
  %fused_glm_value_grad.15 = (f32[32,1]{1,0:T(8,128)S(1)}, f32[32,128]{1,0:T(8,128)S(1)}) custom-call(%get-tuple-element.3704, %copy.40), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(program)/while/body/closed_call/photon.update.fixed/photon.fixed_solve/jit(_solve)/while/body/fused_glm_value_grad/pallas_call" stack_frame_id=77}, backend_config={"custom_call_config":{"body":"TUzvUg"}}
  %copy.15 = f32[4096]{0:T(1024)S(1)} copy(%get-tuple-element.84), backend_config={"flag_configs":[]}
  ROOT %tuple.7 = (f32[4096]{0:T(1024)S(1)}, s32[]{:T(128)}) tuple(%copy.15, %add.18)
}

%wide.region_2.2 (wide.arg_tuple.4: (f32[4096], s32[])) -> pred[] {
  ROOT %lt.8 = pred[]{:T(512)} compare(%get-tuple-element.35, %constant.27), direction=LT
}

ENTRY %main.79 (w.1: f32[4096]) -> f32[4096] {
  %w.1 = f32[4096]{0:T(1024)} parameter(0), metadata={op_name="w"}
  %fusion.2 = f32[4096]{0:T(1024)S(1)} fusion(%w.1, %broadcast_clamp_fusion.2), kind=kCustom, calls=%fused_computation.clone.clone, metadata={op_name="jit(program)/while/body/closed_call/photon.update.per_user/photon.rescore/gather" stack_frame_id=3}, backend_config={"flag_configs":[]}
  %reduce.1 = f32[]{:T(128)} reduce(%fusion.2, %constant.1), dimensions={0}, to_apply=%scalar_add_computation, metadata={op_name="jit(program)/photon.residual/reduce_sum"}
  %while.11 = (f32[4096]{0:T(1024)S(1)}, /*index=1*/s32[]{:T(128)}) while(%tuple.29), condition=%wide.region_2.2, body=%wide.region_1.1.sunk, metadata={op_name="jit(program)/while/body/closed_call/photon.update.fixed/photon.fixed_solve/jit(_solve)/while" stack_frame_id=4}
  %copy-start.1 = (f32[4096]{0:T(1024)S(1)}, f32[4096]{0:T(1024)}, u32[]{:S(2)}) copy-start(%w.1)
  ROOT %get-tuple-element.107 = f32[4096]{0:T(1024)S(1)} get-tuple-element(%while.11), index=0
}
'''
SOLVE = ("jit(program)/while/body/closed_call/photon.update.fixed/"
         "photon.fixed_solve/jit(_solve)/while")


@pytest.mark.parametrize("name, want", [
    # a gather fusion (kind=kCustom): the root's scope
    ("fusion.2", "jit(program)/while/body/closed_call/"
                 "photon.update.per_user/photon.rescore/gather"),
    ("while.11", SOLVE),
    # a Mosaic kernel: a custom-call under its pallas_call's name
    ("fused_glm_value_grad.15", SOLVE + "/body/fused_glm_value_grad/"
                                "pallas_call"),
    ("multiply_reduce_fusion.4", SOLVE + "/body/dot_general"),
    ("reduce.1", "jit(program)/photon.residual/reduce_sum"),
    # made by the compiler, no metadata: the while it runs in
    ("copy.15", SOLVE + "/body"),
    ("lt.8", SOLVE + "/cond"),       # a ROOT line, and the loop's condition
    ("copy-start.1", ""),            # no metadata, in the entry computation
    # never an event of their own, or inside ONE op: not in the table
    ("w.1", None), ("tuple.7", None), ("get-tuple-element.107", None),
    ("gather.5", None), ("reshape.11", None), ("add.8", None),
])
def test_hlo_op_table_on_tpu_style_text(name, want):
    assert hlo_op_table(HLO).get(name) == want


def test_device_scope_sanitises_ids():
    def f(x):
        with device_scope("update", "per-user/a.b"):
            with device_scope("entity_solve", "b0"):
                return x * 2.0

    text = jax.jit(f).lower(np.ones(4, np.float32)).as_text(debug_info=True)
    assert "photon.update.per_user_a_b/photon.entity_solve.b0" in text


# -- the sweeps ---------------------------------------------------------------

@pytest.fixture(scope="module")
def cells():
    """{config: (cfg, coordinates)} at the dry-run sizes."""
    catalog = harness.Catalog()
    train_fits = catalog.module("traffic", "train_fits")
    out = {}
    for name in CONFIGS:
        cfg = harness.sized(catalog.json("configs", name), True)
        recipe = catalog.module("recipes", cfg["recipe"])
        data = recipe.make_training(cfg, 5)
        out[name] = (cfg, train_fits.build_coordinates(cfg, data, None))
    return out


def new_sweep(cells, config):
    cfg, coords = cells[config]
    return FusedSweep(coords, num_iterations=int(cfg["sweeps"]))


@contextlib.contextmanager
def tracing(enabled=True):
    if enabled:  # as obs.enable_tracing does: the probe listens to JAX
        obs.get_probe().listen()
    prev = set_tracer(Tracer(capacity=4096, enabled=enabled))
    try:
        yield obs.get_tracer()
    finally:
        set_tracer(prev)


def scopes_of(path):
    return [p[len("photon."):] for p in path.split("/")
            if p.startswith("photon.")]


# -- (ii) tracer on: the table ------------------------------------------------

@pytest.mark.parametrize("config", CONFIGS)
def test_traced_sweep_records_its_table(cells, config):
    sweep = new_sweep(cells, config)
    with tracing() as tracer:
        out = sweep.run_device()
        sweep.run_device()  # once per sweep object
        tables = tracer.device_tables()
        records = tracer.records()
        exported = tracer.chrome_trace()["otherData"]["device_op_tables"]
        tracer.clear()
        assert tracer.device_tables() == {}
    assert len(out) == 4
    assert list(tables) == ["jit_program"] and exported == tables
    table = tables["jit_program"]
    assert {s for p in table.values() for s in scopes_of(p)} >= LAYERS[config]
    heavy = [n for n in table
             if n.split(".")[0].endswith(("fusion", "while", "custom-call"))]
    scoped = [n for n in heavy if "photon." in table[n]]
    assert len(heavy) > 10 and len(scoped) >= 0.9 * len(heavy), (
        sorted(set(heavy) - set(scoped)))
    names = [r["name"] for r in records]
    assert names.count("descent.device_table") == 1
    assert names.count("descent.dispatch") == 2
    # the executable's own account on the span, and what JAX did to build
    # the main program under it (ISSUE 34): one span a phase, by name
    span = next(r for r in records if r["name"] == "descent.device_table")
    assert span["attrs"]["program"] == "jit_program"
    assert span["attrs"]["instructions"] == len(table)
    assert span["attrs"]["hlo_bytes"] > 1000
    args, _ = sweep._program_args(None, None, 0, None)
    stats = sweep._program.lower(*args).compile().memory_analysis()
    if stats is None:  # a backend with no count: absent, not zero
        assert "temp_bytes" not in span["attrs"]
    else:
        assert span["attrs"]["temp_bytes"] == stats.temp_size_in_bytes
        assert span["attrs"]["argument_bytes"] == stats.argument_size_in_bytes
    built = {r["name"]: r for r in records
             if r["parent"] == span["id"]
             and r["attrs"].get("program") in ("program", "jit(program)")}
    assert set(built) == {"jax.trace", "jax.lower", "jax.compile"}
    assert built["jax.compile"]["attrs"]["cache"] in ("hit", "miss", "off")
    assert all(span["ts_ns"] <= r["ts_ns"] and r["ts_ns"] + r["dur_ns"]
               <= span["ts_ns"] + span["dur_ns"] for r in built.values())
    # the dispatches that follow build nothing: jit's own caches serve them
    dispatched = {r["id"] for r in records if r["name"] == "descent.dispatch"}
    assert not [r for r in records if r["parent"] in dispatched
                and r["name"] in ("jax.lower", "jax.compile")]


# -- (ii-b) the entity-major rescore: one gather a chunk, one a sample ----------

@pytest.mark.parametrize("config, want", [
    # (n-sized gathers, entries of the row gather: chunks x d) a scope
    ("glmix_chip", {"per_user": (0, [384 * 4])}),        # rows entity-major
    ("glmix3_wide", {"per_user": (1, [96 * 16]),         # rows anywhere
                     "per_item": (1, [60 * 16])}),
])
def test_entity_major_rescore_gathers(monkeypatch, config, want):
    """The dry-run sizes sit under the padded-footprint line, so lower it
    here (the program has no knob for it): each random-effect coordinate
    then rescores from the entity-major layout, and its own executable
    holds, under ``photon.update.<cid>/photon.rescore``, ONE gather of a
    coefficient row (d entries) a CHUNK and at most ONE gather of n
    entries, none where the rows arrive entity-major."""
    import re

    from photon_ml_tpu.parallel import bucketing

    monkeypatch.setattr(bucketing, "NARROW_SCORE_PAD_BYTES_MIN", 1)
    catalog = harness.Catalog()
    cfg = harness.sized(catalog.json("configs", config), True)
    data = catalog.module("recipes", cfg["recipe"]).make_training(cfg, 5)
    coords = catalog.module("traffic", "train_fits").build_coordinates(
        cfg, data, None)
    n = len(data["y"])
    sweep = FusedSweep(coords, num_iterations=int(cfg["sweeps"]))
    args, _ = sweep._program_args(None, None, 0, None)
    text = sweep._program.lower(*args).compile().as_text()
    found = {}
    for line in text.splitlines():
        path = re.search(r'op_name="([^"]*)"', line)
        if " gather(" not in line or path is None:
            continue
        path = path.group(1)
        if layer_join.layer_of(path) != "rescore":
            continue
        shape = re.search(r"= \w+\[([\d,]*)\]", line).group(1)
        found.setdefault(layer_join.coordinate_of(path), []).append(
            math.prod(int(v) for v in shape.split(",")))
    assert set(found) == set(want)
    for cid, (big, chunks) in want.items():
        assert sorted(found[cid]) == sorted(chunks + big * [n]), (cid, n)
    published, scores, _, _ = sweep.run_device()
    assert all(np.isfinite(np.asarray(s)).all() for s in scores)


# -- (iii) tracer off: nothing lowered ----------------------------------------

class CountingProgram:
    """The jitted program, with its ``lower`` counted."""

    def __init__(self, program):
        self.program, self.lowered = program, 0

    def __call__(self, *args):
        return self.program(*args)

    def lower(self, *args):
        self.lowered += 1
        return self.program.lower(*args)


@pytest.mark.parametrize("config", CONFIGS)
def test_untraced_sweep_lowers_nothing(cells, config):
    sweep = new_sweep(cells, config)
    sweep._program = counting = CountingProgram(sweep._program)
    with tracing(enabled=False) as tracer:
        out = sweep.run_device()
        assert tracer.device_tables() == {} and tracer.records() == []
    assert len(out) == 4 and counting.lowered == 0
    assert not sweep._table_recorded
    with tracing():
        sweep.run_device()
    assert counting.lowered == 1  # the first TRACED call, and only it


# -- (iv) the same numbers with the tracer on and off --------------------------

@pytest.mark.parametrize("config", CONFIGS)
def test_fit_bitwise_equal_traced_and_untraced(cells, config):
    with tracing(enabled=False):
        plain = new_sweep(cells, config).run_device()
    with tracing():
        traced = new_sweep(cells, config).run_device()
    for a, b in zip(jax.tree.leaves(plain[:3]), jax.tree.leaves(traced[:3])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# -- (v) spans on the profiler's clock ----------------------------------------

class FakeAnnotation:
    live = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        FakeAnnotation.live.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        FakeAnnotation.live.append(("exit", self.name))
        return False


@pytest.mark.parametrize("enabled, want", [
    (True, [("enter", "outer"), ("enter", "inner"), ("exit", "inner"),
            ("exit", "outer")]),
    (False, []),
])
def test_span_opens_a_trace_annotation_only_when_enabled(monkeypatch,
                                                         enabled, want):
    FakeAnnotation.live = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    with tracing(enabled=enabled) as tracer:
        outer = obs.span("outer")
        assert (outer is obs_trace._NOOP) == (not enabled)
        with outer:
            with tracer.span("inner", rows=3) as inner:
                inner.set(bytes=7)
        assert FakeAnnotation.live == want
        attrs = [r["attrs"] for r in tracer.records() if r["name"] == "inner"]
        assert attrs == ([{"rows": 3, "bytes": 7}] if enabled else [])


def test_coordinate_spans_carry_coordinate_and_bytes():
    catalog = harness.Catalog()
    cfg = harness.sized(catalog.json("configs", "glmix_chip"), True)
    data = catalog.module("recipes", cfg["recipe"]).make_training(cfg, 6)
    with tracing() as tracer:
        catalog.module("traffic", "train_fits").build_coordinates(
            cfg, data, None)
        records = tracer.records()
    uploads = [r["attrs"] for r in records if r["name"] == "coord.upload"]
    assert {a["coordinate"] for a in uploads} == {"fixed", "per-user"}
    # the recipe makes the fixed design on the device: nothing to upload,
    # and the span is recorded all the same
    assert [a["bytes"] for a in uploads if a["coordinate"] == "fixed"] == [0]
    assert sum(a["bytes"] for a in uploads) > 0
    assert {r["attrs"]["coordinate"] for r in records
            if r["name"] == "coord.bucket"} == {"per-user"}


@pytest.mark.parametrize("config, by_run", [
    # rows by user, most users under the cap: lanes addressed by the start
    # of their run (``glmix_ml25m``, under its mesh: tests/
    # test_mesh_exchange.py); a movie's rows lie anywhere
    ("glmix_ml20m", {"per-user": True, "per-item": False}),
    # correlated_shards shuffles both id columns: no lane is a run
    ("glmix3_wide", {"per-user": False, "per-item": False}),
    # every user over the cap: reservoirs only
    ("glmix_chip", {"per-user": False}),
])
def test_bucket_span_counts_the_lanes_addressed_by_run(config, by_run):
    """ISSUE 31's counter that says how often the mechanism engages:
    ``coord.bucket`` carries ``run_lanes`` (a class), ``run_slots`` and
    ``index_slots`` (summed; a class's are its lanes x its capacity); and
    ISSUE 35's: ``window_lanes``, ``window`` and ``pick_stages`` (a class),
    ``window_slots`` (summed), a slot counted under one of the three."""
    catalog = harness.Catalog()
    cfg = harness.sized(catalog.json("configs", config), True)
    data = catalog.module("recipes", cfg["recipe"]).make_training(cfg, 6)
    with tracing() as tracer:
        coords = catalog.module("traffic", "train_fits").build_coordinates(
            cfg, data, None)
        spans = {r["attrs"]["coordinate"]: r["attrs"]
                 for r in tracer.records() if r["name"] == "coord.bucket"}
    assert set(spans) == set(by_run)
    for cid, engaged in by_run.items():
        a = spans[cid]
        assert len(a["run_lanes"]) == a["classes"]
        assert a["run_slots"] == sum(
            r * c for r, c in zip(a["run_lanes"], a["capacities"]))
        assert (a["run_slots"] > 0) == engaged, (cid, a)
        assert any("run_start" in dev for dev in coords[cid]._dev) == engaged
        for key in ("window_lanes", "window", "pick_stages"):
            assert len(a[key]) == a["classes"]
        assert a["window_slots"] == sum(
            w * c for w, c in zip(a["window_lanes"], a["capacities"]))
        assert (a["run_slots"] + a["window_slots"] + a["index_slots"]
                == a["slots"])
        for lanes, w, stages, c in zip(a["window_lanes"], a["window"],
                                       a["pick_stages"], a["capacities"]):
            assert (lanes > 0) == (w > 0) == (stages > 0)
            assert w == 0 or c <= w <= bucketing.WINDOW_SPAN_MAX * c
        assert ["windows" in dev for dev in coords[cid]._dev] == [
            lanes > 0 for lanes in a["window_lanes"]]
    if config == "glmix_chip":  # every lane a reservoir of half a user's rows
        a = spans["per-user"]
        assert a["window_lanes"] == a["lanes"] and a["index_slots"] == 0
        assert a["window"] == [cfg["rows_per_user"]]
        assert a["window_slots"] == a["slots"]
        assert a["pick_stages"] == [
            (cfg["rows_per_user"] - a["capacities"][0]).bit_length()]
    if config == "glmix3_wide":  # shuffled ids, no cap: nothing qualifies
        assert all(sum(a["window_lanes"]) == 0 for a in spans.values())
    if config == "glmix_ml20m":  # the cell's share: most of the slots
        a = spans["per-user"]
        by_class = dict(zip(a["capacities"], a["run_lanes"]))
        assert a["run_slots"] > 0.5 * a["slots"]
        assert by_class[4] > 0 and by_class[64] > 0
        assert by_class[1] == by_class[2] == 0  # under RUN_CAPACITY_MIN


# -- (vi) the join and the readers, on a synthetic trace ----------------------

U = "jit(program)/while/body/closed_call/photon.update.per_user/"
F = "jit(program)/while/body/closed_call/photon.update.fixed/"
SOLVER = U + "photon.entity_solve.b0/jit(_vsolve)/vmap()/while/body/"
TABLE = {
    "fused_glm_value_grad.15": F + "photon.fixed_solve/jit(_solve)/while/"
                                   "body/fused_glm_value_grad/pallas_call",
    "multiply_reduce_fusion.42": F + "photon.rescore/dot_general",
    "fusion.63": F + "photon.residual/add",
    "fusion.65": U + "photon.entity_gather/gather",
    "fusion.70": U + "photon.rescore/gather",
    "fusion.69": U + "photon.publish/scatter",
    "fusion.561": SOLVER + "while/body/closed_call/gather",  # line search
    "fusion.540": SOLVER + "mul",                            # outer loop
    "fusion.542": SOLVER + "add",
    "convert_element_type": SOLVER + "convert_element_type",  # a namesake
    "fusion.543": SOLVER + "cond/branch_1_fun/mul",     # not every trip
    "fusion.137": SOLVER + "while",       # made inside the inner loop
    "and_reduce_fusion.2": SOLVER + "while/cond/reduce_or",  # its condition
    "fusion.541": U + "photon.entity_solve.b1/jit(_vsolve)/vmap()/while/"
                      "body/mul",
    "fold_in.1": U + "threefry2x32",       # under the update, in no layer
    "while.222": "jit(program)/while",
}
# name -> [self ns, calls]; 3 fits x 2 sweeps in the slice
OPS_SELF = {
    "fused_glm_value_grad.15": [10e9, 36], "multiply_reduce_fusion.42": [2e9, 6],
    "fusion.63": [1e9, 6], "fusion.65": [5e9, 6], "fusion.70": [40e9, 6],
    "fusion.69": [1e9, 6], "fusion.561": [20e9, 630], "fusion.540": [3e9, 180],
    "fusion.542": [1e9, 180], "convert_element_type": [1e9, 183],
    "fusion.543": [1e9, 90],
    "fusion.137": [0.5e9, 630], "and_reduce_fusion.2": [0.5e9, 810],
    "fusion.541": [3e9, 120], "fold_in.1": [1e9, 6],
    "while.222": [2e9, 3], "copy.99": [8e9, 6],  # copy.99: not in the table
}
SHARES = {"fixed_solve_busy_share": 10.0, "rescore_busy_share": 42.0,
          "entity_gather_busy_share": 5.0, "entity_solve_busy_share": 30.0,
          "unscoped_busy_share": 10.0,
          # b0: 180 trips (not the line search's 630 bodies and 810
          # conditions, nor what bears the inner loop's own path, nor the
          # 183 of a name jit_convert_element_type shares, nor the 90 of
          # an op under a cond), b1: 120; 6 updates
          "entity_solve_iters_per_update": 25.0}
NEW = sorted(SHARES) + ["coord_bucket_s", "coord_upload_s",
                        "dispatch_us_per_fit"]


def readings(profile=True, spans=()):
    busy = sum(v[0] for v in OPS_SELF.values()) * 1e-9
    return {"profile": {"ops_self": OPS_SELF, "busy_s": busy, "chips": 1,
                        "window_s": busy} if profile else None,
            "spans": list(spans), "measured": {"slice_fits": 3},
            "config": {"sweeps": 2}, "obs_spans": [], "counters": {}}


def reader(name):
    return harness.Catalog().module("layer_metrics", name)


@pytest.mark.parametrize("name", sorted(SHARES))
def test_reader_on_a_synthetic_trace(name):
    with tracing() as tracer:
        tracer.record_device_table("jit_program", TABLE)
        assert reader(name).read(readings()) == pytest.approx(SHARES[name])


def test_layers_partition_busy_time():
    with tracing() as tracer:
        tracer.record_device_table("jit_program", TABLE)
        seconds = layer_join.seconds_by(readings())
    assert sum(seconds.values()) == pytest.approx(readings()["profile"]["busy_s"])
    assert seconds["unscoped"] == pytest.approx(10.0)  # while.222 + copy.99
    assert seconds["update.per_user"] == pytest.approx(1.0)
    assert seconds["residual"] == seconds["publish"] == pytest.approx(1.0)
    with tracing() as tracer:
        tracer.record_device_table("jit_program", TABLE)
        by_cid = layer_join.seconds_by(readings(), layer_join.coordinate_of)
    assert by_cid["per_user"] == pytest.approx(77.0)
    assert by_cid["fixed"] == pytest.approx(13.0)


def test_span_readers_read_the_tracer():
    with tracing() as tracer:
        for ts, dur in ((50, 7_000), (1_000, 1_000), (2_000, 3_000)):
            tracer.complete("descent.dispatch", ts, dur)
        tracer.complete("coord.bucket", 0, 2_000_000_000)
        tracer.complete("coord.upload", 0, 500_000_000)
        tracer.complete("coord.upload", 0, 250_000_000)
        # the warm fit's dispatch (ts 50) is outside the window's fits
        r = readings(spans=[("warm_fit", 0, 900), ("fit", 900, 1_900),
                            ("fit", 1_900, 9_000)])
        assert reader("dispatch_us_per_fit").read(r) == pytest.approx(2.0)
        assert reader("coord_bucket_s").read(r) == pytest.approx(2.0)
        assert reader("coord_upload_s").read(r) == pytest.approx(0.75)


@pytest.mark.parametrize("name", NEW)
def test_reader_with_nothing_to_read_returns_none(name):
    with tracing():  # no table, no span; and no device trace
        assert reader(name).read(readings(profile=False)) is None


# -- (vii) the manifest --------------------------------------------------------

def test_manifest_check_passes_and_the_new_metrics_are_well_formed():
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "manifest.py"), "--check"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, timeout=120)
    assert done.returncode == 0, done.stderr
    # as the cells will list them (tools/layer_cells.py): the contract holds
    catalog = harness.Catalog()
    cells_ = [dict(catalog.json("workloads", c),
                   per_layer=catalog.json("workloads", c)["per_layer"] + names)
              for c, names in layer_cells.APPENDED.items()]
    layers = {catalog.json("layer_metrics", n)["layer"]
              for n in catalog.names("layer_metrics")
              if n not in NEW}
    for name in NEW:
        m = catalog.json("layer_metrics", name)
        assert m["name"] == name and manifest.NAME.match(name)
        assert manifest.UNIT.match(m["unit"]) and m["better"] == "lower"
        assert m["source"] in ("device_trace", "program_span")
        assert m["layer"] in layers | {
            "full-sample rescore (parallel/bucketing.py)"}
        assert all(m["moves"] in c["end_to_end"] for c in cells_
                   if name in c["per_layer"])
        assert any(name in c["per_layer"] for c in cells_)


# -- the cells as they will be, end to end on the CPU -------------------------

@pytest.mark.parametrize("cell", sorted(layer_cells.APPENDED))
def test_traced_dry_run_reports_the_span_metrics(cell, tmp_path):
    """What the rehearsal holds a traced CPU dry run to: every
    ``program_span`` metric the cell lists is in the line and finite, every
    ``device_trace`` metric is absent, nothing compiles in the window (the
    table is built inside ``warm_fit``)."""
    path = layer_cells.write(str(tmp_path))
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest", path,
         "--workload", cell + layer_cells.SUFFIX, "--seed", "3000000019",
         "--seconds", "1", "--trace", "1", "--dry-run"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=600,
        env={**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla")})
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    assert line["checks"]["no_compile_in_window"]
    catalog = harness.Catalog(path)
    listed = catalog.json("workloads", cell + layer_cells.SUFFIX)["per_layer"]
    assert set(layer_cells.APPENDED[cell]) <= set(listed)
    want = {n for n in listed
            if catalog.json("layer_metrics", n)["source"] != "device_trace"}
    assert set(line["metrics"]) == want
    assert {"dispatch_us_per_fit", "coord_bucket_s", "coord_upload_s"} <= want
    assert all(math.isfinite(m["value"]) and m["value"] > 0
               for m in line["metrics"].values())


def test_traced_dry_run_of_the_heavy_tailed_cell(tmp_path):
    """``glmix_ml20m.train`` (ISSUE 25) lists its layer metrics itself: a
    traced CPU dry run reports the three the program's spans and iteration
    outputs feed, leaves out what needs a device trace, holds the passive
    rows of the last fit to the plain reference, and compiles nothing in
    the window although a traced fit fetches its iteration counts."""
    cell = "glmix_ml20m.train"
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "3000000019", "--seconds", "1", "--trace", "1",
         "--dry-run"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=600,
        env={**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla")})
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    assert line["checks"]["no_compile_in_window"]
    catalog = harness.Catalog()
    listed = catalog.json("workloads", cell)["per_layer"]
    want = {n for n in listed
            if catalog.json("layer_metrics", n)["source"] != "device_trace"}
    assert set(line["metrics"]) == want
    assert {"solve_classes", "solve_slot_fill",
            "solve_lane_waste_share"} <= want
    assert {"tail_solve_busy_share", "device_idle_share"} <= set(listed) - want
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["solve_classes"] == 14  # 1 to 64 rows: seven powers of two each
    assert 50 < m["solve_slot_fill"] < 100
    assert 0 < m["solve_lane_waste_share"] < 100
    d = line["detail"]
    assert d["passive_entities"] == 8 and d["passive_rows_checked"] > 0
    assert d["passive_rows_err"] < 1e-2 and d["newton_parity_err"] < 1e-2
    assert d["passive_score_err"] < 1e-5  # the scoring alone: float32 rounding
    # both random effects' solves against the reference, the one updated
    # first through its own update on the last fit's scores
    assert set(d["solve_precision"]) == {"per-user", "per-item"}
    assert all(q["entities"] == 64 and q["p10"] < 3e-4 and q["p10"] <= q["max"]
               for q in d["solve_precision"].values())
    assert d["reference_dtype"] == "float32"


def test_traced_dry_run_of_the_tuning_cell(tmp_path):
    """``glmix_tune_ml20m.tune_jobs`` (ISSUE 32): a traced CPU dry run
    reports the span metrics of a trial (the host's part of the suite, the
    proposals, what the chip waits for), leaves out what needs a device
    trace, holds four trials to the plain references and compiles nothing
    in the window although every trial has other weights and the validated
    program's table is recorded."""
    cell = "glmix_tune_ml20m.tune_jobs"
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "3200000019", "--seconds", "2", "--trace", "1",
         "--dry-run"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=900,
        env={**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla")})
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    assert line["checks"]["no_compile_in_window"]
    assert line["checks"]["one_program"]
    catalog = harness.Catalog()
    listed = catalog.json("workloads", cell)["per_layer"]
    want = {n for n in listed
            if catalog.json("layer_metrics", n)["source"] != "device_trace"}
    assert set(line["metrics"]) == want
    assert {"xtune_evaluate_ms_per_trial", "xtune_propose_ms_per_trial",
            "xtune_host_ms_per_trial", "solve_classes"} <= want
    assert {"xtune_validate_busy_share", "device_idle_share",
            "fused_glm_busy_share"} <= set(listed) - want
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert all(math.isfinite(v) and v > 0 for v in m.values())
    # the chip waits for the proposal and for the host's part of a trial
    assert m["xtune_host_ms_per_trial"] > m["xtune_propose_ms_per_trial"]
    assert m["xtune_host_ms_per_trial"] > m["xtune_evaluate_ms_per_trial"]


def test_traced_dry_run_of_the_userbag_cell(tmp_path):
    """``glmix_userbag_ml20m.train`` (ISSUE 36): a traced CPU dry run over a
    per-user effect on the item's sparse bag ends ``correct`` against the
    projected reference (every class reached, exact zeros off the kept
    columns, every row of the capped users), compiles nothing in the
    window, reports the span metrics of the compaction and leaves out what
    needs a device trace."""
    cell = "glmix_userbag_ml20m.train"
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "3600000019", "--seconds", "1", "--trace", "1",
         "--dry-run"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=600,
        env={**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla")})
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    assert line["checks"]["no_compile_in_window"]
    assert line["checks"]["zero_off_support"]
    assert line["checks"]["every_class_reached"]
    catalog = harness.Catalog()
    listed = catalog.json("workloads", cell)["per_layer"]
    want = {n for n in listed
            if catalog.json("layer_metrics", n)["source"] != "device_trace"}
    assert set(line["metrics"]) == want
    assert {"xuserbag_compact_fill", "xuserbag_bucket_s", "solve_classes",
            "solve_slot_fill"} <= want
    assert {"xuserbag_rescore_busy_share", "xuserbag_rescore_hbm_share",
            "xuserbag_backproject_busy_share", "device_idle_share",
            "fused_glm_busy_share"} <= set(listed) - want
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["solve_classes"] == 14  # 1 to 64 rows: seven powers of two each
    assert 0 < m["xuserbag_compact_fill"] <= 100
    assert 0 < m["xuserbag_bucket_s"] < m["coord_build_s"]
    bag = line["detail"]["bag_checks"]["per-user"]
    assert bag["entities"] >= 64 and bag["classes_reached"] == bag["classes"]
    assert bag["off_support"] == 0 and bag["p10"] < 3e-4 <= 1
    assert bag["passive_entities"] == 8 and bag["passive_rows"] > 0
    assert bag["score_err"] < 1e-5   # the sparse rescore alone: float32 rounding
    assert bag["passive_err"] < 5e-2
    assert line["detail"]["reference_dtype"] == "float32"


# -- (viii) a cache another tree filled ---------------------------------------

def test_table_is_this_trees_in_a_cache_another_tree_filled(
        cells, persistent_cache, monkeypatch):
    """The persistent cache's key leaves metadata out, so an executable
    loaded from a cache that the SAME program WITHOUT scopes filled (the
    parent commit's) has no ``photon.*`` in its text.  The traced path
    keys its own compile on metadata and reads this tree's scopes."""
    import photon_ml_tpu.game.coordinate as coordinate
    import photon_ml_tpu.game.fused as fused

    with monkeypatch.context() as m:  # the program as the parent traces it
        for module in (coordinate, fused):
            m.setattr(module, "device_scope",
                      lambda *a: contextlib.nullcontext())
        parent = new_sweep(cells, "glmix_chip")
        args, _ = parent._program_args(None, None, 0, None)
        text = parent._program.lower(*args).compile().as_text()
        assert "photon." not in text
    stale = new_sweep(cells, "glmix_chip")._program.lower(*args).compile()
    # the trap: this tree's program, loaded with the parent's metadata
    assert "photon." not in stale.as_text()
    with tracing() as tracer:
        new_sweep(cells, "glmix_chip").run_device()
        table = tracer.device_tables()["jit_program"]
    assert {s for p in table.values() for s in scopes_of(p)} \
        >= LAYERS["glmix_chip"]
