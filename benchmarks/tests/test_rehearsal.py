"""The benchmark's rehearsal, on the CPU.  Run by its own command, not part
of the repo's tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_rehearsal.py -q

- every cell end to end with ``--dry-run`` (tiny sizes, CPU backend, the
  program's kernels as it runs them there), ``--trace 0`` and ``--trace 1``;
- the ``chips: 4`` path on four virtual devices, as a cell added as data;
- further cells (the two serving cells among them), a second traffic
  parameter set, a configuration and a per-layer metric added from a temp
  dir without editing a file;
- the trace reduction against the small trace recorded on a v5e;
- the two plain references against the system at a small size;
- ``BENCHMARK.json`` against the files and the contract's limits.

Nothing here is a chip result: a dry run's line says ``dry_run``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, REPO]

import manifest  # noqa: E402
import run as harness  # noqa: E402
import trace_reduce  # noqa: E402

RECORDED = os.path.join(HERE, "data", "small_trace.xplane.pb.gz")
CELLS = ["glmix3_wide.train", "glmix_chip.train"]
# measured by PR 22 and left out (PERF.md section 7): everything but the
# cell's own file is under benchmarks/, and the rehearsal adds that file
LEFT_OUT = {
    "glmix3_wide.serve_steady": {
        "name": "glmix3_wide.serve_steady",
        "config": "glmix3_wide",
        "traffic": "serve_steady",
        "chips": 1,
        "end_to_end": [
                "serve_p50_ms",
                "setup_s"
        ],
        "per_layer": [
                "steady_device_idle_share",
                "steady_flush_rows_mean",
                "steady_resolve_us_per_row",
                "steady_execute_us_per_flush",
                "steady_p90_ms",
                "steady_p99_ms",
                "gen_lag_p99_ms"
        ],
        "traffic_params": {
                "slates_per_s": 32,
                "rate_from": "0.8 x 40 slates/s, the highest rate at which no run of mine ever shed (tools/knee_sweep.py and 36 s runs on a v5e, PR 22; tables in PERF.md section 4). At 48 to 50, 3 of 15 runs latched admission for good"
        },
        "why": "open-loop slates below the knee; the real size is the stream"
},
    "glmix3_wide.serve_over": {
        "name": "glmix3_wide.serve_over", "config": "glmix3_wide",
        "traffic": "serve_over", "chips": 1,
        "why": "the slate mix above the knee; admission sheds the excess",
        "end_to_end": ["serve_scores_per_s", "setup_s"],
        "per_layer": ["over_device_idle_share", "flush_rows_mean",
                      "resolve_us_per_row", "execute_us_per_flush",
                      "shed_share", "over_gen_lag_p99_ms", "over_p50_ms",
                      "over_p99_ms"],
        "traffic_params": {"slates_per_s": 75}},
}


def dry_run(cell, trace, seconds=2, extra=(), env=None):
    """One ``--dry-run`` of the benchmark's own command; the parsed line."""
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "3", "--seconds", str(seconds), "--trace", str(trace),
         "--dry-run", *extra],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env={**os.environ, **(env or {})}, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def check_line(line, cell, trace, catalog):
    """The contract's keys, and the metrics the cell's file names."""
    wl = catalog.json("workloads", cell)
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line, key
    assert line["dry_run"] is True
    assert line["correct"] is True, line["checks"]
    # a shed slate is failed, not incorrect: a loaded CPU under the tracer
    # sheds some; a fit never fails
    assert line["attempted"] > line["failed"] >= 0
    assert line["failed"] == 0 or "serve" in cell
    assert line["checks"]["no_compile_in_window"], line["compiles_in_window"]
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    names = wl["per_layer"] if trace else wl["end_to_end"]
    # device-trace metrics have nothing to read on the CPU and are left out
    group = "layer_metrics" if trace else "end_to_end"
    expected = [n for n in names
                if catalog.json(group, n)["source"] != "device_trace"]
    assert set(line["metrics"]) == set(expected)
    for name, m in line["metrics"].items():
        assert m["unit"] == catalog.json(group, name)["unit"]
        assert np.isfinite(m["value"]), name


# -- every cell, end to end --------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_dry_run(cell, trace):
    check_line(dry_run(cell, trace), cell, trace, harness.Catalog())


def test_no_tpu_no_result():
    """Without ``--dry-run`` and without a TPU: no line, another code."""
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[1], "--seed", "0", "--seconds", "1", "--trace", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no TPU" in done.stderr


# -- what a later PR adds, as files only -------------------------------------

def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f)


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """A temp dir that adds, without touching a file under benchmarks/: a
    configuration, a traffic parameter set, a per-layer metric with its
    reader, a fifth cell on one chip and a ``chips: 4`` cell.  The manifest
    lists the temp dir as one more of its ``paths``."""
    root = tmp_path_factory.mktemp("later_pr")
    extra = os.path.join(root, "more")
    catalog = harness.Catalog()
    cfg = catalog.json("configs", "glmix_chip")
    cfg.update(name="glmix_tiny", why="a later PR's configuration",
               dry_run=dict(cfg["dry_run"], users=64))
    _write(os.path.join(extra, "configs", "glmix_tiny.json"), cfg)
    _write(os.path.join(extra, "traffic", "train_fits_few.json"),
           {"extends": "train_fits", "dry_run": {"parity_entities": 8}})
    _write(os.path.join(extra, "layer_metrics", "fits_counted.json"),
           {"name": "fits_counted", "unit": "fits", "better": "higher",
            "source": "host_clock", "layer": "descent program (game/fused.py)",
            "moves": "train_examples_per_s"})
    _write(os.path.join(extra, "layer_metrics", "fits_counted.py"),
           "def read(readings):\n"
           "    return sum(1 for s in readings['spans'] if s[0] == 'fit')\n")
    for name, chips in (("glmix_tiny.train_few", 1),
                        ("glmix_tiny.train_x4", 4)):
        _write(os.path.join(extra, "workloads", name + ".json"), {
            "name": name, "config": "glmix_tiny",
            "traffic": "train_fits_few" if chips == 1 else "train_fits",
            "chips": chips, "why": "added by the rehearsal as data",
            "end_to_end": ["train_examples_per_s", "setup_s"],
            "per_layer": ["fit_s", "fits_counted"], "gates": {}})
    for name, cell in LEFT_OUT.items():
        _write(os.path.join(extra, "workloads", name + ".json"), cell)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    m["paths"] = [BENCH, extra]
    path = os.path.join(root, "BENCHMARK.json")
    _write(path, m)
    return path


def test_fifth_cell_is_data(added):
    line = dry_run("glmix_tiny.train_few", 1, extra=("--manifest", added))
    assert line["correct"] and line["metrics"]["fits_counted"]["value"] >= 1
    assert line["metrics"]["fit_s"]["unit"] == "s"
    line = dry_run("glmix_tiny.train_few", 0, extra=("--manifest", added))
    assert set(line["metrics"]) == {"train_examples_per_s", "setup_s"}
    assert line["detail"]["rows"] == 64 * 48


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", sorted(LEFT_OUT))
def test_left_out_cell_is_one_file(added, cell, trace):
    catalog = harness.Catalog(added)
    check_line(dry_run(cell, trace, extra=("--manifest", added)), cell,
               trace, catalog)
    built = manifest.build(catalog)
    assert not [p for p in manifest.problems(built) if cell in p]
    assert cell in [w["name"] for w in built["workloads"]]
    assert LEFT_OUT[cell]["config"] in [c["name"] for c in built["configs"]]


def test_four_chips_on_virtual_devices(added):
    """``chips: 4``: the mesh is built from ``jax.devices()`` and the rate
    is per chip.  Four virtual CPU devices stand in."""
    line = dry_run("glmix_tiny.train_x4", 0, extra=("--manifest", added))
    assert line["correct"], line["checks"]
    assert line["device"]["count"] == 4
    one = dry_run("glmix_tiny.train_few", 0, extra=("--manifest", added))
    # same rows, same fit: both runs count the rows once, the four-chip one
    # divides by four
    assert line["detail"]["rows"] == one["detail"]["rows"]
    d = line["detail"]
    whole = d["rows"] * d["sweeps"] * d["fits_in_window"]
    per_chip = line["metrics"]["train_examples_per_s"]["value"]
    assert per_chip * line["window_s"] < whole / 4 * 1.05


# -- the manifest and the contract -------------------------------------------

def test_manifest_matches_files_and_contract():
    catalog = harness.Catalog()
    built = manifest.build(catalog)
    assert manifest.problems(built) == []
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        assert json.load(f) == built
    assert [w["name"] for w in built["workloads"]] == sorted(CELLS)
    for group in ("end_to_end", "per_layer"):
        for m in built[group]:
            assert manifest.NAME.match(m["name"]), m["name"]
            assert manifest.UNIT.match(m["unit"]) and len(m["unit"]) <= 16
    # every file under the benchmark is named from a name's characters
    for d, _, files in os.walk(BENCH):
        if "__pycache__" in d or ".pytest_cache" in d:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), REPO)
            assert manifest.PATH.match(rel), rel


def test_nothing_imports_bench_py():
    for d, _, files in os.walk(BENCH):
        for f in files:
            if f.endswith(".py") and f != os.path.basename(__file__):
                with open(os.path.join(d, f)) as fh:
                    text = fh.read()
                assert "import bench\n" not in text
                assert "from bench import" not in text


def test_peaks_keyed_by_device_kind():
    import roofline

    assert roofline.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks_for("some other chip")
    need = roofline.fused_glm_call(1 << 20, 512, "bfloat16")
    assert need["bytes"] == (1 << 20) * 512 * 2 + 3 * (1 << 20) * 4 + 16 * 512 * 4


# -- the trace reduction -----------------------------------------------------

def test_reduction_on_synthetic_events():
    """Nesting, union and gaps, on events whose answer is known."""
    ev = [("while.1", 0.0, 100.0), ("fusion.1", 10.0, 20.0),
          ("soa_newton_step.2", 40.0, 50.0), ("fusion.1", 150.0, 10.0)]
    busy, gaps = trace_reduce.busy_and_gaps(ev, 0.0, 200.0)
    assert busy == 110.0
    assert gaps == [(100.0, 150.0), (160.0, 200.0)]
    own = trace_reduce.self_times(ev, 0.0, 200.0)
    assert own["while.1"] == [30.0, 1]
    assert own["fusion.1"] == [30.0, 2]
    assert trace_reduce.time_of(own, "soa_newton_step") == (
        pytest.approx(50e-9), 1)
    spans = [("fit", 90.0, 170.0), ("outer", 0.0, 1000.0)]
    assert trace_reduce.label_gap((100.0, 150.0), spans) == "fit"
    assert trace_reduce.label_gap((160.0, 200.0), spans) == "outer"
    assert trace_reduce.op_name("%fusion.12 = f32[8]{0} fusion(...)") \
        == "fusion.12"


def test_reduction_on_recorded_trace():
    """The small trace recorded on a v5e (a 32,768-row ``glmix_chip`` fit,
    three fits in the slice): the numbers below were read off it by hand
    (``python benchmarks/trace_reduce.py <file>``) when it was recorded."""
    with open(os.path.join(HERE, "data", "small_trace.expected.json")) as f:
        want = json.load(f)
    got = trace_reduce.reduce(trace_reduce.load(RECORDED))
    assert got is not None and got["chips"] == 1
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    assert 0 < got["busy_s"] <= got["window_s"]
    for kernel, (seconds, calls) in want["kernels"].items():
        s, n = trace_reduce.time_of(got["ops_self"], kernel)
        assert n == calls and s == pytest.approx(seconds, rel=1e-6), kernel
    # self times partition the busy time: nothing counted twice
    total = sum(v[0] for v in got["ops_self"].values()) * 1e-9
    assert total == pytest.approx(got["busy_s"], rel=1e-3)
    assert len(got["breakdown"]["device_ops"]) <= 10
    assert len(got["breakdown"]["idle_gaps"]) <= 5


# -- the plain references against the system, small --------------------------

def test_newton_reference_matches_the_program():
    """The per-entity solve: the program's published coefficients of the
    coordinate updated last vs reference/newton_solve.py, on a small
    ``glmix3_wide``-shaped fit (d = 16, vmapped L-BFGS, float32).

    Tolerance 2e-3 of the sample's largest coefficient: the program stops
    its L-BFGS at a relative gradient tolerance of 1e-7 or 30 iterations in
    float32, and at 32 rows an entity the Hessian's smallest eigenvalue is
    near the L2 weight, so a gradient residual of that size is a
    coefficient error of up to ~1e-3.  A solve at half the iterations, or
    against the wrong offsets, is off by 1e-1 and more."""
    line = dry_run("glmix3_wide.train", 0)
    assert line["detail"]["newton_parity_err"] < 2e-3
    # the Newton SoA path, bf16 storage
    line = dry_run("glmix_chip.train", 0)
    assert line["detail"]["newton_parity_err"] < 2e-3


def test_forward_reference_matches_the_program():
    """The served score: ``GameModel.score`` vs reference/glmix_forward.py
    on seeded coefficients and rows, on the CPU in float32.  Tolerance: the
    reference's own bf16-product bound is the chip's; here both sides are
    float32 sums of 160 products in different orders, so 1e-5 of the
    largest score (a few ulp of the sum of absolute terms)."""
    import importlib.util

    from photon_ml_tpu.game import GameData
    from photon_ml_tpu.models.game import (FixedEffectModel, GameModel,
                                           RandomEffectModel)
    from photon_ml_tpu.models.glm import Coefficients

    spec = importlib.util.spec_from_file_location(
        "glmix_forward", os.path.join(BENCH, "reference", "glmix_forward.py"))
    forward = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(forward)

    rng = np.random.default_rng(5)
    n, users = 200, 30
    xg = rng.standard_normal((n, 128)).astype(np.float32)
    xu = rng.standard_normal((n, 16)).astype(np.float32)
    wg = (rng.standard_normal(128) * 0.05).astype(np.float32)
    wu = (rng.standard_normal((users, 16)) * 0.15).astype(np.float32)
    ids = rng.integers(0, users + 5, size=n)  # some the model never saw
    slots = np.where(ids < users, ids, -1)
    model = GameModel(models={
        "fixed": FixedEffectModel(Coefficients(means=wg), "g"),
        "per-user": RandomEffectModel(
            w_stack=wu, slot_of={e: e for e in range(users)},
            random_effect_type="userId", feature_shard="u")})
    got = np.asarray(model.score(GameData(
        y=np.zeros(n, np.float32), features={"g": xg, "u": xu},
        id_tags={"userId": ids})))
    want, bound = forward.scores(xg, wg, [(xu, wu, slots)])
    assert np.max(np.abs(got - want)) <= 1e-5 * np.max(np.abs(want))
    assert np.all(bound > 0)
