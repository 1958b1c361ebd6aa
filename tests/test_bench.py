"""Unit tests for the bench harness itself (bench.py is the driver's entry
artifact — its measurement and gating logic deserve the same regression
protection as the library)."""

import os
import sys

import pytest

# bench.py lives at the repo root, one level above tests/
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench  # noqa: E402


class TestMeasure:
    def test_five_repeats_and_median(self):
        calls = []

        def thunk():
            calls.append(1)
            return [0.3, 0.1, 0.2, 0.5, 0.4][len(calls) - 1]

        med, timing = bench._measure(thunk)
        assert len(calls) == 5
        assert med == pytest.approx(0.3)
        assert timing == {"n_repeats": 5, "dt_median": 0.3,
                          "dt_min": 0.1, "dt_max": 0.5}

    def test_fast_thunk_accumulates_min_window(self):
        """A sub-ms thunk (TPU a1a's whole solve is ~0.1ms) must repeat until
        >=min_window seconds of samples exist — 5 samples of dispatch jitter
        are not a measurement (VERDICT r2 weak #5, second edition)."""
        med, timing = bench._measure(lambda: 0.001)
        assert timing["n_repeats"] == 500  # 0.5s window / 1ms
        assert med == pytest.approx(0.001)

    def test_fast_thunk_repeat_cap(self):
        """The repeat cap must sit far above min_window/dt for any real
        config so the window is reached, and still bound a pathological
        zero-cost thunk."""
        med, timing = bench._measure(lambda: 0.0)
        assert timing["n_repeats"] == 5000

    def test_slow_config_stops_at_budget(self):
        """Full-scale configs with multi-minute repeats stop at max_total —
        every repeat is seconds long, satisfying the dt>=2s criterion."""
        med, timing = bench._measure(lambda: 50.0, max_total=120.0)
        assert timing["n_repeats"] == 3  # 50+50 < 120 <= 50+50+50
        assert med == 50.0


class TestQualityGates:
    def test_gp_tune_gate_can_fail(self):
        """VERDICT r2 weak #2: the old gate passed on equality (a tuner that
        finds nothing).  The gate must now DEMAND improvement."""
        stats_flat = {"best_auc": 0.90477, "prior_auc": 0.90477, "fits": 7}
        assert bench.quality_gate("gp_tune", stats_flat, None)["pass"] is False
        stats_worse = {"best_auc": 0.80, "prior_auc": 0.85, "fits": 7}
        assert bench.quality_gate("gp_tune", stats_worse, None)["pass"] is False
        stats_better = {"best_auc": 0.88, "prior_auc": 0.84, "fits": 7}
        gate = bench.quality_gate("gp_tune", stats_better, None)
        assert gate["pass"] is True
        assert gate["improvement"] == pytest.approx(0.04)

    def test_auc_gates_use_reference(self):
        ref = {"auc": 0.9}
        assert bench.quality_gate("a1a", {"auc": 0.9001}, ref)["pass"] is True
        assert bench.quality_gate("a1a", {"auc": 0.88}, ref)["pass"] is False
        assert bench.quality_gate("glmix2", {"auc": 0.904},
                                  {"auc": 0.9})["pass"] is True
        # no reference -> explicitly unknown, never silently green
        assert bench.quality_gate("a1a", {"auc": 0.9}, None)["pass"] is None

    def test_sparse1m_gate_relative(self):
        ref = {"mean_nll": 0.5}
        assert bench.quality_gate("sparse1m", {"mean_nll": 0.5001},
                                  ref)["pass"] is True
        assert bench.quality_gate("sparse1m", {"mean_nll": 0.52},
                                  ref)["pass"] is False


class TestEntry:
    def test_entry_from_carries_timing_and_ratio(self):
        got = {"dt": 2.0, "units": 100, "unit": "examples/sec",
               "backend": "cpu", "stats": {"best_auc": 0.9, "prior_auc": 0.8,
                                           "fits": 7},
               "timing": {"n_repeats": 5, "dt_median": 2.0,
                          "dt_min": 1.9, "dt_max": 2.2},
               "impl": "fused"}
        entry = bench._entry_from("gp_tune", got, scale=8, want_cpu_ref=False)
        assert entry["value"] == 50.0
        assert entry["vs_baseline"] is None  # no stand-in requested
        assert entry["timing"]["n_repeats"] == 5
        assert entry["impl"] == "fused"
        assert entry["quality"]["pass"] is True


class TestSynth:
    def test_synth_shapes_scale(self):
        xg, xu, uids, y = bench.synth_tune(8)
        assert len(y) == len(uids) == xg.shape[0] == xu.shape[0] == 8192
        xg1, *_ = bench.synth_tune(1)
        assert xg1.shape[0] == 65536
        data = bench.synth_glmix(8, False)
        assert data["xg"].shape == (2048 * 32, 256)


class TestVariants:
    @pytest.mark.parametrize("platform,want", [
        ("cpu", ["glmix2", "glmix2_host", "glmix2_bf16"]),
        ("tpu", ["glmix2", "glmix2_host", "glmix2_xla", "glmix2_bf16"])])
    def test_variants_share_one_upload_in_process(self, monkeypatch,
                                                  platform, want):
        """glmix2's A/B variants run in THIS process over one design
        upload (no child per variant).  The pallas-off variant exists only
        where there is a pallas path — on a TPU — and the f32 variants see
        the SAME device-resident design while bf16 gets the host bytes."""
        seen = []

        def fake_measure(backend, data, three, impl):
            seen.append((impl, type(data["xg"]).__module__.split(".")[0],
                         os.environ.get("PHOTON_GLM_DISABLE_PALLAS"),
                         id(data["xg"])))
            return {"backend": backend, "dt": 1.0, "impl": impl,
                    "units": 10, "unit": "x/sec", "stats": {}}

        monkeypatch.setattr(bench, "_glmix_measure", fake_measure)
        monkeypatch.setattr(bench, "_select_platform",
                            lambda p: {"platform": platform, "kind": "k",
                                       "count": 1})
        monkeypatch.delenv("PHOTON_GLM_DISABLE_PALLAS", raising=False)
        by = bench.run_glmix2_variants(platform, 128)
        assert list(by) == want
        f32 = [s for s, name in zip(seen, want) if name != "glmix2_bf16"]
        assert {s[1] for s in f32} != {"numpy"}       # uploaded once ...
        assert len({s[3] for s in f32}) == 1          # ... and shared
        assert seen[-1][1] == "numpy"                 # bf16: host-narrowed
        assert [s[2] for s, name in zip(seen, want)
                if name == "glmix2_xla"] == ["1"] * (platform == "tpu")
        assert "PHOTON_GLM_DISABLE_PALLAS" not in os.environ

    def test_failed_variant_costs_only_itself(self, monkeypatch):
        """A variant that raises is recorded with its error text — never
        retried on another impl, never silently dropped — and the env knob
        it toggled is restored."""
        def fake_measure(backend, data, three, impl):
            if impl == "host":
                raise RuntimeError("synthetic host crash")
            return {"backend": backend, "dt": 1.0, "impl": impl,
                    "units": 10, "unit": "x/sec", "stats": {},
                    "storage": os.environ.get("PHOTON_BENCH_STORAGE")}

        monkeypatch.setattr(bench, "_glmix_measure", fake_measure)
        monkeypatch.setattr(bench, "_select_platform",
                            lambda p: {"platform": "cpu", "kind": "cpu",
                                       "count": 1})
        monkeypatch.delenv("PHOTON_BENCH_STORAGE", raising=False)
        by = bench.run_glmix2_variants("cpu", 128)
        assert "synthetic host crash" in by["glmix2_host"]["error"]
        assert by["glmix2"]["impl"] == by["glmix2_bf16"]["impl"] == "fused"
        assert by["glmix2_bf16"]["storage"] == "bfloat16"
        assert "PHOTON_BENCH_STORAGE" not in os.environ


class TestNoFallback:
    def test_fused_crash_is_the_result(self, monkeypatch):
        """run_glmix: a fused-impl exception propagates.  The host loop
        does not stand in for it (it used to, with rc 0 and a fused_error
        tag nobody read)."""
        calls = []

        def fake_measure(backend, data, three, impl):
            calls.append(impl)
            raise RuntimeError("synthetic fused crash")

        monkeypatch.setattr(bench, "_glmix_measure", fake_measure)
        monkeypatch.setattr(bench, "_select_platform",
                            lambda p: {"platform": "cpu", "kind": "cpu",
                                       "count": 1})
        monkeypatch.delenv("PHOTON_BENCH_IMPL", raising=False)
        with pytest.raises(RuntimeError, match="synthetic fused crash"):
            bench.run_glmix("cpu", 128, three=False)
        assert calls == ["fused"]

    def test_no_tpu_means_failure_unless_cpu_is_asked_for(self):
        """JAX falls back to the CPU when no chip answers; the bench must
        not.  ``--platform cpu`` is the only way onto the CPU."""
        with pytest.raises(SystemExit, match="no TPU"):
            bench._select_platform(None)
        dev = bench._select_platform("cpu")
        assert dev["platform"] == "cpu" and dev["count"] >= 1
        for gone in ("probe_platform", "_tpu_evidence_pointer",
                     "_subprocess_json", "run_glmix2_ab_chain"):
            assert not hasattr(bench, gone)


class TestGateFalsifiability:
    """VERDICT r3 weak #3: the glmix gates must be able to FAIL.  Both
    sabotages run the real measurement end-to-end at 1/64 scale against the
    real scipy stand-in; the synthetics' cross-shard correlation is what
    makes the residual fold's absence visible (synth_glmix docstring)."""

    @pytest.fixture(scope="class")
    def glmix64(self):
        data = bench.synth_glmix(64, False)
        return data, bench._scipy_glmix(data, False)

    def test_healthy_run_passes(self, glmix64):
        data, ref = glmix64
        got = bench._glmix_measure("cpu", dict(data), False, "fused")
        gate = bench.quality_gate("glmix2", got["stats"], ref)
        assert gate["pass"] is True
        assert gate["coef_rel_err"] <= 0.01  # healthy margin is ~3e-5

    def test_mis_set_reg_weight_fails(self, glmix64, monkeypatch):
        import dataclasses

        from photon_ml_tpu.core.regularization import Regularization

        data, ref = glmix64
        orig = bench._glmix_coords

        def sabotaged(d, three):
            return {cid: c.rebind(dataclasses.replace(
                c.config, reg=Regularization(l2=c.config.reg.l2 * 100.0)))
                for cid, c in orig(d, three).items()}

        monkeypatch.setattr(bench, "_glmix_coords", sabotaged)
        got = bench._glmix_measure("cpu", dict(data), False, "fused")
        gate = bench.quality_gate("glmix2", got["stats"], ref)
        assert gate["pass"] is False
        assert gate["coef_rel_err"] > 0.05

    def test_broken_residual_fold_fails(self, glmix64, monkeypatch):
        """Coordinates trained against ZERO residuals (the exact breakage a
        wrong fold would cause).  The AUC barely moves — the coefficient
        parity is what catches it, which is why the gate has it."""
        import jax.numpy as jnp

        import photon_ml_tpu.game.coordinate as gc

        data, ref = glmix64
        o_f = gc.FixedEffectCoordinate.trace_update
        o_r = gc.RandomEffectCoordinate.trace_update
        monkeypatch.setattr(
            gc.FixedEffectCoordinate, "trace_update",
            lambda self, s, off, **k: o_f(self, s, jnp.zeros_like(off), **k))
        monkeypatch.setattr(
            gc.RandomEffectCoordinate, "trace_update",
            lambda self, s, off, **k: o_r(self, s, jnp.zeros_like(off), **k))
        got = bench._glmix_measure("cpu", dict(data), False, "fused")
        gate = bench.quality_gate("glmix2", got["stats"], ref)
        assert gate["pass"] is False
        assert gate["auc_diff"] <= 0.005          # AUC alone would pass...
        assert gate["coef_rel_err"] > 0.05        # ...the coef gate fails it


class TestChipGateFalsifiability:
    """VERDICT r4 missing #3: glmix_chip's gate was self-referential (AUC +
    signal/noise columns from the same generative formula).  At CPU-feasible
    scales the device-generated design is host-reconstructible (threefry is
    platform-deterministic), so an INDEPENDENT scipy fit of the same data
    pins coefficient parity — the chip-scale run keeps vs_baseline null but
    inherits this floor-scale anchor as its falsifiable gate."""

    @pytest.fixture(scope="class")
    def chip1024(self):
        # direct stand-in call (like TestGateFalsifiability's _scipy_glmix):
        # going through cpu_ref would read/write the shared repo-level
        # .bench_cpu_cache.json and let a stale entry stand in for the code
        # under test
        got = bench.run_glmix_chip("cpu", 1024)
        ref = bench._scipy_glmix_chip(1024)
        return got, ref

    def test_healthy_run_passes(self, chip1024):
        got, ref = chip1024
        gate = bench.quality_gate("glmix_chip", got["stats"], ref)
        assert gate["pass"] is True
        assert gate["coef_rel_err"] <= 0.01  # healthy margin is ~5e-4
        assert gate["auc_diff"] <= 0.005

    def test_mis_set_reg_weight_fails(self, chip1024, monkeypatch):
        import dataclasses

        import photon_ml_tpu.game.coordinate as gc
        from photon_ml_tpu.core.regularization import Regularization

        _, ref = chip1024
        orig = gc.build_coordinate

        def sabotaged(cid, data, cfg, task, **kw):
            cfg = dataclasses.replace(
                cfg, reg=Regularization(l2=cfg.reg.l2 * 100.0))
            return orig(cid, data, cfg, task, **kw)

        monkeypatch.setattr(gc, "build_coordinate", sabotaged)
        got = bench.run_glmix_chip("cpu", 1024)
        gate = bench.quality_gate("glmix_chip", got["stats"], ref)
        assert gate["pass"] is False
        assert gate["coef_rel_err"] > 0.05

    def test_chip_scale_run_keeps_null_baseline(self, chip1024):
        """A chip-backend run carries no wg (and no scipy ref is reachable):
        the gate must stay the self-band, with no parity fields."""
        got, ref = chip1024
        stats = {k: v for k, v in got["stats"].items() if k != "wg"}
        gate = bench.quality_gate("glmix_chip", stats, ref)
        assert "coef_rel_err" not in gate
        assert gate["pass"] is True


_GOT = {"dt": 2.0, "units": 100, "unit": "examples/sec/chip",
        "stats": {"best_auc": 0.9, "prior_auc": 0.8, "fits": 7},
        "flops_est": 4e12, "bytes_est": 8e11}


class TestPeaksTable:
    """Roofline ratios come from a table keyed by device_kind; the v5e's
    peaks are never applied to whatever else is not a CPU."""

    def test_known_kind_gets_its_own_peaks(self):
        e = bench._entry_from("gp_tune", dict(_GOT, backend="tpu"), 1, False,
                              device_kind="TPU v5 lite")
        assert e["mfu_bf16_peak"] == pytest.approx(2e12 / 197e12, rel=1e-3)
        assert e["hbm_bw_util"] == pytest.approx(4e11 / 819e9, rel=1e-3)

    def test_unknown_kind_is_an_error(self):
        with pytest.raises(KeyError, match="TPU v9"):
            bench.peaks_for("TPU v9")
        with pytest.raises(KeyError, match="no published peaks"):
            bench._entry_from("gp_tune", dict(_GOT, backend="tpu"), 1, False,
                              device_kind="TPU v9")
        # default: the kind this process runs on — here a CPU, which has
        # no entry either
        with pytest.raises(KeyError, match="'cpu'"):
            bench._entry_from("gp_tune", dict(_GOT, backend="tpu"), 1, False)

    def test_cpu_run_carries_no_device_ratio(self):
        e = bench._entry_from("gp_tune", dict(_GOT, backend="cpu"), 8, False)
        assert e["gbytes_per_sec"] == 400.0
        assert not any(k.startswith(("mfu", "hbm_bw")) for k in e)


class TestDefaultRun:
    """``python bench.py``: one process, every config in turn, the device
    named in the line, failures loud."""

    DEV = {"platform": "cpu", "kind": "cpu", "count": 1}

    def _main(self, monkeypatch, capsys, argv, runners):
        import json

        monkeypatch.setattr(sys, "argv", ["bench.py"] + argv)
        monkeypatch.setattr(bench, "_select_platform", lambda p: self.DEV)
        monkeypatch.setattr(bench, "RUNNERS", runners)
        monkeypatch.setenv("PHOTON_BENCH_CONFIGS", ",".join(runners))
        monkeypatch.setenv("PHOTON_BENCH_CPU_REF", "0")
        monkeypatch.setenv("PHOTON_BENCH_AB", "0")
        code = 0
        try:
            bench.main()
        except SystemExit as e:
            code = e.code
        return code, json.loads(capsys.readouterr().out.strip()
                                .splitlines()[-1])

    def test_line_names_the_device_and_runs_in_process(self, monkeypatch,
                                                       capsys):
        pids = []

        def glmix2(platform, scale):
            pids.append((os.getpid(), platform, scale))
            return dict(_GOT, backend="cpu")

        code, line = self._main(monkeypatch, capsys, ["--platform", "cpu"],
                                {"glmix2": glmix2})
        assert code == 0
        assert pids == [(os.getpid(), "cpu", 8)]  # this process, 1/8 scale
        assert line["device"] == self.DEV and line["backend"] == "cpu"
        assert line["value"] == 50.0 and "tpu_evidence" not in line

    def test_failed_config_is_recorded_and_exit_is_nonzero(self, monkeypatch,
                                                           capsys):
        def boom(platform, scale):
            raise RuntimeError("mosaic refused the block")

        code, line = self._main(
            monkeypatch, capsys, ["--platform", "cpu"],
            {"a1a": boom, "glmix2": lambda p, s: dict(_GOT, backend="cpu")})
        assert code == 1
        assert "mosaic refused the block" in line["configs"]["a1a"]["error"]
        assert line["configs"]["glmix2"]["value"] == 50.0  # the rest still ran

    def test_no_substitute_headline(self, monkeypatch, capsys):
        """Without a glmix2 measurement the headline is null — another
        config's number is never presented under its name."""
        code, line = self._main(
            monkeypatch, capsys, ["--platform", "cpu"],
            {"a1a": lambda p, s: dict(_GOT, backend="cpu")})
        assert code == 0 and line["value"] is None
        assert line["metric"] == "glmix_2coord_examples_per_sec_per_chip"

    def test_default_run_without_tpu_exits_nonzero_and_prints_nothing(self):
        """The real entry point, in a real process, where JAX finds no
        accelerator: non-zero exit, no result line."""
        import subprocess

        out = subprocess.run(
            [sys.executable, os.path.join(os.path.dirname(bench.__file__),
                                          "bench.py")],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, PHOTON_BENCH_CONFIGS="a1a"))
        assert out.returncode != 0
        assert out.stdout.strip() == ""
        assert "no TPU" in out.stderr


class TestOpenLoopPlumbing:
    """--serving --open-loop arg plumbing: flags reach run_open_loop_bench
    parsed, and --open-loop alone is rejected (it has no meaning without
    the serving edge)."""

    def test_flags_reach_runner_parsed(self, monkeypatch, capsys):
        import json

        seen = {}

        def fake_runner(**kw):
            seen.update(kw)
            return {"bench": "open_loop_serving", "sweep": []}

        monkeypatch.setattr(bench, "run_open_loop_bench", fake_runner)
        monkeypatch.setattr(sys, "argv", [
            "bench.py", "--serving", "--open-loop",
            "--open-loop-rates", "100,250.5",
            "--open-loop-duration", "1.5",
            "--open-loop-connections", "7",
            "--open-loop-budget-ms", "12.5",
            "--serving-entities", "123",
            "--serving-deadline-us", "300",
            "--out", "ignored.json"])
        bench.main()
        out = capsys.readouterr().out
        assert json.loads(out)["bench"] == "open_loop_serving"
        assert seen["rates"] == [100.0, 250.5]
        assert seen["duration_s"] == 1.5
        assert seen["n_connections"] == 7
        assert seen["budget_ms"] == 12.5
        assert seen["n_entities"] == 123
        assert seen["deadline_us"] == 300.0
        assert seen["out_path"] == "ignored.json"

    def test_empty_rates_mean_calibrated_multipliers(self, monkeypatch,
                                                     capsys):
        seen = {}
        monkeypatch.setattr(bench, "run_open_loop_bench",
                            lambda **kw: seen.update(kw) or {})
        monkeypatch.setattr(sys, "argv",
                            ["bench.py", "--serving", "--open-loop"])
        bench.main()
        assert seen["rates"] is None  # runner calibrates and picks rates

    def test_open_loop_requires_serving(self, monkeypatch):
        monkeypatch.setattr(sys, "argv", ["bench.py", "--open-loop"])
        with pytest.raises(SystemExit) as ei:
            bench.main()
        assert ei.value.code == 2  # argparse error exit


class TestServingMeshPlumbing:
    """--serving --mesh arg plumbing: flags reach run_serving_mesh_bench
    parsed, and --mesh alone is rejected."""

    def test_flags_reach_runner_parsed(self, monkeypatch, capsys):
        import json

        seen = {}

        def fake_runner(**kw):
            seen.update(kw)
            return {"metric": "serving_mesh_scaling", "shards": {}}

        monkeypatch.setattr(bench, "run_serving_mesh_bench", fake_runner)
        monkeypatch.setattr(sys, "argv", [
            "bench.py", "--serving", "--mesh",
            "--mesh-shard-counts", "1,2,4",
            "--serving-entities", "456",
            "--serving-requests", "77",
            "--serving-device-capacity", "32",
            "--zipf", "1.4",
            "--out", "ignored.json"])
        bench.main()
        out = capsys.readouterr().out
        assert json.loads(out)["metric"] == "serving_mesh_scaling"
        assert seen["shard_counts"] == (1, 2, 4)
        assert seen["n_entities"] == 456
        assert seen["n_requests"] == 77
        assert seen["per_shard_capacity"] == 32
        assert seen["zipf"] == 1.4
        assert seen["out_path"] == "ignored.json"

    def test_mesh_requires_serving(self, monkeypatch):
        monkeypatch.setattr(sys, "argv", ["bench.py", "--mesh"])
        with pytest.raises(SystemExit) as ei:
            bench.main()
        assert ei.value.code == 2  # argparse error exit

    def test_unset_capacity_and_zipf_get_defaults(self, monkeypatch):
        seen = {}
        monkeypatch.setattr(bench, "run_serving_mesh_bench",
                            lambda **kw: seen.update(kw) or {})
        monkeypatch.setattr(sys, "argv", ["bench.py", "--serving", "--mesh"])
        bench.main()
        assert seen["per_shard_capacity"] is None  # runner derives n/10
        assert seen["zipf"] == 1.1  # mesh sweep is always skewed


class TestSkewSweepPlumbing:
    """--serving --skew-sweep arg plumbing: flags reach
    run_skew_sweep_bench parsed, and --skew-sweep alone is rejected."""

    def test_flags_reach_runner_parsed(self, monkeypatch, capsys):
        import json

        seen = {}

        def fake_runner(**kw):
            seen.update(kw)
            return {"metric": "serving_skew_robustness"}

        monkeypatch.setattr(bench, "run_skew_sweep_bench", fake_runner)
        monkeypatch.setattr(sys, "argv", [
            "bench.py", "--serving", "--skew-sweep",
            "--skew-values", "0.9,1.3",
            "--skew-shards", "2",
            "--serving-device-capacity", "64",
            "--out", "ignored.json"])
        bench.main()
        out = capsys.readouterr().out
        assert json.loads(out)["metric"] == "serving_skew_robustness"
        assert seen["skews"] == (0.9, 1.3)
        assert seen["n_shards"] == 2
        assert seen["per_shard_capacity"] == 64
        assert seen["out_path"] == "ignored.json"

    def test_skew_sweep_requires_serving(self, monkeypatch):
        monkeypatch.setattr(sys, "argv", ["bench.py", "--skew-sweep"])
        with pytest.raises(SystemExit) as ei:
            bench.main()
        assert ei.value.code == 2  # argparse error exit

    def test_unset_capacity_and_skews_get_defaults(self, monkeypatch):
        seen = {}
        monkeypatch.setattr(bench, "run_skew_sweep_bench",
                            lambda **kw: seen.update(kw) or {})
        monkeypatch.setattr(sys, "argv",
                            ["bench.py", "--serving", "--skew-sweep"])
        bench.main()
        assert seen["per_shard_capacity"] is None  # runner derives n/10
        assert seen["skews"] == (0.8, 1.0, 1.2, 1.5)  # the headline sweep


class TestFleetPlumbing:
    """--fleet arg plumbing (flags reach run_fleet_bench parsed) plus one
    real tiny run asserting the bench's own invariants hold and the JSON
    lands where --out points."""

    def test_flags_reach_runner_parsed(self, monkeypatch, capsys):
        import json

        seen = {}

        def fake_runner(**kw):
            seen.update(kw)
            return {"bench": "fleet_serving", "compiles_after_warm": [0]}

        monkeypatch.setattr(bench, "run_fleet_bench", fake_runner)
        monkeypatch.setattr(sys, "argv", [
            "bench.py", "--fleet",
            "--fleet-models", "3",
            "--fleet-entities", "128",
            "--fleet-requests", "64",
            "--out", "ignored.json"])
        bench.main()
        out = capsys.readouterr().out
        assert json.loads(out)["bench"] == "fleet_serving"
        assert seen["n_models"] == 3
        assert seen["n_entities"] == 128
        assert seen["n_requests"] == 64
        assert seen["out_path"] == "ignored.json"

    def test_tiny_real_run_holds_invariants(self, tmp_path):
        import json

        out_path = str(tmp_path / "fleet.json")
        out = bench.run_fleet_bench(n_entities=48, d=4, n_requests=48,
                                    max_batch=8, n_models=2,
                                    out_path=out_path)
        # the Flare invariant the bench exists to watch: growing the
        # same-shape family compiled nothing after the first warm
        assert out["compiles_after_warm"] == [0, 0]
        assert out["recompiles_after_warm"] == 0
        assert out["shadow"]["pairs"] == 48
        assert out["shadow_overhead_ratio"] > 0
        assert out["canary"]["promote_settle_s"] > 0
        assert out["canary"]["rollback_reason"] == "score_drift"
        with open(out_path) as f:
            assert json.load(f)["bench"] == "fleet_serving"


class TestOnlineBenchCli:
    """--online arg plumbing: flags reach run_online_bench parsed."""

    def test_flags_reach_runner_parsed(self, monkeypatch, capsys):
        import json

        seen = {}

        def fake_runner(**kw):
            seen.update(kw)
            return {"metric": "online_refit_entities_per_s"}

        monkeypatch.setattr(bench, "run_online_bench", fake_runner)
        monkeypatch.setattr(sys, "argv", [
            "bench.py", "--online", "--online-batches", "3",
            "--online-batch-size", "16", "--out", "ignored.json"])
        bench.main()
        out = capsys.readouterr().out
        assert json.loads(out)["metric"] == "online_refit_entities_per_s"
        assert seen["batches"] == 3
        assert seen["batch_size"] == 16
        assert seen["out_path"] == "ignored.json"


class TestReplBenchCli:
    """--repl arg plumbing: flags reach run_repl_bench parsed."""

    def test_flags_reach_runner_parsed(self, monkeypatch, capsys):
        import json

        seen = {}

        def fake_runner(**kw):
            seen.update(kw)
            return {"metric": "repl_store_visible_freshness_ms_p99"}

        monkeypatch.setattr(bench, "run_repl_bench", fake_runner)
        monkeypatch.setattr(sys, "argv", [
            "bench.py", "--repl", "--repl-replicas", "3",
            "--repl-batches", "4", "--repl-batch-size", "16",
            "--out", "ignored.json"])
        bench.main()
        out = capsys.readouterr().out
        assert json.loads(out)["metric"] == \
            "repl_store_visible_freshness_ms_p99"
        assert seen["n_replicas"] == 3
        assert seen["batches"] == 4
        assert seen["batch_size"] == 16
        assert seen["out_path"] == "ignored.json"

    def test_defaults(self, monkeypatch, capsys):
        seen = {}
        monkeypatch.setattr(bench, "run_repl_bench",
                            lambda **kw: seen.update(kw) or {})
        monkeypatch.setattr(sys, "argv", ["bench.py", "--repl"])
        bench.main()
        assert seen["n_replicas"] == 2
        assert seen["batches"] == 8
        assert seen["batch_size"] == 32
        assert seen["out_path"] is None


class TestChaosBenchCli:
    """--chaos arg plumbing: flags reach run_chaos_bench parsed."""

    def test_flags_reach_runner_parsed(self, monkeypatch, capsys):
        import json

        seen = {}

        def fake_runner(**kw):
            seen.update(kw)
            return {"metric": "chaos_time_to_ready_s_max"}

        monkeypatch.setattr(bench, "run_chaos_bench", fake_runner)
        monkeypatch.setattr(sys, "argv", [
            "bench.py", "--chaos", "--chaos-seed", "7",
            "--chaos-rounds", "12", "--out", "ignored.json"])
        bench.main()
        out = capsys.readouterr().out
        assert json.loads(out)["metric"] == "chaos_time_to_ready_s_max"
        assert seen["seed"] == 7
        assert seen["rounds"] == 12
        assert seen["out_path"] == "ignored.json"

    def test_defaults(self, monkeypatch, capsys):
        seen = {}
        monkeypatch.setattr(bench, "run_chaos_bench",
                            lambda **kw: seen.update(kw) or {})
        monkeypatch.setattr(sys, "argv", ["bench.py", "--chaos"])
        bench.main()
        assert seen["seed"] == 0
        # one coverage round per fault class (stall_dist joined in PR 20)
        assert seen["rounds"] == 10
        assert seen["out_path"] is None


class TestStreamBenchCli:
    """--stream arg plumbing: flags reach run_stream_bench parsed, and the
    early dispatch prints the runner's JSON line."""

    def test_flags_reach_runner_parsed(self, monkeypatch, capsys):
        import json

        seen = {}

        def fake_runner(**kw):
            seen.update(kw)
            return {"metric": "stream_ingest_mb_per_s"}

        monkeypatch.setattr(bench, "run_stream_bench", fake_runner)
        monkeypatch.setattr(sys, "argv", [
            "bench.py", "--stream", "--stream-rows", "777",
            "--stream-batch-rows", "256", "--stream-workers", "3",
            "--out", "ignored.json"])
        bench.main()
        out = capsys.readouterr().out
        assert json.loads(out)["metric"] == "stream_ingest_mb_per_s"
        assert seen["n_rows"] == 777
        assert seen["batch_rows"] == 256
        assert seen["workers"] == 3
        assert seen["out_path"] == "ignored.json"

    def test_defaults(self, monkeypatch, capsys):
        seen = {}
        monkeypatch.setattr(bench, "run_stream_bench",
                            lambda **kw: seen.update(kw) or {})
        monkeypatch.setattr(sys, "argv", ["bench.py", "--stream"])
        bench.main()
        assert seen["n_rows"] == 50_000
        assert seen["batch_rows"] == 1024
        assert seen["workers"] == 2
        assert seen["out_path"] is None
