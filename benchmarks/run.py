"""The benchmark's one command: one process, one cell, one result line.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by name (see README.md):

    workloads/<cell>.json        config, traffic mix, chips, why, the names of
                                 the metrics the cell reports, its gates
    configs/<config>.json        the sizes as run, source, assumed, reduced
    recipes/<recipe>.py          seeded data and coefficients for a config
    traffic/<mix>.json           a traffic mix: parameters, and its ``kind``
    traffic/<kind>.py            the general generator a mix's ``kind`` names
    end_to_end/<metric>.json     unit, better, bound, source
    layer_metrics/<metric>.json  layer, unit, better, source, moves, and
                                 optionally ``reader``: another metric's name
    layer_metrics/<metric>.py    optional reader: ``read(readings) -> value``;
                                 with none, what the traffic kind measured
                                 under that name
    reference/                   the plain references ``correct`` leans on

The directories searched are this file's own and the further ``paths`` of
the manifest (``BENCHMARK.json`` at the root of the checkout), so a later
PR adds a cell, a configuration, a traffic mix or a per-layer metric by
adding files and appending entries, and edits no file that is here.

The last line of standard output is the result object.  Without a TPU the
command fails and prints no result; ``--dry-run`` (tiny sizes, the CPU
backend, kernels as the program runs them there) rehearses the control flow
and says ``"dry_run": true`` in a line that is never a result.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
CACHE_DIR = os.path.join(REPO, ".bench_cache", "xla")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(Exception):
    """A run that must end with no result line (exit code 2)."""


# -- finding things by name --------------------------------------------------

class Catalog:
    """The search path: this directory first, then the manifest's further
    ``paths``.  A name is looked up as ``<root>/<group>/<name><suffix>``."""

    def __init__(self, manifest_path: str | None = None):
        self.manifest_path = manifest_path or os.path.join(REPO,
                                                           "BENCHMARK.json")
        self.roots = [HERE]
        if os.path.exists(self.manifest_path):
            with open(self.manifest_path) as f:
                self.manifest = json.load(f)
            base = os.path.dirname(os.path.abspath(self.manifest_path))
            for p in self.manifest.get("paths", []):
                root = os.path.normpath(os.path.join(base, p))
                if root not in self.roots and os.path.isdir(root):
                    self.roots.append(root)
        else:
            self.manifest = None
        self._modules = {}

    def find(self, group: str, name: str, suffix: str) -> str | None:
        for root in self.roots:
            path = os.path.join(root, group, name + suffix)
            if os.path.exists(path):
                return path
        return None

    def json(self, group: str, name: str) -> dict:
        path = self.find(group, name, ".json")
        if path is None:
            raise BenchError(f"no {group}/{name}.json under {self.roots}")
        with open(path) as f:
            return json.load(f)

    def module(self, group: str, name: str, required: bool = True):
        path = self.find(group, name, ".py")
        if path is None:
            if required:
                raise BenchError(f"no {group}/{name}.py under {self.roots}")
            return None
        if path not in self._modules:
            spec = importlib.util.spec_from_file_location(
                f"bench_{group}_{name}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return self._modules[path]

    def names(self, group: str, suffix: str = ".json") -> list:
        out = []
        for root in self.roots:
            d = os.path.join(root, group)
            if os.path.isdir(d):
                out += [f[:-len(suffix)] for f in sorted(os.listdir(d))
                        if f.endswith(suffix) and f[:-len(suffix)] not in out]
        return out


# -- what a traffic kind is handed -------------------------------------------

class Context:
    """One run's state: the cell, the clock, the counters, the trace.

    A traffic kind calls ``window_start()`` at the first measured instant
    (set-up ends there, compilations are counted from there),
    ``window_end()`` at the last, wraps its own host work in ``span(name)``,
    and, in a traced run, brackets a slice of the window with
    ``profile_slice()``."""

    def __init__(self, catalog, workload, config, args, device):
        self.catalog = catalog
        self.workload = workload
        self.config = config
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.dry_run = bool(args.dry_run)
        self.chips = int(workload["chips"])
        self.device = device
        self.spans = []          # (name, start_ns, end_ns) on perf_counter_ns
        self.compiles = 0        # programs compiled or loaded from the cache, in window
        self.setup_s = None
        self.window_s = None
        self.profile = None      # the reduced device trace of the slice
        self._in_window = False
        self._t_window = None
        self.tmp = tempfile.mkdtemp(prefix="bench_")
        self.traffic = self._traffic_mix()

    def _traffic_mix(self) -> dict:
        """The mix's parameters, overridden by the cell's own."""
        mix = dict(self.catalog.json("traffic", self.workload["traffic"]))
        while "extends" in mix:  # a mix can be another mix, with changes
            mix = {**self.catalog.json("traffic", mix.pop("extends")), **mix}
        mix.update(self.workload.get("traffic_params", {}))
        if self.dry_run:
            mix.update(mix.pop("dry_run", {}))
            mix.update(self.workload.get("dry_run", {}))
        return mix

    def mesh(self):
        """None on one chip; over all the chips the cell asks for
        otherwise (the program's own ``make_mesh``: all on the data axis)."""
        if self.chips == 1:
            return None
        import jax

        from photon_ml_tpu.parallel.mesh import make_mesh

        return make_mesh(devices=jax.devices()[: self.chips])

    def on_compile(self, event, *_a, **_k):
        # fired once for every program built, cache hit or not
        if self._in_window and event == _COMPILE_EVENT:
            self.compiles += 1

    def window_start(self) -> float:
        self._t_window = time.perf_counter()
        self.setup_s = self._t_window - _T_PROCESS
        self._in_window = True
        return self._t_window

    def window_end(self) -> float:
        now = time.perf_counter()
        self._in_window = False
        self.window_s = now - self._t_window
        return now

    @contextlib.contextmanager
    def span(self, name: str):
        """A benchmark-side host span: kept on the host clock, and written
        into the profiler's own trace when one is being taken."""
        import jax

        t0 = time.perf_counter_ns()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.spans.append((name, t0, time.perf_counter_ns()))

    def span_seconds(self, names=None) -> dict:
        """Seconds under each benchmark-side span name (all, or ``names``):
        where set-up goes."""
        out = {}
        for name, t0, t1 in self.spans:
            if names is None or name in names:
                out[name] = out.get(name, 0.0) + (t1 - t0) * 1e-9
        return out

    @contextlib.contextmanager
    def profile_slice(self):
        """Trace what runs inside with ``jax.profiler`` and reduce it
        (trace_reduce.py).  A clock-sync annotation ties the profiler's
        clock to ``perf_counter_ns``, so that ``obs`` spans can label the
        idle gaps."""
        import jax

        import trace_reduce

        out = os.path.join(self.tmp, "profile")
        jax.profiler.start_trace(out)
        with jax.profiler.TraceAnnotation(trace_reduce.SYNC_NAME):
            sync_ns = time.perf_counter_ns()
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            t1 = time.perf_counter_ns()
            jax.profiler.stop_trace()
            from photon_ml_tpu import obs

            program_spans = [(r["name"], r["ts_ns"], r["ts_ns"] + r["dur_ns"])
                             for r in obs.get_tracer().records()
                             if r["ph"] == "X"]
            self.profile = trace_reduce.reduce_dir(
                out, sync_perf_ns=sync_ns, slice_perf_ns=(t0, t1),
                host_spans=self.spans + program_spans,
                n_devices=self.chips)

    def memory_peak_bytes(self) -> int:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()[: self.chips]]
        return int(max(peaks))


# -- the run -----------------------------------------------------------------

def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny sizes on the CPU backend: rehearses the "
                         "control flow, NOT a result")
    ap.add_argument("--manifest", default=None,
                    help="another BENCHMARK.json (the rehearsal test's)")
    return ap.parse_args(argv)


def setup_jax(args, chips: int) -> dict:
    """Import JAX, place the compile cache, name the device.  Raises
    BenchError where the cell cannot run here."""
    if args.dry_run:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}")
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # every program, however quick to compile: a later run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not args.dry_run and device["platform"] != "tpu":
        raise BenchError(
            f"no TPU: jax.devices()[0].platform is {device['platform']!r}. "
            "This is not a chip run and no result is printed "
            "(--dry-run rehearses on the CPU).")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chip(s), JAX sees "
                         f"{len(devices)}")
    return device


def sized(config: dict, dry_run: bool) -> dict:
    """The configuration as run: in a dry run its ``dry_run`` sizes."""
    config = dict(config)
    tiny = config.pop("dry_run", {})
    if dry_run:
        config.update(tiny)
    return config


def collect_metrics(ctx: Context, result: dict) -> dict:
    """``--trace 0``: the cell's end-to-end metrics.  ``--trace 1``: its
    per-layer metrics, each from its reader or, with no reader, from what
    the traffic kind measured under that name.  A metric with nothing to
    read is left out."""
    cat, wl = ctx.catalog, ctx.workload
    out = {}
    if not ctx.trace:
        values = dict(result.get("end_to_end", {}), setup_s=ctx.setup_s)
        for name in wl["end_to_end"]:
            if values.get(name) is not None:
                out[name] = {"value": float(values[name]),
                             "unit": cat.json("end_to_end", name)["unit"]}
        return out
    readings = {
        "profile": ctx.profile, "spans": ctx.spans,
        "obs_spans": result.get("obs_spans", []),
        "counters": result.get("counters", {}),
        "measured": result.get("layer_values", {}),
        "config": ctx.config, "workload": wl, "device": ctx.device,
        "chips": ctx.chips, "catalog": cat,
    }
    for name in wl["per_layer"]:
        meta = cat.json("layer_metrics", name)
        # one reader can serve several metrics (the same number under the
        # name of the end-to-end metric it moves in another cell)
        source = meta.get("reader", name)
        reader = cat.module("layer_metrics", source, required=False)
        value = (reader.read(readings) if reader is not None
                 else readings["measured"].get(source))
        if value is not None:
            out[name] = {"value": float(value), "unit": meta["unit"]}
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, HERE)
    try:
        catalog = Catalog(args.manifest)
        workload = catalog.json("workloads", args.workload)
        config = sized(catalog.json("configs", workload["config"]),
                       args.dry_run)
        if args.seconds is None:
            if catalog.manifest is None:
                raise BenchError("--seconds not given and no manifest")
            args.seconds = catalog.manifest["run_seconds"]
        if not os.path.exists(os.path.join(REPO, "photon_ml_tpu",
                                           "__init__.py")):
            raise BenchError(f"the system under test is not at {REPO}: "
                             "nothing to measure")
        sys.path.insert(1, REPO)
        device = setup_jax(args, int(workload["chips"]))
    except BenchError as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 2

    import jax

    ctx = Context(catalog, workload, config, args, device)
    jax.monitoring.register_event_duration_secs_listener(ctx.on_compile)
    if ctx.trace:
        from photon_ml_tpu import obs

        obs.enable_tracing(capacity=1 << 18)
    kind = catalog.module("traffic", ctx.traffic["kind"])
    try:
        result = kind.run(ctx)
    finally:
        shutil.rmtree(ctx.tmp, ignore_errors=True)

    checks = {k: bool(v) for k, v in result.get("checks", {}).items()}
    checks["no_compile_in_window"] = ctx.compiles == 0
    device = dict(device, memory_peak_bytes=ctx.memory_peak_bytes())
    line = {
        "correct": bool(all(checks.values())),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": collect_metrics(ctx, result),
        "device": device,
        "checks": checks,
        "detail": result.get("detail", {}),
        "workload": args.workload, "seed": args.seed,
        "seconds": args.seconds, "window_s": ctx.window_s,
        "compiles_in_window": ctx.compiles,
    }
    if ctx.trace and ctx.profile is not None:
        device["busy_s"] = ctx.profile["busy_s"]
        device["window_s"] = ctx.profile["window_s"]
        line["breakdown"] = ctx.profile["breakdown"]
    if args.dry_run:
        line["dry_run"] = True
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
