"""ALL FIVE training cells with the layer metrics no cell lists yet, as
files in a directory of your choice: PR 23's nine (``layer_cells.py`` knows
two cells; PRs 25 to 33 rebuilt the other three by hand) and PR 34's five
``setup_*``.  What ``workloads/<cell>.json`` will hold once a ``benchmark``
PR appends the names (PERF.md section 7 (1)).

    python3 benchmarks/tools/layer_cells_all.py --out .layer_cells
    python3 benchmarks/run.py --manifest .layer_cells/BENCHMARK.json \
        --workload glmix_ml20m.train_layers --seed 7 --trace 1

With ``--account DIR`` every cell also lists ``setup_account``, a reader
written beside the cells (not under benchmarks/) that reports nothing and
leaves ``DIR/<cell>.<pid>.json``: where the set-up of that run went, by
the program's own spans (``account``).  Nothing under benchmarks/ is
touched.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(TOOLS), TOOLS]

import layer_cells  # noqa: E402
import run as harness  # noqa: E402

SETUP = ["setup_trace_s", "setup_lower_s", "setup_compile_s",
         "setup_cache_misses", "setup_first_run_s"]
LBFGS = layer_cells.APPENDED["glmix3_wide.train"]
APPENDED = {cell: names + SETUP for cell, names in {
    **layer_cells.APPENDED,
    "glmix_ml20m.train": LBFGS,
    # on four chips a reader that COUNTS calls reads four times too many
    # (PERF.md section 7 (8b)): the iterations are left off
    "glmix_ml25m.train_x4": layer_cells.EVERY_CELL,
    # no fit span and no descent.dispatch in a trial: dispatch_us_per_fit
    # finds nothing there and is left out of the line, as the harness allows
    "glmix_tune_ml20m.tune_jobs": LBFGS,
}.items()}
SUFFIX = layer_cells.SUFFIX
ACCOUNT = "setup_account"
PHASES = ("jax.trace", "jax.lower", "jax.compile")


def write(out: str, account: str | None = None) -> str:
    """Write the cells and their manifest under ``out``; the manifest's
    path."""
    catalog = harness.Catalog()
    more = os.path.join(os.path.abspath(out), "more")
    os.makedirs(os.path.join(more, "workloads"), exist_ok=True)
    for cell, names in APPENDED.items():
        wl = catalog.json("workloads", cell)
        wl["name"] = cell + SUFFIX
        wl["per_layer"] = wl["per_layer"] + names + (
            [ACCOUNT] if account else [])
        with open(os.path.join(more, "workloads", wl["name"] + ".json"),
                  "w") as f:
            json.dump(wl, f, indent=2)
    if account:
        metrics = os.path.join(more, "layer_metrics")
        os.makedirs(metrics, exist_ok=True)
        meta = dict(catalog.json("layer_metrics", SETUP[0]), name=ACCOUNT,
                    what="reports nothing: writes the run's set-up account")
        with open(os.path.join(metrics, ACCOUNT + ".json"), "w") as f:
            json.dump(meta, f, indent=2)
        with open(os.path.join(metrics, ACCOUNT + ".py"), "w") as f:
            f.write("import sys\n\n"
                    f"sys.path.insert(0, {TOOLS!r})\n"
                    "import layer_cells_all  # noqa: E402\n\n\n"
                    "def read(readings):\n"
                    "    return layer_cells_all.write_account("
                    f"readings, {os.path.abspath(account)!r})\n")
    manifest = dict(catalog.manifest, paths=[layer_cells.BENCH, more])
    path = os.path.join(os.path.abspath(out), "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(manifest, f, indent=2)
    return path


# -- where a traced run's set-up went ----------------------------------------

def account(readings: dict) -> dict:
    """The set-up of a traced run by the program's own spans: the
    benchmark's spans; per phase the seconds, the events and the largest
    programs; what of each phase lies inside ``warm_fit`` beside that
    span's seconds (``sum``: the four metrics' own sum there;
    ``inside_first_run``: what of it the first run's interval holds itself,
    so counted twice); every compile over a second; every
    ``descent.device_table`` with its attributes and the phases under it."""
    from photon_ml_tpu import obs

    records = [r for r in obs.get_tracer().records() if r["ph"] == "X"]
    bench = readings["spans"]
    window = min((t0 for n, t0, _ in bench if n in ("fit", "trial")),
                 default=None)
    origin = min([t0 for _, t0, _ in bench] + [r["ts_ns"] for r in records])
    started = getattr(sys.modules.get("__main__"), "_T_PROCESS", None)

    def end(r):
        return r["ts_ns"] + r["dur_ns"]

    def s(ns):
        return round(ns * 1e-9, 6)

    def phases_of(spans):
        return {name: s(sum(r["dur_ns"] for r in spans if r["name"] == name))
                for name in PHASES}

    before = [r for r in records if window is None or end(r) <= window]
    phases = {}
    for name in PHASES:
        spans = [r for r in before
                 if r["name"] == name and "program" in r["attrs"]]
        by_program = {}
        for r in spans:
            p = by_program.setdefault(r["attrs"]["program"], [0, 0])
            p[0] += r["dur_ns"]
            p[1] += 1
        largest = sorted(by_program.items(), key=lambda kv: -kv[1][0])[:12]
        phases[name] = {
            "seconds": s(sum(r["dur_ns"] for r in spans)),
            "events": len(spans),
            "largest": [{"program": p, "seconds": s(ns), "spans": k}
                        for p, (ns, k) in largest]}
    out = {
        "workload": readings["workload"]["name"],
        "benchmark_spans": [{"name": n, "at_s": s(t0 - origin),
                             "seconds": s(t1 - t0)}
                            for n, t0, t1 in bench
                            if n not in ("fit", "trial")],
        "window_at_s": None if window is None else s(window - origin),
        # run.py's own set-up clock: a traced line does not print setup_s
        "setup_s": (None if window is None or started is None
                    else round(window * 1e-9 - started, 6)),
        "phases": phases,
        "listener_events": sum(p["events"] for p in phases.values()),
        "compiles_over_1s": [
            dict(r["attrs"], seconds=s(r["dur_ns"]),
                 at_s=s(r["ts_ns"] - origin))
            for r in before if r["name"] == "jax.compile"
            and "program" in r["attrs"] and r["dur_ns"] > 1e9],
    }
    for n, t0, t1 in bench:
        if n != "warm_fit":
            continue
        inside = [r for r in before if t0 <= r["ts_ns"] and end(r) <= t1
                  and (r["name"] == "descent.device_table"
                       or "program" in r["attrs"])]
        built = max((end(r) for r in inside
                     if r["name"] == "descent.device_table"), default=None)

        # the metrics' own sum over warm_fit, beside the span; then the
        # same seconds told apart: what the first run's interval holds
        # itself (the checks' small programs) is in the sum twice
        parts = phases_of(inside)
        found = {"seconds": s(t1 - t0), "parts": parts}
        if built is not None:
            parts["first_run"] = s(t1 - built)
            found["inside_first_run"] = phases_of(
                [r for r in inside if r["ts_ns"] >= built])
        found["sum"] = round(sum(parts.values()), 6)
        out["warm_fit"] = found
    out["device_tables"] = [
        {**r["attrs"], "seconds": s(r["dur_ns"]),
         "at_s": s(r["ts_ns"] - origin),
         "under_it": phases_of([c for c in records
                                if c["parent"] == r["id"]]),
         "compile": next((c["attrs"] for c in records
                          if c["parent"] == r["id"]
                          and c["name"] == "jax.compile"), None)}
        for r in records if r["name"] == "descent.device_table"]
    import jax

    out["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[: readings["chips"]])
    firsts = {}
    for r in records:  # the first dispatch holds what a later one does not
        if r["name"] in ("descent.dispatch", "descent.fused_validated"):
            firsts.setdefault(r["name"], s(r["dur_ns"]))
    out["first_seconds"] = firsts
    return out


def write_account(readings: dict, directory: str) -> None:
    """The ``setup_account`` reader: never a value, never raises."""
    try:
        os.makedirs(directory, exist_ok=True)
        found = account(readings)
        path = os.path.join(directory,
                            f"{found['workload']}.{os.getpid()}.json")
        with open(path, "w") as f:
            json.dump(found, f, indent=1)
    except Exception:
        import traceback

        traceback.print_exc()
    return None


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--account", default=None,
                    help="a directory for the runs' set-up accounts")
    args = ap.parse_args()
    print(write(args.out, args.account))
