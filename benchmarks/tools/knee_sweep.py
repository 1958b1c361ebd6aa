"""Find the knee of a serving cell ONCE, by a sweep on the chip.

    python benchmarks/tools/knee_sweep.py --cell <a serving cell's file> \
        --rates 60,90,120,150,180,220 --seconds 20 --out chiprun_out/knee.json

One run of the benchmark's own command per rate, each a process of its own,
one after the other, so every point is measured exactly as a cell is: the
rate is data (a copy of the cell's file with ``slates_per_s`` set, in a temp
dir that a copy of the manifest lists among its ``paths``).  The knee is the
highest rate
at which nothing is shed and the backlog does not grow over the window
(the second half's median latency stays within 1.5 x the first half's).
The builder then writes 0.8 x and 1.25 x the knee into the cells' files as
plain numbers, and the table into PERF.md.  No run ever calibrates itself.

This script never imports JAX: each child holds the chip in turn.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)


def cell_at(cell: dict, rate: float, root: str) -> tuple:
    """(name, manifest path) of ``cell`` offered at ``rate``, as files
    under ``root``."""
    name = f"{cell['name']}.at_{rate:g}"
    params = dict(cell.get("traffic_params", {}), slates_per_s=rate)
    os.makedirs(os.path.join(root, "workloads"), exist_ok=True)
    with open(os.path.join(root, "workloads", name + ".json"), "w") as f:
        json.dump(dict(cell, name=name, traffic_params=params), f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["paths"] = [HERE, root]
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return name, path


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True,
                    help="the cell's file (workloads/<cell>.json)")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--dry-run", action="store_true")
    args = ap.parse_args()
    with open(args.cell) as f:
        cell = json.load(f)
    rows, root = [], tempfile.mkdtemp(prefix="knee_")
    for rate in [float(r) for r in args.rates.split(",")]:
        name, manifest = cell_at(cell, rate, root)
        cmd = [sys.executable, os.path.join(HERE, "run.py"),
               "--workload", name, "--seed", str(args.seed),
               "--seconds", str(args.seconds), "--trace", "0",
               "--manifest", manifest]
        if args.dry_run:
            cmd.append("--dry-run")
        done = subprocess.run(cmd, stdout=subprocess.PIPE, text=True)
        if done.returncode != 0 or not done.stdout.strip():
            rows.append({"slates_per_s": rate, "rc": done.returncode})
            continue
        line = json.loads(done.stdout.strip().splitlines()[-1])
        gen = line["detail"]["generator"]
        rows.append({
            "slates_per_s": rate, "correct": line["correct"],
            "setup_s": line["metrics"]["setup_s"]["value"],
            "slates_due": gen["slates_due"], "shed": gen["slates_shed"],
            "lost": gen["slates_lost"], "errors": gen["slates_error"],
            "lines_per_s": gen["lines"]["scored_in_window"] / gen["window_s"],
            "p50_ms": gen["scored_p50_ms"], "p99_ms": gen["scored_p99_ms"],
            "first_half_p50_ms": gen["first_half_p50_ms"],
            "second_half_p50_ms": gen["second_half_p50_ms"],
            "gen_lag_p99_ms": gen["gen_lag_p99_ms"],
            "settle_s": gen["settle_s"],
            "flush_rows": (line["detail"]["counters"]["scored_samples"]
                           / max(line["detail"]["counters"]["batches"], 1)),
            "sustained": (gen["slates_shed"] == 0 and gen["slates_lost"] == 0
                          and gen["second_half_p50_ms"]
                          <= 1.5 * gen["first_half_p50_ms"]),
        })
        print(json.dumps(rows[-1]), flush=True)
    shutil.rmtree(root, ignore_errors=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
