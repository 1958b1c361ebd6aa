"""Assemble ``BENCHMARK.json`` from the benchmark's own files, and hold it
to the contract's limits.

    python benchmarks/manifest.py --write     # (re)write BENCHMARK.json
    python benchmarks/manifest.py --check     # fail if it differs

The files under this directory are the source: ``harness.json`` (command,
paths, run_seconds), one file per configuration, cell and metric.  A later
PR that adds files either appends the matching entries to
``BENCHMARK.json`` by hand or runs ``--write``; tests/test_rehearsal.py
holds the two to each other.  A metric's ``workloads`` are the cells whose
files name it.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from run import REPO, Catalog  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def build(catalog: Catalog) -> dict:
    harness = catalog.json(".", "harness")
    cells = [catalog.json("workloads", n) for n in catalog.names("workloads")]
    configs = []
    for name in sorted({c["config"] for c in cells}):
        cfg = catalog.json("configs", name)
        path = os.path.relpath(catalog.find("configs", name, ".json"), REPO)
        configs.append({"name": name, "source": cfg["source"], "file": path,
                        "reduced": cfg["reduced"], "why": cfg["why"]})

    def cells_of(kind, metric):
        return [c["name"] for c in cells if metric in c[kind]]

    end_to_end, per_layer = [], []
    for name in catalog.names("end_to_end"):
        m = catalog.json("end_to_end", name)
        used = cells_of("end_to_end", name)
        if used:
            entry = {k: m[k] for k in ("name", "unit", "better", "bound",
                                       "source")}
            if len(used) < len(cells):
                entry["workloads"] = used
            end_to_end.append(entry)
    for name in catalog.names("layer_metrics"):
        m = catalog.json("layer_metrics", name)
        used = cells_of("per_layer", name)
        if used:
            entry = {k: m[k] for k in ("name", "unit", "better", "source",
                                       "layer", "moves")}
            if len(used) < len(cells):
                entry["workloads"] = used
            per_layer.append(entry)
    return {
        "command": harness["command"], "paths": harness["paths"],
        "run_seconds": harness["run_seconds"], "configs": configs,
        "workloads": [{k: c[k] for k in ("name", "config", "traffic",
                                         "chips", "why")} for c in cells],
        "end_to_end": end_to_end, "per_layer": per_layer,
    }


def problems(m: dict) -> list:
    """Every breach of the contract's limits that can be read off the
    manifest itself; empty when there is none."""
    bad = []

    def line(what, text, limit=200):
        if not (isinstance(text, str) and 1 <= len(text) <= limit
                and "\n" not in text and "\t" not in text):
            bad.append(f"{what}: not 1..{limit} characters on one line")

    if set(m) != {"command", "paths", "run_seconds", "configs", "workloads",
                  "end_to_end", "per_layer"}:
        bad.append(f"keys {sorted(m)}")
    if not 1 <= len(m["command"]) <= 32:
        bad.append("command: 1..32 words")
    for w in m["command"]:
        line("command word", w)
        if w.startswith("/") or ".." in w.split("/"):
            bad.append(f"command word {w!r} leaves the repo")
    if not 1 <= len(m["paths"]) <= 16 or not all(
            PATH.match(p) for p in m["paths"]):
        bad.append("paths")
    if not (isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51):
        bad.append("run_seconds: a whole number from 1 to 51")
    cells, configs = m["workloads"], m["configs"]
    if not 2 <= len(cells) <= 24 or not 1 <= len(configs) <= 24:
        bad.append("2..24 cells, 1..24 configs")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in m[group]]
        if len(set(names)) != len(names):
            bad.append(f"{group}: a name twice")
        bad += [f"{group}: name {n!r}" for n in names if not NAME.match(n)]
    files = [c["file"] for c in configs]
    if len(set(files)) != len(files):
        bad.append("two configurations share a file")
    for c in configs:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c['name']}: keys {sorted(c)}")
        line(f"config {c['name']} source", c["source"])
        line(f"config {c['name']} why", c["why"])
        if not any(c["file"].startswith(p + "/") for p in m["paths"]):
            bad.append(f"config {c['name']}: file outside paths")
        if len(c["reduced"]) > 16 or not all(NAME.match(k)
                                             for k in c["reduced"]):
            bad.append(f"config {c['name']}: reduced")
        if not any(w["config"] == c["name"] for w in cells):
            bad.append(f"config {c['name']}: used by no cell")
    pairs = [(w["config"], w["traffic"]) for w in cells]
    if len(set(pairs)) != len(pairs):
        bad.append("a pair of configuration and traffic twice")
    for w in cells:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"cell {w['name']}: keys {sorted(w)}")
        line(f"cell {w['name']} why", w["why"])
        if w["chips"] not in (1, 4) or not NAME.match(w["traffic"]):
            bad.append(f"cell {w['name']}: chips or traffic")
        if w["config"] not in {c["name"] for c in configs}:
            bad.append(f"cell {w['name']}: unknown config")
    if sum(w["chips"] == 4 for w in cells) > max(1, len(cells) // 4):
        bad.append("more than a quarter of the cells ask for 4 chips")
    e2e = {e["name"]: e for e in m["end_to_end"]}
    if "setup_s" not in e2e or not 1 <= len(e2e) <= 16:
        bad.append("end_to_end: 1..16 metrics, setup_s among them")
    if not 1 <= len(m["per_layer"]) <= 128:
        bad.append("per_layer: 1..128 metrics")
    cell_names = [w["name"] for w in cells]
    reported = {n: set() for n in cell_names}
    for e in m["end_to_end"]:
        extra = set(e) - {"name", "unit", "better", "bound", "source",
                          "workloads"}
        if extra or not UNIT.match(e["unit"]) \
                or e["better"] not in ("lower", "higher") \
                or e["source"] not in ("host_clock", "device_trace") \
                or not 0.01 <= e["bound"] <= 0.1:
            bad.append(f"end_to_end {e['name']}: {sorted(extra)} or a value")
        for w in e.get("workloads", cell_names):
            reported[w].add(e["name"])
    for w, names in reported.items():
        if "setup_s" not in names or len(names) < 2:
            bad.append(f"cell {w}: setup_s and one other end-to-end metric")
    layered = set()
    for p in m["per_layer"]:
        extra = set(p) - {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if extra or not UNIT.match(p["unit"]) \
                or p["better"] not in ("lower", "higher") \
                or p["source"] not in SOURCES or p["moves"] not in e2e:
            bad.append(f"per_layer {p['name']}: {sorted(extra)} or a value")
        line(f"per_layer {p['name']} layer", p["layer"])
        for w in p.get("workloads", cell_names):
            layered.add(w)
            if p["moves"] not in reported[w]:
                bad.append(f"per_layer {p['name']} in {w}: the cell does "
                           f"not report {p['moves']}")
    bad += [f"cell {w}: no per-layer metric" for w in cell_names
            if w not in layered]
    if len(json.dumps(m)) > 64 * 1024:
        bad.append("over 64 KiB")
    return bad


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--write", action="store_true")
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args()
    path = os.path.join(REPO, "BENCHMARK.json")
    built = build(Catalog(path))
    bad = problems(built)
    for b in bad:
        print("contract: " + b, file=sys.stderr)
    if args.write:
        with open(path, "w") as f:
            json.dump(built, f, indent=2)
            f.write("\n")
    if args.check:
        with open(path) as f:
            if json.load(f) != built:
                print("BENCHMARK.json differs from the files under "
                      "benchmarks/", file=sys.stderr)
                return 1
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
