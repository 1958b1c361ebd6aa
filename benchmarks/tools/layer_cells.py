"""The training cells WITH the layer metrics of PR 23, as files in a
directory of your choice: what ``workloads/<cell>.json`` will hold once a
``benchmark`` PR appends the names below to the cells' ``per_layer`` lists
(PR 23 could not: it may edit no file the benchmark has).

    python3 benchmarks/tools/layer_cells.py --out .layer_cells
    python3 benchmarks/run.py --manifest .layer_cells/BENCHMARK.json \
        --workload glmix_chip.train_layers --seed 7 --trace 1

Each cell is copied under ``<cell>_layers`` with the names appended, and a
manifest lists the directory as one more of its ``paths`` (the way
tests/test_rehearsal.py adds cells).  Nothing under benchmarks/ is touched.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run as harness  # noqa: E402

EVERY_CELL = ["fixed_solve_busy_share", "entity_solve_busy_share",
              "entity_gather_busy_share", "rescore_busy_share",
              "unscoped_busy_share", "dispatch_us_per_fit",
              "coord_bucket_s", "coord_upload_s"]
APPENDED = {
    "glmix_chip.train": EVERY_CELL,
    "glmix3_wide.train": EVERY_CELL + ["entity_solve_iters_per_update"],
}
SUFFIX = "_layers"


def write(out: str) -> str:
    """Write the cells and their manifest under ``out``; the manifest's
    path."""
    catalog = harness.Catalog()
    more = os.path.join(os.path.abspath(out), "more")
    os.makedirs(os.path.join(more, "workloads"), exist_ok=True)
    for cell, names in APPENDED.items():
        wl = catalog.json("workloads", cell)
        wl["name"] = cell + SUFFIX
        wl["per_layer"] = wl["per_layer"] + names
        with open(os.path.join(more, "workloads", wl["name"] + ".json"),
                  "w") as f:
            json.dump(wl, f, indent=2)
    manifest = dict(catalog.manifest, paths=[BENCH, more])
    path = os.path.join(os.path.abspath(out), "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(manifest, f, indent=2)
    return path


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    print(write(ap.parse_args().out))
