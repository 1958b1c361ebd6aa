"""Median, quartiles and spread (interquartile distance over the median) of
each metric over a file of result lines, one run a line: what a bound is
set from (about five times the widest spread, never under 1%).

    python benchmarks/tools/spread.py chiprun_out/set1_<cell>.jsonl ...
"""

from __future__ import annotations

import json
import sys

import numpy as np


def main(paths) -> int:
    for path in paths:
        with open(path) as f:
            lines = [json.loads(ln) for ln in f if ln.strip()]
        print(f"{path}: {len(lines)} run(s), correct "
              f"{sum(ln['correct'] for ln in lines)}, failed/attempted "
              f"{[(ln['failed'], ln['attempted']) for ln in lines]}")
        names = sorted({m for ln in lines for m in ln["metrics"]})
        for name in names:
            v = np.asarray([ln["metrics"][name]["value"] for ln in lines
                            if name in ln["metrics"]])
            q1, med, q3 = np.percentile(v, [25, 50, 75])
            print(f"  {name:28s} median {med:.6g}  quartiles {q1:.6g} "
                  f"{q3:.6g}  spread {100 * (q3 - q1) / med:.2f}%  "
                  f"min {v.min():.6g} max {v.max():.6g}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
