#!/usr/bin/env python3
"""Spreads of a cell's runs, as the builder's contract defines them:

    python3 benchmarks/tools/spread.py chiprun_out/sets/<cell>.jsonl

The file holds one line per run: the run's result object plus ``"set"``
(1 or 2) and ``"seed"``.  For each metric: per set the median and the
spread (third minus first quartile by ``statistics.quantiles(n=4)``, as
a share of the median), the wider of the two, five times that (the
bound to set, never under 1 %), and how far the second set's median
lies from the first's.
"""
from __future__ import annotations

import json
import statistics
import sys


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(path: str) -> int:
    runs = [json.loads(line) for line in open(path) if line.strip()]
    bad = [r for r in runs if not r.get("correct") or r.get("failed")]
    print(f"{len(runs)} runs, {len(bad)} with correct false or failed > 0")
    names = sorted({m for r in runs for m in r["metrics"]})
    for name in names:
        sets = {}
        for r in runs:
            if name in r["metrics"]:
                sets.setdefault(r["set"], []).append(
                    r["metrics"][name]["value"])
        line = [name]
        spreads, medians = [], []
        for k in sorted(sets):
            v = sets[k]
            medians.append(statistics.median(v))
            spreads.append(spread(v) if len(v) >= 2 else float("nan"))
            line.append(f"set{k}: n={len(v)} median={medians[-1]:.6g} "
                        f"spread={100 * spreads[-1]:.3f}%")
        widest = max(spreads)
        line.append(f"5x widest={100 * 5 * widest:.2f}%")
        if len(medians) == 2:
            line.append(f"set2/set1-1={100 * (medians[1] / medians[0] - 1):+.3f}%")
        print("  ".join(line))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
