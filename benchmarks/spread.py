#!/usr/bin/env python3
"""Run-to-run spread of a cell's metrics, as the bounds are set from it.

    python3 benchmarks/spread.py setA.log setB.log

Each file holds the output of several runs of one cell (one result line
each, the last JSON object a run printed). For each metric: every set's
median and its spread, the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median; the
wider of the sets' spreads; and the bound that five times it would give.
"""

from __future__ import annotations

import json
import statistics
import sys


def result_lines(path: str) -> list[dict]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line.startswith('{"correct"'):
                out.append(json.loads(line))
    return out


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> None:
    sets = [result_lines(p) for p in sys.argv[1:]]
    names = sorted({m for s in sets for r in s for m in r["metrics"]})
    for name in names:
        row, widest = [], 0.0
        for s in sets:
            values = [r["metrics"][name]["value"] for r in s
                      if name in r["metrics"]]
            if len(values) < 2:
                continue
            sp = spread(values)
            widest = max(widest, sp)
            row.append(f"n={len(values)} median={statistics.median(values):.6g} "
                       f"spread={sp:.4%} min={min(values):.6g} max={max(values):.6g}")
        print(f"{name}: " + " | ".join(row)
              + f" | widest={widest:.4%} five_times={5 * widest:.4%}")
    bad = [r for s in sets for r in s if not r["correct"] or r["failed"]]
    print(f"runs={sum(len(s) for s in sets)} not_correct_or_failed={len(bad)}")


if __name__ == "__main__":
    main()
