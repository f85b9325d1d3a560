"""Sets of runs of one cell: medians and spreads as the driver reads them.

    python -m benchmarks.harness.spread <set A result lines> <set B result lines>

Each file holds result lines (the last line of each run's output), one run
a line. A metric's spread in a set is the distance between its quartiles
over its median; a cell's spread is the wider of its two sets'. A bound is
about five times the widest spread over the cells, never under 1 %."""

from __future__ import annotations

import json
import sys

from benchmarks.harness.result import percentile


def read(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.startswith("{") and '"correct"' in line]


def summary(runs: list[dict]) -> dict:
    out = {}
    for name in runs[0]["metrics"]:
        values = [r["metrics"][name]["value"] for r in runs if name in r["metrics"]]
        median = percentile(values, 50)
        out[name] = {
            "n": len(values), "median": median, "min": min(values), "max": max(values),
            "spread": (percentile(values, 75) - percentile(values, 25)) / median,
        }
    return out


def main(paths: list[str]) -> None:
    sets = [summary(read(p)) for p in paths]
    for path, s in zip(paths, sets):
        print(path)
        for name, v in s.items():
            print(f"  {name}: n={v['n']} median={v['median']:.6g} min={v['min']:.6g} "
                  f"max={v['max']:.6g} spread={v['spread'] * 100:.4f}%")
    if len(sets) == 2:
        for name in sets[0]:
            a, b = sets[0][name], sets[1][name]
            print(f"{name}: wider spread {max(a['spread'], b['spread']) * 100:.4f}%, "
                  f"second median over first {(b['median'] / a['median'] - 1) * 100:+.4f}%")


if __name__ == "__main__":
    main(sys.argv[1:])
