"""Spread of each metric over sets of runs, as the bounds are set from it.

  python3 chipbench/tools/spread.py <results.jsonl> [...]

Each input line is a run's result line (the last line ``run.py`` prints),
optionally wrapped as ``{"set": <name>, "seed": <n>, "result": {...}}``.
For each set and metric it prints the median and the spread: the distance
between the first and third quartile, as ``statistics.quantiles(values,
n=4)`` gives them, over the median.  It also prints the two readings a
check of a bound takes: the mean of the sets' spreads with each set's run
farthest from the median left out, and the widest set's spread.
"""

from __future__ import annotations

import json
import statistics
import sys
from collections import defaultdict


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed(values: list[float]) -> list[float]:
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def main() -> None:
    sets: dict[str, dict[str, list[float]]] = defaultdict(lambda: defaultdict(list))
    for path in sys.argv[1:]:
        for line in open(path):
            line = line.strip()
            if not line:
                continue
            row = json.loads(line)
            name, result = (row["set"], row["result"]) if "result" in row else (path, row)
            if not result.get("correct"):
                print(f"# not correct: {name} {result.get('checks')}")
            for metric, m in result["metrics"].items():
                sets[name][metric].append(m["value"])
    metrics = sorted({m for s in sets.values() for m in s})
    for metric in metrics:
        per_set = {n: s[metric] for n, s in sets.items() if len(s.get(metric, [])) >= 2}
        for name, vals in per_set.items():
            print(f"{metric} {name}: n={len(vals)} median={statistics.median(vals)!r} "
                  f"spread={spread(vals):.4%} values={vals}")
        if len(per_set) >= 2:
            tight = statistics.mean(spread(trimmed(v)) for v in per_set.values()
                                    if len(v) >= 3)
            wide = max(spread(v) for v in per_set.values())
            print(f"{metric}: mean trimmed spread {tight:.4%}, widest set spread "
                  f"{wide:.4%}, five times widest {5 * wide:.4%}")


if __name__ == "__main__":
    main()
