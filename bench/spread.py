"""Spreads of a cell's runs, and the bound they suggest.

    python3 bench/spread.py <dir>/A-*.out <dir>/B-*.out

Each file holds one run's standard output, the result on its last line;
a file's set is the part of its name before the first ``-``.  For each
end-to-end metric and set: the median and the spread, the distance
between the first and the third quartile (``statistics.quantiles(values,
n=4)``) as a share of the median.  Then, over the sets: ``loose``, the
widest spread; ``tight``, the mean of the sets' spreads with each set's
run farthest from its median left out; and ``bound``, five times
``loose`` and at least 0.01.  It also gives the largest reading of each
number ``checks`` compared.  A run that is not correct is listed and
left out of the spreads.
"""
from __future__ import annotations

import json
import statistics
import sys
from pathlib import Path
from typing import Dict, List


def spread(values: List[float]) -> float:
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def without_farthest(values: List[float]) -> List[float]:
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return values[:far] + values[far + 1:]


def summarize(results: Dict[str, List[dict]]) -> dict:
    """``results``: set name -> result objects of that set's runs."""
    out = {"runs": {k: len(v) for k, v in results.items()},
           "not_correct": {k: sum(not r["correct"] for r in v)
                           for k, v in results.items()},
           "metrics": {}, "checks": {}}
    sets = {k: [r for r in v if r["correct"]] for k, v in results.items()}
    names = sorted({m for v in sets.values() for r in v
                    for m in r["metrics"]})
    for name in names:
        per = {k: [r["metrics"][name]["value"] for r in v
                   if name in r["metrics"]] for k, v in sets.items()}
        per = {k: v for k, v in per.items() if len(v) >= 3}
        if not per:
            continue
        spreads = {k: spread(v) for k, v in per.items()}
        tight = [spread(without_farthest(v)) for v in per.values()
                 if len(v) >= 4]
        loose = max(spreads.values())
        out["metrics"][name] = {
            "median": {k: statistics.median(v) for k, v in per.items()},
            "spread": spreads, "loose": loose,
            "tight": sum(tight) / len(tight) if tight else None,
            "bound": max(5 * loose, 0.01)}
    for r in (r for v in results.values() for r in v):
        for name, c in r.get("checks", {}).items():
            got = out["checks"].setdefault(name, {"max": c["value"],
                                                  "limit": c["limit"]})
            got["max"] = max(got["max"], c["value"])
    return out


def load(paths) -> Dict[str, List[dict]]:
    results: Dict[str, List[dict]] = {}
    for p in map(Path, paths):
        lines = p.read_text().strip().splitlines()
        if not lines:
            continue
        results.setdefault(p.name.split("-")[0], []).append(
            json.loads(lines[-1]))
    return results


if __name__ == "__main__":
    print(json.dumps(summarize(load(sys.argv[1:])), indent=1))
