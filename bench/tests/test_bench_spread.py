"""The spreads that bounds are set from: quartiles as Python's
statistics.quantiles gives them, as a share of the median."""
import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spread  # noqa: E402


def result(v, correct=True, gap=0.01):
    return {"correct": correct, "metrics": {"x": {"value": v, "unit": "s"}},
            "checks": {"widest_gap": {"value": gap, "limit": 0.09}}}


def test_spread_is_quartile_distance_over_median():
    vals = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert spread.spread(vals) == (q3 - q1) / statistics.median(vals)


def test_summary_takes_the_wider_set_and_leaves_out_incorrect_runs(
        tmp_path):
    a = [100, 101, 99, 100, 102, 98]
    b = [100, 104, 96, 100, 108, 92]
    for name, vals in (("A", a), ("B", b)):
        for i, v in enumerate(vals):
            (tmp_path / f"{name}-{i}.out").write_text(
                "noise\n" + json.dumps(result(float(v))) + "\n")
    (tmp_path / "B-9.out").write_text(json.dumps(
        result(500.0, correct=False, gap=0.5)))
    got = spread.summarize(spread.load(sorted(tmp_path.glob("*.out"))))
    m = got["metrics"]["x"]
    assert got["not_correct"] == {"A": 0, "B": 1}
    assert m["loose"] == spread.spread([float(v) for v in b])
    assert m["bound"] == max(5 * m["loose"], 0.01)
    tight = (spread.spread(spread.without_farthest([float(v) for v in a]))
             + spread.spread(spread.without_farthest(
                 [float(v) for v in b]))) / 2
    assert m["tight"] == tight
    assert got["checks"]["widest_gap"]["max"] == 0.5
