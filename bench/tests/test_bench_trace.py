"""The trace reduction, on a small trace recorded on one TPU v5e chip:
a smoke-size engine admitting, decoding and forking, with a 50 ms host
sleep between two bursts of work."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import trace as tr  # noqa: E402

DATA = Path(__file__).with_name("data") / "probe_trace.json"


@pytest.fixture(scope="module")
def events():
    return tr.load_events(DATA)


@pytest.fixture(scope="module")
def window(events):
    host = [e for e in events if e[0] == "host"]
    return min(e[3] for e in host), max(e[3] + e[4] for e in host)


def test_busy_is_the_union_of_program_executions(events, window):
    lo, hi = window
    spans = sorted((max(e[3], lo), min(e[3] + e[4], hi))
                   for e in events if e[0] == "module")
    spans = [(s, e) for s, e in spans if e > s]
    busy, end = 0.0, -1.0
    for s, e in spans:             # sweep: count each instant once
        if e <= end:
            continue
        busy += e - max(s, end)
        end = e
    red = tr.reduce(events, window)
    assert red["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-12)
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert 0.0 < red["idle_share"] < 1.0
    assert red["idle_share"] == pytest.approx(
        1.0 - red["busy_s"] / red["window_s"])


def test_decode_is_the_most_frequent_lambda(events, window):
    red = tr.reduce(events, window)
    assert red["program_runs"]["decode"] == 60
    # the other lambda (suffix prefill), the page gather and the two
    # write-back shapes
    assert red["program_runs"]["admission"] == 6 + 6 + 4 + 2
    assert 0.0 < red["program_s"]["decode"] < red["busy_s"]


def test_idle_gap_is_named_by_the_host_span_around_it(events, window):
    red = tr.reduce(events, window)
    gaps = dict(red["idle_gaps"])
    assert 0.045 <= gaps["idle_sleep"] <= 0.06
    # the rest of the idle time lies inside the engine's own host work
    assert set(gaps) == {"idle_sleep", "step_all"}
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])


def test_operations_are_attributed_to_their_program(events, window):
    red = tr.reduce(events, window)
    assert red["device_ops"]
    assert all(not name.startswith("?/") for name, _ in red["device_ops"])
    assert all(name.split("/")[0].startswith("jit_")
               for name, _ in red["device_ops"])


def test_union_and_clip():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert tr.clip([[0, 3], [5, 8]], 2, 6) == [[2, 3], [5, 6]]


def test_program_names_live_in_one_file():
    progs = json.loads(tr.PROGRAMS_FILE.read_text())
    assert progs["decode"] == {"module": "jit__lambda",
                               "pick": "most_frequent"}
    assert "decode" in progs["admission"]["exclude"]


def test_host_time_per_span(events, window):
    red = tr.reduce(events, window)
    assert red["host_s"]["idle_sleep"] == pytest.approx(0.050591928)
    assert red["host_s"]["step_all"] == pytest.approx(
        (105711306.0 + 102570596.0) * 1e-9)
