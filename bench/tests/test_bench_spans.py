"""The program's host spans in a trace: seconds and runs per span, idle
time named by the innermost span over it, and the two readers built on
them, on a small event list worked out by hand."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import spans as sp  # noqa: E402
from bench.lib import trace as tr  # noqa: E402
from bench.run import load_reader  # noqa: E402

MS = 1e6                                  # nanoseconds in a millisecond
DEV, HOST = "/device:TPU:0", "/host:CPU"
WINDOW = (0.0, 1000 * MS)


def _ev(kind, name, start_ms, end_ms, plane=HOST):
    return (kind, plane, name, start_ms * MS, (end_ms - start_ms) * MS)


# device busy 100-300 and 500-700 ms: idle 0-100, 300-500, 700-1000
EVENTS = [
    _ev("module", "jit__lambda(1)", 100, 300, DEV),
    _ev("module", "jit__lambda(1)", 500, 700, DEV),
    _ev("host", "bench.event.engine-step", 0, 310),
    _ev("host", "bench.event.xfer-rdma0", 300, 500),
    _ev("host", "bench.event.engine-step", 690, 1000),
    # pump 0-300 holds the completion (half of the first gap) and the
    # wait for the step's tokens
    _ev("span", "specgen.engine.pump", 0, 300),
    _ev("span", "specgen.engine.complete", 10, 60),
    _ev("span", "specgen.engine.sync", 100, 290),
    # a migration whose copies cover most of the second gap
    _ev("span", "specgen.store.migrate_chunk", 310, 490),
    _ev("span", "specgen.pool.read_copy", 320, 480),
    # a pump covering a third of the last gap: too little to name it
    _ev("span", "specgen.engine.pump", 690, 800),
    # one migration across the window's end, one pump after it
    _ev("span", "specgen.store.migrate_chunk", 950, 1100),
    _ev("span", "specgen.engine.pump", 1100, 1200),
]


def test_seconds_and_runs_per_span_are_clipped_to_the_window():
    red = sp.reduce(EVENTS, WINDOW)
    assert red["span_s"] == pytest.approx({
        "specgen.engine.pump": 0.300 + 0.110,
        "specgen.engine.complete": 0.050,
        "specgen.engine.sync": 0.190,
        "specgen.store.migrate_chunk": 0.180 + 0.050,
        "specgen.pool.read_copy": 0.160})
    assert red["span_runs"] == {
        "specgen.engine.pump": 2, "specgen.engine.complete": 1,
        "specgen.engine.sync": 1, "specgen.store.migrate_chunk": 2,
        "specgen.pool.read_copy": 1}


def test_idle_is_named_by_the_innermost_span_covering_half_of_it():
    red = sp.reduce(EVENTS, WINDOW)
    got = dict(red["idle_by_span"])
    assert got == pytest.approx({
        # 0-100: the completion covers exactly half, inside the pump
        "specgen.engine.complete": 0.100,
        # 300-500: the copies cover 160 of 200 ms, inside the chunk
        "specgen.pool.read_copy": 0.200,
        # 700-1000: no program span covers half; the bench label stays
        "event.engine-step": 0.300})
    assert [n for n, _ in red["idle_by_span"]] == [
        "event.engine-step", "specgen.pool.read_copy",
        "specgen.engine.complete"]
    red_all = tr.reduce(EVENTS, WINDOW)
    assert sum(got.values()) == pytest.approx(
        red_all["window_s"] - red_all["busy_s"])


def test_label_rule():
    spans = [_ev("span", "outer", 0, 100), _ev("span", "inner", 40, 60)]
    assert sp.label(0 * MS, 100 * MS, spans) == "outer"   # inner: 20 %
    assert sp.label(40 * MS, 60 * MS, spans) == "inner"
    assert sp.label(30 * MS, 70 * MS, spans) == "inner"   # exactly half
    assert sp.label(200 * MS, 300 * MS, spans) is None
    assert sp.idle([[10, 20], [30, 40]], 0, 50) == [(0, 10), (20, 30),
                                                     (40, 50)]
    assert sp.idle([[0, 50]], 0, 50) == []


def _ctx(red, c0, c1):
    return SimpleNamespace(trace=red, c0=c0, c1=c1)


def test_readers_by_hand():
    red = {**tr.reduce(EVENTS, WINDOW), **sp.reduce(EVENTS, WINDOW)}
    c0 = {"pages_migrated": 10, "decode_dispatches": 5}
    c1 = {"pages_migrated": 33, "decode_dispatches": 7}
    # 230 ms in migrate_chunk over 23 pages
    assert load_reader("migrate_page_ms")(_ctx(red, c0, c1)) == \
        pytest.approx(10.0)
    # (410 ms of pump - 190 ms of sync) over 2 dispatches
    assert load_reader("step_host_ms")(_ctx(red, c0, c1)) == \
        pytest.approx(110.0)


def test_readers_read_nothing_where_the_program_has_no_spans():
    """A program without the spans or the counter (or a harness that
    does not reduce them) gives no value, and no error."""
    old = tr.reduce(EVENTS, WINDOW)
    new = {**old, **sp.reduce(EVENTS, WINDOW)}
    c0 = {"pages_migrated": 10, "decode_dispatches": 5}
    c1 = {"pages_migrated": 10, "decode_dispatches": 7}
    plain = {"decode_dispatches": 5}, {"decode_dispatches": 7}
    mig = load_reader("migrate_page_ms")
    step = load_reader("step_host_ms")
    assert mig(_ctx(new, c0, c1)) is None                # no page moved
    assert mig(_ctx(new, *plain)) is None                # no counter
    assert mig(_ctx(old, c0, {**c1, "pages_migrated": 12})) is None
    assert step(_ctx(old, *plain)) is None               # no spans
    assert step(_ctx(new, c0, c0)) is None               # no step
    assert step(_ctx(new, *plain)) == pytest.approx(110.0)


def test_span_events_leave_the_trace_reduction_as_it_was():
    """On the recorded probe trace, every key ``trace.reduce`` returns
    keeps its value when program spans are added to the events."""
    events = tr.load_events(ROOT / "bench" / "tests" / "data" /
                            "probe_trace.json")
    host = [e for e in events if e[0] == "host"]
    window = min(e[3] for e in host), max(e[3] + e[4] for e in host)
    lo, hi = window
    extra = [("span", HOST, "specgen.engine.pump", lo + k * 1e6, 5e6)
             for k in range(0, int((hi - lo) // 1e6), 7)]
    assert tr.reduce(events + extra, window) == tr.reduce(events, window)
    red = sp.reduce(events + extra, window)
    assert red["span_runs"]["specgen.engine.pump"] == len(extra)
    assert sum(v for _, v in red["idle_by_span"]) == pytest.approx(
        tr.reduce(events, window)["window_s"]
        - tr.reduce(events, window)["busy_s"])


def test_load_keeps_program_spans_beside_bench_spans(tmp_path):
    """A profile recorded here holds a ``bench.*`` annotation and a
    program span; ``load`` returns the first as kind "host", the second
    as kind "span"."""
    import jax
    from repro.core.spans import ENGINE_PUMP, host_span

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.traced"):
            with host_span(ENGINE_PUMP):
                jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    events = sp.load(sorted(tmp_path.glob("**/*.xplane.pb"))[-1])
    kinds = {(e[0], e[2]) for e in events if e[0] in ("host", "span")}
    assert kinds == {("host", "bench.traced"),
                     ("span", "specgen.engine.pump")}
