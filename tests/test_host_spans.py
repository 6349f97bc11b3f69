"""Host spans on the profiler's clock (``repro.core.spans.host_span``).

A tiny engine on an async transport plane, with a prefix store whose
local budget forces every parked prefix out, runs under the JAX
profiler.  The trace it writes must hold every span of ``HOST_SPANS``,
nested as the engine and the store nest them, and the store's
``pages_migrated`` counter must count the pages the migrations moved.
"""
import ast
from pathlib import Path

import numpy as np
import jax
import pytest

from repro.core import spans
from repro.core.clock import EventLoop
from repro.models import schema
from repro.models.layers import Runtime
from repro.models.registry import get_smoke
from repro.serving.engine import Engine
from repro.serving.kvcache import PrefixCacheStore
from repro.serving.transport import (LinkSpec, RemoteTierPool,
                                     TransportConfig, TransportLink,
                                     TransportPlane)

SRC = Path(__file__).resolve().parent.parent / "src" / "repro"
CFG = get_smoke("qwen2-1.5b")


def _engine():
    loop = EventLoop()
    plane = TransportPlane(
        loop=loop, link=TransportLink(loop, LinkSpec(bandwidth=1e9,
                                                     latency=1e-4)),
        tier=RemoteTierPool(bytes_per_device=1 << 30, devices=1),
        cfg=TransportConfig(mode="async"))
    store = PrefixCacheStore(local_budget_bytes=1,
                             remote_budget_bytes=1 << 30, transport=plane)
    params = schema.init_params(CFG, jax.random.PRNGKey(0))
    return Engine(CFG, params, Runtime(), max_len=96, cache_store=store,
                  max_batch=4, transport=plane, clocking="event")


def _host_events(trace_dir):
    """(name, start_ns, end_ns) of every ``specgen.*`` host event."""
    xp = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    assert xp, "the profiler wrote no trace"
    pd = jax.profiler.ProfileData.from_file(str(xp[-1]))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("specgen.")]


def _inside(inner, outer, events):
    """Every ``inner`` event lies within some ``outer`` event."""
    outs = [(s, e) for n, s, e in events if n == outer]
    return all(any(s0 <= s and e <= e0 for s0, e0 in outs)
               for n, s, e in events if n == inner)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    eng = _engine()
    rs = np.random.RandomState(0)
    for n in (24, 40, 33):
        eng.submit(list(rs.randint(0, CFG.vocab_size, n)),
                   max_new_tokens=4, temperature=0.0)
    eng.run_all()                        # compile outside the trace
    for n in (30, 21):
        eng.submit(list(rs.randint(0, CFG.vocab_size, n)),
                   max_new_tokens=4, temperature=0.0)
    before = eng.store.stats.pages_migrated
    trace_dir = tmp_path_factory.mktemp("host-spans")
    jax.profiler.start_trace(str(trace_dir))
    try:
        eng.run_all()
        eng.transport.drain()
    finally:
        jax.profiler.stop_trace()
    return eng, _host_events(trace_dir), before


def test_every_host_span_is_in_the_profile(traced):
    _eng, events, _ = traced
    assert set(spans.HOST_SPANS) <= {n for n, _, _ in events}


def test_host_spans_nest_as_the_program_does(traced):
    _eng, events, _ = traced
    assert _inside(spans.ENGINE_SYNC, spans.ENGINE_PUMP, events)
    assert _inside(spans.ENGINE_LAUNCH, spans.ENGINE_PUMP, events)
    assert _inside(spans.POOL_READ_COPY, spans.STORE_MIGRATE_CHUNK, events)
    assert _inside(spans.POOL_READ_GATHER, spans.STORE_MIGRATE_CHUNK,
                   events)


def test_pages_migrated_counts_the_pages_moved_host_side(traced):
    eng, _events, before = traced
    st = eng.store
    moved = sum(len(e.payload.host["n"]) for e in st._remote.values())
    assert st.stats.migrations >= 2 and st.stats.evictions_remote == 0
    assert moved > 0
    assert st.stats.pages_migrated == moved
    assert st.stats.pages_migrated > before      # the traced part moved


def test_sync_migration_counts_its_pages():
    """The blocking path (no plane) counts the payload's pages too."""
    store = PrefixCacheStore(local_budget_bytes=1,
                             remote_budget_bytes=1 << 30)
    params = schema.init_params(CFG, jax.random.PRNGKey(0))
    eng = Engine(CFG, params, Runtime(), max_len=96, cache_store=store,
                 max_batch=2)
    g = eng.submit(list(range(1, 41)), max_new_tokens=3, temperature=0.0)
    eng.run(g)
    moved = sum(len(e.payload.host["n"]) for e in store._remote.values())
    assert store.stats.migrations >= 1 and moved > 0
    assert store.stats.pages_migrated == moved


def _host_span_args():
    """(file, argument source) of every ``host_span(...)`` call under
    ``src/repro``."""
    out = []
    for path in SRC.rglob("*.py"):
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and \
                    getattr(node.func, "id", None) == "host_span":
                out.append((path.name, ast.unparse(node.args[0])))
    return out


def test_every_host_span_site_names_a_constant():
    """No call site formats a span name: each passes one of the
    module's constants, and each constant is in ``HOST_SPANS``."""
    calls = _host_span_args()
    for f, arg in calls:
        assert arg.isidentifier() and \
            getattr(spans, arg) in spans.HOST_SPANS, (f, arg)
    assert {getattr(spans, a) for _, a in calls} == set(spans.HOST_SPANS)
    assert len(set(spans.HOST_SPANS)) == len(spans.HOST_SPANS)
    assert all(n.startswith("specgen.") for n in spans.HOST_SPANS)


def test_host_span_runs_without_a_profiler():
    with spans.host_span(spans.ENGINE_PUMP):
        x = 1
    assert x == 1
