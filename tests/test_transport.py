"""Remote-KV transport plane (DESIGN.md §Remote-KV-transport).

Acceptance bar for the transfer-aware store:

  * modeled transfer durations follow the link formula
    ``latency + bytes/bandwidth`` exactly (and jitter, when enabled, is
    seeded — run-to-run deterministic);
  * the link is SERIAL: concurrent submissions queue FIFO;
  * migrate -> restore through the async plane decodes bitwise
    identically to the synchronous legacy path;
  * backpressure applies the configured policy (defer / drop /
    write-through-to-host) instead of silently overflowing the tier,
    and the tier's capacity follows the elastic scheduler's live split;
  * the fetch-vs-recompute cost model skips fetches slower than
    re-prefilling;
  * aborted fetches NEVER fire callbacks (transfers cancelled, pages
    released, the entry stays restorable);
  * a golden virtual-clock trace pins run-to-run determinism.  (The
    synchronous legacy mode — no plane attached — must reproduce the
    PR-3 golden fixtures unchanged: tests/test_evalplane.py pins that.)
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.clock import EventLoop
from repro.core.scheduler import ElasticScheduler, SchedulerConfig
from repro.models import schema
from repro.models.layers import Runtime
from repro.models.registry import get_smoke
from repro.serving.engine import Engine
from repro.serving.kvcache import PendingFetch, PrefixCacheStore
from repro.serving.transport import (LinkSpec, RemoteTierPool,
                                     TransportConfig, TransportLink,
                                     TransportPlane)

CFG = get_smoke("qwen2-1.5b")
PARAMS = schema.init_params(CFG, jax.random.PRNGKey(0))


def make_plane(mode="async", bandwidth=1e9, latency=1e-4, jitter=0.0,
               seed=0, tier_bytes=1 << 30, devices=1, **cfg):
    loop = EventLoop()
    return TransportPlane(
        loop=loop,
        link=TransportLink(loop, LinkSpec(bandwidth=bandwidth,
                                          latency=latency, jitter=jitter,
                                          seed=seed)),
        tier=RemoteTierPool(bytes_per_device=tier_bytes, devices=devices),
        cfg=TransportConfig(mode=mode, **cfg))


def make_engine(transport=None, local=1, remote=1 << 30, max_batch=4,
                **kw):
    store = PrefixCacheStore(local_budget_bytes=local,
                             remote_budget_bytes=remote,
                             transport=transport)
    return Engine(CFG, PARAMS, Runtime(), max_len=96, cache_store=store,
                  max_batch=max_batch, transport=transport, **kw)


def prompt(seed, n=24):
    return list(np.random.RandomState(seed).randint(0, CFG.vocab_size, n))


def payload(nbytes):
    return {"k": jnp.zeros((nbytes // 4,), jnp.float32)}


# ----------------------------------------------------------- link model
def test_transfer_duration_matches_bandwidth_latency_formula():
    plane = make_plane(bandwidth=2e9, latency=5e-3)
    link = plane.link
    t = link.submit(10_000_000, tag="a")
    plane.loop.run()
    want = 5e-3 + 10_000_000 / 2e9
    assert link.model_duration(10_000_000) == pytest.approx(want)
    assert t.finished - t.started == pytest.approx(want)
    assert t.started == 0.0                       # idle link: starts now


def test_link_is_serial_fifo():
    plane = make_plane(bandwidth=1e9, latency=0.01)
    a = plane.link.submit(1_000_000, tag="a")     # 0.011 s
    b = plane.link.submit(2_000_000, tag="b")     # 0.012 s
    plane.loop.run()
    assert a.started == 0.0
    assert b.started == pytest.approx(a.finished)  # queued behind a
    assert plane.link.queue_wait_total == pytest.approx(a.finished)
    assert plane.link.bytes_moved == 3_000_000


def test_jitter_is_seeded_deterministic():
    def durations(seed):
        plane = make_plane(bandwidth=1e9, latency=0.01, jitter=0.3,
                           seed=seed)
        ts = [plane.link.submit(n) for n in (1000, 5000, 2000)]
        plane.loop.run()
        return [t.duration for t in ts]

    assert durations(7) == durations(7)
    assert durations(7) != durations(8)
    base = 0.01 + 1000 / 1e9
    assert durations(7)[0] != pytest.approx(base)  # jitter did perturb


def test_cancelled_transfer_never_fires():
    plane = make_plane(bandwidth=1e9, latency=0.01)
    fired = []
    infl = plane.link.submit(1000, tag="in-flight")
    queued = plane.link.submit(1000, tag="queued")
    for t in (infl, queued):
        t.future.add_done_callback(lambda f: fired.append(f))
        plane.link.cancel(t)
    plane.loop.run()
    assert fired == []
    assert plane.link.transfers_cancelled == 2
    assert plane.link.transfers_done == 0


# ----------------------------------------------------------- tier pool
def test_remote_tier_capacity_follows_scheduler_split():
    loop = EventLoop()
    sched = ElasticScheduler(loop, SchedulerConfig(num_devices=6))
    tier = RemoteTierPool(bytes_per_device=100, sched=sched,
                          host_pool="profiling")
    assert tier.capacity == sched.n_prof * 100
    # queue-max reallocation: validation-heavy last iteration shrinks
    # the profiling pool -> remote capacity shrinks live
    sched.L_val, sched.L_prof = 10, 1
    sched.begin_iteration(1)
    assert sched.n_prof == 1 and tier.capacity == 100
    assert tier.reserve(90) and not tier.reserve(20)
    assert tier.denials == 1
    tier.release(90)
    assert tier.used == 0


# ---------------------------------------------------- backpressure policy
def _store_with(plane, local=100, **kw):
    return PrefixCacheStore(local_budget_bytes=local, transport=plane, **kw)


def test_backpressure_defer_keeps_entry_local_until_headroom():
    plane = make_plane(tier_bytes=4000, backpressure="defer")
    st = _store_with(plane, local=4000)
    st.put([1], payload(4000), length=1)
    st.put([2], payload(4000), length=1)        # LRU [1] wants to migrate
    plane.drain()
    assert plane.tier.used == 4000              # [1] went remote
    st.put([3], payload(4000), length=1)        # tier full: [2] DEFERRED
    assert st.stats.migrations_deferred >= 1
    assert st.local_bytes == 8000               # over budget, deliberately
    got, _ = st.get([2])
    assert got is not None and st.stats.hits_local >= 1  # still local


def test_backpressure_drop_evicts_lru_skip():
    plane = make_plane(tier_bytes=4000, backpressure="drop")
    st = _store_with(plane, local=4000)
    st.put([1], payload(4000), length=1)
    st.put([2], payload(4000), length=1)
    plane.drain()
    st.put([3], payload(4000), length=1)        # tier full: [2] dropped
    assert st.stats.migrations_dropped == 1
    assert st.stats.evictions_local == 1
    assert st.local_bytes == 4000               # budget held
    got, _ = st.get([2])
    assert got is None                          # gone, not parked


def test_backpressure_write_through_host():
    plane = make_plane(tier_bytes=4000, backpressure="host",
                       prefill_tokens_per_s=1.0)
    st = _store_with(plane, local=4000, remote_budget_bytes=1 << 20)
    st.put([1], payload(4000), length=1)
    st.put([2], payload(4000), length=1)
    plane.drain()
    st.put([3], payload(4000), length=1)        # tier full: [2] -> host
    assert st.stats.migrations_host == 1
    assert st.local_bytes == 4000
    assert plane.tier.used == 4000              # host copy is unbudgeted
    got, _ = st.get([2])                        # restorable (remote tier)
    assert got is not None
    got.retain("t")
    plane.drain()
    assert got.ready


def test_fetch_cost_model_prefers_recompute():
    # prefill is modeled MUCH faster than the wire: a remote hit should
    # come back as a miss (recompute) rather than a slow fetch
    plane = make_plane(bandwidth=1e3, latency=1.0,
                       prefill_tokens_per_s=1e9)
    st = _store_with(plane, local=1)
    st.put([1, 2, 3], payload(4000), length=3)
    plane.drain()                               # migrated out
    got, ln = st.get([1, 2, 3])
    assert got is None and ln == 0
    assert st.stats.recomputes_chosen == 1
    assert st.stats.misses == 1
    assert plane.fetches_started == 0           # nothing hit the wire


def test_defer_ages_out_after_k_puts_to_drop():
    """ROADMAP deferred-migration aging: the defer policy may keep the
    local tier over budget only so long — after K deferred puts it
    falls back to drop, so local memory is bounded even when remote
    headroom never returns."""
    plane = make_plane(tier_bytes=4000, backpressure="defer",
                       defer_max_puts=2)
    st = _store_with(plane, local=4000)
    st.put([1], payload(4000), length=1)
    st.put([2], payload(4000), length=1)        # LRU [1] migrates
    plane.drain()
    assert plane.tier.used == 4000              # tier now full
    st.put([3], payload(4000), length=1)        # defer 1
    st.put([4], payload(4000), length=1)        # defer 2
    assert st.stats.migrations_deferred == 2
    assert st.stats.migrations_defer_aged == 0
    assert st.local_bytes == 12000              # over budget, deferred
    st.put([5], payload(4000), length=1)        # aged: falls back to drop
    assert st.stats.migrations_defer_aged >= 1
    assert st.stats.migrations_dropped >= 1
    assert st.local_bytes <= 4000               # budget restored
    # headroom returning resets the aging window
    plane.tier.release(4000)
    st.put([6], payload(4000), length=1)
    plane.drain()
    assert st.stats.migrations >= 2
    assert st._defers_since_headroom == 0


def test_defer_ages_out_after_t_seconds_under_shrinking_tier():
    """The time bound, under the scenario the ROADMAP names: arrival-
    rate reallocation shrinks the hosting pool, the tier is suddenly
    over-subscribed, and deferred entries may only wait T virtual
    seconds before the fallback policy applies."""
    loop = EventLoop()
    sched = ElasticScheduler(loop, SchedulerConfig(num_devices=4))
    plane = TransportPlane(
        loop=loop,
        link=TransportLink(loop, LinkSpec(bandwidth=1e9, latency=1e-4)),
        tier=RemoteTierPool(bytes_per_device=4000, sched=sched,
                            host_pool="profiling"),
        cfg=TransportConfig(mode="async", backpressure="defer",
                            defer_max_s=1.0, defer_fallback="drop"))
    st = _store_with(plane, local=4000)
    assert sched.n_prof == 2                    # capacity 8000
    st.put([1], payload(4000), length=1)
    st.put([2], payload(4000), length=1)        # [1] migrates
    st.put([3], payload(4000), length=1)        # [2] migrates: tier full
    plane.drain()
    assert plane.tier.used == 8000
    # validation-heavy iteration shrinks the profiling pool: remote
    # capacity halves mid-run, the tier is over-subscribed
    sched.L_val, sched.L_prof = 10, 1
    sched.begin_iteration(1)
    assert plane.tier.capacity == 4000 and plane.tier.headroom < 0
    st.put([4], payload(4000), length=1)        # defer (time window opens)
    assert st.stats.migrations_deferred == 1
    plane.tick(2.0)                             # T=1.0s elapses
    st.put([5], payload(4000), length=1)        # aged: drop fallback
    assert st.stats.migrations_defer_aged >= 1
    assert st.stats.migrations_dropped >= 1
    assert st.local_bytes <= 4000


# ------------------------------------------------- engine: async restore
def test_async_migrate_restore_bitwise_identical_to_sync_path():
    """The full loop — park, streamed page-granular migrate-out,
    future-backed fetch, deferred admission — must decode the same
    tokens as the legacy synchronous device_get path."""
    p = prompt(12)
    ref = make_engine()                         # legacy: no plane
    r1 = ref.submit(p, max_new_tokens=4, temperature=0.0)
    out1 = ref.run(r1)
    r2 = ref.submit(p, max_new_tokens=4, temperature=0.0)
    out2 = ref.run(r2)
    assert ref.store.stats.migrations >= 1      # tiny local budget

    plane = make_plane(prefill_tokens_per_s=1.0)   # cost model: fetch
    eng = make_engine(transport=plane)
    g1 = eng.submit(p, max_new_tokens=4, temperature=0.0)
    a1 = eng.run(g1)
    assert plane.migrations_started >= 1        # parked prefix went async
    g2 = eng.submit(p, max_new_tokens=4, temperature=0.0)
    a2 = eng.run(g2)
    assert (a1, a2) == (out1, out2), "async transport diverged"
    assert eng.fetch_deferrals >= 1             # admission awaited pages
    assert plane.fetches_done >= 1
    assert eng.store.stats.fetches_pending >= 1


def test_compressed_wire_migrate_fetch():
    """TransportConfig.compress int8-quantizes streamed page chunks:
    the host payload is int8, fewer modeled bytes ride the link (priced
    via PagePool.compressed_page_bytes), the plane counts wire bytes
    and savings, and the restore still decodes deterministically."""
    p = prompt(15)

    def run(plane):
        eng = make_engine(transport=plane)
        g1 = eng.submit(p, max_new_tokens=4, temperature=0.0)
        out1 = eng.run(g1)
        plane.drain()                           # migrations fully out
        g2 = eng.submit(p, max_new_tokens=4, temperature=0.0)
        out2 = eng.run(g2)
        plane.drain()
        return eng, out1, out2

    plane = make_plane(prefill_tokens_per_s=1.0, compress=True)
    eng = make_engine(transport=plane)
    g1 = eng.submit(p, max_new_tokens=4, temperature=0.0)
    out1 = eng.run(g1)
    plane.drain()
    cpb = eng.pool.compressed_page_bytes
    assert cpb < eng.pool.page_bytes
    entries = list(eng.store._remote.values())  # admission + retire puts
    assert entries and all(e.payload.wire_compress for e in entries)
    page0 = entries[0].payload.host["pages"][0][0]   # 1st page, layer 0
    assert page0["k"]["q"].dtype == np.int8
    assert page0["kv_pos"].dtype == np.int32
    total_pages = sum(len(e.payload.host["n"]) for e in entries)
    assert plane.wire_bytes_compressed == total_pages * cpb
    assert plane.link.bytes_moved == total_pages * cpb
    assert plane.wire_bytes_saved == total_pages * (eng.pool.page_bytes
                                                    - cpb) > 0
    # the fetch moves the same compressed bytes back over the wire
    mig_wire = plane.wire_bytes_compressed
    g2 = eng.submit(p, max_new_tokens=4, temperature=0.0)
    out2 = eng.run(g2)
    assert plane.fetches_done >= 1
    assert plane.wire_bytes_compressed > mig_wire
    assert len(out2) == 4
    # lossy codec, but deterministic: an identical run reproduces it
    _, b1, b2 = run(make_plane(prefill_tokens_per_s=1.0, compress=True))
    assert (b1, b2) == (out1, out2)
    # the raw-wire reference moves strictly more bytes for the same flow
    plane_raw = make_plane(prefill_tokens_per_s=1.0)
    _, _, _ = run(plane_raw)
    plane.drain()
    assert plane_raw.wire_bytes_compressed == 0
    assert plane_raw.link.bytes_moved > plane.link.bytes_moved


def test_sync_mode_charges_engine_blocked_time():
    """mode="sync" is the priced device_get baseline: identical tokens,
    and every byte across the tier boundary blocks the engine for the
    full modeled duration."""
    p = prompt(13)
    plane = make_plane(mode="sync", prefill_tokens_per_s=1.0)
    eng = make_engine(transport=plane)
    g1 = eng.submit(p, max_new_tokens=4, temperature=0.0)
    out1 = eng.run(g1)
    assert plane.engine_blocked_s > 0.0         # migrations blocked
    blocked_mig = plane.engine_blocked_s
    g2 = eng.submit(p, max_new_tokens=4, temperature=0.0)
    out2 = eng.run(g2)
    assert plane.engine_blocked_s > blocked_mig  # the fetch blocked too

    ref = make_engine()
    r1 = ref.submit(p, max_new_tokens=4, temperature=0.0)
    r2dup = ref.run(r1)
    g2r = ref.submit(p, max_new_tokens=4, temperature=0.0)
    assert (out1, out2) == (r2dup, ref.run(g2r))


def test_aborted_fetch_never_fires_and_leaks_nothing():
    """Cancelling the only generation awaiting a fetch aborts it:
    callbacks never fire, destination pages return to the pool, and the
    entry stays restorable in the remote tier."""
    p = prompt(14)
    plane = make_plane(bandwidth=1e3, latency=0.5,   # slow wire
                       prefill_tokens_per_s=1e-9)    # ...but fetch anyway
    eng = make_engine(transport=plane)
    g1 = eng.submit(p, max_new_tokens=4, temperature=0.0)
    out1 = eng.run(g1)
    plane.drain()                                # migration fully out
    pages_before = eng.pool.pages_in_use
    g2 = eng.submit(p, max_new_tokens=4, temperature=0.0)
    eng.step_all()                               # starts the fetch, defers
    assert eng.generation(g2).status == "pending"
    assert eng.store.fetches_in_flight == 1
    fired = []
    pf = eng._awaiting_fetch[g2]
    pf.add_done_callback(lambda f: fired.append(f))
    eng.cancel(g2)                               # last waiter walks away
    plane.loop.run()                             # drain any stale events
    assert fired == []
    assert plane.fetches_cancelled == 1
    assert eng.store.fetches_in_flight == 0
    assert eng.pool.pages_in_use == pages_before  # no leaked dest pages
    # the entry survived the abort: a fresh submission fetches it again
    g3 = eng.submit(p, max_new_tokens=4, temperature=0.0)
    assert eng.run(g3) == out1
    assert plane.fetches_done >= 1


def test_pool_pressure_sheds_urgently_even_in_async_mode():
    """Page-pool pressure cannot wait for the wire: shed_oldest moves
    stored prefixes out BLOCKING (priced, but immediate), so admission
    never deadlocks on an async migration."""
    plane = make_plane()
    eng = make_engine(transport=plane, local=1 << 30, max_batch=4,
                      num_pages=8)
    for i in range(3):
        g = eng.submit(prompt(20 + i, 18), max_new_tokens=2,
                       temperature=0.0)
        eng.run(g)                               # parks prefixes locally
    # a new admission needs more pages than are free: reclaim sheds
    # stored prefixes synchronously and admission proceeds
    g = eng.submit(prompt(30, 40), max_new_tokens=2, temperature=0.0)
    eng.run(g)
    assert eng.store.stats.migrations >= 1
    assert plane.engine_blocked_s > 0.0          # urgent moves blocked


# ------------------------------------------------- determinism (golden)
def _transport_events(loop):
    """(t, event, '<link>:<tag>:<nbytes>') of every link event on the
    loop's composed trace."""
    return [(t, ev, tag) for t, plane, ev, tag in loop.trace
            if plane == "transport"]


def _trace_run(seed):
    plane = make_plane(bandwidth=1e6, latency=0.01, jitter=0.2, seed=seed,
                       tier_bytes=50_000, backpressure="defer")
    plane.loop.enable_trace()
    st = _store_with(plane, local=10_000)
    for i in range(6):
        st.put([i], payload(8000), length=1)
        plane.tick(0.05)
    st.get([0])
    st.get([1])
    plane.drain()
    return _transport_events(plane.loop)


def test_golden_virtual_clock_trace_is_run_to_run_deterministic():
    """Same seed => the full (time, event, link:tag:nbytes) transport
    trace is IDENTICAL, floats included.  (Legacy sync mode — no plane —
    must reproduce the PR-3 golden fixtures: pinned in
    test_evalplane.py.)"""
    a, b = _trace_run(3), _trace_run(3)
    assert a == b
    assert len(a) > 10
    events = {e for _, e, _ in a}
    assert {"enq", "start", "done"} <= events
    # jitter drew from the seeded stream: a different seed perturbs the
    # event times but not determinism
    c = _trace_run(4)
    assert c != a and len(c) == len(a)


def test_engine_async_trace_deterministic_across_runs():
    def run_once():
        plane = make_plane(prefill_tokens_per_s=1.0)
        plane.loop.enable_trace()
        eng = make_engine(transport=plane)
        p = prompt(15)
        g1 = eng.submit(p, max_new_tokens=3, temperature=0.0)
        eng.run(g1)
        g2 = eng.submit(p, max_new_tokens=3, temperature=0.0)
        eng.run(g2)
        plane.drain()
        return _transport_events(plane.loop)

    a = run_once()
    assert a and a == run_once()


# --------------------------------------------- mid-flight edge cases
def test_lookup_during_migrate_out_recomputes_not_joins():
    """An entry whose pages are still streaming OUT is neither resident
    nor restorable: the lookup must answer recompute — NOT hand back a
    bogus join of the migration job."""
    plane = make_plane(bandwidth=1e3, latency=0.5,   # slow wire
                       prefill_tokens_per_s=1.0)
    st = _store_with(plane, local=1)
    st.put([1, 2, 3], payload(4000), length=3)       # migration starts
    assert plane.migrations_started == 1
    assert plane.migrations_done == 0                # still on the wire
    got, ln = st.get([1, 2, 3])
    assert got is None and ln == 0
    assert st.stats.recomputes_chosen == 1
    plane.drain()                                    # lands eventually
    assert plane.migrations_done == 1


def test_reput_during_fetch_cancels_handle_and_engine_reprobes():
    """put() on a key whose fetch has live waiters tears the old entry
    down; the parked handle flips to CANCELLED (never 'ready' with a
    host-side payload) and a holder re-probes the fresh local entry."""
    plane = make_plane(bandwidth=1e3, latency=0.5,
                       prefill_tokens_per_s=0.01)    # fetch always wins
    st = _store_with(plane, local=1 << 20)
    st.put([7, 8], payload(4000), length=2)
    assert st.suspend([7, 8])                        # -> remote tier
    plane.drain()
    got, _ = st.get([7, 8])
    got.retain("gen-a")
    assert not got.ready and not got.cancelled
    st.put([7, 8], payload(4000), length=2)          # re-put: fresh local
    assert got.cancelled and not got.ready
    assert plane.fetches_cancelled == 1
    got.release_waiter("gen-a")                      # must not blow up
    fresh, ln = st.get([7, 8])                       # re-probe: local hit
    assert fresh is not None and not isinstance(fresh, PendingFetch)
    assert ln == 2


def test_partial_migration_dispose_releases_each_page_exactly_once():
    """Disposing an entry whose migration is mid-stream (some chunks
    landed and released, one on the wire) must release only the
    still-resident pages — the chunk/page index mix-up would
    double-release the landed ones (pool assertion) with
    pages_per_transfer > 1."""
    plane = make_plane(bandwidth=1e6, latency=0.5,
                       pages_per_transfer=2)
    eng = make_engine(transport=plane, local=1)
    g = eng.submit(prompt(40, 40), max_new_tokens=2, temperature=0.0)
    out = eng.run(g)                     # parks a >=3-page prefix:
    #                                      chunks of 2 + 1 pages
    assert plane.migrations_started >= 1
    assert plane.migrations_done == 0
    plane.tick(0.6)                      # first chunk landed, tail queued
    assert plane.link.transfers_done >= 1
    # re-put the same key (a rerun retires the same prefix): the old
    # mid-stream entry is disposed — every page exactly once
    g2 = eng.submit(prompt(40, 40), max_new_tokens=2, temperature=0.0)
    assert eng.run(g2) == out
    plane.drain()
    for gid in (g, g2):
        eng.cancel(gid)
    while eng.store.shed_oldest():
        pass
    plane.drain()
    assert (eng.pool.refcount[1:] >= 0).all()


# ------------------------------------------------- store-level API shape
def test_get_longest_returns_pending_fetch_then_payload():
    plane = make_plane(prefill_tokens_per_s=1.0)
    st = _store_with(plane, local=1)
    st.put([1, 2, 3, 4], payload(4000), length=4)
    plane.drain()
    got, ln = st.get_longest([1, 2, 3, 4, 5])
    assert isinstance(got, PendingFetch) and ln == 4
    assert not got.ready
    got.retain("t")
    plane.drain()
    assert got.ready
    assert jax.tree.leaves(got.payload)[0].shape == (1000,)
    # landed: the entry is local again, joined hits are plain payloads
    got2, _ = st.get_longest([1, 2, 3, 4, 5])
    assert not isinstance(got2, PendingFetch)
    assert st.stats.hits_local >= 1
