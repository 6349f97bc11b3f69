"""Paged KV cache: zero-copy forks, refcounts, page sharing, sampler.

The acceptance bar for the paged refactor (DESIGN.md §Paged-KV /
§Refcount-CoW):

  * ``fork()`` performs ZERO KV-array copies at fork time — verified by
    counting pool writes/copies — and copy-on-write peels at most one
    page per writer afterwards;
  * engine cache bytes for B concurrent forks of one parent scale with
    UNIQUE pages, not ``B * max_len``;
  * refcounts hit zero after retire/cancel and store eviction (no page
    leaks), and pool exhaustion raises a clear error instead of
    silently scattering out of range;
  * the store counts page-level sharing between entries (CacheStats);
  * the fused on-device sampler matches its host references.
"""
from types import SimpleNamespace

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.models import schema
from repro.models.layers import Runtime
from repro.models.registry import get_smoke
from repro.serving.engine import Engine
from repro.serving.kvcache import PrefixCacheStore
from repro.serving.pagepool import PagedPrefix, PagePool, PagePoolExhausted
from repro.serving.sampler import (fold_in_keys, sample_token,
                                   sample_token_ref, sample_tokens)

CFG = get_smoke("qwen2-1.5b")
PARAMS = schema.init_params(CFG, jax.random.PRNGKey(0))


def make_engine(max_batch=8, max_len=96, local=1 << 30, remote=1 << 30,
                **kw):
    store = PrefixCacheStore(local_budget_bytes=local,
                             remote_budget_bytes=remote)
    return Engine(CFG, PARAMS, Runtime(), max_len=max_len,
                  cache_store=store, max_batch=max_batch, **kw)


def prompt(seed, n=12):
    return list(np.random.RandomState(seed).randint(0, CFG.vocab_size, n))


# ----------------------------------------------------- zero-copy forks
def test_fork_is_zero_kv_copies_then_cow_per_writer():
    """fork() = block-table copy + refcount bumps (no pool writes, no
    page copies); the NEXT decode step peels at most one CoW page per
    writer of the shared boundary page."""
    eng = make_engine(max_batch=8, max_len=128)
    g = eng.submit(prompt(0, 30), max_new_tokens=16, temperature=0.0)
    eng.step(g)                                 # admit + 1 token
    parent = eng.generation(g)
    w0, c0 = eng.pool.page_writes, eng.pool.page_copies
    rc0 = eng.pool.refcount.copy()
    forks = [eng.fork(g, max_new_tokens=4, temperature=0.0)
             for _ in range(4)]
    assert eng.pool.page_writes == w0, "fork wrote KV pages"
    assert eng.pool.page_copies == c0, "fork copied KV pages"
    for p in parent.pages:                      # only refcounts moved
        assert eng.pool.refcount[p] == rc0[p] + 4
    for f in forks:
        assert eng.generation(f).pages == parent.pages
    eng.step_all()                              # 5 writers, shared page
    peeled = eng.pool.page_copies - c0
    assert 0 < peeled <= 5
    assert eng.pool.page_writes == w0           # still no row rewrites


def test_fork_bytes_scale_with_unique_pages_not_max_len():
    """B forks of one parent cost unique (shared + divergent) pages,
    not B * max_len."""
    B = 8
    eng = make_engine(max_batch=B, max_len=128)
    g = eng.submit(prompt(1, 30), max_new_tokens=40, temperature=0.0)
    eng.step(g)
    shared = len(eng.generation(g).pages)
    bytes_before = eng.cache_bytes()
    for _ in range(B - 1):
        eng.fork(g, max_new_tokens=4, temperature=0.0)
    assert eng.cache_bytes() == bytes_before    # forks allocate nothing
    eng.step_all()                              # every row writes once
    used = eng.pool.pages_in_use
    # at most one fresh (CoW or appended) page per live row
    assert used <= shared + B
    dense_pages = B * eng.pool.pages_per_row    # the old B*max_len cost
    assert used < dense_pages // 2
    assert eng.cache_bytes() == used * eng.pool.page_bytes


def test_fork_bit_identity_over_shared_pages():
    """Children decoding THROUGH shared pages (before/after CoW) match
    unforked reruns of the same context bit-for-bit."""
    eng = make_engine(max_batch=6, max_len=128)
    g = eng.submit(prompt(2, 18), max_new_tokens=20, temperature=0.0)
    for _ in range(5):
        eng.step(g)
    forks = [eng.fork(g, max_new_tokens=6, temperature=0.0)
             for _ in range(3)]
    ctx = {f: list(eng.generation(f).tokens) for f in forks}
    out = eng.run_all()
    fresh = make_engine(max_batch=6, max_len=128)
    for f in forks:
        rerun = fresh.submit(ctx[f], max_new_tokens=6, temperature=0.0)
        assert fresh.run(rerun) == out[f], "fork diverged over pages"


# ------------------------------------------------------ refcount hygiene
def test_refcounts_zero_after_cancel_no_leaks():
    eng = make_engine(max_batch=4, store_prefixes=False)
    gids = [eng.submit(prompt(i, 12), max_new_tokens=8, temperature=0.0)
            for i in range(3)]
    eng.step_all()
    f = eng.fork(gids[0], max_new_tokens=4, temperature=0.0)
    eng.step_all()
    for gid in gids + [f]:
        eng.cancel(gid)
    assert eng.pool.pages_in_use == 0
    assert (eng.pool.refcount[1:] == 0).all()
    assert eng.cache_bytes() == 0


def test_pagepool_occupancy_gauges_track_refcounts():
    """§Observability satellite: every dispatched engine step samples
    pagepool in-use/shared/free gauges into the metrics registry (a
    timestamped occupancy timeline), and the registry's final sample
    agrees with the refcount-zero-at-end invariant after cancel."""
    from repro.core.clock import EventLoop
    from repro.serving.transport import TransportConfig, TransportPlane

    loop = EventLoop()
    loop.enable_metrics()
    plane = TransportPlane(loop=loop, cfg=TransportConfig(mode="async"))
    eng = Engine(CFG, PARAMS, Runtime(), max_len=96, max_batch=4,
                 transport=plane, clocking="event", store_prefixes=False)
    gids = [eng.submit(prompt(20 + i, 12), max_new_tokens=8,
                       temperature=0.0) for i in range(2)]
    eng.kick()
    loop.run(stop=lambda: len(eng.generation(gids[0]).emitted) >= 3)
    f = eng.fork(gids[0], max_new_tokens=4, temperature=0.0)
    # the zero-copy shared pages CoW-peel on the child's next write, so
    # sample the occupancy explicitly while the sharing is live
    eng.sample_pool_metrics()
    g_use = loop.metrics.get_gauge("pagepool/in_use")
    g_shared = loop.metrics.get_gauge("pagepool/shared")
    g_free = loop.metrics.get_gauge("pagepool/free")
    assert g_shared.value > 0                         # fork shared pages
    loop.run(stop=lambda: len(eng.generation(f).emitted) >= 1)
    assert g_use is not None and len(g_use.samples) > 0
    assert max(v for _t, v in g_use.samples) > 0
    # in_use + free is conserved at every sample (null page excluded)
    total = eng.pool.num_pages - 1
    for (t, u), (t2, fr) in zip(g_use.samples, g_free.samples):
        assert t == t2 and u + fr == total
    for gid in gids + [f]:
        eng.cancel(gid)
    eng.sample_pool_metrics()                    # final end-state sample
    assert (eng.pool.refcount[1:] == 0).all()
    assert g_use.samples[-1][1] == 0.0
    assert g_shared.samples[-1][1] == 0.0
    assert g_free.samples[-1][1] == float(total)


def test_refcounts_zero_after_retire_and_store_eviction():
    """Retirement parks pages in the store; evicting the store (no
    remote tier) must return every page to the pool."""
    eng = make_engine(max_batch=2, remote=0)
    for i in range(2):
        gid = eng.submit(prompt(10 + i, 14), max_new_tokens=4,
                         temperature=0.0)
        eng.run(gid)
    assert eng.pool.pages_in_use > 0            # store holds prefixes
    while eng.store.shed_oldest():              # no remote: evict all
        pass
    assert len(eng.store) == 0
    assert eng.pool.pages_in_use == 0
    assert (eng.pool.refcount[1:] == 0).all()


def test_pool_exhaustion_raises_clear_error():
    eng = make_engine(max_batch=4, max_len=96, num_pages=4, remote=0,
                      local=0)                   # 3 usable pages
    g = eng.submit(prompt(3, 70), max_new_tokens=4, temperature=0.0)
    with pytest.raises(PagePoolExhausted, match="page pool exhausted"):
        eng.step(g)


def test_pool_exhaustion_mid_admission_leaks_nothing():
    """A PagePoolExhausted raised partway through a bucketed admission
    must roll every fresh allocation and acquired store ref back, so
    cancelling generations really does free the pool (the error's own
    recovery advice)."""
    eng = make_engine(max_batch=4, max_len=96, num_pages=4, remote=0,
                      local=0, store_prefixes=False)    # 3 usable pages
    gids = [eng.submit(prompt(30 + i, 40), max_new_tokens=4,
                       temperature=0.0) for i in range(3)]
    with pytest.raises(PagePoolExhausted):
        eng.step_all()                  # first group fits, next raises
    live_pages = sum(len(eng.generation(g).pages) for g in gids)
    assert eng.pool.pages_in_use == live_pages      # no orphan refs
    for g in gids:
        eng.cancel(g)
    assert eng.pool.pages_in_use == 0
    assert (eng.pool.refcount[1:] == 0).all()


def test_remote_hit_larger_than_local_budget_still_restores():
    """Regression: a prefix whose bytes exceed the LOCAL budget must
    survive the restore-from-remote path — the store may not migrate
    the just-restored payload back out before the engine acquires it."""
    eng = make_engine(max_batch=2, local=1, remote=1 << 30)
    p = prompt(12, 24)
    g1 = eng.submit(p, max_new_tokens=4, temperature=0.0)
    out1 = eng.run(g1)                  # parked, migrates straight out
    assert eng.store.stats.migrations >= 1
    g2 = eng.submit(p, max_new_tokens=4, temperature=0.0)
    assert eng.run(g2) == out1          # remote hit restores + decodes
    assert eng.store.stats.hits_remote >= 1


# -------------------------------------------------- store page sharing
def test_store_entries_share_stem_pages_and_stats_count_it():
    eng = make_engine(max_batch=4, max_len=128)
    st = eng.store.stats
    stem = prompt(4, 40)
    g1 = eng.submit(stem, max_new_tokens=2, temperature=0.0)
    eng.run(g1)
    assert st.pages_stored > 0
    assert 0 < st.pages_shared <= st.pages_stored
    g2 = eng.submit(stem + prompt(5, 8), max_new_tokens=2,
                    temperature=0.0)
    eng.run(g2)
    # two stored prefixes extending the same reasoning stem reference
    # the SAME page ids (structural sharing, not copies)
    payloads = [e.payload for e in eng.store._local.values()]
    page_sets = [set(p.pages) for p in payloads if p.pages]
    assert any(a & b for i, a in enumerate(page_sets)
               for b in page_sets[i + 1:]), "no stem pages shared"


def test_remote_migration_moves_pages_and_restores_bitwise():
    """flush_to_remote releases device pages; a later admission
    restores them into fresh pages and decodes identically."""
    eng = make_engine(max_batch=2)
    p = prompt(6, 24)
    g1 = eng.submit(p, max_new_tokens=4, temperature=0.0)
    out1 = eng.run(g1)
    in_use = eng.pool.pages_in_use
    assert eng.store.flush_to_remote() >= 1
    assert eng.pool.pages_in_use < in_use       # pages actually left
    g2 = eng.submit(p, max_new_tokens=4, temperature=0.0)
    assert eng.run(g2) == out1
    assert eng.store.stats.restores >= 1


def _filled_pool(layout):
    """A pool whose arenas hold random bits, so a misplaced page shows."""
    pool = PagePool(CFG, max_batch=2, max_len=64, page_size=8,
                    layout=layout)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))

    def fill(a):
        if jnp.issubdtype(a.dtype, jnp.integer):
            return jax.random.randint(next(keys), a.shape, 0, 1 << 20,
                                      a.dtype)
        return jax.random.normal(next(keys), a.shape, a.dtype)
    return pool, jax.tree.map(fill, pool.init_cache())


def _arena_pages(pool, cache, pages):
    """Each attention layer's pages, indexed leaf by leaf on the device
    and copied one leaf at a time."""
    idx = np.asarray(pages)
    if pool.layout == "fused":
        return [{k: np.asarray(a[idx + r * pool.num_pages])
                 for k, a in cache["arena"].items()}
                for r in range(len(pool._ranks))]
    return [{k: np.asarray(a[idx]) for k, a in cache[i].items()}
            for i in pool._ranks]


def _assert_bitwise(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert sorted(g) == sorted(w)
        for k in w:
            assert g[k].dtype == w[k].dtype and g[k].shape == w[k].shape
            assert g[k].tobytes() == w[k].tobytes(), k


@pytest.mark.parametrize("pages", [[3], [7, 2, 9, 4, 1]],
                         ids=["1-page", "5-pages"])
@pytest.mark.parametrize("layout", ["layers", "fused"])
def test_read_pages_copies_stacked_leaves_bitwise(layout, pages):
    """read_pages hands back, bitwise, the per-layer dicts a per-leaf
    read gives, with three host copies a call (one per leaf kind)
    whatever the number of attention layers."""
    pool, cache = _filled_pool(layout)
    want = _arena_pages(pool, cache, pages)
    assert len(want) == 2 and len(want[0]) == 3      # 3·A = 6 leaves
    for call in (1, 2):
        got = pool.read_pages(cache, pages)
        _assert_bitwise(got, want)
        assert pool.host_reads == call
        assert pool.host_copies == 3 * call


@pytest.mark.parametrize("layout", ["layers", "fused"])
def test_streamed_migrate_out_then_fetch_restores_pages_bitwise(layout):
    """A streamed migrate-out in multi-page chunks, sliced per page on
    the host, then fetched back in other chunk bounds, lands the same
    bits in the fresh pages."""
    pool, cache = _filled_pool(layout)
    eng = SimpleNamespace(pool=pool, _cache=cache)
    src = pool.alloc(5)
    want = _arena_pages(pool, cache, src)
    pre = PagedPrefix.capture(eng, src, None, length=5 * pool.page_size)
    pool.release(src)                    # the entry holds the only refs
    assert pre.migrate_out_begin() == 5
    pre.migrate_out_chunk(0, 2)
    pre.migrate_out_chunk(2, 5)
    pre.migrate_out_finish()
    assert pool.pages_in_use == 0 and pool.host_reads == 2
    assert [len(d[0]["k"]) for d in pre.host["pages"]] == [1] * 5
    # the released pages may come back: wipe the arenas first
    eng._cache = jax.tree.map(jnp.zeros_like, eng._cache)
    dst = pre.fetch_begin()
    pre.fetch_chunk(0, 3)
    pre.fetch_chunk(3, 5)
    pre.fetch_finish()
    assert pre.pages == dst and pre.on_device
    _assert_bitwise(_arena_pages(pool, eng._cache, dst), want)


# --------------------------------------------------- bucketed admission
def test_bucketed_admission_one_dispatch_per_shape():
    """Same-length pending prompts admit in ONE batched suffix-prefill
    dispatch; mixed lengths split into one dispatch per bucket; outputs
    stay bit-identical to serial admission."""
    eng = make_engine(max_batch=8)
    gids = [eng.submit(prompt(100 + i, 12), max_new_tokens=4,
                       temperature=0.0) for i in range(6)]
    eng.step_all()
    assert eng.suffix_prefill_dispatches == 1
    assert eng.suffix_prefill_rows == 6
    assert eng.admission_dispatches_saved == 5
    out = eng.run_all()
    serial = make_engine(max_batch=1)
    for i, gid in enumerate(gids):
        g2 = serial.submit(prompt(100 + i, 12), max_new_tokens=4,
                           temperature=0.0)
        assert serial.run(g2) == out[gid], f"bucketed gen {i} diverged"

    mixed = make_engine(max_batch=8)
    for i, n in enumerate([10, 10, 13, 13, 13]):
        mixed.submit(prompt(200 + i, n), max_new_tokens=2,
                     temperature=0.0)
    mixed.step_all()
    assert mixed.suffix_prefill_dispatches == 2     # two length buckets
    assert mixed.admission_dispatches_saved == 3


# -------------------------------------------------------- device sampler
def test_device_sampler_matches_host_references():
    """Greedy rows match the numpy reference argmax; stochastic rows
    match the inverse-CDF host mirror given the same uniform."""
    rs = np.random.RandomState(7)
    B, V = 16, 32
    logits = (rs.randn(B, V) * 3).astype(np.float32)
    temps = np.array([0.0] * 5 + [0.7] * 6 + [1.3] * 5, np.float32)
    seeds = np.arange(B, dtype=np.uint32)
    pos = ((np.arange(B) * 7) % 13).astype(np.int32)
    out = np.asarray(sample_tokens(jnp.asarray(logits), temps, seeds, pos))
    keys = fold_in_keys(jnp.asarray(seeds), jnp.asarray(pos))
    u = np.asarray(jax.vmap(
        lambda k: jax.random.uniform(k, (), jnp.float32))(keys))
    for i in range(B):
        if temps[i] <= 0:
            assert out[i] == sample_token(logits[i], 0.0)
        else:
            assert out[i] == sample_token_ref(logits[i], float(temps[i]),
                                              float(u[i]))


def test_device_sampler_top_k_restricts_support():
    rs = np.random.RandomState(9)
    B, V, K = 8, 64, 5
    logits = rs.randn(B, V).astype(np.float32)
    temps = np.full((B,), 1.0, np.float32)
    seeds = np.arange(B, dtype=np.uint32)
    pos = np.zeros((B,), np.int32)
    out = np.asarray(sample_tokens(jnp.asarray(logits), temps, seeds, pos,
                                   top_k=K))
    topk = np.argsort(logits, axis=-1)[:, -K:]
    for i in range(B):
        assert out[i] in topk[i]


def test_paged_pallas_kernel_parity_on_serving_path():
    """layers.attention_paged behind Runtime.use_pallas lowers to the
    block-table-consuming flash-decoding kernel (interpret mode) and
    matches the gather-then-attend lowering; arena writes are bitwise
    identical either way (one shared scatter path)."""
    from repro.models import layers as L

    rs = np.random.RandomState(3)
    B, ps, nb = 3, 16, 4
    num_pages = 1 + B * nb                       # page 0 = null page
    KV, Dh, D = CFG.num_kv_heads, CFG.head_dim, CFG.d_model
    lens = np.array([5, 37, 63])                 # per-row written tokens
    bt = np.asarray(
        [[1 + b * nb + j for j in range(nb)] for b in range(B)], np.int32)
    kv_pos = np.full((num_pages, ps), L.EMPTY_SLOT, np.int64)
    for b in range(B):                           # contiguous position order
        for j in range(nb):
            for i in range(ps):
                pos = j * ps + i
                if pos < lens[b]:
                    kv_pos[bt[b, j], i] = pos
    # unwritten slots keep GARBAGE K/V: both lowerings must mask them
    arenas = {
        "k": jnp.asarray(rs.randn(num_pages, ps, KV, Dh), jnp.float32),
        "v": jnp.asarray(rs.randn(num_pages, ps, KV, Dh), jnp.float32),
        "kv_pos": jnp.asarray(kv_pos, jnp.int32),
    }
    p = PARAMS["layers"][0]["attn"]
    x = jnp.asarray(rs.randn(B, 1, D) * 0.3, jnp.float32)
    positions = jnp.asarray(lens[:, None], jnp.int32)
    bt = jnp.asarray(bt)

    for active in (None, jnp.asarray([True, False, True])):
        out_g, new_g = L.attention_paged(
            CFG, p, x, positions, L.no_shard, Runtime(), arenas, bt,
            write_active=active)
        out_p, new_p = L.attention_paged(
            CFG, p, x, positions, L.no_shard, Runtime(use_pallas=True),
            arenas, bt, write_active=active)
        np.testing.assert_allclose(np.asarray(out_g, np.float32),
                                   np.asarray(out_p, np.float32),
                                   atol=2e-4, rtol=2e-4)
        for kk in ("k", "v", "kv_pos"):          # one shared write path
            np.testing.assert_array_equal(np.asarray(new_g[kk]),
                                          np.asarray(new_p[kk]))


def test_engine_pallas_paged_decode_matches_gather_engine():
    """The serving engine with Runtime(use_pallas=True) (paged kernel on
    the decode path, forks included) emits the same greedy tokens as
    the default gather-then-attend engine."""
    def build(runtime):
        store = PrefixCacheStore(local_budget_bytes=1 << 30,
                                 remote_budget_bytes=1 << 30)
        return Engine(CFG, PARAMS, runtime, max_len=96,
                      cache_store=store, max_batch=4)

    dense, pallas = build(Runtime()), build(Runtime(use_pallas=True))
    outs = {}
    for name, eng in (("dense", dense), ("pallas", pallas)):
        g0 = eng.submit(prompt(11, 14), max_new_tokens=6, temperature=0.0)
        g1 = eng.submit(prompt(12, 9), max_new_tokens=6, temperature=0.0)
        eng.step_all()                           # admit + first token
        f0 = eng.fork(g0, max_new_tokens=4, temperature=0.0)
        outs[name] = {"g0": eng.run(g0), "g1": eng.run(g1),
                      "f0": eng.run(f0)}
    assert outs["dense"] == outs["pallas"]


def test_engine_stochastic_streams_reproducible_per_seed():
    """Sampling is a pure function of (seed, position, logits): the
    same submission replays identically; a different seed diverges."""
    outs = []
    for _ in range(2):
        eng = make_engine(max_batch=2, store_prefixes=False)
        g = eng.submit(prompt(8, 10), max_new_tokens=12, temperature=0.9,
                       seed=123)
        outs.append(eng.run(g))
    assert outs[0] == outs[1]
    eng = make_engine(max_batch=2, store_prefixes=False)
    g = eng.submit(prompt(8, 10), max_new_tokens=12, temperature=0.9,
                   seed=124)
    assert eng.run(g) != outs[0]
