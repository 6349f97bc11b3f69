"""Generation engine: continuous-batched decode over a PAGED KV cache.

This is the real-model path of the system (examples/serve_spec.py runs
it on a reduced config).  SpecGen's SpecController talks to engines
through the ``GenerationStream`` protocol, which the simulated LLM in
``repro.search.llm_sim`` also implements — the controller cannot tell
the difference (the paper's "no changes to the underlying LLM" claim).

Architecture (DESIGN.md §Paged-KV)
----------------------------------
Attention K/V lives in a global page pool (``serving.pagepool``): each
live generation owns a *block table* — an ordered page-id list covering
its positions — instead of a dense ``(max_len,)`` cache row, so

  * ``fork()`` is a block-table copy plus refcount bumps: ZERO KV bytes
    move at fork time.  Pages copy lazily (copy-on-write at page
    granularity) only when a writer reaches a page some other holder —
    parent, sibling fork, or stored prefix — still references, so B
    forks of one parent cost ``unique divergent pages``, not
    ``B * max_len``;
  * suspended prefixes are parked in the two-tier ``PrefixCacheStore``
    as PAGE LISTS (``pagepool.PagedPrefix``): stored prefixes sharing a
    reasoning stem share the stem's pages, local->remote migration
    moves pages rather than rows, and a partial hit restores shared
    pages and suffix-prefills only into fresh ones.

Every decode step is still ONE fixed-shape jitted dispatch over the
whole ``max_batch`` batch — per-row positions, an ``active`` mask and
the padded block-table matrix let generations sit at different depths
and admit/retire without recompilation — and now the dispatch also
samples ON DEVICE (per-row fold-in keys; serving.sampler), so only a
(B,) token vector crosses the host boundary per step.  Admissions are
bucketed: pending generations with the same (cached-prefix, suffix)
shape batch into one suffix-prefill dispatch.  Because the model's
forward/prefill/decode all lower to the same attention core
(repro.models.layers.attend) and paged gathers only append exact-zero
masked slots, a row's trajectory is bit-identical whichever batch
composition, slot, or page placement it executes in — which is what
makes speculative forks trustworthy.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.metrics import MetricsRegistry
from repro.core.spans import (ENGINE_ADMIT, ENGINE_COMPLETE, ENGINE_LAUNCH,
                              ENGINE_PREPARE_WRITES, ENGINE_PUMP,
                              ENGINE_SYNC, ROOT, SpanRecorder, host_span)
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.models.layers import Runtime
from repro.distributed.sharding import (DECODE_RULES, NO_SHARD,
                                        PREFILL_DECODE_RULES, ShardCtx)
from repro.serving.kvcache import (PendingFetch, PrefixCacheStore,
                                   tree_bytes)
from repro.serving.pagepool import PagePool, PagedPrefix, \
    PagePoolExhausted, _ceil_div, _pow2_pad
from repro.serving.sampler import sample_tokens

# shared inert recorders for engines with no transport plane (no loop):
# disabled, so they never read a clock or store anything
_NULL_SPANS = SpanRecorder(None)
_NULL_METRICS = MetricsRegistry(None)


@dataclasses.dataclass
class EngineStepEvent:
    """One batched decode dispatch on the composed timeline (DESIGN.md
    §Engine-on-loop): the virtual time it ran at and the active-row set
    it advanced.  Recorded (when the loop's composed trace is enabled)
    for BOTH clockings — under ``"event"`` the step IS a scheduled loop
    event; under the legacy ``"stall"`` path it is stamped just before
    the dispatch ticks the clock — so the two modes' step traces are
    directly comparable."""
    t: float
    gen_ids: Tuple[int, ...]


@dataclasses.dataclass
class Generation:
    gen_id: int
    tokens: List[int]                 # full context (prompt + emitted)
    prompt_len: int
    slot: int = -1                    # row in the batched dispatch
    pos: int = 0
    status: str = "pending"           # pending|running|done|cancelled
    max_new_tokens: int = 64
    temperature: float = 0.7
    reasoning: bool = True            # reasoning vs speculative fork
    parent: Optional[int] = None      # forked from (None = root)
    emitted: List[int] = dataclasses.field(default_factory=list)
    rng_seed: int = 0
    pages: List[int] = dataclasses.field(default_factory=list)
    final_prefix: Any = None          # retained PagedPrefix when not parked
    # per-generation stream subscription (DESIGN.md §One-loop):
    # on_token fires at each completed decode step with the new token,
    # on_done exactly once when the generation retires "done" (never on
    # cancellation — a cancelled stream just stops)
    on_token: Optional[Callable[["Generation", int], None]] = None
    on_done: Optional[Callable[["Generation"], None]] = None
    span: int = -1                    # causal row span sid (§Observability):
    #                                   opened at submit/fork, closed at retire


class Engine:
    """Single-model engine: continuous batching + prefix reuse + forks."""

    def __init__(self, cfg: ModelConfig, params, runtime: Runtime = Runtime(),
                 max_len: int = 512, cache_store: PrefixCacheStore = None,
                 store_prefixes: bool = True, max_batch: int = 8,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 top_k: int = 0, transport=None, clocking: str = "event",
                 mesh=None, bucket_lengths: bool = True):
        assert clocking in ("event", "stall")
        self.cfg, self.params, self.runtime = cfg, params, runtime
        # scan decode (DESIGN.md §Sharded-scan-decode): with
        # runtime.scan_layers the pool keeps the FUSED layout (one
        # arena, pattern-stacked dense state) and the decode dispatch is
        # one lax.scan over pattern units on pre-stacked params —
        # bitwise == the layer_barrier loop, ~n_layers fewer traced
        # dispatches per step.  Suffix prefill rides the same scan as a
        # CONTINUATION of the stacked state at start_pos, so bucketed
        # admission is ONE compiled executable per length bucket.
        self.scan = bool(runtime.scan_layers)
        # length-bucketed admission (DESIGN.md §Scan suffix prefill):
        # suffix token counts pad to the next power of two (the padded
        # tail's cache writes DROP via valid_len, so padded == unpadded
        # bitwise) and start_pos is a traced scalar — executable count
        # is bounded by the (rows, length) bucket grid instead of
        # growing with every distinct prefix offset.  bucket_lengths=
        # False keeps exact-length groups (the unpadded reference the
        # parity tests compare against).
        self.bucket_lengths = bool(bucket_lengths)
        # mesh=None is THE golden path (byte-identical traces); a mesh
        # shards batch rows over 'data' and arena pages over 'model'
        # under DECODE_RULES — data movement only, numerics untouched.
        # Admission shards under PREFILL_DECODE_RULES, the projection
        # of PREFILL_RULES onto the same two axes.
        self.mesh = mesh
        self.shard = (ShardCtx(mesh=mesh, rules=DECODE_RULES)
                      if mesh is not None else NO_SHARD)
        self._prefill_shard = (
            ShardCtx(mesh=mesh, rules=PREFILL_DECODE_RULES)
            if mesh is not None else NO_SHARD)
        # who owns virtual time (DESIGN.md §Engine-on-loop):
        #   "event"  batched run_all() is DRIVEN FROM the shared event
        #            loop — each decode dispatch is a scheduled
        #            EngineStepEvent, fetch-parked rows wake by future
        #            resolution, and the clock belongs to the loop;
        #   "stall"  the legacy path: the engine ticks the transport
        #            clock from inside each dispatch and stalls it when
        #            every row is parked (kept for bitwise parity tests
        #            and callers without an async plane).
        self.clocking = clocking
        self._evented = False                   # inside _run_all_evented
        self.step_events: List[EngineStepEvent] = []
        self.max_len = max_len
        self.max_batch = max_batch
        self.top_k = top_k
        self.pool = PagePool(cfg, max_batch=max_batch, max_len=max_len,
                             page_size=page_size, num_pages=num_pages,
                             cache_dtype=runtime.cache_dtype,
                             layout="fused" if self.scan else "layers")
        self.pool.reclaim = self._reclaim_pages
        # NOTE: `cache_store or ...` would discard an EMPTY store
        # (PrefixCacheStore defines __len__) — compare to None instead
        self.store = cache_store if cache_store is not None else \
            PrefixCacheStore(local_budget_bytes=1 << 30,
                             remote_budget_bytes=1 << 30,
                             transport=transport)
        if transport is not None and self.store.plane is None:
            self.store.plane = transport
        self.transport = transport if transport is not None \
            else self.store.plane
        self.store_prefixes = store_prefixes
        self._gens: Dict[int, Generation] = {}
        self._ids = itertools.count()
        self._cache = None                      # pagepool cache pytree
        self._free: List[int] = list(range(max_batch))
        # generations waiting on an in-flight remote-KV fetch: they stay
        # "pending" (other rows keep decoding) until the tail page lands
        self._awaiting_fetch: Dict[int, PendingFetch] = {}
        self.fetch_deferrals = 0                # admissions parked on a fetch
        # persistent evented pump (DESIGN.md §One-loop): the same state
        # the one-shot _run_all_evented closure used to hold, promoted
        # to the instance so controllers can keep the engine decoding
        # across submissions via kick() without anyone calling run_all
        self._pump = {"scheduled": False, "parked_at": None,
                      "last_step": 0.0, "inflight": None}
        # causal step/park span sids — at most one of each in flight
        self._step_span = -1
        self._park_span = -1
        # fetch jobs carrying a wake callback: holds the job OBJECTS
        # (identity via id() would go stale — a completed job can be
        # GC'd and a later, distinct job reuse its address, silently
        # suppressing its wake)
        self._pump_armed: List[Any] = []
        self.tokens_prefilled = 0
        self.tokens_decoded = 0
        self.tokens_not_decoded = 0             # cancelled before decode
        self.decode_dispatches = 0              # jitted decode calls
        self.suffix_prefill_dispatches = 0      # batched admission calls
        self.suffix_prefill_rows = 0            # generations admitted via them

        cfg_, rt, shard_ = cfg, runtime, self.shard
        if mesh is not None:
            # pin params replicated on the mesh once (DECODE_RULES keep
            # every contraction replicated — bitwise-safe, no TP
            # partial-sum reassociation)
            from jax.sharding import NamedSharding, PartitionSpec
            self.params = params = jax.tree.map(
                lambda a: jax.device_put(
                    a, NamedSharding(mesh, PartitionSpec())), params)
        # decode-dispatch params: pre-stacked along the pattern-unit
        # axis for scan mode (host-side, once), the plain per-layer
        # tree otherwise
        self._dparams = T.stack_params(cfg, params) if self.scan \
            else params
        # suffix-prefill executables, keyed on the (rows, length)
        # BUCKET (Gp, mp) — prefix offset and real suffix length are
        # traced inputs, so each entry holds exactly one executable
        # (prefill_retraces observes any drift from that)
        self._prefills: Dict[Tuple[int, int], Any] = {}
        # THE decode dispatch: whole batch, per-row positions/block
        # tables, active mask, fused on-device sampling; the cache
        # (arenas + dense rows) is donated and updated in place
        self._decode = jax.jit(
            lambda p, tok, cache, bt, pos, act, temp, seeds: (
                lambda lg_c: (sample_tokens(lg_c[0], temp, seeds, pos,
                                            top_k=top_k), lg_c[1])
            )(T.decode_step(cfg_, p, tok, cache, pos, rt, shard_,
                            active=act, block_tables=bt)),
            donate_argnums=(2,))

    # ------------------------------------------------------- observability
    @property
    def _spans(self) -> SpanRecorder:
        return self.transport.loop.spans if self.transport is not None \
            else _NULL_SPANS

    @property
    def _metrics(self) -> MetricsRegistry:
        return self.transport.loop.metrics if self.transport is not None \
            else _NULL_METRICS

    def sample_pool_metrics(self) -> None:
        """Gauge-sample pagepool occupancy (pages in use / shared /
        free) onto the virtual-clock timeline — called at every decode
        dispatch so page pressure is visible per step, and callable at
        run end to assert refcounts drained (tests/test_paged.py)."""
        m = self._metrics
        if not m.enabled:
            return
        pool = self.pool
        # the null page (id 0) is bookkeeping, not occupancy
        shared = int((pool.refcount[1:] > 1).sum())
        m.gauge("pagepool/in_use").set(float(pool.pages_in_use))
        m.gauge("pagepool/shared").set(float(shared))
        m.gauge("pagepool/free").set(float(pool.pages_free))

    # ----------------------------------------------------------- lifecycle
    def submit(self, prompt_tokens: List[int], *, max_new_tokens: int = 64,
               temperature: float = 0.7, reasoning: bool = True,
               seed: int = 0) -> int:
        assert prompt_tokens, "empty prompt: nothing to condition on"
        assert len(prompt_tokens) < self.max_len, (
            f"prompt of {len(prompt_tokens)} tokens does not fit "
            f"max_len={self.max_len}: the scatter cache write would "
            f"silently drop out-of-range positions")
        gid = next(self._ids)
        g = Generation(
            gen_id=gid, tokens=list(prompt_tokens),
            prompt_len=len(prompt_tokens), max_new_tokens=max_new_tokens,
            temperature=temperature, reasoning=reasoning, rng_seed=seed)
        g.span = self._spans.begin("engine", "row", f"g{gid}")
        if max_new_tokens <= 0:             # nothing to decode: done
            g.status = "done"
            self._spans.end(g.span)
        self._gens[gid] = g
        return gid

    def fork(self, parent_id: int, *, max_new_tokens: int = 64,
             temperature: float = 0.7, seed: int = 0) -> int:
        """Fork a speculative generation from the parent's CURRENT prefix.

        Block-table copy + per-page refcount bumps: ZERO KV-array
        copies, zero prefill recompute — the divergent suffix only
        starts consuming pages when the child (or parent) next writes
        into a shared page and copy-on-write peels that one page off.
        (Recurrent / ring-buffer layers hold fixed-size per-row state —
        a single "page" — which IS copied here; attention KV is not.)
        """
        parent = self._gens[parent_id]
        assert parent.status == "running", "fork requires a live parent"
        gid = next(self._ids)
        slot = self._claim_slot()
        pages = list(parent.pages)
        self.pool.ref(pages)
        self._cache = self.pool.dense_copy(self._cache, parent.slot, slot)
        child = Generation(
            gen_id=gid, tokens=list(parent.tokens),
            prompt_len=len(parent.tokens), slot=slot,
            pos=parent.pos, status="running",
            max_new_tokens=max_new_tokens, temperature=temperature,
            reasoning=False, parent=parent_id, rng_seed=seed,
            pages=pages)
        child.span = self._spans.begin("engine", "row", f"g{gid}")
        self._gens[gid] = child
        self.store.stats.tokens_reused += parent.pos
        return gid

    def subscribe(self, gen_id: int, *,
                  on_token: Optional[Callable[[Generation, int],
                                              None]] = None,
                  on_done: Optional[Callable[[Generation], None]] = None
                  ) -> None:
        """Attach per-generation stream callbacks (the controller seam):
        ``on_token(gen, token)`` at each completed decode step,
        ``on_done(gen)`` once at "done" retirement.  Subscribing to an
        already-finished generation fires ``on_done`` immediately."""
        g = self._gens[gen_id]
        if on_token is not None:
            g.on_token = on_token
        if on_done is not None:
            if g.status == "done":
                on_done(g)
            elif g.status != "cancelled":
                g.on_done = on_done

    def cancel(self, gen_id: int) -> None:
        """Cancel a generation mid-flight: remaining decode work is
        never dispatched (``tokens_not_decoded``), its pages drop their
        refcounts, and an awaited prefix fetch is aborted when this was
        its last waiter.  Safe between a step's compute and completion
        phases — the completion skips non-running rows."""
        g = self._gens.get(gen_id)
        if g and g.status in ("pending", "running"):
            self._retire(g, "cancelled")
            # last-waiter-walks-away: if the pump was parked on the
            # fetch this cancellation just aborted, that future will
            # never resolve — re-arm a pump step at the next grid point
            # so it re-evaluates (goes idle, or re-parks on fetches
            # other rows still await)
            self._on_fetch_landed(None)

    def suspend_to_store(self, gen_id: int) -> None:
        """Park a generation's prefix in the cache store (local tier; the
        store migrates it remote under memory pressure).  Works for live
        generations (pages shared with the running row) and finished
        ones (prefix retained at retirement when it wasn't auto-parked).
        """
        g = self._gens[gen_id]
        if g.slot >= 0 and g.pos > 0:
            payload = self._capture_prefix(g)
        elif g.final_prefix is not None:
            payload, g.final_prefix = g.final_prefix, None
        else:
            return
        self.store.put(g.tokens[: g.pos], payload, length=g.pos)

    def _reclaim_pages(self, need: int) -> None:
        """Page-pool pressure: shed LRU stored prefixes (they migrate to
        the remote tier — host memory — or evict) until ``need`` pages
        are free or the local store tier is empty.  Live generations'
        pages are never touched."""
        while self.pool.pages_free < need and self.store.shed_oldest():
            pass

    # ----------------------------------------------------------- slot mgmt
    def _ensure_cache(self) -> None:
        if self._cache is None:
            cache = self.pool.init_cache()
            if self.mesh is not None:
                # place the arenas/dense rows per DECODE_RULES up front
                # so the decode jit never reshards the (big) cache
                cache = jax.device_put(
                    cache, self.pool.cache_shardings(self.shard, cache))
            self._cache = cache

    def _claim_slot(self) -> int:
        if not self._free:
            raise RuntimeError(
                f"engine full: {self.max_batch} rows live; retire or "
                f"cancel a generation before admitting another")
        self._ensure_cache()
        return self._free.pop(0)

    def _capture_prefix(self, g: Generation) -> PagedPrefix:
        n_pages = _ceil_div(g.pos, self.pool.page_size)
        return PagedPrefix.capture(
            self, g.pages[:n_pages],
            self.pool.read_dense_row(self._cache, g.slot), g.pos)

    def _retire(self, g: Generation, status: str) -> None:
        g.status = status
        self._spans.end(g.span, status=status)
        if status == "cancelled":
            # early termination's decode savings: tokens this row will
            # never compute (the paper's cut generation cost)
            self.tokens_not_decoded += max(
                g.max_new_tokens - len(g.emitted), 0)
        pf = self._awaiting_fetch.pop(g.gen_id, None)
        if pf is not None:
            # abort the awaited fetch: when this was its last waiter the
            # store cancels the transfers — no callback ever fires
            pf.release_waiter(g.gen_id)
        if g.slot >= 0:
            if status == "done" and g.pos > 0:
                # the finished prefix must survive the row recycle:
                # auto-park its pages (later forks/extensions restore
                # instead of re-prefilling), or retain them on the
                # generation so an explicit suspend_to_store still works
                payload = self._capture_prefix(g)
                if self.store_prefixes:
                    self.store.put(g.tokens[: g.pos], payload,
                                   length=g.pos)
                else:
                    g.final_prefix = payload
            if g.pages:
                self.pool.release(g.pages)
                g.pages = []
            self._free.append(g.slot)
            g.slot = -1
        if status == "done" and g.on_done is not None:
            # fire AFTER the row is recycled: the callback sees a clean
            # engine (free slot, parked prefix) and may fork/submit
            cb, g.on_done = g.on_done, None
            cb(g)

    # ----------------------------------------------------------- admission
    def _admit_all(self, pending: Sequence[Generation]) -> None:
        """Admit pending generations, BUCKETED: same (cached-prefix len,
        prompt len) admissions share one batched suffix-prefill dispatch
        (row counts are padded to powers of two so trace counts stay
        bounded on bursty arrivals).  The prefix store is consulted
        first: a full hit restores shared pages with zero recompute; a
        partial hit suffix-prefills only the divergent remainder into
        fresh pages."""
        with host_span(ENGINE_ADMIT):
            take = list(pending)[: len(self._free)]
            if not take:
                return
            self._ensure_cache()
            groups: Dict[Tuple[int, int], List] = {}
            for g in take:
                n = g.prompt_len - 1        # decode consumes the last token
                pf = self._awaiting_fetch.get(g.gen_id)
                if pf is not None and pf.cancelled:
                    # the fetch was torn down underneath us (re-put of the
                    # key, sibling abort): drop the dead handle and re-probe
                    # the store like a fresh admission
                    del self._awaiting_fetch[g.gen_id]
                    pf.release_waiter(g.gen_id)
                    pf = None
                if pf is not None:
                    if not pf.ready:
                        continue            # pages still on the wire: stay
                    #                         pending, other rows decode on
                    del self._awaiting_fetch[g.gen_id]
                    pf.release_waiter(g.gen_id)
                    payload, clen = pf.payload, pf.length
                elif n == 0:
                    payload, clen = None, 0
                else:
                    payload, clen = self.store.get_longest(g.tokens[:n])
                    if isinstance(payload, PendingFetch):
                        # future-backed remote hit: await it only when the
                        # suffix prefill actually needs the pages — park the
                        # admission, keep decoding everyone else
                        payload.retain(g.gen_id)
                        self._awaiting_fetch[g.gen_id] = payload
                        self.fetch_deferrals += 1
                        continue
                if payload is not None:
                    pages, extra = payload.acquire()
                else:
                    pages, extra, clen = [], None, 0
                if clen >= n:                           # full hit / 1-token
                    self._admit_ready(g, n, pages, extra)
                else:
                    self.store.note_recompute(n - clen)
                    groups.setdefault((clen, n), []).append(
                        (g, pages, extra))
            ordered = sorted(groups.items())
            for gi, ((clen, n), items) in enumerate(ordered):
                try:
                    self._admit_group(clen, n, items)
                except PagePoolExhausted:
                    # _admit_group rolled its own items back; drop the
                    # acquired store refs of the still-unprocessed groups
                    # too so exhaustion never strands refcounts (the gens
                    # stay "pending" and can re-admit after pressure eases)
                    for _, later in ordered[gi + 1:]:
                        for g, pages, _extra in later:
                            if pages:
                                self.pool.release(pages)
                    raise

    def _admit_ready(self, g: Generation, n: int, pages, extra) -> None:
        g.pages = pages
        slot = self._free.pop(0)
        if extra is not None:
            self._cache = self.pool.dense_admit(self._cache, extra, [slot])
        g.slot, g.pos, g.status = slot, n, "running"

    def _admit_group(self, clen: int, n: int, items) -> None:
        pool, ps = self.pool, self.pool.page_size
        W = pool.pages_per_row
        G = len(items)
        Gp = _pow2_pad(G)
        first = clen // ps
        n_new = _ceil_div(n, ps) - first
        m = n - clen                    # real suffix tokens
        mp = _pow2_pad(m) if self.bucket_lengths else m
        fresh = []
        try:
            for _ in items:
                fresh.append(pool.alloc(n_new))
        except PagePoolExhausted:
            # transactional rollback: earlier items' fresh pages and
            # every acquired store ref go back, or cancel/retire could
            # never actually free the pool (orphaned refcounts)
            for f in fresh:
                pool.release(f)
            for _g, pages, _extra in items:
                if pages:
                    pool.release(pages)
            raise
        self._cache = pool.flush_scrub(self._cache)
        page_mat = np.zeros((Gp, W), np.int64)      # pad: null page 0
        toks = np.zeros((Gp, mp), np.int32)         # length pad: token 0
        for i, (g, pages, _) in enumerate(items):
            page_mat[i, : len(pages)] = pages
            toks[i, :m] = g.tokens[clen:n]
        rows = pool.gather_rows(self._cache, page_mat,
                                np.full((Gp,), clen, np.int64))
        rows = self._overlay_extras(rows, items)
        # prefix offset and real length are TRACED scalars: one
        # executable per (Gp, mp) bucket serves every offset, and the
        # padded tail [m, mp) drops all its cache writes via valid_len
        sp, vl = jnp.int32(clen), jnp.int32(m)
        slots = [self._free.pop(0) for _ in range(G)]
        if self.scan:
            # ONE fused admit executable: stack the gathered rows, run
            # the scan-continuation prefill, scatter the suffix pages
            # into the fused arena and the dense rows into their slots
            # — the admission analogue of the scan decode dispatch.
            # The write window [w0, w0+nw) covers the fresh block-table
            # columns at any page alignment; clamping w0 (not the
            # slice) keeps the traced dynamic_slice exact.
            nw = min((mp + 2 * ps - 2) // ps, W)
            w0 = min(first, W - nw)
            write_mat = np.full((Gp, nw), pool.num_pages, np.int64)
            for i in range(G):
                write_mat[i, first - w0: first - w0 + n_new] = fresh[i]
            slot_arr = np.full((Gp,), self.max_batch, np.int32)
            slot_arr[:G] = slots
            self._cache, rows = self._admit_fused(Gp, mp)(
                self._dparams, self._cache, jnp.asarray(toks), rows,
                jnp.asarray(write_mat, jnp.int32),
                jnp.asarray(slot_arr), jnp.int32(w0), sp, vl)
            pool.note_rows_written(write_mat)
        else:
            _, rows = self._suffix_prefill(Gp, mp)(
                self.params, jnp.asarray(toks), rows, sp, vl)
            write_mat = np.full((Gp, n_new), pool.num_pages, np.int64)
            for i in range(G):
                write_mat[i] = fresh[i]
            self._cache = pool.write_rows(self._cache, rows, write_mat,
                                          first)
        self.suffix_prefill_dispatches += 1
        self.suffix_prefill_rows += G
        for i, (g, pages, _) in enumerate(items):
            if pages[first:]:
                # the shared boundary page was merged into a fresh page
                # by the prefill write — drop the acquired ref on it
                pool.release(pages[first:])
            g.pages = pages[:first] + fresh[i]
            g.slot, g.pos, g.status = slots[i], n, "running"
        if not self.scan:
            self._cache = pool.dense_admit(self._cache, rows, slots)
        self.tokens_prefilled += (n - clen) * G
        if self.store_prefixes:
            for i, (g, _, _) in enumerate(items):
                payload = PagedPrefix.capture(
                    self, g.pages, self._slice_dense_rows(rows, i), n)
                self.store.put(g.tokens[:n], payload, length=n)

    def _overlay_extras(self, rows, items):
        """Write stored recurrent/ring state into the gathered row batch
        (no-op for pure-attention stacks)."""
        dense = self.pool.dense_layers
        if not dense:
            return rows
        for i, (_, _, extra) in enumerate(items):
            if extra is None:
                continue
            for li in dense:
                rows[li] = jax.tree.map(
                    lambda full, e: full.at[i].set(e[0]),
                    rows[li], extra[li])
        return rows

    def _slice_dense_rows(self, rows, i: int):
        if not self.pool.dense_layers:
            return None
        dense = set(self.pool.dense_layers)
        return [jax.tree.map(lambda a: a[i: i + 1], c)
                if li in dense else None
                for li, c in enumerate(rows)]

    def _suffix_prefill(self, Gp: int, mp: int):
        """Jitted per-layer-loop prefill for one (rows, length) bucket.
        Prefix offset and real suffix length arrive as traced scalars,
        so the memo entry compiles exactly once — a memo keyed on exact
        offsets (the pre-bucketing design) grew one executable per
        distinct prefix length."""
        key = (Gp, mp)
        fn = self._prefills.get(key)
        if fn is None:
            cfg, rt, shard = self.cfg, self.runtime, self._prefill_shard
            fn = self._prefills[key] = jax.jit(
                lambda p, t, c, sp, vl: T.prefill(
                    cfg, p, t, cache=c, start_pos=sp, valid_len=vl,
                    runtime=rt, shard=shard))
        return fn

    def _admit_fused(self, Gp: int, mp: int):
        """The scan path's ONE admission executable per (rows, length)
        bucket: stack the gathered dense rows into the scan-state
        layout, CONTINUE them through the scan-over-pattern-units
        prefill at the traced offset, then land the results — suffix
        pages into the fused arena (one scatter per leaf, traced window
        start) and dense rows into their slots (padded slots index out
        of bounds and drop).  The whole chain is one compiled dispatch,
        vs ~n_layers for the per-layer loop it replaces."""
        key = (Gp, mp)
        fn = self._prefills.get(key)
        if fn is None:
            cfg, rt = self.cfg, self.runtime
            shard, pool = self._prefill_shard, self.pool

            def admit(p, cache, toks, rows, write_mat, slots, w0, sp, vl):
                state = T.stack_decode_state(cfg, rows)
                _, state = T.prefill(cfg, p, toks, cache=state,
                                     start_pos=sp, valid_len=vl,
                                     runtime=rt, shard=shard)
                rows2 = T.unstack_decode_state(cfg, state)
                cache = pool.write_rows_traced(cache, rows2, write_mat,
                                               w0)
                cache = pool._dense_admit_fused_impl(cache, rows2, slots)
                return cache, rows2

            fn = self._prefills[key] = jax.jit(admit, donate_argnums=(1,))
        return fn

    @property
    def prefill_retraces(self) -> int:
        """Executables beyond one per (rows, length) bucket: 0 when the
        bucket keying is shape-complete (every admission shape a bucket
        sees maps to the same compiled signature); anything else means
        admission is silently recompiling."""
        return sum(max(f._cache_size() - 1, 0)
                   for f in self._prefills.values())

    @property
    def admission_dispatches_saved(self) -> int:
        """Suffix-prefill dispatches bucketing avoided vs one-at-a-time
        admission (each batched group of G rows saves G-1)."""
        return self.suffix_prefill_rows - self.suffix_prefill_dispatches

    # ----------------------------------------------------------- execution
    def _prepare_writes(self, gens: Sequence[Generation]) -> None:
        """Make every writer's target page exclusively owned BEFORE the
        dispatch: append a fresh page at a page boundary, and
        copy-on-write a page some other holder still references.  All
        page copies of the step batch into one scatter."""
        with host_span(ENGINE_PREPARE_WRITES):
            pool, ps = self.pool, self.pool.page_size
            srcs, dsts = [], []
            for g in gens:
                wp = g.pos // ps
                if wp >= len(g.pages):
                    g.pages.append(pool.alloc(1)[0])
                elif pool.refcount[g.pages[wp]] > 1:
                    new = pool.alloc(1)[0]
                    srcs.append(g.pages[wp])
                    dsts.append(new)
                    pool.release([g.pages[wp]])
                    g.pages[wp] = new
            self._cache = pool.flush_scrub(self._cache)
            if srcs:
                self._cache = pool.copy_pages(self._cache, srcs, dsts)

    def _dispatch(self, gens: Sequence[Generation]) -> None:
        """ONE jitted decode step advancing every generation in ``gens``
        (decode + on-device sampling fused).  A dispatch spans one
        ``decode_step_s`` of virtual time: the compute phase runs at
        the step's start, its COMPLETIONS (token appends, retirements
        and the migrations they trigger) materialize at the step's end
        — the legacy path ticks the clock between the two, the evented
        path completes at the next ``EngineStepEvent``."""
        nxt = self._dispatch_compute(gens)
        if self.transport is not None and not self._evented:
            # legacy stall clocking: the dispatch itself advances the
            # clock one decode step, so in-flight migrations and
            # fetches make progress WHILE rows decode.  Under the
            # event-driven path time is owned by the loop — the step
            # ran AT its scheduled instant and the next step event is
            # one decode_step_s later.
            self.transport.tick()
        self._dispatch_complete(gens, nxt)

    def _dispatch_compute(self, gens: Sequence[Generation]):
        self._prepare_writes(gens)
        with host_span(ENGINE_LAUNCH):
            B, W = self.max_batch, self.pool.pages_per_row
            tok = np.zeros((B, 1), np.int32)
            pos = np.zeros((B,), np.int32)
            act = np.zeros((B,), bool)
            temp = np.zeros((B,), np.float32)
            seeds = np.zeros((B,), np.uint32)
            bt = np.zeros((B, W), np.int32)             # pad: null page 0
            for g in gens:
                tok[g.slot, 0] = g.tokens[g.pos]
                pos[g.slot] = g.pos
                act[g.slot] = True
                temp[g.slot] = g.temperature
                seeds[g.slot] = np.uint32(g.rng_seed & 0xFFFFFFFF)
                bt[g.slot, : len(g.pages)] = g.pages
            nxt, self._cache = self._decode(
                self._dparams, jnp.asarray(tok), self._cache,
                jnp.asarray(bt), jnp.asarray(pos), jnp.asarray(act),
                jnp.asarray(temp), jnp.asarray(seeds))
        with host_span(ENGINE_SYNC):
            nxt = np.asarray(nxt)
        self.decode_dispatches += 1
        if self.transport is not None:
            loop = self.transport.loop
            if loop.trace is not None:
                # only when the composed timeline is enabled: a
                # long-lived engine must not grow an unread step list
                self.step_events.append(EngineStepEvent(
                    loop.now, tuple(g.gen_id for g in gens)))
            loop.record("engine", "step", f"n={len(gens)}")
            # decode-step interval span: compute opens it, the step's
            # completion (one decode_step_s later on the evented path,
            # immediately on the legacy one) closes it
            self._step_span = loop.spans.begin("engine", "step",
                                               f"n={len(gens)}",
                                               parent=ROOT)
        self.sample_pool_metrics()
        return nxt

    def _dispatch_complete(self, gens: Sequence[Generation], nxt) -> None:
        with host_span(ENGINE_COMPLETE):
            self._spans.end(self._step_span)
            self._step_span = -1
            for g in gens:
                if g.status != "running":
                    # cancelled between this step's compute and completion
                    # (early termination): its slot is already recycled —
                    # appending nxt[g.slot] would steal another row's token
                    continue
                t = int(nxt[g.slot])
                g.tokens.append(t)
                g.emitted.append(t)
                g.pos += 1
                self.tokens_decoded += 1
                if g.on_token is not None:
                    g.on_token(g, t)
                if g.status != "running":
                    continue              # on_token cancelled this row
                if len(g.emitted) >= g.max_new_tokens or \
                        g.pos >= self.max_len - 1:
                    self._retire(g, "done")

    def step(self, gen_id: int) -> Optional[int]:
        """Advance one generation by one token; returns it (or None)."""
        g = self._gens[gen_id]
        if g.status == "pending":
            if not self._free:
                raise RuntimeError(
                    f"engine full: {self.max_batch} rows live; retire or "
                    f"cancel a generation before admitting another")
            self._admit_all([g])
            if g.status == "pending" and g.gen_id in self._awaiting_fetch:
                # sole caller, nothing else to decode: the engine really
                # is blocked on the wire — advance the clock and charge
                # the stall
                self.transport.stall(self.transport.cfg.decode_step_s)
                return None
        if g.status != "running":
            return None
        self._dispatch([g])
        return g.tokens[-1]

    def step_all(self) -> List[int]:
        """One decode step for EVERY live generation in a single batched
        dispatch (admitting pending ones, bucketed, as slots allow).
        Returns the gen_ids that advanced."""
        pending = [g for g in self._gens.values() if g.status == "pending"]
        if pending and self._free:
            self._admit_all(pending)
        live = [g for g in self._gens.values() if g.status == "running"]
        if live:
            self._dispatch(live)
        return [g.gen_id for g in live]

    def run(self, gen_id: int) -> List[int]:
        g = self._gens[gen_id]
        while g.status in ("pending", "running"):
            self.step(gen_id)
        return g.emitted

    def run_all(self) -> Dict[int, List[int]]:
        """Drain every submitted generation via batched stepping.

        With an async transport plane and ``clocking="event"`` the
        drain is DRIVEN FROM the shared event loop (each decode
        dispatch a scheduled event); otherwise the legacy stall loop
        runs (sync planes block inside admissions, so the engine must
        own time there)."""
        if self.transport is not None and self.clocking == "event" \
                and self.transport.cfg.mode == "async":
            return self._run_all_evented()
        while any(g.status in ("pending", "running")
                  for g in self._gens.values()):
            if not self.step_all():
                if self._awaiting_fetch and self.transport is not None \
                        and self.transport.in_flight:
                    # every row is parked on a remote-KV fetch: stall
                    # the engine until the next pages land
                    self.transport.stall(self.transport.cfg.decode_step_s)
                    continue
                break                            # only blocked pendings
        return {gid: g.emitted for gid, g in self._gens.items()}

    def _run_all_evented(self) -> Dict[int, List[int]]:
        """Drain the engine FROM the event loop via the persistent pump
        (``kick``/``_pump_step``): run the shared loop until the pump
        goes idle (drained or only blocked pendings remain)."""
        self._evented = True
        try:
            self.kick()
            self.loop.run(stop=self.pump_idle)
        finally:
            self._evented = False
        return {gid: g.emitted for gid, g in self._gens.items()}

    # -------------------------------------------------- persistent pump
    # The engine's decode clock as a PERMANENT resident of the shared
    # loop (DESIGN.md §One-loop): each batched decode dispatch is a
    # scheduled ``EngineStepEvent`` one ``decode_step_s`` after the
    # previous; when every row is parked on an in-flight fetch the
    # engine schedules NOTHING — parked rows wake via the fetch
    # future's resolution (no polling), at the next decode-step grid
    # point (bit-matching the legacy stall path's k x decode_step_s
    # stalls), the gap charged to ``engine_blocked_s``.  When nothing
    # is left to decode the pump goes idle and a later ``submit`` +
    # ``kick`` re-arms it — that is how SpecControllers keep their
    # generations flowing without ever calling ``run_all``.

    def kick(self) -> None:
        """(Re)arm the evented pump after submit/fork.  No-op when the
        pump is already active (scheduled or parked on a fetch) or when
        this engine is not loop-clocked."""
        if self.transport is None or self.clocking != "event" or \
                self.transport.cfg.mode != "async":
            return
        p = self._pump
        if p["scheduled"] or p["parked_at"] is not None:
            return
        p["last_step"] = self.loop.now       # step grid restarts here
        self._pump_schedule(0.0)

    def pump_idle(self) -> bool:
        return not self._pump["scheduled"] and \
            self._pump["parked_at"] is None

    def _pump_schedule(self, delay: float) -> None:
        self._pump["scheduled"] = True
        self.loop.schedule(delay, self._pump_step, tag="engine-step")

    def _on_fetch_landed(self, _f) -> None:
        p = self._pump
        if p["parked_at"] is None or p["scheduled"]:
            return
        # wake at the next decode-step grid point at/after the landing
        # (successive addition, exactly the stall path's accumulated
        # k x dt — float-identical timelines)
        dt = self.transport.cfg.decode_step_s
        target = p["last_step"]
        while target < self.loop.now and dt > 0.0:
            target += dt
        self._pump_schedule(max(target - self.loop.now, 0.0))

    def _pump_step(self) -> None:
        with host_span(ENGINE_PUMP):
            plane, loop, p = self.transport, self.loop, self._pump
            p["scheduled"] = False
            p["last_step"] = loop.now
            if p["parked_at"] is not None:
                plane.engine_blocked_s += loop.now - p["parked_at"]
                p["parked_at"] = None
                loop.record("engine", "wake", "")
                self._spans.end(self._park_span)
                self._park_span = -1
            if p["inflight"] is not None:
                # the dispatch launched one decode step ago completes NOW:
                # token appends, retirements and the migrations they
                # trigger land at the step's end, exactly where the stall
                # path's post-tick completion put them
                gens, nxt = p["inflight"]
                p["inflight"] = None
                self._dispatch_complete(gens, nxt)
            pending = [g for g in self._gens.values()
                       if g.status == "pending"]
            if pending and self._free:
                self._admit_all(pending)
            live = [g for g in self._gens.values() if g.status == "running"]
            if live:
                p["inflight"] = (live, self._dispatch_compute(live))
                self._pump_schedule(plane.cfg.decode_step_s)
                return
            if not any(g.status == "pending" for g in self._gens.values()):
                return                              # idle: drained
            if not (self._awaiting_fetch and plane.in_flight):
                return                              # idle: blocked pendings
            # every row is parked on the wire: arm wake-on-resolution for
            # each distinct in-flight fetch job and go idle
            p["parked_at"] = loop.now
            loop.record("engine", "park",
                        f"waiting={len(self._awaiting_fetch)}")
            self._park_span = loop.spans.begin(
                "engine", "park", f"waiting={len(self._awaiting_fetch)}",
                parent=ROOT)
            self._pump_armed = [j for j in self._pump_armed
                                if not (j.done or j.cancelled)]
            for pf in list(self._awaiting_fetch.values()):
                job = pf.job
                if job.done or job.cancelled or \
                        any(j is job for j in self._pump_armed):
                    continue
                self._pump_armed.append(job)
                job.future.add_done_callback(self._on_fetch_landed)

    def close_open_spans(self) -> None:
        """End-of-run span closure.  A pool run stops the shared loop
        the moment its controllers finish, which can freeze virtual
        time MID decode step (the completion event never fires) or
        while the pump is parked on a fetch.  Close the in-flight
        step/park spans at the frozen clock — "time stopped" is not a
        leak — so ``unclosed_spans`` afterwards reports only genuine
        lifecycle bugs.  Idempotent; call before auditing/exporting."""
        self._spans.end(self._step_span, status="eos")
        self._step_span = -1
        self._spans.end(self._park_span, status="eos")
        self._park_span = -1

    def generation(self, gen_id: int) -> Generation:
        return self._gens[gen_id]

    @property
    def loop(self):
        """The shared EventLoop this engine is clocked by (via its
        transport plane); None for un-planed engines."""
        return self.transport.loop if self.transport is not None else None

    @property
    def live(self) -> int:
        return sum(g.status == "running" for g in self._gens.values())

    @property
    def slots_free(self) -> int:
        return len(self._free)

    def admission_headroom(self) -> float:
        """Free-page fraction of the arena — the traffic plane's
        admission-shed signal (DESIGN.md §Traffic-plane).  Admission
        control reads this BEFORE starting a workflow and defers/sheds
        while it is below ``AdmissionConfig.page_headroom``, so the
        pool's own loud failure path (``PagePoolExhausted`` + reclaim)
        stays what it is: an error, not a load-management mechanism."""
        return self.pool.pages_free / max(self.pool.num_pages - 1, 1)

    @property
    def mid_step(self) -> bool:
        """True while a decode dispatch is in flight (compute done,
        completion pending).  Forking an attention-only stack here is
        safe — CoW peels the shared write page; recurrent/dense rows
        are only consistent at step boundaries, so callers gate on
        this."""
        return self._pump["inflight"] is not None

    def cache_bytes(self) -> int:
        """KV bytes actually IN USE: allocated pages (shared pages count
        once — the paged fork economics) plus the fixed-size dense rows
        of recurrent/ring layers.  The arena reservation itself is not
        usage, exactly like an allocator's arena."""
        if self._cache is None:
            return 0
        return self.pool.bytes_in_use + \
            self.pool.dense_bytes(self._cache)
