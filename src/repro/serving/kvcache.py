"""Prefix KV-cache store with local/remote tiers (paper §6.2.3).

*Forking* a generation from a reasoning prefix is structural sharing —
zero copy, zero tokens recomputed: paged engines store PAGE LISTS
(``pagepool.PagedPrefix``), so entries extending the same reasoning
stem share the stem's refcounted pages outright (DESIGN.md
§Paged-store).  What costs memory is keeping suspended prefixes alive
in the serving pool; SpecGen's insight is that the validation/profiling
pool has spare memory that can hold them.  This module implements
exactly that accounting:

  * ``local``  tier = serving-pool memory (budgeted),
  * ``remote`` tier = spare validation/profiling-pool memory (budgeted
    by a byte count, or — transport-aware mode — by the live
    ``RemoteTierPool`` fed from the elastic scheduler's split),
  * on local pressure (byte budget OR the page pool running dry),
    entries MIGRATE local->remote (device-to-device RDMA in the paper
    via Mooncake).  Legacy mode moves bytes synchronously
    (``device_get``/``device_put``); with a ``TransportPlane`` attached
    (serving/transport.py) migrations are ASYNC page-granular streams
    on a modeled bandwidth/latency link, overlapping decode, and the
    remote tier applies BACKPRESSURE (defer / drop / write-through-to-
    host) instead of silently overflowing,
  * a fork that finds its prefix (either tier) restores the cached state
    instead of recomputing prefill — remote hits in async mode return a
    future-backed ``PendingFetch`` the engine awaits only when the
    suffix-prefill actually needs the pages, and a fetch-vs-recompute
    cost model skips fetches slower than re-prefilling.

For recurrent architectures (SSD / RG-LRU) the "KV cache" is the fixed
size recurrence state; entries then snapshot (state, boundary) pairs —
same interface, coarser sharing granularity (DESIGN.md
§Arch-applicability).
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from typing import Any, Dict, Iterable, Optional, Tuple

import numpy as np
import jax


def prefix_key(tokens: Iterable[int]) -> str:
    h = hashlib.blake2b(digest_size=16)
    h.update(np.asarray(list(tokens), np.int32).tobytes())
    return h.hexdigest()


def tree_bytes(tree) -> int:
    return sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree.leaves(tree))


@dataclasses.dataclass
class CacheEntry:
    key: str
    length: int                 # tokens represented by this prefix
    nbytes: int
    tier: str                   # "local" | "remote"
    payload: Any                # cache pytree (device) or host copy
    job: Any = None             # in-flight MigrationJob / FetchJob
    tier_reserved: bool = False  # holds a RemoteTierPool reservation


@dataclasses.dataclass
class CacheStats:
    hits_local: int = 0
    hits_remote: int = 0
    misses: int = 0
    tokens_reused: int = 0
    tokens_recomputed: int = 0
    migrations: int = 0
    pages_migrated: int = 0     # paged payloads' pages moved host-side
    restores: int = 0
    bytes_migrated: int = 0
    evictions_local: int = 0
    evictions_remote: int = 0
    # paged payloads (serving.pagepool.PagedPrefix) only:
    pages_stored: int = 0       # pages referenced by entries at put time
    pages_shared: int = 0       # of those, pages some OTHER holder also
    #                             referenced (live row, sibling entry) —
    #                             the store-level structural sharing a
    #                             dense-row store cannot have
    # transport-aware mode only:
    fetches_pending: int = 0    # remote hits answered with a PendingFetch
    recomputes_chosen: int = 0  # cost model preferred prefill over fetch
    migrations_deferred: int = 0   # backpressure: kept local for now
    migrations_defer_aged: int = 0  # defer aging bound hit: fell back
    migrations_dropped: int = 0    # backpressure: evicted (LRU-skip)
    migrations_host: int = 0       # backpressure: write-through-to-host

    @property
    def hits(self) -> int:
        return self.hits_local + self.hits_remote


class PendingFetch:
    """A remote hit in flight: the payload the engine will acquire once
    the streamed restore lands.  ``ready`` flips when the tail chunk
    arrives; ``retain``/``release_waiter`` track which admissions are
    awaiting it — when the last waiter walks away (iteration-boundary
    abort, cancelled generation) the fetch itself is cancelled and its
    callbacks NEVER fire (transport abort contract).

    The handle pins the JOB it was issued for (not ``entry.job``): if
    the fetch is torn down underneath it — a re-put of the same key
    disposes the entry, a sibling waiter aborted — ``cancelled`` flips
    and the holder must re-probe the store instead of acquiring a
    host-side payload."""

    __slots__ = ("store", "entry", "job")

    def __init__(self, store: "PrefixCacheStore", entry: CacheEntry):
        self.store = store
        self.entry = entry
        self.job = entry.job

    @property
    def ready(self) -> bool:
        return self.job.done

    @property
    def cancelled(self) -> bool:
        return self.job.cancelled

    @property
    def payload(self) -> Any:
        return self.entry.payload

    @property
    def length(self) -> int:
        return self.entry.length

    def add_done_callback(self, fn) -> None:
        self.job.future.add_done_callback(fn)

    def retain(self, token) -> None:
        self.job.waiters.add(token)

    def release_waiter(self, token) -> None:
        self.job.waiters.discard(token)
        if not self.job.waiters and not self.job.done \
                and not self.job.cancelled \
                and self.entry.job is self.job:
            self.store._cancel_fetch(self.entry)


class PrefixCacheStore:
    """Two-tier LRU prefix store with migrate-on-pressure semantics.

    ``transport`` (a ``serving.transport.TransportPlane``) switches the
    tier boundary from synchronous ``device_get``/``device_put`` to the
    modeled RDMA link: ``mode="sync"`` keeps blocking moves but prices
    them; ``mode="async"`` streams migrations/fetches page-granularly,
    overlapping decode.  ``transport=None`` (default) is the legacy
    path, bit-for-bit unchanged."""

    def __init__(self, local_budget_bytes: int,
                 remote_budget_bytes: int = 0,
                 migrate_on_pressure: bool = True,
                 transport: Any = None):
        self.local_budget = local_budget_bytes
        self.remote_budget = remote_budget_bytes
        self.migrate_on_pressure = migrate_on_pressure
        self.plane = transport
        self._local: "OrderedDict[str, CacheEntry]" = OrderedDict()
        self._remote: "OrderedDict[str, CacheEntry]" = OrderedDict()
        self.stats = CacheStats()
        # defer aging (TransportConfig.defer_max_puts / defer_max_s):
        # consecutive deferred puts since the tier last had headroom,
        # and when the local tier first went over budget
        self._defers_since_headroom = 0
        self._over_budget_at: Optional[float] = None

    # ------------------------------------------------------------ internals
    @property
    def _async(self) -> bool:
        return self.plane is not None and self.plane.cfg.mode == "async"

    def _tier_bytes(self, tier: "OrderedDict[str, CacheEntry]") -> int:
        return sum(e.nbytes for e in tier.values())

    @property
    def local_bytes(self) -> int:
        return self._tier_bytes(self._local)

    @property
    def remote_bytes(self) -> int:
        return self._tier_bytes(self._remote)

    @property
    def fetches_in_flight(self) -> int:
        return sum(1 for e in self._remote.values()
                   if e.job is not None and e.job.kind == "fetch"
                   and not e.job.done)

    def _dispose(self, entry_or_payload) -> None:
        """True eviction: paged payloads must drop their page refs (the
        pool reclaims unshared pages); plain pytrees just get GC'd."""
        payload = entry_or_payload
        if isinstance(entry_or_payload, CacheEntry):
            entry = entry_or_payload
            payload = entry.payload
            if entry.job is not None:       # mid-migration disposal
                self._cancel_job(entry)
            if entry.tier_reserved:
                self.plane.tier.release(entry.nbytes)
                entry.tier_reserved = False
        release = getattr(payload, "release", None)
        if release is not None:
            release()

    def _cancel_job(self, entry: CacheEntry) -> None:
        job = entry.job
        entry.job = None
        job.cancel()
        if job.kind == "fetch":
            if hasattr(entry.payload, "fetch_abort"):
                entry.payload.fetch_abort()
        elif hasattr(entry.payload, "migrate_out_abort"):
            # chunks past next_chunk never transferred: their pages
            # (PAGE index = the pending chunk's lo bound) still hold
            # device refs; landed chunks already released theirs
            moved_upto = (job.chunks[job.next_chunk][0]
                          if job.next_chunk < len(job.chunks)
                          else len(entry.payload._out_ids))
            entry.payload.migrate_out_abort(moved_upto)

    # --------------------------------------------------- remote-tier gates
    def _remote_budget_ok(self, nbytes: int) -> bool:
        """Legacy byte-budget gate (no transport plane)."""
        return self.remote_budget > 0 and \
            nbytes + self.remote_bytes <= self.remote_budget

    def _migrate_or_evict(self, entry: CacheEntry, *,
                          urgent: bool = False) -> str:
        """Move a local entry across the tier boundary, or apply the
        backpressure policy.  Returns "migrated" | "deferred" |
        "evicted".  ``urgent`` (page-pool pressure) forces a blocking
        move even in async mode — the pool needs the pages NOW."""
        if self.plane is None:
            if self._remote_budget_ok(entry.nbytes):
                self._to_remote_sync(entry)
                return "migrated"
            self.stats.evictions_local += 1
            self._dispose(entry)
            return "evicted"
        # transport-aware: the RemoteTierPool is the capacity gate
        if not self.plane.tier.reserve(entry.nbytes):
            policy = self.plane.cfg.backpressure
            if policy == "defer" and not urgent:
                if not self._defer_aged():
                    self._note_defer()
                    self.stats.migrations_deferred += 1
                    self.plane.migrations_deferred += 1
                    return "deferred"
                # aging bound hit (K deferred puts or T seconds over
                # budget): stop waiting for tier headroom and apply the
                # configured fallback to this entry
                self.stats.migrations_defer_aged += 1
                self.plane.migrations_defer_aged += 1
                policy = self.plane.cfg.defer_fallback
            if policy == "host" and self._remote_budget_ok(entry.nbytes):
                # write-through-to-host: bypass the modeled link and the
                # tier budget; plain host memory takes the entry
                self.stats.migrations_host += 1
                self.plane.migrations_host += 1
                self._to_remote_sync(entry)
                return "migrated"
            self.stats.migrations_dropped += 1
            self.plane.migrations_dropped += 1
            self.stats.evictions_local += 1
            self._dispose(entry)
            return "evicted"
        # reservation granted: remote headroom returned — aging resets
        self._defers_since_headroom = 0
        self._over_budget_at = None
        entry.tier_reserved = True
        if self._async and not urgent:
            self._to_remote_async(entry)
        else:
            self.plane.migrations_started += 1
            self.plane.migrations_done += 1
            self.plane.transfer_sync(entry.nbytes, tag="mig-out")
            self._to_remote_sync(entry)
        return "migrated"

    def _defer_aged(self) -> bool:
        """Has the bounded-defer policy aged out?  True once K puts have
        deferred since the tier last had headroom, or the local tier has
        sat over budget for T virtual seconds (0 = unbounded)."""
        cfg = self.plane.cfg
        if cfg.defer_max_puts > 0 and \
                self._defers_since_headroom >= cfg.defer_max_puts:
            return True
        if cfg.defer_max_s > 0.0 and self._over_budget_at is not None \
                and self.plane.loop.now - self._over_budget_at \
                >= cfg.defer_max_s:
            return True
        return False

    def _note_defer(self) -> None:
        self._defers_since_headroom += 1
        if self._over_budget_at is None:
            self._over_budget_at = self.plane.loop.now

    # ----------------------------------------------------- migration paths
    def _to_remote_sync(self, entry: CacheEntry) -> None:
        """Blocking move of the payload out of serving memory into the
        pool store (``device_get`` stands in for Mooncake RDMA on this
        container).  Paged payloads move PAGES — page contents go
        host-side and the device pages are released immediately — not
        whole rows."""
        if hasattr(entry.payload, "migrate_out"):
            entry.payload = entry.payload.migrate_out()
            self.stats.pages_migrated += entry.payload.num_pages
        else:
            entry.payload = jax.tree.map(
                lambda l: np.asarray(jax.device_get(l)), entry.payload)
        entry.tier = "remote"
        self._remote[entry.key] = entry
        self._remote.move_to_end(entry.key)
        self.stats.migrations += 1
        self.stats.bytes_migrated += entry.nbytes

    def _to_remote_async(self, entry: CacheEntry) -> None:
        """Streamed migrate-out: the entry lands in the remote tier NOW
        (lookups see it there) while its page chunks ride the link;
        each chunk's device pages are released as its transfer
        completes."""
        from repro.serving.transport import MigrationJob

        plane, payload = self.plane, entry.payload
        entry.tier = "remote"
        self._remote[entry.key] = entry
        self._remote.move_to_end(entry.key)
        self.stats.migrations += 1
        if hasattr(payload, "migrate_out_begin"):
            if hasattr(payload, "wire_compress"):
                payload.wire_compress = bool(plane.cfg.compress)
            n_pages = payload.migrate_out_begin()
            page_bytes = self._wire_page_bytes(payload)
            chunks = self._chunks(entry.nbytes, n_pages, page_bytes)
            self._note_wire_compression(payload, n_pages, chunks)

            def mover(lo, hi):
                payload.migrate_out_chunk(lo, hi)
                self.stats.pages_migrated += hi - lo

            def on_done():
                entry.payload = payload.migrate_out_finish()
                entry.job = None
                self.stats.bytes_migrated += entry.nbytes
        else:
            chunks = [(0, 1, entry.nbytes)]

            def mover(lo, hi):
                pass                        # moved wholesale at the end

            def on_done():
                entry.payload = jax.tree.map(
                    lambda l: np.asarray(jax.device_get(l)), entry.payload)
                entry.job = None
                self.stats.bytes_migrated += entry.nbytes
        entry.job = MigrationJob(plane, entry, chunks, mover, on_done)

    def _wire_page_bytes(self, payload) -> int:
        """Per-page bytes a streamed transfer of this payload puts on
        the modeled link: the raw arena page, or the int8-quantized
        wire format when the payload migrated out compressed
        (TransportConfig.compress)."""
        pool = payload.engine.pool
        if getattr(payload, "wire_compress", False):
            return pool.compressed_page_bytes
        return pool.page_bytes

    def _note_wire_compression(self, payload, n_pages: int,
                               chunks) -> None:
        """Account compressed wire traffic on the plane: bytes actually
        put on the link, and the raw-minus-wire savings."""
        if not getattr(payload, "wire_compress", False):
            return
        raw = n_pages * payload.engine.pool.page_bytes
        wire = sum(c[2] for c in chunks)
        self.plane.wire_bytes_compressed += wire
        self.plane.wire_bytes_saved += max(raw - wire, 0)

    def _chunks(self, nbytes: int, n_pages: int, page_bytes: int):
        """[(lo, hi, nbytes)] page-index ranges for streamed transfer."""
        per = max(1, self.plane.cfg.pages_per_transfer)
        out, lo = [], 0
        while lo < n_pages:
            hi = min(lo + per, n_pages)
            out.append((lo, hi, (hi - lo) * page_bytes))
            lo = hi
        return out or [(0, 0, nbytes)]

    # -------------------------------------------------------- restore paths
    def _restore_payload(self, entry: CacheEntry):
        if entry.tier == "remote":
            self.stats.restores += 1
            self.stats.bytes_migrated += entry.nbytes
            if self.plane is not None:
                self.plane.transfer_sync(entry.nbytes, tag="fetch")
                self.plane.fetches_started += 1
                self.plane.fetches_done += 1
            if hasattr(entry.payload, "migrate_in"):
                return entry.payload.migrate_in()
            return jax.tree.map(jax.device_put, entry.payload)
        return entry.payload

    def _start_fetch(self, entry: CacheEntry) -> Optional[PendingFetch]:
        """Begin a streamed restore; None => fall back to recompute
        (destination pages unavailable)."""
        from repro.serving.transport import FetchJob

        payload = entry.payload
        if hasattr(payload, "fetch_begin"):
            try:
                payload.fetch_begin()
            except Exception:               # page pool dry: recompute
                return None
            page_bytes = self._wire_page_bytes(payload)
            chunks = self._chunks(entry.nbytes, payload.num_pages,
                                  page_bytes)
            self._note_wire_compression(payload, payload.num_pages,
                                        chunks)

            def uploader(lo, hi):
                payload.fetch_chunk(lo, hi)

            def on_done():
                entry.payload = payload.fetch_finish()
                self._fetch_landed(entry)
        else:
            chunks = [(0, 1, entry.nbytes)]

            def uploader(lo, hi):
                pass

            def on_done():
                entry.payload = jax.tree.map(jax.device_put, entry.payload)
                self._fetch_landed(entry)
        entry.job = FetchJob(self.plane, entry, chunks, uploader, on_done)
        return PendingFetch(self, entry)

    def _fetch_landed(self, entry: CacheEntry) -> None:
        """Tail chunk arrived: the entry is local again; its remote-tier
        reservation frees (which may unblock deferred migrations)."""
        entry.job = None
        entry.tier = "local"
        self._remote.pop(entry.key, None)
        self.stats.restores += 1
        self.stats.bytes_migrated += entry.nbytes
        if entry.tier_reserved:
            self.plane.tier.release(entry.nbytes)
            entry.tier_reserved = False
        # rebalance around the restored entry, never evicting it (same
        # contract as the synchronous remote-hit path): it joins local
        # only AFTER the budget pass
        self._evict_until(self._local, self.local_budget, migrating=True)
        self._local[entry.key] = entry
        self._local.move_to_end(entry.key)

    def _cancel_fetch(self, entry: CacheEntry) -> None:
        """Abort an in-flight fetch (last waiter gone): transfers are
        cancelled — no callback fires — uploaded destination pages are
        released, and the entry stays restorable in the remote tier."""
        if entry.job is None:
            return
        self._cancel_job(entry)

    # ------------------------------------------------------------ eviction
    def _evict_until(self, tier: "OrderedDict[str, CacheEntry]",
                     budget: int, migrating: bool) -> None:
        while self._tier_bytes(tier) > budget and tier:
            key, entry = tier.popitem(last=False)       # LRU
            if migrating and self.migrate_on_pressure and \
                    entry.job is None:
                outcome = self._migrate_or_evict(entry)
                if outcome == "deferred":
                    # backpressure: the remote tier is full.  The entry
                    # stays local (still LRU-first) and local runs over
                    # budget until tier headroom returns — deliberate:
                    # never silently overflow the remote tier.
                    tier[key] = entry
                    tier.move_to_end(key, last=False)
                    return
            elif migrating:
                self.stats.evictions_local += 1
                self._dispose(entry)
            else:
                self.stats.evictions_remote += 1
                self._dispose(entry)

    # ----------------------------------------------------------------- API
    def put(self, tokens, payload, *, length: Optional[int] = None) -> str:
        key = prefix_key(tokens)
        nbytes = getattr(payload, "nbytes", None)
        if nbytes is None:
            nbytes = tree_bytes(payload)
        old = self._local.pop(key, None) or self._remote.pop(key, None)
        if old is not None and old.payload is not payload:
            self._dispose(old)          # re-put: drop the stale entry
        if hasattr(payload, "shared_page_count"):
            self.stats.pages_stored += payload.num_pages
            self.stats.pages_shared += payload.shared_page_count()
        entry = CacheEntry(key=key, length=length or len(list(tokens)),
                           nbytes=nbytes, tier="local", payload=payload)
        self._local[key] = entry
        self._local.move_to_end(key)
        self._evict_until(self._local, self.local_budget, migrating=True)
        return key

    def get(self, tokens) -> Tuple[Optional[Any], int]:
        """Return (payload-on-device | PendingFetch | None, length)."""
        key = prefix_key(tokens)
        got = self._lookup(key)
        if got is not None:
            return got
        self.stats.misses += 1
        return None, 0

    def get_longest(self, tokens) -> Tuple[Optional[Any], int]:
        """Longest cached prefix of ``tokens`` (either tier).

        Serving admission uses this: a generation whose exact prompt is
        not cached can still reuse a shorter reasoning prefix and
        suffix-prefill only the divergent remainder (paper §6.2.3 —
        fork-from-reasoning-prefix).  Counts one hit or one miss total,
        regardless of how many candidate lengths were probed.  In
        transport-aware async mode a remote hit comes back as a
        ``PendingFetch`` — await it only when the pages are needed.
        """
        toks = list(tokens)
        lengths = sorted(
            {e.length for tier in (self._local, self._remote)
             for e in tier.values() if e.length <= len(toks)},
            reverse=True)
        for ln in lengths:
            got = self._lookup(prefix_key(toks[:ln]))
            if got is not None:
                return got
        self.stats.misses += 1
        return None, 0

    def _lookup(self, key: str) -> Optional[Tuple[Any, int]]:
        if key in self._local:
            e = self._local[key]
            self._local.move_to_end(key)
            self.stats.hits_local += 1
            self.stats.tokens_reused += e.length
            return e.payload, e.length
        if key in self._remote:
            e = self._remote[key]
            if self._async:
                return self._lookup_remote_async(e)
            self._remote.pop(key)
            try:
                payload = self._restore_payload(e)
            except Exception:
                self._remote[key] = e       # e.g. page-pool exhaustion:
                raise                       # keep the entry restorable
            e.payload, e.tier = payload, "local"
            if e.tier_reserved:
                self.plane.tier.release(e.nbytes)
                e.tier_reserved = False
            # rebalance to budget around the restored entry but NEVER
            # evict it in this call: migrating it back out would MUTATE
            # the payload object the caller is about to acquire (paged
            # payloads release their device pages on migrate_out).  It
            # may leave local transiently over budget; the next put()
            # evicts it normally, after the caller holds its own refs.
            self._evict_until(self._local, self.local_budget, migrating=True)
            self._local[key] = e
            self._local.move_to_end(key)
            self.stats.hits_remote += 1
            self.stats.tokens_reused += e.length
            return payload, e.length
        return None

    def _lookup_remote_async(self, e: CacheEntry
                             ) -> Optional[Tuple[Any, int]]:
        """Remote hit under the async plane: cost-model the fetch, and
        answer with a future-backed PendingFetch instead of blocking."""
        job = e.job
        if job is not None and job.kind == "fetch":
            # a fetch is already streaming: join it (no double count)
            return PendingFetch(self, e), e.length
        if job is not None:
            # still migrating OUT: neither resident nor restorable yet —
            # recomputing beats waiting for the turnaround
            self.stats.recomputes_chosen += 1
            self.plane.recomputes_chosen += 1
            return None
        payload = e.payload
        n_pages = getattr(payload, "num_pages", 0)
        page_bytes = (self._wire_page_bytes(payload)
                      if hasattr(payload, "engine") else 0)
        if not self.plane.prefer_fetch(e.nbytes, e.length, n_pages,
                                       page_bytes):
            self.stats.recomputes_chosen += 1
            self.plane.recomputes_chosen += 1
            return None
        pf = self._start_fetch(e)
        if pf is None:                      # no destination pages
            self.stats.recomputes_chosen += 1
            self.plane.recomputes_chosen += 1
            return None
        self._remote.move_to_end(e.key)
        self.stats.hits_remote += 1
        self.stats.tokens_reused += e.length
        self.stats.fetches_pending += 1
        return pf, e.length

    def note_recompute(self, tokens_recomputed: int) -> None:
        self.stats.tokens_recomputed += tokens_recomputed

    def suspend(self, tokens) -> bool:
        """Explicitly migrate a prefix to the remote tier (paper: local
        serving memory approaching capacity)."""
        key = prefix_key(tokens)
        e = self._local.pop(key, None)
        if e is None:
            return False
        outcome = self._migrate_or_evict(e)
        if outcome == "deferred":
            self._local[key] = e
            self._local.move_to_end(key, last=False)
            return False
        if outcome == "migrated":
            if self.plane is None:
                self._evict_until(self._remote, self.remote_budget,
                                  migrating=False)
            return True
        return False

    def shed_oldest(self) -> bool:
        """Pressure hook: drop the LRU *local* entry's device residency
        — migrate it remote when it fits (restorable), else evict it.
        The paged engine calls this when the page pool runs dry, so
        stored prefixes yield pages to live generations instead of
        starving admission.  Page-pool pressure is URGENT: the pages
        must free NOW, so even the async plane moves these blocking
        (charging the link inline).  Returns False once local is
        empty."""
        if not self._local:
            return False
        _key, entry = self._local.popitem(last=False)
        self._migrate_or_evict(entry, urgent=True)
        return True

    def flush_to_remote(self) -> int:
        """Migrate every local entry to the remote tier (operator-driven
        memory-pressure drill; entries that don't fit remotely are
        evicted).  An EXPLICIT flush migrates even when automatic
        migrate-on-pressure is disabled.  Returns entries migrated."""
        before = self.stats.migrations
        prev, self.migrate_on_pressure = self.migrate_on_pressure, True
        try:
            while self._local:
                _key, entry = self._local.popitem(last=False)
                self._migrate_or_evict(entry, urgent=True)
        finally:
            self.migrate_on_pressure = prev
        return self.stats.migrations - before

    def __contains__(self, tokens) -> bool:
        key = prefix_key(tokens)
        return key in self._local or key in self._remote

    def __len__(self) -> int:
        return len(self._local) + len(self._remote)
