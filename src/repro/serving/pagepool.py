"""Paged KV-cache pool: block tables, refcounts, copy-on-write pages.

The engine's fork economics (DESIGN.md §Paged-KV) rest on this module:
instead of one dense ``(max_batch, max_len)`` K/V row per generation,
every attention layer owns a global arena of ``num_pages`` pages of
``page_size`` key slots, and each generation holds a *block table* — an
ordered list of page ids covering positions ``[0, pos)``.  Forking a
speculative child is then a block-table copy plus refcount bumps: ZERO
KV bytes move at fork time.  Pages copy lazily, only when a writer is
about to scatter into a page some other holder (parent, sibling fork,
or a stored prefix) still references.

``PagePool`` itself is a host-side accountant (refcounts, free list,
copy/write counters) plus a factory of jitted arena ops; the arena
arrays themselves live in the engine's donated cache pytree so every
mutation is an in-place XLA scatter, never a pool-wide copy.  Page 0 is
the permanently-empty *null page*: block tables are padded with it, so
gathers of short tables bring only ``EMPTY_SLOT`` positions, which the
unified attention mask (models.layers.attend) discards exactly.

Recurrent state (SSD / RG-LRU) and ring-buffered local-attention state
are fixed-size per generation — they "degenerate to one page" and stay
slot-indexed dense rows (DESIGN.md §Arch-applicability).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.spans import (POOL_READ_COPY, POOL_READ_GATHER,
                              STORE_MIGRATE_CHUNK, host_span)
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.models.layers import EMPTY_SLOT


class PagePoolExhausted(RuntimeError):
    """Raised instead of silently scattering out of the arena."""


def autotune_pool(fork_depth_hist, *, max_batch: int, max_len: int,
                  page_sizes: Sequence[int] = (8, 16, 32, 64)
                  ) -> Dict[str, float]:
    """ROADMAP autotuner: size the arena from OBSERVED fork depth.

    The default pool (``num_pages = 1 + 2*B*pages_per_row``) budgets
    every slot fully unshared plus the same again for stored prefixes —
    safe, but blind to how forky the workload actually is.  The
    fork-depth histogram (``core.metrics`` "fork_depth", observed at
    every fork) gives the p95 concurrent speculative generations per
    workflow.  Deeper forking means (a) more page SHARING — forks hold
    the parent's prefix pages by refcount, so their private footprint
    is just the decoded suffix — and (b) more copy-on-write boundary
    traffic — each fork eventually copies the one partially-shared
    page, so large pages duplicate more prefix slots per copy.

    Deterministic pure rules:
      * ``page_size``: largest candidate <= max_len / (4 * depth_p95) —
        deep forking drives pages smaller (cheap CoW boundary page);
        shallow workloads keep big pages (short block tables);
      * ``num_pages``: 1 (null page) + B*pages_per_row live rows, plus
        a prefix/CoW allowance scaling with observed depth instead of
        the blanket 2x — ceil(B * (0.5 + depth_p95/4)) rows' worth,
        clamped to [0.5x, 2x] of the live budget.
    """
    depth = 1.0
    if fork_depth_hist is not None and getattr(fork_depth_hist, "total", 0):
        depth = max(1.0, float(fork_depth_hist.percentile(0.95)))
    target = max_len / (4.0 * depth)
    cands = sorted(page_sizes)
    page_size = cands[0]
    for c in cands:
        if c <= target:
            page_size = c
    ppr = _ceil_div(max_len, page_size)
    live = max_batch * ppr
    allowance = int(math.ceil(max_batch * (0.5 + depth / 4.0))) * ppr
    allowance = min(max(allowance, (live + 1) // 2), 2 * live)
    return {"page_size": page_size, "num_pages": 1 + live + allowance,
            "fork_depth_p95": depth}


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _pow2_pad(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


class PagePool:
    """Page accounting + jitted arena ops for one model's decode cache.

    The cache pytree this pool manages is a per-layer list:

      * attention / MoE layers: ``{"k","v"}`` arenas of shape
        ``(num_pages, page_size, KV, Dh)`` and a ``(num_pages,
        page_size)`` ``kv_pos`` arena (EMPTY_SLOT = unwritten);
      * every other kind (local ring, SSD, RG-LRU): the dense
        ``(max_batch, ...)`` per-slot state from ``T.cache_spec``.
    """

    def __init__(self, cfg: ModelConfig, *, max_batch: int, max_len: int,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 cache_dtype: str = "", layout: str = "layers"):
        assert page_size > 0
        assert layout in ("layers", "fused")
        self.layout = layout
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.page_size = page_size
        self.pages_per_row = _ceil_div(max_len, page_size)
        if num_pages is None:
            # enough for every slot to run unshared to max_len, plus the
            # same again for stored prefixes; sharing means real usage
            # sits far below this (and it is 2x pages, not 2x rows, that
            # an operator tunes — the max_len*max_batch preallocation is
            # gone)
            num_pages = 1 + 2 * max_batch * self.pages_per_row
        self.num_pages = num_pages
        self.cache_dtype_str = cache_dtype
        self.dtype = (jnp.dtype(cache_dtype) if cache_dtype
                      else jnp.dtype(cfg.dtype))
        kinds = cfg.layer_kinds()
        self._attn_set = {i for i, k in enumerate(kinds)
                          if k in ("attn", "moe")}
        self.dense_layers = [i for i in range(len(kinds))
                             if i not in self._attn_set]
        # fused layout (DESIGN.md §Sharded-scan-decode): the cache is the
        # scan-decode state dict — ONE arena whose page axis concatenates
        # the per-layer arenas (rank r's slab is [r*num_pages,
        # (r+1)*num_pages)), dense state stacked per pattern position.
        # Host accounting stays in LOGICAL pages; ops translate.
        self._A = len(self._attn_set)
        self._ranks = sorted(self._attn_set)
        self._dense_loc: Dict[int, tuple] = {}
        if layout == "fused":
            _, pat = T._pattern(cfg)
            n_units = len(kinds) // len(pat)
            for li in self.dense_layers:
                if li < n_units * len(pat):
                    it, j = divmod(li, len(pat))
                    self._dense_loc[li] = ("u", j, it)
                else:
                    self._dense_loc[li] = ("t", li - n_units * len(pat))
        kv_bytes = (page_size * cfg.num_kv_heads * cfg.head_dim
                    * self.dtype.itemsize)
        self.page_bytes = len(self._attn_set) * (2 * kv_bytes
                                                 + page_size * 4)
        # wire bytes of one page under int8 K/V quantization
        # (distributed.compression.compress_kv_pages): K and V become
        # one byte per element plus a 4-byte per-page scale each;
        # kv_pos stays int32.  Used by the store to price streamed
        # transfers when TransportConfig.compress is on.
        kv_q = page_size * cfg.num_kv_heads * cfg.head_dim
        self.compressed_page_bytes = len(self._attn_set) * (
            2 * (kv_q + 4) + page_size * 4)
        # ---- host-side accounting.  refcount[p] == 0 <=> p is free.
        self.refcount = np.zeros((num_pages,), np.int64)
        self.refcount[0] = 1                    # null page: never handed out
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._scrub_pending: List[int] = []     # reused pages, stale kv_pos
        self._dirty: set = set()                # freed-with-content pages
        self.page_copies = 0                    # CoW page copies (device)
        self.page_writes = 0                    # pages scattered into arenas
        self.host_reads = 0                     # read_pages calls
        self.host_copies = 0                    # arrays they copied to host
        self.reclaim = None                     # pressure hook: (need)->None
        # ---- jitted arena ops (memoized executables live on the pool);
        # each op has a per-layer-list impl and a fused-state impl — the
        # wrappers keep ONE host-facing contract (logical page ids, the
        # per-attention-layer host payload / dense-row formats) so the
        # engine, prefix store and transport never see the layout
        fused = layout == "fused"
        self._scrub_op = jax.jit(
            self._scrub_fused_impl if fused else self._scrub_impl,
            donate_argnums=(0,))
        self._copy_op = jax.jit(
            self._copy_fused_impl if fused else self._copy_impl,
            donate_argnums=(0,))
        self._gather_op = jax.jit(
            self._gather_fused_impl if fused else self._gather_impl)
        self._write_op = jax.jit(
            self._write_fused_impl if fused else self._write_impl,
            static_argnums=(3,), donate_argnums=(0,))
        self._read_op = jax.jit(
            self._read_fused_impl if fused else self._read_impl)
        self._upload_op = jax.jit(
            self._upload_fused_impl if fused else self._upload_impl,
            donate_argnums=(0,))
        self._dense_copy_op = jax.jit(
            self._dense_copy_fused_impl if fused else self._dense_copy_impl,
            donate_argnums=(0,))
        self._dense_admit_op = jax.jit(
            self._dense_admit_fused_impl if fused
            else self._dense_admit_impl, donate_argnums=(0,))

    # ------------------------------------------------------------- layout
    def init_cache(self):
        """Arenas for attention layers; dense per-slot rows otherwise.

        ``layout="fused"`` returns the scan-decode state dict instead of
        the per-layer list (``T.stack_decode_state`` of the same
        arrays): one fused arena, pattern-stacked dense state."""
        cfg, P, ps = self.cfg, self.num_pages, self.page_size
        spec = T.cache_spec(cfg, self.max_batch, self.max_len,
                            self.cache_dtype_str)
        cache = []
        for i, s in enumerate(spec):
            if i in self._attn_set:
                cache.append({
                    "k": jnp.zeros((P, ps, cfg.num_kv_heads, cfg.head_dim),
                                   self.dtype),
                    "v": jnp.zeros((P, ps, cfg.num_kv_heads, cfg.head_dim),
                                   self.dtype),
                    "kv_pos": jnp.full((P, ps), EMPTY_SLOT, jnp.int32),
                })
            else:
                cache.append({k: T._init_leaf(k, shape, dt)
                              for k, (shape, dt) in s.items()})
        if self.layout == "fused":
            return T.stack_decode_state(cfg, cache, paged=True)
        return cache

    def cache_logical_axes(self):
        """Logical-axis tree congruent with ``init_cache()``'s pytree
        (for Engine(mesh=...) placement under DECODE_RULES): arenas
        shard their page axis over 'kv_pages', dense rows their slot
        axis over 'act_batch'; everything else replicates."""
        arena_ax = {"k": ("kv_pages", None, "act_kv", None),
                    "v": ("kv_pages", None, "act_kv", None),
                    "kv_pos": ("kv_pages", None)}
        la = T.cache_logical_axes(self.cfg)
        if self.layout != "fused":
            return [arena_ax if i in self._attn_set else la[i]
                    for i in range(len(la))]
        kinds = self.cfg.layer_kinds()
        _, pat = T._pattern(self.cfg)
        n_units = len(kinds) // len(pat)

        def stacked(ax):        # leading pattern-unit axis: replicated
            return {k: (None,) + tuple(v) for k, v in ax.items()}

        units = tuple(
            None if T._paged_kind(pat[j]) else stacked(la[j])
            for j in range(len(pat))) if n_units else ()
        tail = tuple(
            None if T._paged_kind(kinds[n_units * len(pat) + t])
            else la[n_units * len(pat) + t]
            for t in range(len(kinds) - n_units * len(pat)))
        arena = arena_ax if self._A else None
        return {"units": units, "tail": tail, "arena": arena}

    def cache_shardings(self, ctx, cache):
        """NamedSharding tree congruent with ``cache`` under ``ctx``
        (explicit walk: the fused state's None/empty containers would
        fool generic axes-leaf detection)."""
        def walk(c, a):
            if c is None:
                return None
            if isinstance(c, dict):
                return {k: walk(c[k], a[k]) for k in c}
            if isinstance(c, (list, tuple)):
                return type(c)(walk(x, y) for x, y in zip(c, a))
            return ctx.named(a, c.shape)
        return walk(cache, self.cache_logical_axes())

    def _fused_ids(self, pages) -> np.ndarray:
        """Logical page ids -> physical fused-arena ids, one row per
        attention-layer rank (slab r owns [r*P, (r+1)*P)).  The logical
        drop pad ``num_pages`` must NOT be offset per rank — r*P +
        num_pages lands inside slab r+1 — so it maps straight to the
        fused drop index A*P."""
        pg = np.asarray(pages, np.int64)
        offs = (np.arange(self._A, dtype=np.int64)
                * self.num_pages).reshape((self._A,) + (1,) * pg.ndim)
        return np.where(pg < self.num_pages, pg + offs,
                        self._A * self.num_pages)

    # -------------------------------------------------------- accounting
    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - 1 - len(self._free)

    @property
    def bytes_in_use(self) -> int:
        return self.pages_in_use * self.page_bytes

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free) and self.reclaim is not None:
            # local pressure: let the owner shed stored prefixes (the
            # engine migrates LRU store entries to the remote tier,
            # whose budget is host memory, not pool pages)
            self.reclaim(n)
        if n > len(self._free):
            raise PagePoolExhausted(
                f"page pool exhausted: need {n} page(s) but only "
                f"{len(self._free)} of {self.num_pages - 1} are free "
                f"({self.pages_in_use} in use across live generations and "
                f"stored prefixes). Retire/cancel generations, shrink the "
                f"prefix store budgets, or raise Engine(num_pages=...).")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self.refcount[p] = 1
            if p in self._dirty:
                self._dirty.discard(p)
                self._scrub_pending.append(p)
        return pages

    def _unschedule_scrub(self, pages: Sequence[int]) -> None:
        """A full-page overwrite (CoW copy, prefill write, remote
        upload) makes the pending scrub not just redundant but WRONG —
        flushed later it would erase the new kv_pos."""
        if self._scrub_pending:
            drop = set(pages)
            self._scrub_pending = [p for p in self._scrub_pending
                                   if p not in drop]

    def ref(self, pages: Sequence[int]) -> None:
        for p in pages:
            assert self.refcount[p] > 0, f"ref of free page {p}"
            self.refcount[p] += 1

    def release(self, pages: Sequence[int]) -> None:
        for p in pages:
            assert self.refcount[p] > 0, f"double release of page {p}"
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                self._free.append(p)
                self._dirty.add(p)

    # -------------------------------------------------------- arena ops
    # Every op takes the engine's cache pytree and returns the updated
    # one (mutating ops donate, so the arenas update in place on device).

    def flush_scrub(self, cache):
        """Reset kv_pos of reallocated pages BEFORE they are attended.

        Freshly reallocated decode-append pages get one slot written per
        step; the other slots must read EMPTY, not whatever a previous
        owner left behind.  Must run before copies/writes of the same
        step (a scrub after a CoW copy would erase it)."""
        if not self._scrub_pending:
            return cache
        pages = self._scrub_pending
        self._scrub_pending = []
        width = _pow2_pad(len(pages))
        arr = np.full((width,), self.num_pages, np.int64)   # pad -> drop
        arr[: len(pages)] = pages
        if self.layout == "fused":
            arr = self._fused_ids(arr).ravel()
        return self._scrub_op(cache, jnp.asarray(arr))

    def _scrub_impl(self, cache, pages):
        out = []
        for i, c in enumerate(cache):
            if i in self._attn_set:
                c = dict(c)
                c["kv_pos"] = c["kv_pos"].at[pages].set(
                    EMPTY_SLOT, mode="drop")
            out.append(c)
        return out

    def _scrub_fused_impl(self, cache, pages):
        if cache["arena"] is None:     # dense-only stack: pages are
            return cache               # block-table bookkeeping only
        ar = dict(cache["arena"])
        ar["kv_pos"] = ar["kv_pos"].at[pages].set(EMPTY_SLOT, mode="drop")
        return dict(cache, arena=ar)

    def copy_pages(self, cache, srcs: Sequence[int], dsts: Sequence[int]):
        """Batched CoW page copies (one scatter per arena leaf)."""
        if not srcs:
            return cache
        assert len(srcs) == len(dsts)
        width = _pow2_pad(max(len(srcs), 1))
        s = np.zeros((width,), np.int64)                    # pad src: page 0
        d = np.full((width,), self.num_pages, np.int64)     # pad dst: drop
        s[: len(srcs)] = srcs
        d[: len(dsts)] = dsts
        self._unschedule_scrub(dsts)
        self.page_copies += len(srcs)
        if self.layout == "fused":
            # rank-major rows of both arrays pair up elementwise, so the
            # one fused scatter copies every layer's slab page at once
            s, d = self._fused_ids(s).ravel(), self._fused_ids(d).ravel()
        return self._copy_op(cache, jnp.asarray(s), jnp.asarray(d))

    def _copy_impl(self, cache, srcs, dsts):
        out = []
        for i, c in enumerate(cache):
            if i in self._attn_set:
                c = {k: a.at[dsts].set(a[srcs], mode="drop")
                     for k, a in c.items()}
            out.append(c)
        return out

    def _copy_fused_impl(self, cache, srcs, dsts):
        if cache["arena"] is None:
            return cache
        ar = {k: a.at[dsts].set(a[srcs], mode="drop")
              for k, a in cache["arena"].items()}
        return dict(cache, arena=ar)

    def gather_rows(self, cache, page_mat: np.ndarray,
                    lengths: np.ndarray):
        """Materialize dense single-row caches from block tables.

        page_mat (G, pages_per_row) int (padded with the null page),
        lengths (G,).  Returns a per-layer dense cache batch: attention
        layers become (G, pages_per_row*page_size, KV, Dh) rows ready
        for suffix prefill; other layers come back zero-initialized for
        the caller to overlay stored state."""
        return self._gather_op(cache, jnp.asarray(page_mat, jnp.int32),
                               jnp.asarray(lengths, jnp.int32))

    def _gather_impl(self, cache, page_mat, lengths):
        cfg = self.cfg
        G = page_mat.shape[0]
        spec = T.cache_spec(cfg, G, self.max_len, self.cache_dtype_str)
        rows = []
        for i, c in enumerate(cache):
            if i in self._attn_set:
                rows.append({
                    "k": c["k"][page_mat].reshape(
                        G, -1, cfg.num_kv_heads, cfg.head_dim),
                    "v": c["v"][page_mat].reshape(
                        G, -1, cfg.num_kv_heads, cfg.head_dim),
                    "kv_pos": c["kv_pos"][page_mat].reshape(G, -1),
                    "pos": lengths,
                })
            else:
                rows.append({k: T._init_leaf(k, shape, dt)
                             for k, (shape, dt) in spec[i].items()})
        return rows

    def _gather_fused_impl(self, cache, page_mat, lengths):
        # page_mat holds only real pages + the null pad 0, all < P, so a
        # plain slab offset is safe (rank r's null page r*P is EMPTY)
        cfg = self.cfg
        G = page_mat.shape[0]
        ar = cache["arena"]
        spec = T.cache_spec(cfg, G, self.max_len, self.cache_dtype_str)
        rows, r = [], 0
        for i in range(len(cfg.layer_kinds())):
            if i in self._attn_set:
                mat = page_mat + r * self.num_pages
                rows.append({
                    "k": ar["k"][mat].reshape(
                        G, -1, cfg.num_kv_heads, cfg.head_dim),
                    "v": ar["v"][mat].reshape(
                        G, -1, cfg.num_kv_heads, cfg.head_dim),
                    "kv_pos": ar["kv_pos"][mat].reshape(G, -1),
                    "pos": lengths,
                })
                r += 1
            else:
                rows.append({k: T._init_leaf(k, shape, dt)
                             for k, (shape, dt) in spec[i].items()})
        return rows

    def write_rows(self, cache, rows, page_mat: np.ndarray,
                   first_page: int):
        """Scatter prefilled dense rows back into arena pages.

        page_mat (G, n_new) destination pages per row (pad rows with
        ``num_pages`` to drop them — G-bucketed admission padding);
        ``first_page`` is the first block-table index being written, so
        row slice [first_page*ps, (first_page+n_new)*ps) lands on the
        pages.  Whole pages are overwritten (kv_pos included), so the
        written pages need no scrub."""
        real_pages = np.asarray(page_mat)[np.asarray(page_mat)[:, 0]
                                          < self.num_pages]
        self._unschedule_scrub(real_pages.ravel().tolist())
        self.page_writes += int(real_pages.size)
        return self._write_op(cache, rows,
                              jnp.asarray(page_mat, jnp.int32),
                              int(first_page))

    def _write_impl(self, cache, rows, page_mat, first_page):
        cfg, ps = self.cfg, self.page_size
        G, n_new = page_mat.shape
        lo, hi = first_page * ps, (first_page + n_new) * ps
        out = []
        for i, c in enumerate(cache):
            if i in self._attn_set:
                r = rows[i]
                c = {
                    "k": c["k"].at[page_mat].set(
                        r["k"][:, lo:hi].reshape(
                            G, n_new, ps, cfg.num_kv_heads, cfg.head_dim),
                        mode="drop"),
                    "v": c["v"].at[page_mat].set(
                        r["v"][:, lo:hi].reshape(
                            G, n_new, ps, cfg.num_kv_heads, cfg.head_dim),
                        mode="drop"),
                    "kv_pos": c["kv_pos"].at[page_mat].set(
                        r["kv_pos"][:, lo:hi].reshape(G, n_new, ps),
                        mode="drop"),
                }
            out.append(c)
        return out

    def write_rows_traced(self, cache, rows, page_mat, first_page):
        """Trace-level fused write-back for the scan-admission
        executable (length-bucketed suffix prefill): the
        ``_write_fused_impl`` scatter with a TRACED ``first_page``, so
        ONE bucketed executable serves every prefix offset.  page_mat
        (G, nw) covers a fixed pow2-bucket window of block-table
        columns; pad columns hold ``num_pages`` and drop.  The caller
        must keep the window in range (window_start + nw <=
        pages_per_row — see Engine._admit_group) and account host-side
        via ``note_rows_written``."""
        assert self.layout == "fused"
        if cache["arena"] is None:
            return cache
        cfg, ps = self.cfg, self.page_size
        G, nw = page_mat.shape
        lo = first_page * ps
        offs = (jnp.arange(self._A, dtype=page_mat.dtype)
                * self.num_pages)[:, None, None]
        mats = jnp.where(page_mat[None] < self.num_pages,
                         page_mat[None] + offs,
                         self._A * self.num_pages)
        ar = dict(cache["arena"])
        for name in ("k", "v", "kv_pos"):
            tail_shape = ((ps, cfg.num_kv_heads, cfg.head_dim)
                          if name != "kv_pos" else (ps,))
            stacked = jnp.stack([
                jax.lax.dynamic_slice_in_dim(
                    rows[i][name], lo, nw * ps, axis=1
                ).reshape((G, nw) + tail_shape)
                for i in self._ranks])
            ar[name] = ar[name].at[mats].set(stacked, mode="drop")
        return dict(cache, arena=ar)

    def note_rows_written(self, page_mat: np.ndarray) -> None:
        """Host accounting for a trace-level ``write_rows_traced``:
        written pages need no scrub (overwritten whole) and count as
        page writes."""
        real = np.asarray(page_mat)
        real = real[real < self.num_pages]
        self._unschedule_scrub(real.ravel().tolist())
        self.page_writes += int(real.size)

    def _write_fused_impl(self, cache, rows, page_mat, first_page):
        # stack the per-layer prefilled rows along a leading rank axis
        # and land them in ONE scatter per leaf, whatever the depth
        if cache["arena"] is None:
            return cache
        cfg, ps = self.cfg, self.page_size
        G, n_new = page_mat.shape
        lo, hi = first_page * ps, (first_page + n_new) * ps
        offs = (jnp.arange(self._A, dtype=page_mat.dtype)
                * self.num_pages)[:, None, None]
        mats = jnp.where(page_mat[None] < self.num_pages,
                         page_mat[None] + offs,
                         self._A * self.num_pages)
        ar = dict(cache["arena"])
        for name in ("k", "v", "kv_pos"):
            tail_shape = ((ps, cfg.num_kv_heads, cfg.head_dim)
                          if name != "kv_pos" else (ps,))
            stacked = jnp.stack([
                rows[i][name][:, lo:hi].reshape((G, n_new) + tail_shape)
                for i in self._ranks])
            ar[name] = ar[name].at[mats].set(stacked, mode="drop")
        return dict(cache, arena=ar)

    # ------------------------------------------------- migration support
    # A read gathers each leaf kind's pages stacked across attention
    # layers, (A, n, ...): three device arrays whatever the depth.
    def _read_impl(self, cache, pages):
        attn = [cache[i] for i in self._ranks]
        return {k: jnp.stack([c[k][pages] for c in attn]) for k in attn[0]}

    def _read_fused_impl(self, cache, pages):
        ids = pages[None] + (jnp.arange(self._A, dtype=pages.dtype)
                             * self.num_pages)[:, None]
        return {k: a[ids] for k, a in cache["arena"].items()}

    def read_pages(self, cache, pages: Sequence[int]):
        """Page contents -> host numpy (one dict per attention layer),
        the RDMA-out half of the store's local->remote migration.  One
        ``device_get`` starts every stacked leaf's copy before it waits
        on any; the per-layer dicts are views of the host copies."""
        self.host_reads += 1
        if not self._A:
            return []
        with host_span(POOL_READ_GATHER):
            got = self._read_op(cache, jnp.asarray(list(pages), jnp.int32))
        with host_span(POOL_READ_COPY):
            host = jax.device_get(got)
        self.host_copies += len(host)
        return [{k: a[r] for k, a in host.items()} for r in range(self._A)]

    def _upload_impl(self, cache, host, pages):
        out = []
        j = 0
        for i, c in enumerate(cache):
            if i in self._attn_set:
                c = {k: a.at[pages].set(jnp.asarray(host[j][k]))
                     for k, a in c.items()}
                j += 1
            out.append(c)
        return out

    def _upload_fused_impl(self, cache, host, pages):
        # host payload keeps the per-attention-layer dict-list format
        # (migration/transport compatibility); stack along rank to land
        # every layer's pages in one scatter per leaf
        idx = jnp.concatenate([pages + r * self.num_pages
                               for r in range(self._A)])
        ar = {k: a.at[idx].set(jnp.concatenate(
                  [jnp.asarray(h[k]) for h in host]))
              for k, a in cache["arena"].items()}
        return dict(cache, arena=ar)

    def upload_pages(self, cache, host, pages: Sequence[int]):
        """Host page payloads -> freshly allocated arena pages (the
        restore half of remote migration).  Uploaded pages are written
        whole, so no scrub is needed."""
        self._unschedule_scrub(pages)
        self.page_writes += len(pages)
        return self._upload_op(cache, host,
                               jnp.asarray(list(pages), jnp.int32))

    # ------------------------------------------------- dense-state ops
    # Recurrent / ring-buffer layers keep per-slot dense rows; these ops
    # are layout-aware so the engine never branches on where that state
    # lives (per-layer list vs pattern-stacked scan-decode state).

    def dense_copy(self, cache, src_slot: int, dst_slot: int):
        """Copy one slot's dense rows to another (fork of recurrent
        state; attention K/V forks via the block table instead)."""
        if not self.dense_layers:
            return cache
        return self._dense_copy_op(cache, jnp.int32(src_slot),
                                   jnp.int32(dst_slot))

    def _dense_copy_impl(self, cache, s, d):
        dense = set(self.dense_layers)
        return [jax.tree.map(lambda a: a.at[d].set(a[s]), c)
                if i in dense else c for i, c in enumerate(cache)]

    def _dense_copy_fused_impl(self, cache, s, d):
        # stacked units carry (n_units, batch, ...): slot axis is 1
        units = tuple(
            c if c is None else
            jax.tree.map(lambda a: a.at[:, d].set(a[:, s]), c)
            for c in cache["units"])
        tail = tuple(
            c if c is None else
            jax.tree.map(lambda a: a.at[d].set(a[s]), c)
            for c in cache["tail"])
        return dict(cache, units=units, tail=tail)

    def dense_admit(self, cache, rows, slots: Sequence[int]):
        """Write admitted generations' dense rows (gather_rows/prefill
        format: per-layer list of G-row batches) into their slots."""
        if not self.dense_layers:
            return cache
        return self._dense_admit_op(cache, rows,
                                    jnp.asarray(slots, jnp.int32))

    def _dense_admit_impl(self, cache, rows, slots):
        dense = set(self.dense_layers)
        return [jax.tree.map(
                    lambda full, r: full.at[slots].set(
                        r[: slots.shape[0]]), c, rows[i])
                if i in dense else c for i, c in enumerate(cache)]

    def _dense_admit_fused_impl(self, cache, rows, slots):
        ns = slots.shape[0]
        units = list(cache["units"])
        tail = list(cache["tail"])
        for li in self.dense_layers:
            loc = self._dense_loc[li]
            if loc[0] == "u":
                _, j, it = loc
                units[j] = jax.tree.map(
                    lambda full, r: full.at[it, slots].set(r[:ns]),
                    units[j], rows[li])
            else:
                t = loc[1]
                tail[t] = jax.tree.map(
                    lambda full, r: full.at[slots].set(r[:ns]),
                    tail[t], rows[li])
        return dict(cache, units=tuple(units), tail=tuple(tail))

    def read_dense_row(self, cache, slot: int):
        """One slot's dense rows as a per-layer list of (1, ...) trees
        (None at attention layers) — the PagedPrefix ``extra`` payload,
        format-identical across layouts."""
        if not self.dense_layers:
            return None
        if self.layout != "fused":
            dense = set(self.dense_layers)
            return [jax.tree.map(lambda a: a[slot: slot + 1], c)
                    if i in dense else None
                    for i, c in enumerate(cache)]
        out = []
        for li in range(len(self.cfg.layer_kinds())):
            loc = self._dense_loc.get(li)
            if loc is None:
                out.append(None)
            elif loc[0] == "u":
                _, j, it = loc
                out.append(jax.tree.map(lambda a: a[it, slot: slot + 1],
                                        cache["units"][j]))
            else:
                out.append(jax.tree.map(lambda a: a[slot: slot + 1],
                                        cache["tail"][loc[1]]))
        return out

    def dense_bytes(self, cache) -> int:
        """Bytes of the fixed-size dense (recurrent/ring) state."""
        from repro.serving.kvcache import tree_bytes     # cycle-free
        if not self.dense_layers:
            return 0
        if self.layout != "fused":
            return sum(tree_bytes(cache[i]) for i in self.dense_layers)
        return sum(tree_bytes(c)
                   for c in (*cache["units"], *cache["tail"])
                   if c is not None)


# --------------------------------------------------------------- prefixes
@dataclasses.dataclass
class PagedPrefix:
    """A stored prefix = a refcounted page list (+ dense extras).

    This is the PrefixCacheStore payload for paged engines: the entry
    holds one reference per page, so two stored prefixes sharing a
    reasoning stem share the stem's pages outright, and a store entry
    can outlive (or be forked from) the generation that produced it.
    ``extra`` carries the non-paged layers' per-row state (recurrent /
    ring buffers) as a per-layer list of (1, ...) pytrees, or None.

    The store drives migration through the three hooks below:
    ``migrate_out`` (device pages -> host copies, pages released),
    ``migrate_in`` (fresh pages allocated + uploaded) and ``release``
    (drop the refs on eviction).
    """
    engine: Any
    pages: List[int]
    extra: Any
    length: int
    host: Any = None                    # host payload when migrated out
    migrating: bool = False             # streamed migrate-out in flight
    # host payload is int8-quantized (TransportConfig.compress): set by
    # the store at streamed migrate-out, consulted for wire pricing and
    # chunk decode on the way back.  Tier BUDGETS stay in raw arena
    # bytes (capacity semantics); only link pricing and the host copy
    # shrink.
    wire_compress: bool = False

    @classmethod
    def capture(cls, engine, pages: Sequence[int], extra, length: int):
        engine.pool.ref(pages)
        return cls(engine=engine, pages=list(pages), extra=extra,
                   length=length)

    @property
    def on_device(self) -> bool:
        return self.host is None and not self.migrating

    @property
    def num_pages(self) -> int:
        if self.migrating:
            return len(self._out_ids)
        return len(self.pages) if self.on_device else len(self.host["n"])

    @property
    def nbytes(self) -> int:
        from repro.serving.kvcache import tree_bytes     # cycle-free
        n = self.num_pages * self.engine.pool.page_bytes
        if self.extra is not None:
            n += sum(tree_bytes(e) for e in self.extra if e is not None)
        return n

    def shared_page_count(self) -> int:
        """Pages some OTHER holder also references (refcount > 1)."""
        if not self.on_device:
            return 0
        rc = self.engine.pool.refcount
        return int(sum(1 for p in self.pages if rc[p] > 1))

    def acquire(self):
        """Hand a holder its own refs; returns (pages copy, extra)."""
        assert self.on_device, "acquire() before migrate_in()"
        self.engine.pool.ref(self.pages)
        return list(self.pages), self.extra

    def release(self) -> None:
        if self.on_device and self.pages:
            self.engine.pool.release(self.pages)
        self.pages, self.host, self.extra = [], None, None

    def migrate_out(self):
        eng = self.engine
        self.wire_compress = False      # sync path: raw pages, always
        with host_span(STORE_MIGRATE_CHUNK):
            data = eng.pool.read_pages(eng._cache, self.pages)
            self.host = {"data": data, "n": list(self.pages)}
            if self.extra is not None:
                self.extra = jax.tree.map(
                    lambda l: np.asarray(jax.device_get(l)), self.extra)
            eng.pool.release(self.pages)
        self.pages = []
        return self

    def migrate_in(self):
        eng = self.engine
        pages = eng.pool.alloc(len(self.host["n"]))
        # _host_chunk handles both host formats AND wire decompression
        data = self._host_chunk(0, len(self.host["n"]))
        eng._cache = eng.pool.upload_pages(eng._cache, data, pages)
        self.pages, self.host = pages, None
        if self.extra is not None:
            self.extra = jax.tree.map(jnp.asarray, self.extra)
        return self

    # ------------------------------------------- streamed (chunked) hooks
    # The transport plane (serving/transport.py) drives these: migration
    # moves the block table page-range by page-range, releasing each
    # range's device pages as soon as its transfer lands; a fetch
    # preallocates destination pages and uploads ranges as they arrive,
    # so the restore starts before the tail is off the wire.

    @staticmethod
    def _slice_pages(data, lo: int, hi: int):
        return [jax.tree.map(lambda a: a[lo:hi], d) for d in data]

    def migrate_out_begin(self) -> int:
        """Start a streamed migrate-out; returns the page count.  Until
        the tail chunk lands the prefix is neither acquirable (not
        on_device) nor restorable."""
        assert self.on_device, "migrate_out_begin on a non-resident prefix"
        self._out_ids = list(self.pages)
        self._out_data: List[Any] = [None] * len(self._out_ids)
        self.migrating = True
        return len(self._out_ids)

    def migrate_out_chunk(self, lo: int, hi: int) -> None:
        """Move block-table slice [lo, hi) host-side and release those
        device pages immediately — they can serve live generations
        while the rest of the migration is still on the wire."""
        from repro.distributed.compression import compress_kv_pages

        eng = self.engine
        ids = self._out_ids[lo:hi]
        with host_span(STORE_MIGRATE_CHUNK):
            data = eng.pool.read_pages(eng._cache, ids)
            if self.wire_compress:
                data = compress_kv_pages(data)
            for j in range(lo, hi):
                self._out_data[j] = self._slice_pages(data, j - lo,
                                                      j - lo + 1)
            eng.pool.release(ids)

    def migrate_out_finish(self):
        self.host = {"pages": self._out_data, "n": self._out_ids}
        self.pages = []
        self.migrating = False
        del self._out_data, self._out_ids
        if self.extra is not None:
            self.extra = jax.tree.map(
                lambda l: np.asarray(jax.device_get(l)), self.extra)
        return self

    def migrate_out_abort(self, moved_upto: int) -> None:
        """Tear down a part-way migration (the entry is being disposed):
        chunks past ``moved_upto`` never transferred — release their
        still-held device refs; staged host data is dropped."""
        eng = self.engine
        rest = self._out_ids[moved_upto:]
        if rest:
            eng.pool.release(rest)
        self.pages, self.migrating = [], False
        del self._out_data, self._out_ids

    def fetch_begin(self) -> List[int]:
        """Preallocate destination pages for a streamed restore (may
        raise PagePoolExhausted — the caller falls back to recompute)."""
        assert not self.on_device and not self.migrating
        self._in_pages = self.engine.pool.alloc(len(self.host["n"]))
        return list(self._in_pages)

    def _host_chunk(self, lo: int, hi: int):
        from repro.distributed.compression import decompress_kv_pages

        if "pages" in self.host:
            data = jax.tree.map(lambda *xs: np.concatenate(xs, axis=0),
                                *self.host["pages"][lo:hi])
        else:
            data = self._slice_pages(self.host["data"], lo, hi)
        if self.wire_compress:
            data = decompress_kv_pages(data, self.engine.pool.dtype)
        return data

    def fetch_chunk(self, lo: int, hi: int) -> None:
        eng = self.engine
        eng._cache = eng.pool.upload_pages(
            eng._cache, self._host_chunk(lo, hi), self._in_pages[lo:hi])

    def fetch_finish(self):
        self.pages = self._in_pages
        self.host = None
        del self._in_pages
        if self.extra is not None:
            self.extra = jax.tree.map(jnp.asarray, self.extra)
        return self

    def fetch_abort(self) -> None:
        """Cancelled fetch: uploaded + reserved destination pages go
        back to the pool; host payload stays restorable."""
        self.engine.pool.release(self._in_pages)
        del self._in_pages
