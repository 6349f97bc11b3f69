"""Remote-KV transport plane: Mooncake-style async page migration.

The paper (§6.2.3) parks reasoning-prefix KV in *spare validation/
profiling-GPU memory* over Mooncake RDMA so speculative forks skip
prefix recomputation.  Until this module the reproduction faked that
tier with synchronous ``device_get``/``device_put`` inside the store —
zero modeled transfer cost, and every migration blocked the engine's
step loop.  This module is the transfer fabric (DESIGN.md
§Remote-KV-transport):

  * ``TransportLink`` — one serial RDMA-like link with a configurable
    bandwidth/latency model.  A transfer's modeled duration is

        duration = latency + nbytes / bandwidth        (x jitter)

    (jitter, when enabled, is drawn from a seeded RNG so traces stay
    run-to-run deterministic).  Transfers queue FIFO on the link and
    become events on the ``core/clock.py`` loop; each resolves a
    ``Future`` on completion.  Cancelled transfers NEVER fire their
    callbacks — the same abort contract as the async eval plane.

  * ``RemoteTierPool`` — the remote tier's byte budget.  Capacity is
    per *hosting device* (spare validation/profiling memory); when an
    ``ElasticScheduler`` is attached the hosting-device count tracks
    the live pool split, so arrival-rate reallocation shrinks/grows
    remote capacity mid-run.  ``reserve`` is the backpressure gate: a
    denied reservation triggers the store's configured policy instead
    of silently overflowing.

  * ``TransportPlane`` — the bundle (loop + link + tier pool + config)
    the store, engine, controller and scheduler share.  ``mode="sync"``
    is the blocking baseline: the same link model, but every transfer
    charges its full duration to ``engine_blocked_s`` inline (the old
    ``device_get`` behavior with honest pricing).  ``mode="async"``
    lets transfers overlap decode: the engine ticks the clock once per
    decode dispatch and only blocks when an admission actually needs
    pages that have not landed yet.

The plane models TIME; the store still moves real bytes (device_get /
device_put between the serving arenas and host memory stands in for
RDMA on this container).  With no plane attached the store behaves
exactly as before — the synchronous legacy path is the default and is
pinned by the PR-3 golden fixtures.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Callable, Deque, List, Optional, Tuple

import numpy as np

from repro.core.clock import EventLoop, Future


# ---------------------------------------------------------------- link model
@dataclasses.dataclass
class LinkSpec:
    """Bandwidth/latency model of one migration link.

    Defaults approximate one Mooncake-style RDMA NIC: ~12 GB/s
    effective bandwidth, tens of microseconds of per-transfer setup.
    """
    bandwidth: float = 12e9          # bytes / second
    latency: float = 30e-6           # per-transfer setup seconds
    jitter: float = 0.0              # +- fraction of the modeled duration
    seed: int = 0                    # jitter RNG seed (determinism)


class Transfer:
    """One queued/in-flight/completed transfer on a link."""

    __slots__ = ("nbytes", "tag", "future", "submitted", "started",
                 "finished", "duration", "cancelled", "span")

    def __init__(self, nbytes: int, tag: str, now: float):
        self.nbytes = int(nbytes)
        self.tag = tag
        self.future = Future()
        self.submitted = now
        self.started: Optional[float] = None
        self.finished: Optional[float] = None
        self.duration = 0.0
        self.cancelled = False
        self.span = -1                   # causal span sid (§Observability)

    @property
    def done(self) -> bool:
        return self.finished is not None


class TransportLink:
    """Serial FIFO link: one transfer on the wire at a time.

    Completion events live on the shared event loop, so link activity
    interleaves deterministically with scheduler grants and controller
    events.  With the loop's composed trace on (``loop.enable_trace``),
    every enq/start/done/cancel lands there as a ``"transport"`` event
    tagged ``<link>:<tag>:<nbytes>`` — the golden virtual-clock trace
    the determinism tests pin.
    """

    def __init__(self, loop: EventLoop, spec: Optional[LinkSpec] = None,
                 name: str = "rdma0"):
        self.loop = loop
        # fresh spec per link: a shared default instance would let one
        # caller's in-place tweak leak into every other default link
        self.spec = spec if spec is not None else LinkSpec()
        self.name = name
        self._rs = np.random.RandomState(self.spec.seed)
        self._queue: Deque[Transfer] = deque()
        self._current: Optional[Transfer] = None
        # stats
        self.transfers_done = 0
        self.transfers_cancelled = 0
        self.bytes_moved = 0
        self.busy_total = 0.0
        self.queue_wait_total = 0.0
        self._t0 = loop.now

    # -------------------------------------------------------------- model
    def model_duration(self, nbytes: int) -> float:
        """The jitter-free formula: latency + bytes/bandwidth."""
        return self.spec.latency + nbytes / self.spec.bandwidth

    def _draw_duration(self, nbytes: int) -> float:
        d = self.model_duration(nbytes)
        if self.spec.jitter > 0.0:
            d *= 1.0 + self.spec.jitter * (2.0 * self._rs.random_sample()
                                           - 1.0)
        return d

    # ---------------------------------------------------------- lifecycle
    def _record(self, event: str, tag: str, nbytes: int) -> None:
        # composed timeline: the event, attributed to this link,
        # interleaves with engine steps and eval grants (core.trace);
        # the tag is built only when the loop records
        if self.loop.trace is not None:
            self.loop.record("transport", event,
                             f"{self.name}:{tag}:{nbytes}")

    def submit(self, nbytes: int, tag: str = "") -> Transfer:
        t = Transfer(nbytes, tag, self.loop.now)
        # transfer span opens at SUBMIT (queue wait is part of it) and
        # closes at _finish — or at cancel when still queued, since a
        # queued-cancelled transfer never reaches the wire
        t.span = self.loop.spans.begin("transport", "transfer",
                                       f"{self.name}:{tag}")
        self._record("enq", tag, t.nbytes)
        self._queue.append(t)
        self._pump()
        return t

    def cancel(self, t: Transfer) -> None:
        """Abort a transfer: its future never fires.  A queued transfer
        is dropped before reaching the wire; an in-flight one holds the
        wire to completion (the DMA is committed) but its result is
        discarded — mirroring the scheduler's abort semantics."""
        if t.cancelled or t.done:
            t.future.cancel()
            return
        t.cancelled = True
        t.future.cancel()
        if t.started is None:
            # never reaches _finish: close the span here
            self.loop.spans.end(t.span, status="cancel")
        self._record("cancel", t.tag, t.nbytes)

    def _pump(self) -> None:
        while self._current is None and self._queue:
            t = self._queue.popleft()
            if t.cancelled:
                self.transfers_cancelled += 1
                continue
            self._current = t
            t.started = self.loop.now
            t.duration = self._draw_duration(t.nbytes)
            self.queue_wait_total += t.started - t.submitted
            self._record("start", t.tag, t.nbytes)
            self.loop.schedule(t.duration, lambda tt=t: self._finish(tt),
                               tag=f"xfer-{self.name}")

    def _finish(self, t: Transfer) -> None:
        t.finished = self.loop.now
        self.busy_total += t.finished - t.started
        self._current = None
        self._record("done", t.tag, t.nbytes)
        self.loop.spans.end(t.span,
                            status="cancel" if t.cancelled else "ok")
        if t.cancelled:
            self.transfers_cancelled += 1
        else:
            self.transfers_done += 1
            self.bytes_moved += t.nbytes
            t.future.resolve(t)
        self._pump()

    # ------------------------------------------------------------ metrics
    @property
    def queued(self) -> int:
        return len(self._queue)

    @property
    def in_flight(self) -> int:
        return 0 if self._current is None else 1

    @property
    def idle(self) -> bool:
        return self._current is None and not self._queue

    def utilization(self, t_end: Optional[float] = None) -> float:
        t_end = self.loop.now if t_end is None else t_end
        busy = self.busy_total
        if self._current is not None and self._current.started is not None:
            busy += t_end - self._current.started
        return busy / max(t_end - self._t0, 1e-9)


# ---------------------------------------------------------------- tier pool
class RemoteTierPool:
    """Byte budget of the remote (spare eval-device memory) tier.

    ``bytes_per_device`` is the spare memory each hosting device
    contributes.  With a scheduler attached, the hosting-device count
    follows the live pool split (``host_pool`` names which side of the
    elastic split hosts the tier — the paper uses validation/profiling
    GPUs; the profiling pool is the default because validation devices
    turn over fastest).  Reallocation therefore shrinks/grows capacity
    mid-run, and ``reserve`` denials are the store's backpressure
    signal.
    """

    def __init__(self, bytes_per_device: int, devices: int = 1,
                 sched: Any = None, host_pool: str = "profiling"):
        assert host_pool in ("profiling", "validation", "all")
        self.bytes_per_device = int(bytes_per_device)
        self._devices = devices
        self.sched = sched
        self.host_pool = host_pool
        self.used = 0
        self.reserved_peak = 0
        self.denials = 0

    def host_devices(self) -> int:
        if self.sched is None:
            return self._devices
        n_val, n_prof = self.sched.capacity
        return {"profiling": n_prof, "validation": n_val,
                "all": n_val + n_prof}[self.host_pool]

    @property
    def capacity(self) -> int:
        return self.host_devices() * self.bytes_per_device

    @property
    def headroom(self) -> int:
        return self.capacity - self.used

    def reserve(self, nbytes: int) -> bool:
        if self.used + nbytes > self.capacity:
            self.denials += 1
            return False
        self.used += nbytes
        self.reserved_peak = max(self.reserved_peak, self.used)
        return True

    def release(self, nbytes: int) -> None:
        self.used = max(0, self.used - nbytes)


# ------------------------------------------------------------------- plane
@dataclasses.dataclass
class TransportConfig:
    mode: str = "async"              # "async" | "sync" (blocking baseline)
    backpressure: str = "defer"      # "defer" | "drop" | "host"
    # fetch-vs-recompute cost model: fetching a cached prefix only wins
    # when the modeled transfer time beats re-prefilling it locally
    fetch_cost_model: bool = True
    prefill_tokens_per_s: float = 20000.0
    # virtual seconds one decode dispatch advances the clock by (how
    # much transfer progress overlaps each decode step)
    decode_step_s: float = 2e-3
    # controller-side accounting: KV bytes per reasoning-prefix token
    # (used to price speculative-fork prefix fetches)
    bytes_per_token: int = 4096
    # streamed chunk size for paged payloads, in PAGES per transfer
    pages_per_transfer: int = 1
    # int8-quantize streamed K/V page chunks on the wire
    # (distributed.compression codec).  Applies to the ASYNC streamed
    # migrate/fetch hooks only — the sync/urgent paths keep moving raw
    # pages — and is lossy (per-page abs-max quantization), so it stays
    # off by default: golden traces and the bitwise admission contract
    # are pinned with it disabled.
    compress: bool = False
    # deferred-migration AGING (ROADMAP item): the "defer" policy keeps
    # the local tier over budget until remote headroom returns — bound
    # it.  After ``defer_max_puts`` consecutive deferred puts OR
    # ``defer_max_s`` virtual seconds over budget, the store falls back
    # to ``defer_fallback`` ("drop" | "host") for that entry.  0 keeps
    # the unbounded legacy defer (golden traces unchanged).
    defer_max_puts: int = 0
    defer_max_s: float = 0.0
    defer_fallback: str = "drop"


class TransportPlane:
    """Shared bundle: loop + link + remote tier + policy knobs.

    Owned jointly by the PrefixCacheStore (migrations/fetches), the
    Engine (clock ticks per decode step, admission waits), the
    SpecController (prefix-fetch pricing for speculative forks) and the
    ElasticScheduler (utilization traces, tier-capacity feed).
    """

    def __init__(self, loop: Optional[EventLoop] = None,
                 link: Optional[TransportLink] = None,
                 tier: Optional[RemoteTierPool] = None,
                 cfg: Optional[TransportConfig] = None):
        self.loop = loop if loop is not None else EventLoop()
        self.link = link if link is not None else TransportLink(self.loop)
        self.tier = tier if tier is not None else RemoteTierPool(
            bytes_per_device=1 << 30)
        self.cfg = cfg if cfg is not None else TransportConfig()
        # accounting the benchmarks report
        self.engine_blocked_s = 0.0      # sync transfers + async stalls
        self.migrations_started = 0
        self.migrations_done = 0
        self.migrations_deferred = 0     # backpressure: kept local
        self.migrations_defer_aged = 0   # defer aging bound hit: fell back
        self.migrations_dropped = 0      # backpressure: evicted (LRU-skip)
        self.migrations_host = 0         # backpressure: write-through host
        self.fetches_started = 0
        self.fetches_done = 0
        self.fetches_cancelled = 0
        self.fetch_wait_s = 0.0          # request -> last page landed
        self.recomputes_chosen = 0       # cost model said prefill instead
        self.prefix_fetches = 0          # controller-side fork fetches
        self.prefix_fetch_s = 0.0
        # wire compression (cfg.compress): bytes actually put on the
        # link in compressed form, and raw-minus-wire savings
        self.wire_bytes_compressed = 0
        self.wire_bytes_saved = 0

    # ------------------------------------------------------------- timing
    def tick(self, dt: Optional[float] = None) -> None:
        """Advance the virtual clock (one decode step by default): due
        transfer events run, overlapping migration with decode."""
        self.loop.run(until=self.loop.now
                      + (self.cfg.decode_step_s if dt is None else dt))

    def stall(self, dt: float) -> None:
        """Advance the clock while the engine has nothing to decode —
        the blocked time async mode still pays (awaited fetches)."""
        t0 = self.loop.now
        self.loop.run(until=t0 + dt)
        self.engine_blocked_s += self.loop.now - t0

    def drain(self) -> None:
        """Run the loop until the link is idle (tests/benchmarks)."""
        self.loop.run(stop=lambda: self.link.idle)

    @property
    def in_flight(self) -> int:
        return self.link.queued + self.link.in_flight

    # ------------------------------------------------------ sync baseline
    def transfer_sync(self, nbytes: int, tag: str = "") -> None:
        """Blocking transfer (the priced ``device_get`` baseline): the
        clock advances by the full modeled duration and the whole wait
        is charged to the engine."""
        t = self.link.submit(nbytes, tag=tag)
        t0 = self.loop.now
        self.loop.run(stop=lambda: t.done)
        self.engine_blocked_s += self.loop.now - t0

    # --------------------------------------------------------- cost model
    def chunk_sizes(self, payload_nbytes: int, num_pages: int,
                    page_bytes: int) -> List[int]:
        """Split a payload into streamed transfer chunks (page-granular
        for paged payloads; one chunk otherwise)."""
        if num_pages <= 0:
            return [payload_nbytes]
        per = max(1, self.cfg.pages_per_transfer)
        sizes, left = [], num_pages
        while left > 0:
            k = min(per, left)
            sizes.append(k * page_bytes)
            left -= k
        return sizes

    def fetch_time(self, payload_nbytes: int, num_pages: int = 0,
                   page_bytes: int = 0) -> float:
        """Modeled end-to-end transfer time of a payload (queue-free)."""
        return sum(self.link.model_duration(n) for n in
                   self.chunk_sizes(payload_nbytes, num_pages, page_bytes))

    def recompute_time(self, tokens: int) -> float:
        return tokens / max(self.cfg.prefill_tokens_per_s, 1e-9)

    def prefer_fetch(self, payload_nbytes: int, tokens: int,
                     num_pages: int = 0, page_bytes: int = 0) -> bool:
        """Fetch-vs-recompute: fetch only when the modeled transfer
        beats re-prefilling the same tokens at the serving rate."""
        if not self.cfg.fetch_cost_model:
            return True
        return (self.fetch_time(payload_nbytes, num_pages, page_bytes)
                <= self.recompute_time(tokens))

    def prefix_fetch(self, tokens: int, tag: str = "prefix",
                     on_done: Optional[Callable[[], None]] = None
                     ) -> Tuple[float, Optional[Transfer]]:
        """Controller-side fork accounting: fetch a reasoning prefix's
        KV for a speculative fork.  Returns (modeled latency, transfer)
        — the transfer rides the shared link (it shows up in
        utilization traces and queues behind migrations)."""
        nbytes = tokens * self.cfg.bytes_per_token
        self.prefix_fetches += 1
        lat = self.fetch_time(nbytes)
        self.prefix_fetch_s += lat
        t = self.link.submit(nbytes, tag=tag)
        if on_done is not None:
            t.future.add_done_callback(lambda _f: on_done())
        return lat, t


# --------------------------------------------------------------- jobs
class MigrationJob:
    """Async local->remote migration of one store entry, streamed in
    page-granular chunks.  Each chunk transfer, on completion, moves
    that chunk's bytes host-side and releases its device pages; the
    entry counts as migrated when the tail chunk lands."""

    kind = "migration"
    __slots__ = ("plane", "entry", "chunks", "next_chunk", "done",
                 "cancelled", "future", "transfers", "on_done", "_mover",
                 "waiters", "span")

    def __init__(self, plane: TransportPlane, entry: Any,
                 chunks: List[Tuple[int, int, int]],
                 mover: Callable[[int, int], None],
                 on_done: Callable[[], None]):
        self.plane = plane
        self.entry = entry
        self.chunks = chunks                 # [(lo, hi, nbytes)]
        self.next_chunk = 0
        self.done = False
        self.cancelled = False
        self.future = Future()
        self.transfers: List[Transfer] = []
        self.on_done = on_done
        self._mover = mover                  # (lo, hi) -> move bytes out
        self.waiters: set = set()
        # job span spanning the whole streamed migration; its chunk
        # transfers parent under it via the cursor
        self.span = plane.loop.spans.begin(
            "transport", "migration", str(getattr(entry, "key", "")))
        plane.migrations_started += 1
        self._submit_next()

    def _submit_next(self) -> None:
        if self.cancelled:
            return
        if self.next_chunk >= len(self.chunks):
            self.done = True
            self.plane.migrations_done += 1
            self.plane.loop.spans.end(self.span)
            self.on_done()
            self.future.resolve(self)
            return
        lo, hi, nbytes = self.chunks[self.next_chunk]
        self.plane.loop.spans.push_parent(self.span)
        t = self.plane.link.submit(nbytes, tag="mig-out")
        self.plane.loop.spans.pop_parent()
        self.transfers.append(t)
        t.future.add_done_callback(lambda _f, lo=lo, hi=hi:
                                   self._landed(lo, hi))

    def _landed(self, lo: int, hi: int) -> None:
        if self.cancelled:
            return
        self._mover(lo, hi)
        self.next_chunk += 1
        self._submit_next()

    def cancel(self) -> None:
        """Stop streaming (the entry is being disposed mid-migration):
        outstanding transfers are cancelled and no callback fires."""
        if self.done or self.cancelled:
            return
        self.cancelled = True
        self.future.cancel()
        self.plane.loop.spans.end(self.span, status="cancel")
        for t in self.transfers:
            self.plane.link.cancel(t)


class FetchJob:
    """Async remote->local fetch of one store entry: page chunks stream
    back and upload as they land (the restore starts before the tail
    arrives).  ``handle`` is what the store hands to the engine."""

    kind = "fetch"
    __slots__ = ("plane", "entry", "chunks", "next_chunk", "done",
                 "cancelled", "future", "transfers", "on_done",
                 "_uploader", "requested_at", "waiters", "span")

    def __init__(self, plane: TransportPlane, entry: Any,
                 chunks: List[Tuple[int, int, int]],
                 uploader: Callable[[int, int], None],
                 on_done: Callable[[], None]):
        self.plane = plane
        self.entry = entry
        self.chunks = chunks
        self.next_chunk = 0
        self.done = False
        self.cancelled = False
        self.future = Future()
        self.transfers: List[Transfer] = []
        self.on_done = on_done
        self._uploader = uploader            # (lo, hi) -> upload chunk
        self.requested_at = plane.loop.now
        self.waiters: set = set()            # engine gen_ids awaiting
        self.span = plane.loop.spans.begin(
            "transport", "fetch", str(getattr(entry, "key", "")))
        plane.fetches_started += 1
        self._submit_next()

    def _submit_next(self) -> None:
        if self.cancelled:
            return
        if self.next_chunk >= len(self.chunks):
            self.done = True
            self.plane.fetches_done += 1
            self.plane.fetch_wait_s += (self.plane.loop.now
                                        - self.requested_at)
            self.plane.loop.spans.end(self.span)
            self.on_done()
            self.future.resolve(self)
            return
        lo, hi, nbytes = self.chunks[self.next_chunk]
        self.plane.loop.spans.push_parent(self.span)
        t = self.plane.link.submit(nbytes, tag="fetch")
        self.plane.loop.spans.pop_parent()
        self.transfers.append(t)
        t.future.add_done_callback(lambda _f, lo=lo, hi=hi:
                                   self._landed(lo, hi))

    def _landed(self, lo: int, hi: int) -> None:
        if self.cancelled:
            return
        self._uploader(lo, hi)
        self.next_chunk += 1
        self._submit_next()

    def cancel(self) -> None:
        """Abort the fetch: in-flight/queued transfers are cancelled and
        no callback (including the handle future's) ever fires."""
        if self.done or self.cancelled:
            return
        self.cancelled = True
        self.future.cancel()
        self.plane.loop.spans.end(self.span, status="cancel")
        for t in self.transfers:
            self.plane.link.cancel(t)
        self.plane.fetches_cancelled += 1
