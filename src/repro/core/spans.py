"""Spans: causal spans on the loop's clock, host spans on the profiler's.

Two kinds of span, on two clocks, for two questions.

**Causal spans on the loop's clock: the lifecycle audit.**  The
composed ``(t, plane, event, tag)`` trace answers *what happened when*;
causal spans answer *why*: every interval of interest — a workflow, a
reasoning generation, a speculative fork, an eval request (and its
device-execution sub-interval), a transport transfer, an engine decode
step — is recorded as a ``Span`` with a PARENT edge to the span that
caused it, forming one causal tree per run:

    workflow ─ gen ─ fork ─ transfer        (prefix fetch on the wire)
                   └ eval ─ exec ─ build    (grant-time kernel build)
             engine row / step / park       (decode substrate)

Spans are pure bookkeeping on the virtual clock (``loop.now``, where a
decode step lasts ``decode_step_s``): their durations are never wall
time.  Opening or closing one schedules NO loop events, consumes NO
randomness and appends NOTHING to ``loop.trace`` — the byte-pinned
golden traces are untouched whether spans are enabled or not.
``SpanRecorder`` is always present on an ``EventLoop`` but disabled by
default; ``EventLoop.enable_spans()`` opts a run in, and call sites
record unconditionally (a disabled recorder's ``open`` returns -1 and
``close`` no-ops).

Causal parents cross module boundaries without widening every call
signature via the CURRENT-PARENT cursor: the initiator brackets the
downstream call in ``push_parent``/``pop_parent`` and the callee reads
``current_parent`` (calls are synchronous on the one loop, so the
cursor cannot race).

The tier-1-enforced invariant (generalizing the §One-loop
``unclosed_generations`` audit): every opened span closes EXACTLY once
on every path — normal completion, early termination, fork-declined,
eval abort, cancelled fetch, ``PagePoolExhausted`` rollback.
``unclosed_spans`` returns the offenders; ``double_closes`` counts
close-after-close bugs (both must be empty/zero once a run finishes).

**Host spans on the profiler's clock: time.**  ``host_span(name)`` is a
``jax.profiler.TraceAnnotation`` around host work on the serving hot
path (the engine's pump, the prefix store's page migrations).  A
running profiler stamps it on the clock of the device trace, in the
same ``.xplane.pb``, so every stretch the device sits idle can be put
down to the host work around it.  There is no switch: with no profiler
running, one enter and exit costs about a microsecond.  Every name is a
constant of ``HOST_SPANS``, so no call site formats a string.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

ROOT = -1          # parent of top-level spans

# Host spans on the profiler's clock, and what each covers:
# one decode-step event of the engine's pump
ENGINE_PUMP = "specgen.engine.pump"
# token appends, retirements, subscriber callbacks
ENGINE_COMPLETE = "specgen.engine.complete"
# store lookups, suffix prefill, write-back, prefix capture
ENGINE_ADMIT = "specgen.engine.admit"
# page allocation, copy-on-write, the scrub and copy scatters
ENGINE_PREPARE_WRITES = "specgen.engine.prepare_writes"
# the batch arrays through the decode call
ENGINE_LAUNCH = "specgen.engine.launch"
# the host waiting for the step's tokens
ENGINE_SYNC = "specgen.engine.sync"
# one chunk of a prefix's pages moved host-side and released
STORE_MIGRATE_CHUNK = "specgen.store.migrate_chunk"
# the dispatch of a page read's gather
POOL_READ_GATHER = "specgen.pool.read_gather"
# the page read's device-to-host copies
POOL_READ_COPY = "specgen.pool.read_copy"
HOST_SPANS = (ENGINE_PUMP, ENGINE_COMPLETE, ENGINE_ADMIT,
              ENGINE_PREPARE_WRITES, ENGINE_LAUNCH, ENGINE_SYNC,
              STORE_MIGRATE_CHUNK, POOL_READ_GATHER, POOL_READ_COPY)


def host_span(name: str):
    """A host span on the profiler's clock (a context manager); ``name``
    is one of ``HOST_SPANS``.  jax is imported here, not at the top, so
    the simulator's core stays free of it."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


@dataclasses.dataclass
class Span:
    sid: int
    parent: int                      # sid of the causing span (ROOT = none)
    plane: str                       # gen | eval | transport | engine
    kind: str                        # workflow|gen|fork|eval|exec|build|
    #                                  transfer|migration|fetch|row|step|park
    tag: str
    t0: float
    t1: float = -1.0                 # -1.0 while open
    status: str = ""                 # ""(open) | ok | abort | cancel | ...

    @property
    def open(self) -> bool:
        return self.t1 < 0.0

    @property
    def duration(self) -> float:
        return 0.0 if self.open else self.t1 - self.t0


class SpanRecorder:
    """Span store attached to one EventLoop (``loop.spans``).

    Disabled recorders are inert null objects so instrumentation sites
    never branch; ``enable()`` turns recording on for the run."""

    def __init__(self, loop):
        self._loop = loop
        self.enabled = False
        self.spans: List[Span] = []
        self._open: Dict[int, Span] = {}
        self._parents: List[int] = []
        self.double_closes = 0

    def enable(self) -> "SpanRecorder":
        self.enabled = True
        return self

    # ------------------------------------------------------------ record
    def begin(self, plane: str, kind: str, tag: str = "",
              parent: Optional[int] = None) -> int:
        """Open a span at ``loop.now``; returns its sid (-1 disabled).
        ``parent=None`` inherits the current-parent cursor."""
        if not self.enabled:
            return ROOT
        sid = len(self.spans)
        s = Span(sid=sid,
                 parent=self.current_parent if parent is None else parent,
                 plane=plane, kind=kind, tag=tag, t0=self._loop.now)
        self.spans.append(s)
        self._open[sid] = s
        return sid

    def end(self, sid: int, status: str = "ok") -> None:
        """Close a span at ``loop.now``.  Closing -1 (disabled open) is
        a no-op; closing an already-closed span counts a double-close —
        the audit the lifecycle tests pin to zero."""
        if not self.enabled or sid < 0:
            return
        s = self._open.pop(sid, None)
        if s is None:
            if 0 <= sid < len(self.spans):
                self.double_closes += 1
            return
        s.t1 = self._loop.now
        s.status = status

    def point(self, plane: str, kind: str, tag: str = "",
              parent: Optional[int] = None) -> int:
        """Instantaneous span (t0 == t1): grant-time build/cache events."""
        sid = self.begin(plane, kind, tag, parent=parent)
        self.end(sid)
        return sid

    # ---------------------------------------------------- causal cursor
    @property
    def current_parent(self) -> int:
        return self._parents[-1] if self._parents else ROOT

    def push_parent(self, sid: int) -> None:
        if self.enabled:
            self._parents.append(sid)

    def pop_parent(self) -> None:
        if self.enabled and self._parents:
            self._parents.pop()

    # ------------------------------------------------------------- query
    def open_spans(self) -> List[Span]:
        return [self._open[k] for k in sorted(self._open)]

    def ancestry(self, sid: int) -> List[Span]:
        """Causal chain root -> ... -> span (cycle-proof by sid order:
        parents always precede children)."""
        chain: List[Span] = []
        while 0 <= sid < len(self.spans):
            s = self.spans[sid]
            chain.append(s)
            sid = s.parent if s.parent < s.sid else ROOT
        return chain[::-1]


def unclosed_spans(spans) -> List[Tuple[str, str, str]]:
    """(plane, kind, tag) of every span still open — the §Observability
    invariant says this must be empty once a run finishes.  Accepts a
    SpanRecorder or a plain span list."""
    if isinstance(spans, SpanRecorder):
        spans = spans.spans
    return sorted((s.plane, s.kind, s.tag) for s in spans or [] if s.open)


def format_top_spans(spans, n: int = 20) -> str:
    """Byte-stable "top spans" report: the ``n`` longest closed spans,
    duration-descending (ties broken by sid — deterministic), one
    ``repr(dur)<TAB>plane<TAB>kind<TAB>tag<TAB>repr(t0)`` line each."""
    if isinstance(spans, SpanRecorder):
        spans = spans.spans
    closed = [s for s in spans or [] if not s.open]
    closed.sort(key=lambda s: (-s.duration, s.sid))
    return "".join(
        f"{s.duration!r}\t{s.plane}\t{s.kind}\t{s.tag}\t{s.t0!r}\n"
        for s in closed[:n])
