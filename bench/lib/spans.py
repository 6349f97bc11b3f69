"""The program's own host spans in a profiler trace.

The serving program stamps ``specgen.*`` spans on the profiler's clock
(``repro.core.spans.HOST_SPANS``): the engine's pump and its parts, the
prefix store's page migrations and the page reads inside them.  This
module adds them to ``trace``'s events and reduces them:

* ``load`` returns ``trace.load``'s events plus one event of kind
  ``"span"`` per program span.  ``trace.reduce`` reads only kinds
  ``"module"``, ``"op"`` and ``"host"``, so what it returns is the same
  with or without them.
* ``reduce`` gives the seconds (clipped to the window) and the count of
  each span name, and the device-idle seconds named by the innermost
  program span that covers at least half of each idle gap (the rule
  ``trace`` uses for its ``bench.*`` labels); a gap that no program span
  covers keeps its ``bench.*`` label.
"""
from __future__ import annotations

from collections import Counter, defaultdict
from typing import List, Sequence, Tuple

from bench.lib import trace as tr

PREFIX = "specgen."


def span_events(xplane_path) -> List[tr.Event]:
    import jax
    pd = jax.profiler.ProfileData.from_file(str(xplane_path))
    return [("span", plane.name, e.name, e.start_ns, e.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIX)]


def load(xplane_path) -> List[tr.Event]:
    return tr.load(xplane_path) + span_events(xplane_path)


def reduce(events: Sequence[tr.Event], window: Tuple[float, float]
           ) -> dict:
    """``span_s`` and ``span_runs`` per span name, and ``idle_by_span``:
    [label, idle seconds] pairs, most first, inside ``window``."""
    lo, hi = window
    spans = [e for e in events if e[0] == "span"]
    span_s, span_runs = defaultdict(float), Counter()
    for e in spans:
        inside = min(e[3] + e[4], hi) - max(e[3], lo)
        if inside > 0:
            span_s[e[2]] += inside * 1e-9
            span_runs[e[2]] += 1
    mods = [e for e in events if e[0] == "module"
            and e[3] + e[4] > lo and e[3] < hi]
    dev = min((e[1] for e in mods), default=None)  # trace.reduce's device
    busy = tr.clip(tr.union((e[3], e[3] + e[4]) for e in mods
                            if e[1] == dev), lo, hi)
    host = [e for e in events if e[0] == "host"]
    by_label = defaultdict(float)
    for s, e in idle(busy, lo, hi):
        by_label[label(s, e, spans) or tr._label(s, e, host)] += \
            (e - s) * 1e-9
    return {"span_s": dict(span_s), "span_runs": dict(span_runs),
            "idle_by_span": [[n, v] for n, v in sorted(
                by_label.items(), key=lambda kv: -kv[1])]}


def idle(busy: Sequence[Sequence[float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] outside the sorted, disjoint ``busy``."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]


def label(s: float, e: float, spans: Sequence[tr.Event]):
    """The innermost program span covering at least half of [s, e], or
    None."""
    best, best_dur = None, None
    for h in spans:
        cover = min(e, h[3] + h[4]) - max(s, h[3])
        if cover <= 0 or cover < 0.5 * (e - s):
            continue
        if best_dur is None or h[4] < best_dur:
            best, best_dur = h[2], h[4]
    return best
