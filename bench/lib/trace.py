"""Reduce a profiler trace to device busy time, idle gaps and device
time per named program.

A trace is first flattened to plain events (``load``), then reduced
(``reduce``).  Device events come from each ``/device:*`` plane's
"XLA Modules" line (one event per program execution) and its "XLA Ops"
line (one per operation); host events are the benchmark's own
``TraceAnnotation`` spans, whose names start with ``bench.``.  Times are
nanoseconds on the trace's one clock.
"""
from __future__ import annotations

import bisect
import json
from collections import Counter, defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

PROGRAMS_FILE = Path(__file__).with_name("programs.json")
HOST_PREFIX = "bench."

# (kind, device, name, start_ns, dur_ns); kind is "module", "op" or "host"
Event = Tuple[str, str, str, float, float]


def load(xplane_path: str) -> List[Event]:
    import jax
    pd = jax.profiler.ProfileData.from_file(str(xplane_path))
    out: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                kind = {"XLA Modules": "module", "XLA Ops": "op"}.get(
                    line.name)
                if kind is None:
                    continue
                for e in line.events:
                    out.append((kind, plane.name, e.name, e.start_ns,
                                e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        out.append(("host", plane.name, e.name, e.start_ns,
                                    e.duration_ns))
    return out


def save_events(events: Sequence[Event], path) -> None:
    Path(path).write_text(json.dumps([list(e) for e in events]))


def load_events(path) -> List[Event]:
    return [tuple(e) for e in json.loads(Path(path).read_text())]


def module_base(name: str) -> str:
    """'jit__lambda(3419...)' -> 'jit__lambda'."""
    return name.split("(", 1)[0]


def op_base(name: str) -> str:
    """'%fusion.12 = bf16[...] fusion(...)' -> 'fusion.12'."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def clip(intervals, lo: float, hi: float) -> List[List[float]]:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def classify(module_events: Sequence[Event], programs: dict
             ) -> Dict[str, set]:
    """Full module names (with fingerprint) that make up each program."""
    runs = Counter(e[2] for e in module_events)
    out: Dict[str, set] = {}
    for prog, rule in programs.items():
        if not isinstance(rule, dict):
            continue
        if "module" in rule:
            cands = {n: c for n, c in runs.items()
                     if module_base(n) == rule["module"]}
            if not cands:
                out[prog] = set()
            elif rule.get("pick") == "most_frequent":
                out[prog] = {max(cands, key=lambda n: (cands[n], n))}
            else:
                out[prog] = set(cands)
    for prog, rule in programs.items():
        if isinstance(rule, dict) and "modules" in rule:
            taken = set().union(*(out.get(x, set())
                                  for x in rule.get("exclude", [])))
            out[prog] = {n for n in runs
                         if module_base(n) in rule["modules"]} - taken
    return out


def reduce(events: Sequence[Event], window: Tuple[float, float],
           programs: Optional[dict] = None, top: int = 10) -> dict:
    """Busy union, idle share, device time per program, top device ops,
    idle time by the host span around it, and host time per span name,
    all inside ``window`` = (start_ns, end_ns)."""
    if programs is None:
        programs = json.loads(PROGRAMS_FILE.read_text())
    lo, hi = window
    win_s = (hi - lo) * 1e-9
    mods = [e for e in events if e[0] == "module"
            and e[3] + e[4] > lo and e[3] < hi]
    devices = sorted({e[1] for e in mods})
    busy_by_dev = {}
    for dev in devices:
        iv = union((e[3], e[3] + e[4]) for e in mods if e[1] == dev)
        busy_by_dev[dev] = clip(iv, lo, hi)
    busy_s = (sum(sum(e - s for s, e in iv) for iv in busy_by_dev.values())
              / len(devices) * 1e-9) if devices else 0.0
    members = classify(mods, programs)
    prog_time, prog_runs = {}, {}
    for prog, names in members.items():
        sel = [e for e in mods if e[2] in names]
        prog_time[prog] = sum(
            max(0.0, min(e[3] + e[4], hi) - max(e[3], lo))
            for e in sel) * 1e-9 / max(len(devices), 1)
        prog_runs[prog] = len(sel) / max(len(devices), 1)
    ops = defaultdict(float)
    index = _module_index(mods)
    for e in events:
        if e[0] == "op" and e[3] + e[4] > lo and e[3] < hi:
            ops[f"{_module_at(index, e)}/{op_base(e[2])}"] += e[4] * 1e-9
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    host = [e for e in events if e[0] == "host"]
    gaps = idle_gaps(busy_by_dev.get(devices[0], []) if devices else [],
                     lo, hi, host, top)
    host_s = defaultdict(float)
    for e in host:
        host_s[e[2][len(HOST_PREFIX):]] += max(
            0.0, min(e[3] + e[4], hi) - max(e[3], lo)) * 1e-9
    return {"window_s": win_s, "busy_s": busy_s, "host_s": dict(host_s),
            "idle_share": 1.0 - busy_s / win_s if win_s > 0 else None,
            "program_s": prog_time, "program_runs": prog_runs,
            "device_ops": [[n, s] for n, s in top_ops],
            "idle_gaps": gaps}


def _module_index(mods: Sequence[Event]) -> Dict[str, list]:
    idx: Dict[str, list] = defaultdict(list)
    for e in mods:
        idx[e[1]].append((e[3], e[3] + e[4], module_base(e[2])))
    for v in idx.values():
        v.sort()
    return idx


def _module_at(index: Dict[str, list], op: Event) -> str:
    """The program an operation ran in: the module execution that covers
    the operation's start on the same device."""
    lst = index.get(op[1], [])
    i = bisect.bisect_right(lst, (op[3], float("inf"), "")) - 1
    if i >= 0 and lst[i][0] <= op[3] <= lst[i][1]:
        return lst[i][2]
    return "?"


def idle_gaps(busy: Sequence[Sequence[float]], lo: float, hi: float,
              host: Sequence[Event], top: int) -> List[list]:
    """The longest stretches with nothing on the device, each named by
    the innermost ``bench.*`` host span that covers most of it."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]
    by_label = defaultdict(float)
    for s, e in gaps:
        by_label[_label(s, e, host)] += (e - s) * 1e-9
    return [[n, v] for n, v in sorted(by_label.items(),
                                      key=lambda kv: -kv[1])[:top]]


def _label(s: float, e: float, host: Sequence[Event]) -> str:
    best, best_key = "host (no bench span)", None
    for h in host:
        hs, he = h[3], h[3] + h[4]
        cover = min(e, he) - max(s, hs)
        if cover <= 0 or cover < 0.5 * (e - s):
            continue
        if best_key is None or h[4] < best_key:     # innermost
            best, best_key = h[2][len(HOST_PREFIX):], h[4]
    return best

