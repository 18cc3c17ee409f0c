"""Reduce a JAX profiler trace to the device metrics the benchmark reports.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
each device's XLA ops and asynchronous ops (name, start, duration) and the
benchmark's own host spans (``bench.*``, written with ``TraceAnnotation``).  ``reduce``
turns those into busy and idle time, collective time that no compute hides,
the ops that took most time and the longest idle gaps, each named by the
host span it fell in.  Both work on any trace, so a test can check them on
a small recorded one.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(
    r"all-gather|reduce-scatter|all-reduce|collective-permute|all-to-all")
TOP = 10

Event = Tuple[float, float, str]          # (start_ns, duration_ns, name)


def xplane_file(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(name: str) -> str:
    """``fusion.3`` from an event named by its whole HLO instruction
    (``%fusion.3 = bf16[...] fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def load_profile(profile) -> Dict[str, object]:
    """{"devices": {plane: [Event]}, "async": {plane: [Event]},
    "spans": [Event]} from a ProfileData."""
    devices: Dict[str, List[Event]] = {}
    asyncs: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    summary = []
    for plane in profile.planes:
        lines = list(plane.lines)
        summary.append((plane.name, [ln.name for ln in lines]))
        if DEVICE_PLANE.match(plane.name):
            for ln in lines:
                if ln.name in (OPS_LINE, ASYNC_LINE):
                    into = devices if ln.name == OPS_LINE else asyncs
                    into.setdefault(plane.name, []).extend(
                        (e.start_ns, e.duration_ns, op_name(e.name)) for e in ln.events)
        elif plane.name.startswith("/host:"):
            for ln in lines:
                spans.extend((e.start_ns, e.duration_ns, e.name)
                             for e in ln.events if e.name.startswith(SPAN_PREFIX))
    if not any(devices.values()):
        raise ValueError(f"no '{OPS_LINE}' events on any TPU plane; "
                         f"planes and lines: {summary}")
    return {"devices": devices, "async": asyncs, "spans": spans}


def load(trace_dir: Path) -> Dict[str, object]:
    from jax.profiler import ProfileData
    return load_profile(ProfileData.from_file(str(xplane_file(trace_dir))))


# ---------------------------------------------------------------------------
# interval arithmetic

def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def measure(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(base: Sequence[Tuple[float, float]],
             cut: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """``base`` minus ``cut``, both already unions."""
    out, j = [], 0
    for a, b in base:
        while j < len(cut) and cut[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(cut) and cut[k][0] < b:
            if cut[k][0] > cur:
                out.append((cur, cut[k][0]))
            cur = max(cur, cut[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def clip(intervals, t0: float, t1: float):
    return [(max(a, t0), min(b, t1)) for a, b in intervals if b > t0 and a < t1]


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Per op name, its time minus that of ops nested inside it."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []            # [end, name, child_time]
    for start, dur, name in sorted(events, key=lambda e: (e[0], -e[1])):
        # an op is nested in the one before only if it ends inside it
        while stack and (stack[-1][0] <= start or start + dur > stack[-1][0]):
            end, n, child = stack.pop()
            out[n] -= child
        if stack:
            stack[-1][2] += dur
        out[name] += dur
        stack.append([start + dur, name, 0.0])
    for end, n, child in stack:
        out[n] -= child
    return out


def _innermost_span(spans: Sequence[Event], t: float) -> str:
    best = None
    for start, dur, name in spans:
        if start <= t <= start + dur and (best is None or dur < best[0]):
            best = (dur, name)
    return best[1] if best else "outside the window"


# ---------------------------------------------------------------------------

def reduce(trace: Dict[str, object]) -> Dict[str, object]:
    """Device metrics over the first ``bench.window`` span of the trace.

    ``busy_s`` is the union of op intervals inside the window, averaged over
    devices; ``exposed_collective_s`` the part of the union of collective
    ops, synchronous or asynchronous, during which no other op runs on that
    device, averaged likewise.  Times are in seconds."""
    spans = trace["spans"]
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN} span")
    t0, dur, _ = min(windows)
    t1 = t0 + dur
    devices = {k: v for k, v in trace["devices"].items() if v}
    busy, exposed, collective = [], [], []
    op_time: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for plane, events in devices.items():
        inside = lambda evs: [(max(s, t0), min(s + d, t1) - max(s, t0), n)
                              for s, d, n in evs if s + d > t0 and s < t1]
        ev = inside(events)
        ops = union([(s, s + d) for s, d, _ in ev])
        coll = union([(s, s + d) for s, d, n in ev + inside(trace["async"].get(plane, []))
                      if COLLECTIVE.search(n)])
        comp = union([(s, s + d) for s, d, n in ev if not COLLECTIVE.search(n)])
        busy.append(measure(ops))
        collective.append(measure(coll))
        exposed.append(measure(subtract(coll, comp)))
        for name, t in self_times(ev).items():
            op_time[name] += t / len(devices)
        for a, b in subtract([(t0, t1)], ops):
            gaps.append((b - a, (a + b) / 2))
    n = len(devices)
    gaps.sort(key=lambda g: -g[0])
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "devices": n,
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "collective_s": sum(collective) / n * 1e-9,
        "exposed_collective_s": sum(exposed) / n * 1e-9,
        "device_ops": [[name, t * 1e-9] for name, t in top_ops],
        "idle_gaps": [[_innermost_span(spans, mid), g * 1e-9]
                      for g, mid in gaps[:TOP]],
    }
