"""Reduce a profiler trace by the program's own spans and named scopes.

``trace.py`` reduces the traced window by the benchmark's ``bench.*`` spans
and XLA's op names.  This module reads the same ``.xplane.pb`` for what the
program marks itself:

- host spans named ``serve.*``, which ``Server.generate`` opens with
  ``repro.obs.trace.TRACER.annotated`` (a ``TraceAnnotation`` each);
- each device op's scope: the last component of the op's HLO ``op_name``
  path that is one of ``SCOPES``, the ``jax.named_scope`` names in
  ``repro.models``, else ``unscoped``.  XLA numbers its fusions anew after
  any change to a step; the scope survives the change.

``reduce`` gives device self time per scope, idle time and span time per
span, and the longest idle gaps named by the innermost ``bench.*`` or
``serve.*`` span.  The harness hands a reader only ``trace.reduce``'s
numbers, so ``for_reduction`` finds the traced run's file itself and checks
that it is the file those numbers came from.

    python3 chipbench/program_trace.py <trace directory>

prints the reduction of a trace that a ``--trace 1`` run left behind.
"""
from __future__ import annotations

import functools
import heapq
import json
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import trace  # noqa: E402

SCOPES = ("embed", "norm", "attention", "kv_cache", "mlp", "head")
UNSCOPED = "unscoped"
SPAN_PREFIXES = ("bench.", "serve.")
#: the stat of a device op's metadata that holds the op's HLO ``op_name``,
#: as ``<op_name>:``, in a v5e trace (jax 0.9.0)
OP_NAME_STAT = "tf_op"
#: how close two reductions' windows lie when they read one trace: far
#: below the host's jitter from one traced window to the next
WINDOW_MATCH_S = 1e-6
#: where ``run.py`` leaves each traced run's trace
TRACES = Path(__file__).resolve().parents[1] / "experiments" / "chipbench" / "trace"

Event = Tuple[float, float, str]          # (start_ns, duration_ns, name or scope)

#: the fields of the profiler's ``xplane.proto`` read here, with its field
#: numbers; a map is read as the repeated (key, value) entries it is on the
#: wire.  ``jax.profiler.ProfileData`` shows neither an event's metadata id
#: nor the metadata's stats, where a device op's ``tf_op`` sits.
XPLANE_FIELDS = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, str, False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True)],
    "EventMetadataEntry": [("key", 1, int, False), ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, int, False), ("value", 2, "XStatMetadata", False)],
    "XLine": [("name", 2, str, False), ("timestamp_ns", 3, int, False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, int, False), ("offset_ps", 2, int, False),
               ("duration_ps", 3, int, False)],
    "XStat": [("metadata_id", 1, int, False), ("str_value", 5, str, False)],
    "XEventMetadata": [("name", 2, str, False), ("stats", 5, "XStat", True)],
    "XStatMetadata": [("name", 2, str, False)],
}


@functools.lru_cache(maxsize=1)
def xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory
    F = descriptor_pb2.FieldDescriptorProto
    scalar = {str: F.TYPE_STRING, int: F.TYPE_INT64}
    proto = descriptor_pb2.FileDescriptorProto(name="chipbench_xplane.proto",
                                               package="chipbench_xplane")
    for message, fields in XPLANE_FIELDS.items():
        m = proto.message_type.add(name=message)
        for name, number, kind, repeated in fields:
            f = m.field.add(name=name, number=number,
                            label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if kind in scalar:
                f.type = scalar[kind]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, f".chipbench_xplane.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench_xplane.XSpace"))


def scope_of(op_name: str) -> str:
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def parse(data: bytes) -> Dict[str, object]:
    """{"devices": {plane: [(start, duration, scope)]}, "spans": [Event]} from
    a serialized XSpace: each device's ops on its ``XLA Ops`` line, and the
    host spans named ``bench.*`` or ``serve.*``; times in nanoseconds."""
    space = xspace_class()()
    space.ParseFromString(data)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in space.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            op_name = {m.key for m in plane.stat_metadata if m.value.name == OP_NAME_STAT}
            scopes = {m.key: scope_of(next((st.str_value for st in m.value.stats
                                            if st.metadata_id in op_name), ""))
                      for m in plane.event_metadata}
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ts = line.timestamp_ns
                    ops.extend((ts + e.offset_ps / 1000, e.duration_ps / 1000,
                                scopes.get(e.metadata_id, UNSCOPED))
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            names = {m.key: m.value.name for m in plane.event_metadata}
            kept = {k for k, n in names.items() if n.startswith(SPAN_PREFIXES)}
            for line in plane.lines:
                ts = line.timestamp_ns
                spans.extend((ts + e.offset_ps / 1000, e.duration_ps / 1000,
                              names[e.metadata_id])
                             for e in line.events if e.metadata_id in kept)
    return {"devices": devices, "spans": spans}


def load(path: Path) -> Dict[str, object]:
    return parse(Path(path).read_bytes())


# ---------------------------------------------------------------------------

def innermost_pieces(spans: Sequence[Event], t0: float, t1: float
                     ) -> List[Tuple[float, float, str]]:
    """[t0, t1] cut where any span starts or ends, each piece named by the
    shortest span that holds it ("no span" where none does)."""
    inside = sorted((max(s, t0), min(s + d, t1), d, n)
                    for s, d, n in spans if s + d > t0 and s < t1)
    cuts = sorted({t0, t1} | {x for a, b, _, _ in inside for x in (a, b)})
    pieces, live, i = [], [], 0               # live: heap of (duration, end, name)
    for a, b in zip(cuts, cuts[1:]):
        while i < len(inside) and inside[i][0] <= a:
            _, end, d, name = inside[i]
            heapq.heappush(live, (d, end, name))
            i += 1
        while live and live[0][1] <= a:
            heapq.heappop(live)
        pieces.append((a, b, live[0][2] if live else "no span"))
    return pieces


def overlap_by_name(intervals: Sequence[Tuple[float, float]],
                    pieces: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Time of ``intervals`` (a union, sorted) in each piece, by piece name."""
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for a, b in intervals:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            pa, pb, name = pieces[k]
            out[name] += min(b, pb) - max(a, pa)
            k += 1
    return out


def reduce(tr: Dict[str, object]) -> Dict[str, object]:
    """Program metrics over the first ``bench.window`` span of the trace, in
    seconds: ``scope_s`` device self time per scope and ``idle_by_span_s``
    idle time per innermost span, both averaged over devices;
    ``idle_in_span_s`` idle time inside any span of each name, averaged
    likewise; ``span_s`` the seconds each span name covers in the window;
    ``idle_gaps`` the longest gaps, named by the innermost span."""
    spans = tr["spans"]
    windows = [s for s in spans if s[2] == trace.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {trace.WINDOW_SPAN} span")
    t0, dur, _ = min(windows)
    t1 = t0 + dur
    window_s = (t1 - t0) * 1e-9
    devices = {k: v for k, v in tr["devices"].items() if v}
    n = len(devices)
    pieces = innermost_pieces(spans, t0, t1)
    by_name: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for s, d, name in spans:
        by_name[name].append((s, s + d))
    by_name = {k: trace.union(trace.clip(v, t0, t1)) for k, v in by_name.items()}
    scope_s: Dict[str, float] = defaultdict(float)
    idle_by_span: Dict[str, float] = defaultdict(float)
    idle_in_span: Dict[str, float] = defaultdict(float)
    gaps = []
    for events in devices.values():
        ev = [(max(s, t0), min(s + d, t1) - max(s, t0), sc)
              for s, d, sc in events if s + d > t0 and s < t1]
        for scope, t in trace.self_times(ev).items():
            scope_s[scope] += t / n
        idle = trace.subtract([(t0, t1)], trace.union([(s, s + d) for s, d, _ in ev]))
        for name, t in overlap_by_name(idle, pieces).items():
            idle_by_span[name] += t / n
        for name, u in by_name.items():
            idle_in_span[name] += trace.measure(trace.subtract(idle, trace.subtract(idle, u))) / n
        gaps.extend((b - a, (a + b) / 2) for a, b in idle)
    gaps.sort(key=lambda g: -g[0])
    seconds = lambda ns: {k: v * 1e-9 for k, v in sorted(ns.items())}
    return {
        "window_s": window_s,
        "scope_s": seconds(scope_s),
        "idle_by_span_s": seconds(idle_by_span),
        "idle_in_span_s": seconds(idle_in_span),
        "span_s": seconds({k: trace.measure(v) for k, v in by_name.items()}),
        "idle_gaps": [[trace._innermost_span(spans, mid), g * 1e-9]
                      for g, mid in gaps[:trace.TOP]],
    }


# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, mtime_ns: int) -> Dict[str, object]:
    return reduce(load(Path(path)))


def for_reduction(reduced: Optional[Dict[str, object]]) -> Optional[Dict[str, object]]:
    """The program reduction of the trace that ``reduced`` (``trace.reduce``'s
    result, as readers get it) came from: the newest trace ``run.py`` left,
    if its window is that of ``reduced``; else None."""
    if not reduced:
        return None
    found = sorted(TRACES.glob("*/plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns)
    if not found:
        return None
    try:
        out = _reduce_file(str(found[-1]), found[-1].stat().st_mtime_ns)
    except ValueError:                        # not a traced window of run.py's
        return None
    return out if abs(out["window_s"] - reduced["window_s"]) < WINDOW_MATCH_S else None


def main(argv: Sequence[str]) -> int:
    print(json.dumps(reduce(load(trace.xplane_file(Path(argv[0])))), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
