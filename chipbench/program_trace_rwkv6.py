"""The program reduction of a traced run (``program_trace.py``) by the
rwkv6 model's named scopes: ``embed``, ``norm``, ``time_mix``, ``wkv``,
``channel_mix`` and ``head`` (``models/rwkv.py``, ``rwkv_model.py``) in
place of the decoder's.

XLA gives a fusion the ``op_name`` of its root.  In the rwkv6 decode step
the fusion that reads and updates a layer's WKV state has for root the
layer loop's own stacking of the new state, which names no scope, so its
device time would fall outside ``wkv``.  ``fused_scopes`` reads the
compiled step's HLO for such ops: where the ops fused into one name a
single scope, the op is charged to it.

    python3 chipbench/program_trace_rwkv6.py <trace directory> [<run>.reduced.json]

prints the reduction of a trace that a ``--trace 1`` run left behind, with
the fused ops' scopes from the run's counts where its ``.reduced.json`` is
given.
"""
from __future__ import annotations

import functools
import json
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import program_trace, trace  # noqa: E402

SCOPES = ("embed", "norm", "time_mix", "wkv", "channel_mix", "head")

_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) ")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = ")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(op_name: str) -> str:
    """``program_trace.scope_of`` by the rwkv6 scopes."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return program_trace.UNSCOPED


def _key(op_name: str, op: str) -> str:
    """An op of one jitted step: ``jit(decode_step)/fusion.241``."""
    return op_name.split("/", 1)[0] + "/" + op


def fused_scopes(hlo: str) -> Dict[str, str]:
    """{``<jit>/<op>``: scope} for the ops of a compiled module (its text)
    whose own ``op_name`` names no scope while the ops fused into them name
    exactly one."""
    comps: Dict[str, List[str]] = {}
    lines: List[str] = []
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            lines = comps.setdefault(m.group(1), [])
        elif _INSTRUCTION.match(line):
            lines.append(line)

    def scope(line: str) -> str:
        m = _OP_NAME.search(line)
        return scope_of(m.group(1) if m else "")

    def inner(comp: str) -> set:
        """The scopes named in a computation and in those it calls."""
        out = set()
        for line in comps.get(comp, ()):
            out.add(scope(line))
            for called in _CALLS.findall(line):
                out |= inner(called)
        return out

    fused = {c for body in comps.values() for line in body for c in _CALLS.findall(line)}
    out = {}
    for comp, body in comps.items():
        if comp in fused:
            continue
        for line in body:
            own = _OP_NAME.search(line)
            if own is None or scope(line) != program_trace.UNSCOPED:
                continue
            named = set().union(*(inner(c) for c in _CALLS.findall(line)))
            named.discard(program_trace.UNSCOPED)
            if len(named) == 1:
                out[_key(own.group(1), _INSTRUCTION.match(line).group(1))] = named.pop()
    return out


def parse(data: bytes, op_scopes: Optional[Dict[str, str]] = None) -> Dict[str, object]:
    """``program_trace.parse`` by the rwkv6 scopes, where an op that
    ``op_scopes`` names (``fused_scopes``'s keys) takes the scope it gives."""
    op_scopes = op_scopes or {}
    space = program_trace.xspace_class()()
    space.ParseFromString(data)
    devices: Dict[str, List[program_trace.Event]] = {}
    spans: List[program_trace.Event] = []
    for plane in space.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            tf_op = {m.key for m in plane.stat_metadata
                     if m.value.name == program_trace.OP_NAME_STAT}
            scopes = {}
            for m in plane.event_metadata:
                path = next((st.str_value for st in m.value.stats
                             if st.metadata_id in tf_op), "")
                scopes[m.key] = (op_scopes.get(_key(path, trace.op_name(m.value.name)))
                                 or scope_of(path))
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ts = line.timestamp_ns
                    ops.extend((ts + e.offset_ps / 1000, e.duration_ps / 1000,
                                scopes.get(e.metadata_id, program_trace.UNSCOPED))
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            names = {m.key: m.value.name for m in plane.event_metadata}
            for line in plane.lines:
                ts = line.timestamp_ns
                spans.extend((ts + e.offset_ps / 1000, e.duration_ps / 1000,
                              names[e.metadata_id]) for e in line.events
                             if names[e.metadata_id].startswith(program_trace.SPAN_PREFIXES))
    return {"devices": devices, "spans": spans}


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, mtime_ns: int, op_scopes: tuple) -> Dict[str, object]:
    return program_trace.reduce(parse(Path(path).read_bytes(), dict(op_scopes)))


def for_reduction(reduced: Optional[Dict[str, object]],
                  op_scopes: Optional[Dict[str, str]] = None) -> Optional[Dict[str, object]]:
    """As ``program_trace.for_reduction``, by the rwkv6 scopes: the
    reduction of the newest trace ``run.py`` left, if its window is that of
    ``reduced``; else None."""
    if not reduced:
        return None
    found = sorted(program_trace.TRACES.glob("*/plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns)
    if not found:
        return None
    try:
        out = _reduce_file(str(found[-1]), found[-1].stat().st_mtime_ns,
                           tuple(sorted((op_scopes or {}).items())))
    except ValueError:                        # not a traced window of run.py's
        return None
    close = abs(out["window_s"] - reduced["window_s"]) < program_trace.WINDOW_MATCH_S
    return out if close else None


def main(argv: Sequence[str]) -> int:
    data = trace.xplane_file(Path(argv[0])).read_bytes()
    op_scopes = (json.loads(Path(argv[1]).read_text())["counts"]["op_scopes"]
                 if len(argv) > 1 else None)
    print(json.dumps(program_trace.reduce(parse(data, op_scopes)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
