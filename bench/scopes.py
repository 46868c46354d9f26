#!/usr/bin/env python3
"""Device time by named scope, and idle gaps by the engine's innermost span.

The engine names its device work with ``jax.named_scope`` (``join.sorted.
search``, ``join.prefix_sum``, ...) and its host phases with ``rel.*``
spans (``repro.core.tracing``).  This module reads both from one
``jax.profiler`` trace:

* each device op's scope: the ``op_name`` of its instruction in the
  program's optimized HLO, which the profiler keeps in the trace (the op
  events themselves carry only the instruction), reduced to the deepest
  known scope name that is not the path's last component (the last one
  names the primitive, and ``sort`` or ``gather`` are primitives too);
  an op whose path holds none is ``unscoped:<last component>``, or
  ``unscoped:<opcode>`` where its instruction has no ``op_name``;
* the host spans: ``rel.*`` and the harness's ``query:<template>``, each
  with the index of its thread's line on the host plane.

:func:`summarize` gives device seconds by scope (per device the union of
the scope's op intervals, so that a ``while`` and the fusions of its body
count once), the share of busy time no scope names, and each idle gap
labelled ``query:<template>/<innermost rel. span>`` (per thread the
shortest ``rel.`` span over the gap's midpoint; a gap no ``rel.`` span
covers keeps the label of ``trace_reduce.label``).  The window is
``trace_reduce``'s: the first query span's start to the last one's end.

    python bench/scopes.py <profile logdir or .xplane.pb>

prints the summary as one JSON object.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import sys
import warnings
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import trace_reduce as tr  # noqa: E402

#: the scope names the engine puts on device code
SCOPES = frozenset({
    "join.sorted.sort", "join.sorted.search", "join.prefix_sum",
    "join.expand", "join.dense.build", "join.dense.probe",
    "join.dense.pallas", "join.dict.remap", "decode", "filter", "sort",
    "aggregate", "gather", "op.join", "op.join_aggregate", "op.sort",
    "op.group_by"})
ENGINE_SPAN = "rel."
UNSCOPED = "unscoped:"

Span = Tuple[str, float, float, int]       # (name, start_ns, end_ns, thread)


@dataclasses.dataclass
class ScopedTrace:
    ops: List[tr.Op]          # (scope, start_ns, end_ns, device)
    spans: List[Span]         # rel.* and query:* spans
    devices: int = 1


# -- the HLO protos the profiler keeps on its /host:metadata plane ---------
#
# The TPU's op events carry their instruction (``%while.8 = ...``) but not
# its ``op_name``, and ``jax.profiler.ProfileData`` shows no event metadata.
# The trace file also holds each program's optimized HLO, with every
# instruction's ``op_name``; a protobuf wire-format reader takes it from
# there.  Field numbers: XSpace.planes 1; XPlane.name 2, .event_metadata 4,
# .stat_metadata 5 (maps: key 1, value 2); XEventMetadata.name 2, .stats 5;
# XStatMetadata.name 2; XStat.metadata_id 1, .bytes_value 6; HloProto
# .hlo_module 1; HloModuleProto.computations 3; HloComputationProto
# .instructions 2; HloInstructionProto.name 1, .metadata 7;
# OpMetadata.op_name 2.

HLO_PROTO = "Hlo Proto"
METADATA_PLANE = "/host:metadata"
MODULE_LINE = "XLA Modules"


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf: bytes):
    """``(field number, value)`` of each field of one protobuf message: an
    int for varints, bytes for length-delimited and fixed-width fields."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} not supported")
        yield key >> 3, value


def _first(buf: bytes, number: int, default=b""):
    return next((v for n, v in _fields(buf) if n == number), default)


def _instruction_op_names(hlo_proto: bytes) -> Dict[str, str]:
    """``{instruction name: op_name}`` of every computation of a module."""
    out = {}
    module = _first(hlo_proto, 1)
    for n, comp in _fields(module):
        if n != 3:
            continue
        for m, inst in _fields(comp):
            if m == 2:
                name = _first(inst, 1).decode()
                out[name] = _first(_first(inst, 7), 2).decode()
    return out


def hlo_op_names(raw: bytes) -> Dict[str, Dict[str, str]]:
    """``{program: {instruction name: op_name}}`` from an ``.xplane.pb``'s
    bytes; a program is named as its ``XLA Modules`` events are,
    ``jit_program(<id>)``."""
    out: Dict[str, Dict[str, str]] = {}
    for n, plane in _fields(raw):
        if n != 1 or _first(plane, 2).decode() != METADATA_PLANE:
            continue
        stat_names = {}
        for m, entry in _fields(plane):
            if m == 5:
                stat_names[_first(entry, 1, 0)] = _first(_first(entry, 2),
                                                         2).decode()
        for m, entry in _fields(plane):
            if m != 4:
                continue
            meta = _first(entry, 2)
            for k, stat in _fields(meta):
                if (k == 5 and stat_names.get(_first(stat, 1, 0))
                        == HLO_PROTO):
                    out[_first(meta, 2).decode()] = _instruction_op_names(
                        _first(stat, 6))
    return out


def op_path(event_name: str, program: Dict[str, str]) -> str:
    """The ``op_name`` path of an op event from its program's HLO: the
    event's name is the instruction's (``%while.8 = ...`` on the TPU,
    ``while.8`` on the CPU); ``""`` where the program holds none."""
    inst = event_name.split(" ", 1)[0].lstrip("%")
    return program.get(inst, "")


def scope_of(path: str, fallback: str = "") -> str:
    """The deepest known scope name among ``path``'s components but the
    last, else ``unscoped:<last component>`` (``fallback`` for a path that
    is empty).  A TF-style ``name:type`` last component loses its type."""
    parts = [p for p in path.split("/") if p]
    for part in reversed(parts[:-1]):
        if part in SCOPES:
            return part
    last = parts[-1].split(":")[0] if parts else fallback
    return UNSCOPED + last


def opcode(name: str) -> str:
    """An op event's opcode (``while``, ``fusion``, ``sort``), without the
    instruction number that changes from program to program."""
    m = tr._HLO.match(name)
    if m is not None:
        return m["op"]
    return re.sub(r"\.\d+$", "", name.split(" ")[0]) if name else ""


def load(path: str, device_plane: str = tr.DEVICE_PLANE,
         op_line: str = tr.OP_LINE,
         host_plane: str = tr.HOST_PLANE) -> ScopedTrace:
    """Read the scoped device ops and the engine and query spans of one
    ``.xplane.pb``; ``op_line`` matches line names by prefix."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    programs = hlo_op_names(raw)
    data = ProfileData.from_serialized_xspace(raw)
    ops: List[tr.Op] = []
    spans: List[Span] = []
    devices = 0
    for plane in data.planes:
        if plane.name.startswith(device_plane):
            lines = list(plane.lines)
            runs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           ev.name) for line in lines
                          if line.name == MODULE_LINE for ev in line.events)
            for line in lines:
                if not line.name.startswith(op_line):
                    continue
                for ev in line.events:
                    program = _program_of(ev, runs, programs)
                    scope = scope_of(op_path(ev.name, program),
                                     fallback=opcode(ev.name))
                    ops.append((scope, ev.start_ns,
                                ev.start_ns + ev.duration_ns, devices))
            devices += 1
        if plane.name.startswith(host_plane):
            for thread, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith((ENGINE_SPAN, tr.QUERY_SPAN)):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns, thread))
    return ScopedTrace(ops=ops, spans=spans, devices=max(devices, 1))


def _program_of(ev, runs, programs: Dict[str, Dict[str, str]]
                ) -> Dict[str, str]:
    """The instruction op_names of an op event's program: named by the
    ``XLA Modules`` event that covers its start (the TPU), else by the
    event's ``hlo_module`` and ``program_id`` stats (the CPU)."""
    for start, end, name in runs:
        if start <= ev.start_ns <= end:
            return programs.get(name, {})
    with warnings.catch_warnings():
        # the first read of any event's stats warns that their builtin
        # type has no __module__; nothing is wrong with the stats
        warnings.simplefilter("ignore", DeprecationWarning)
        stats = {k: v for k, v in ev.stats}
    if "hlo_module" not in stats:
        return {}
    return programs.get(f"{stats['hlo_module']}({stats['program_id']})", {})


def scope_seconds(ops: Sequence[tr.Op], lo: float,
                  hi: float) -> Dict[str, float]:
    """Device seconds by scope over ``[lo, hi]``: per device the union of
    the scope's op intervals, summed over devices."""
    by: Dict[Tuple[str, int], list] = defaultdict(list)
    for scope, s, e, dev in ops:
        by[(scope, dev)].append((s, e))
    out: Dict[str, float] = defaultdict(float)
    for (scope, _), ivs in by.items():
        out[scope] += sum(e - s for s, e in tr.union(tr.clip(ivs, lo, hi))
                          ) / 1e9
    return {k: v for k, v in out.items() if v > 0}


def label(gap: Tuple[float, float], spans: Sequence[Span]) -> str:
    """``query:<template>/<innermost rel. span>`` for each thread that has
    a ``rel.`` span over the gap's midpoint, joined by ``+``; else the
    query spans' label."""
    mid = (gap[0] + gap[1]) / 2
    over = [sp for sp in spans if sp[1] <= mid <= sp[2]]
    names = set()
    for thread in {t for n, _, _, t in over if n.startswith(ENGINE_SPAN)}:
        mine = [sp for sp in over if sp[3] == thread]
        inner = min((sp for sp in mine if sp[0].startswith(ENGINE_SPAN)),
                    key=lambda sp: sp[2] - sp[1])[0]
        query = [n for n, *_ in mine if n.startswith(tr.QUERY_SPAN)]
        names.add(f"{'+'.join(sorted(query)) or 'no query'}/{inner}")
    if names:
        return "+".join(sorted(names))
    return tr.label(gap, [sp[:3] for sp in over
                          if sp[0].startswith(tr.QUERY_SPAN)])


def summarize(trace: ScopedTrace, top: int = 10) -> dict:
    """Device seconds by scope, the unscoped share of busy time and the
    labelled idle gaps, over the query spans' window."""
    queries = [sp for sp in trace.spans if sp[0].startswith(tr.QUERY_SPAN)]
    points = [(s, e) for _, s, e, _ in queries] or \
        [(s, e) for _, s, e, _ in trace.ops]
    if not points:
        return {"window_s": 0.0, "busy_s": 0.0, "queries": 0,
                "scope_seconds": {}, "unscoped_share": None,
                "device_scopes": [], "idle_gaps": []}
    lo = min(s for s, _ in points)
    hi = max(e for _, e in points)
    per_device = [tr.union(tr.clip([(s, e) for _, s, e, d in trace.ops
                                    if d == dev], lo, hi))
                  for dev in range(trace.devices)]
    busy_ns = sum(e - s for b in per_device for s, e in b)
    unscoped_ns = sum(
        e - s for dev in range(trace.devices)
        for s, e in tr.union(tr.clip([(s, e) for n, s, e, d in trace.ops
                                      if d == dev
                                      and n.startswith(UNSCOPED)], lo, hi)))
    seconds = scope_seconds(trace.ops, lo, hi)
    busy = tr.union([iv for b in per_device for iv in b])
    gaps = sorted(((label(g, trace.spans), (g[1] - g[0]) / 1e9)
                   for g in tr.idle_gaps(busy, lo, hi)), key=lambda x: -x[1])
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": busy_ns / 1e9 / trace.devices,
            "queries": len(queries),
            "scope_seconds": seconds,
            "unscoped_share": (unscoped_ns / busy_ns if busy_ns > 0
                               else None),
            "device_scopes": [[k, v] for k, v in sorted(
                seconds.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[k, v] for k, v in gaps[:top]]}


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.split("\n\n")[-2].strip(), file=sys.stderr)
        return 2
    path = args[0]
    if os.path.isdir(path):
        path = tr.find_xplane(path)
    print(json.dumps(summarize(load(path))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
