"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A traced run records one ``jax.profiler`` trace.  :func:`load` reads its
``.xplane.pb`` into two lists of intervals on one clock, in nanoseconds:

* the device's operations: the events of the ``XLA Ops`` line of each
  TPU device plane (``/device:TPU:<n>``);
* the harness's own spans: the ``query:<template>`` annotations that it
  wraps around every ``QueryServer.submit``, on the host plane.

:func:`summarize` reduces them: the union of the busy intervals and the
idle share of the traced window, device time by operation name, each idle
gap labelled by the query spans that cover it, and the ``breakdown``
lists of the result line.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]        # (name, start_ns, end_ns)
Op = Tuple[str, float, float, int]         # the same, and the device's index

DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
QUERY_SPAN = "query:"


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    spans: List[Interval]
    devices: int = 1


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                         # per device, averaged
    op_seconds: Dict[str, float]          # summed device time by op name
    gaps: List[Tuple[str, float]]         # idle gaps, longest first
    queries: List[str]                    # templates of the traced spans

    @property
    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def seconds_where(self, keep) -> float:
        """Device seconds of the ops whose name ``keep(name)`` accepts."""
        return sum(s for op, s in self.op_seconds.items() if keep(op))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:top]]}


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def load(path: str, device_plane: str = DEVICE_PLANE, op_line: str = OP_LINE,
         host_plane: str = HOST_PLANE, span_prefix: str = QUERY_SPAN) -> Trace:
    """Read the device ops and the query spans of one ``.xplane.pb``.

    Op events are named by :func:`op_name`; ``op_line`` matches line
    names by prefix.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: List[Op] = []
    spans: List[Interval] = []
    devices = 0
    for plane in data.planes:
        if plane.name.startswith(device_plane):
            for line in plane.lines:
                if not line.name.startswith(op_line):
                    continue
                for ev in line.events:
                    ops.append((op_name(ev.name), ev.start_ns,
                                ev.start_ns + ev.duration_ns, devices))
            devices += 1
        if plane.name.startswith(host_plane):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return Trace(ops=ops, spans=spans, devices=max(devices, 1))


_HLO = re.compile(r"%?(?P<name>[^\s=]+) = (?P<type>.+?) (?P<op>[\w-]+)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(text: str) -> str:
    """A short, stable name for an op event: its HLO instruction name,
    opcode and result type without layouts, and a custom call's target.
    On the TPU an event's name is the whole HLO instruction, such as
    ``%radix_hash_probe.3 = (s32[8388608]{0:T(1024)}, ...) custom-call(...),
    custom_call_target="tpu_custom_call", ...``."""
    m = _HLO.match(text)
    if m is None:
        return text[:120]
    kind = re.sub(r"{[^}]*}|/\*[^*]*\*/", "", m["type"])
    out = f"{m['name']} {m['op']} {kind[:80]}"
    target = _TARGET.search(text)
    return f"{out} {target.group(1)}" if target else out


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The union of ``(start, end)`` intervals, merged and sorted."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def idle_gaps(busy: Sequence[Tuple[float, float]], lo: float,
              hi: float) -> List[Tuple[float, float]]:
    """The intervals of ``[lo, hi]`` that ``busy`` (merged) leaves free."""
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def label(gap: Tuple[float, float], spans: Sequence[Interval]) -> str:
    """The names of the query spans that cover the gap's midpoint."""
    mid = (gap[0] + gap[1]) / 2
    names = sorted({n for n, s, e in spans if s <= mid <= e})
    return "+".join(names) if names else "no query"


def summarize(trace: Trace) -> TraceSummary:
    """Reduce a trace over its window: from the first query span's start to
    the last one's end (the whole trace, where it has no query span)."""
    points = [(s, e) for _, s, e in trace.spans] or \
        [(s, e) for _, s, e, _ in trace.ops]
    if not points:
        return TraceSummary(0.0, 0.0, {}, [], [])
    lo = min(s for s, _ in points)
    hi = max(e for _, e in points)
    per_device = [union(clip([(s, e) for _, s, e, d in trace.ops if d == dev],
                             lo, hi)) for dev in range(trace.devices)]
    busy_ns = sum(e - s for b in per_device for s, e in b) / trace.devices
    # idle gaps: where no device runs anything
    busy = union([iv for b in per_device for iv in b])
    op_seconds: Dict[str, float] = defaultdict(float)
    for name, s, e, _ in trace.ops:
        if e > lo and s < hi:
            op_seconds[name] += (min(e, hi) - max(s, lo)) / 1e9
    gaps = sorted(((label(g, trace.spans), (g[1] - g[0]) / 1e9)
                   for g in idle_gaps(busy, lo, hi)), key=lambda x: -x[1])
    queries = [n[len(QUERY_SPAN):] for n, _, _ in trace.spans]
    return TraceSummary(window_s=(hi - lo) / 1e9, busy_s=busy_ns / 1e9,
                        op_seconds=dict(op_seconds), gaps=gaps,
                        queries=queries)
