"""Reduce a JAX profiler trace to device busy time, op times and idle gaps.

A trace is read into plain `Event` tuples (plane, line, name, start, end in
nanoseconds), so the reduction is ordinary code that a test can check on a
small recorded trace. Device operations are the events on the `XLA Ops`
line of each `/device:TPU:<n>` plane; a loop and the operations of its body
both appear there, so busy time is the union of all of them, and time by
name counts the innermost operations only. Host spans are the events on the
`/host:CPU` plane whose names start with `bench:`: the benchmark marks its
own calls into the program with `jax.profiler.TraceAnnotation`.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

OPS_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MARK = "bench:"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start: float          # ns
    end: float            # ns


def load(trace_dir: str) -> List[Event]:
    """The device operations and the benchmark's host spans of every
    `.xplane.pb` under `trace_dir`. An operation is named by its HLO
    instruction (`%env_step_pallas.7 = ...` reads `env_step_pallas.7`)."""
    from jax.profiler import ProfileData
    out: List[Event] = []
    for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True):
        for plane in ProfileData.from_file(path).planes:
            device = plane.name.startswith(DEVICE_PLANE)
            if not (device or plane.name == HOST_PLANE):
                continue
            for line in plane.lines:
                if device and line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name = ev.name
                    if not device and not name.startswith(MARK):
                        continue
                    if device:
                        name = name.split(" = ", 1)[0].lstrip("%")
                    s = float(ev.start_ns)
                    out.append(Event(plane.name, line.name, name, s,
                                     s + float(ev.duration_ns)))
    return out


def leaves(events: List[Event]) -> List[Event]:
    """The operations that contain no other: a loop and the operations of
    its body share the line, and only the body's are counted by name."""
    ev = sorted(events, key=lambda e: (e.start, -e.end))
    return [e for e, nxt in zip(ev, ev[1:] + [None])
            if nxt is None or nxt.start >= e.end]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


@dataclass
class DeviceTrace:
    window_s: float
    busy_s: float                         # mean over the devices traced
    devices: int
    op_seconds: Dict[str, float] = field(default_factory=dict)
    op_calls: Dict[str, int] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def ops_matching(self, fragment: str) -> Tuple[float, int]:
        """(seconds, calls) summed over every op whose name contains
        `fragment`, on all devices traced."""
        s = sum(v for k, v in self.op_seconds.items() if fragment in k)
        n = sum(v for k, v in self.op_calls.items() if fragment in k)
        return s, n

    def breakdown(self, n: int = 10) -> Dict[str, list]:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.idle_gaps, key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def reduce(events: List[Event], window: str = MARK + "profiled"
           ) -> Optional[DeviceTrace]:
    """Busy time, op times and idle gaps inside the host span named
    `window`. Returns None when the trace holds no such span or no device
    operation inside it."""
    spans = [e for e in events if e.plane == HOST_PLANE and e.name == window]
    if not spans:
        return None
    lo, hi = min(e.start for e in spans), max(e.end for e in spans)
    marks = [e for e in events if e.plane == HOST_PLANE
             and e.name.startswith(MARK) and e.name != window]
    ops = [e for e in events if e.plane.startswith(DEVICE_PLANE)
           and e.line == OPS_LINE and e.end > lo and e.start < hi]
    if not ops:
        return None
    planes = sorted({e.plane for e in ops})
    busy, gaps = [], []
    op_s: Dict[str, float] = {}
    op_n: Dict[str, int] = {}
    for pl in planes:
        mine = [e for e in ops if e.plane == pl]
        merged = _clip(union((e.start, e.end) for e in mine), lo, hi)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if pl == planes[0]:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e > s:
                    gaps.append((_label(marks, (s + e) / 2), (e - s) * 1e-9))
        for e in leaves(mine):
            d = (min(e.end, hi) - max(e.start, lo)) * 1e-9
            op_s[e.name] = op_s.get(e.name, 0.0) + d
            op_n[e.name] = op_n.get(e.name, 0) + 1
    return DeviceTrace(window_s=(hi - lo) * 1e-9,
                       busy_s=sum(busy) / len(busy), devices=len(planes),
                       op_seconds=op_s, op_calls=op_n, idle_gaps=gaps)


def _label(marks: List[Event], t: float) -> str:
    """The innermost benchmark span around time t, which says what the host
    was doing while the device waited."""
    inside = [m for m in marks if m.start <= t < m.end]
    if not inside:
        return "between benchmark calls"
    return min(inside, key=lambda m: m.end - m.start).name[len(MARK):]
