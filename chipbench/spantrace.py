"""Attribute a profiled window's device idle time to the program's spans.

`devtrace` labels each idle gap with the benchmark's own `bench:` marks,
which sit around its calls into the program. The program's tracer also
writes every one of its spans into the profile, as a host event named
`eat:<span>` with the span's args as stats (`repro.telemetry.trace`), on
the device's clock. This module reads those too, and keeps what
`devtrace` computes as it is: `reduce` calls `devtrace.reduce` on the same
events, so `busy_s`, `window_s`, `op_seconds`, `op_calls` and every gap's
duration are the same numbers. It adds:

* gap labels from the innermost span of either kind (a program span by its
  name, a benchmark mark as `bench:<name>`);
* `idle_by_span`: idle seconds per innermost span, each gap cut at the
  spans' edges, so a gap that covers two spans is divided between them;
* `span_calls`: per program span started in the window, its count and the
  sums of its numeric args (the `steps` of `decode`);
* each device op's named scope, from the `tf_op` stat of its event
  metadata (the op's `jax.named_scope` path), which `ProfileData` does not
  expose: `_tf_ops` reads it from the `.xplane.pb` with protobuf.

The profile puts host and device events on one clock only roughly: on a
v5e a program's device events can read over a millisecond earlier than the
host call that launched it. Each launch is on both sides (the host's
`DoEnqueueProgram` and the device's `XLA Modules` event carry its
`run_id`), and a program cannot start before its launch, so the largest
lead of host over device among them is taken as the clock offset and the
spans are moved onto the device's clock by it before they name the gaps.
`devtrace`'s numbers are left as they are.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Tuple

from chipbench import devtrace
from chipbench.devtrace import DEVICE_PLANE, HOST_PLANE, MARK, OPS_LINE, Event

PROGRAM = "eat:"
NO_SPAN = "between benchmark calls"
LAUNCH = "DoEnqueueProgram"
MODULES_LINE = "XLA Modules"


class Span(NamedTuple):
    name: str             # with its prefix: `eat:decode`, `bench:window`
    start: float          # ns
    end: float            # ns
    args: Dict


class Launch(NamedTuple):
    plane: str            # the device plane the program ran on
    host: float           # ns: its launch began on the host
    device: float         # ns: it began on the device, by the device


# (device plane, start in ns, op name) -> the op's scope path
Scopes = Dict[Tuple[str, float, str], str]


def load(trace_dir: str
         ) -> Tuple[List[Event], List[Span], Scopes, List[Launch]]:
    """`devtrace.load`'s events, the program's spans with their args, the
    scope path of every device op that has one, and the launches seen on
    both the host and the device."""
    from jax.profiler import ProfileData
    spans: List[Span] = []
    scopes: Scopes = {}
    launched: Dict[Tuple[int, int], float] = {}
    began: Dict[Tuple[int, int], Tuple[str, float]] = {}
    for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True):
        tf_op = _tf_ops(path)
        for plane in ProfileData.from_file(path).planes:
            device = plane.name.startswith(DEVICE_PLANE)
            if not (device or plane.name == HOST_PLANE):
                continue
            mine = tf_op.get(plane.name, {})
            for line in plane.lines:
                if device and line.name == MODULES_LINE:
                    ordinal = int(plane.name[len(DEVICE_PLANE):])
                    for ev in line.events:
                        run = dict(ev.stats).get("run_id")
                        if run is not None:
                            began[(ordinal, run)] = (plane.name,
                                                     float(ev.start_ns))
                if device and line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = float(ev.start_ns)
                    if not device and ev.name == LAUNCH:
                        st = dict(ev.stats)
                        if "run_id" in st:
                            launched[(st.get("device_ordinal", 0),
                                      st["run_id"])] = s
                    elif device:
                        scope = mine.get(ev.name)
                        if scope:
                            name = ev.name.split(" = ", 1)[0].lstrip("%")
                            scopes[(plane.name, s, name)] = scope
                    elif ev.name.startswith(PROGRAM):
                        spans.append(Span(ev.name, s,
                                          s + float(ev.duration_ns),
                                          dict(ev.stats)))
    launches = [Launch(began[k][0], t, began[k][1])
                for k, t in launched.items() if k in began]
    return devtrace.load(trace_dir), spans, scopes, launches


def clock_offset(launches: List[Launch], plane: str) -> float:
    """Host clock minus device clock on `plane`, in ns: the largest lead of
    a launch on the host over its program's start on the device (0 with
    no launch seen on both)."""
    return max((la.host - la.device for la in launches if la.plane == plane),
               default=0.0)


@dataclass
class SpanTrace(devtrace.DeviceTrace):
    idle_by_span: Dict[str, float] = field(default_factory=dict)
    span_calls: Dict[str, Dict[str, float]] = field(default_factory=dict)
    scope_op_seconds: Dict[str, float] = field(default_factory=dict)
    scope_op_calls: Dict[str, int] = field(default_factory=dict)
    clock_offset_s: float = 0.0

    def scope_seconds(self, scope: str) -> Tuple[float, int]:
        """(seconds, calls) of the innermost device ops whose scope path
        holds `scope` as one of its parts (`policy` matches
        `.../vmap(policy)/dot_general`), on all devices traced."""
        hit = [k for k in self.scope_op_seconds if in_scope(k, scope)]
        return (sum(self.scope_op_seconds[k] for k in hit),
                sum(self.scope_op_calls[k] for k in hit))


def in_scope(path: str, scope: str) -> bool:
    return any(p == scope or p.endswith(f"({scope})")
               for p in re.split(r"[/;]", path.rstrip(":")))


def reduce(events: List[Event], spans: List[Span], scopes: Scopes,
           launches: List[Launch] = (), window: str = MARK + "profiled"
           ) -> Optional[SpanTrace]:
    """`devtrace.reduce`'s trace of the window, with the gaps named by the
    program's spans (moved onto the first device's clock), the idle time
    and calls per span, and the op time per scope path."""
    base = devtrace.reduce(events, window)
    if base is None:
        return None
    win = [e for e in events if e.plane == HOST_PLANE and e.name == window]
    lo, hi = min(e.start for e in win), max(e.end for e in win)
    ops = [e for e in events if e.plane.startswith(DEVICE_PLANE)
           and e.line == OPS_LINE and e.end > lo and e.start < hi]
    planes = sorted({e.plane for e in ops})
    off = clock_offset(list(launches), planes[0])
    marks = [Span(e.name, e.start, e.end, {}) for e in events
             if e.plane == HOST_PLANE and e.name.startswith(MARK)
             and e.name != window]
    inside = [s for s in spans if s.end - off > lo and s.start - off < hi]
    segs = segments([sp._replace(start=sp.start - off, end=sp.end - off)
                     for sp in marks + inside], lo, hi)
    first = [e for e in ops if e.plane == planes[0]]
    merged = devtrace._clip(devtrace.union((e.start, e.end) for e in first),
                            lo, hi)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    idle: Dict[str, float] = {}
    j = 0
    for s, e in gaps:             # both in time order: one pass
        while segs[j][1] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < e:
            a, b, name = segs[k]
            idle[name] = idle.get(name, 0.0) + (min(b, e) - max(a, s)) * 1e-9
            k += 1
    calls: Dict[str, Dict[str, float]] = {}
    for sp in spans:              # called inside the window, by the host
        if not lo <= sp.start < hi:
            continue
        rec = calls.setdefault(sp.name[len(PROGRAM):], {"calls": 0})
        rec["calls"] += 1
        for k, v in sp.args.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                rec[k] = rec.get(k, 0) + v
    sc_s: Dict[str, float] = {}
    sc_n: Dict[str, int] = {}
    for pl in planes:
        for e in devtrace.leaves([o for o in ops if o.plane == pl]):
            scope = scopes.get((pl, e.start, e.name))
            if scope:
                d = (min(e.end, hi) - max(e.start, lo)) * 1e-9
                sc_s[scope] = sc_s.get(scope, 0.0) + d
                sc_n[scope] = sc_n.get(scope, 0) + 1
    starts = [a for a, _, _ in segs]
    labelled = [(segs[bisect.bisect_right(starts, (s + e) / 2) - 1][2],
                 dur) for (s, e), (_, dur) in zip(gaps, base.idle_gaps)]
    return SpanTrace(window_s=base.window_s, busy_s=base.busy_s,
                     devices=base.devices, op_seconds=base.op_seconds,
                     op_calls=base.op_calls, idle_gaps=labelled,
                     idle_by_span=idle, span_calls=calls,
                     scope_op_seconds=sc_s, scope_op_calls=sc_n,
                     clock_offset_s=off * 1e-9)


def label(span: Span) -> str:
    if span.name.startswith(PROGRAM):
        return span.name[len(PROGRAM):]
    return span.name


def segments(spans: List[Span], lo: float, hi: float
             ) -> List[Tuple[float, float, str]]:
    """[lo, hi) cut at every span edge, each piece named by the innermost
    (shortest) span open over it, or `NO_SPAN`."""
    cuts = sorted({lo, hi} | {t for sp in spans for t in (sp.start, sp.end)
                              if lo < t < hi})
    order = sorted(spans, key=lambda sp: sp.start)
    out: List[Tuple[float, float, str]] = []
    active: List[Span] = []
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(order) and order[i].start <= a:
            active.append(order[i])
            i += 1
        active = [sp for sp in active if sp.end > a]
        inner = min(active, key=lambda sp: sp.end - sp.start, default=None)
        out.append((a, b, NO_SPAN if inner is None else label(inner)))
    return out


# ---- the `tf_op` stat of each device op's event metadata ----------------
def _tf_ops(path: str) -> Dict[str, Dict[str, str]]:
    """{device plane: {event metadata name: tf_op}} of one `.xplane.pb`."""
    xspace = _xspace_type()()
    with open(path, "rb") as f:
        xspace.ParseFromString(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for plane in xspace.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        names = {m.key: m.value.name for m in plane.stat_metadata}
        want = [k for k, v in names.items() if v == "tf_op"]
        if not want:
            continue
        mine = out[plane.name] = {}
        for m in plane.event_metadata:
            for st in m.value.stats:
                if st.metadata_id == want[0]:
                    mine[m.value.name] = (st.str_value
                                          or names.get(st.ref_value, ""))
    return out


_XSPACE = None


def _xspace_type():
    """The few fields of tsl's `XSpace` (tsl/profiler/protobuf/xplane.proto)
    this module reads, as a protobuf message class built at run time."""
    global _XSPACE
    if _XSPACE is None:
        from google.protobuf import descriptor_pb2, descriptor_pool
        from google.protobuf import message_factory
        F = descriptor_pb2.FieldDescriptorProto
        f = descriptor_pb2.FileDescriptorProto(
            name="chipbench_xplane.proto", package="chipbench_xplane",
            syntax="proto3")

        def msg(name, *fields):
            m = f.message_type.add(name=name)
            for number, fname, kind, ref in fields:
                many = ref is not None and ref.startswith("*")
                fd = m.field.add(name=fname, number=number, type=kind,
                                 label=F.LABEL_REPEATED if many
                                 else F.LABEL_OPTIONAL)
                if kind == F.TYPE_MESSAGE:
                    fd.type_name = ".chipbench_xplane." + ref.lstrip("*")

        msg("XStat", (1, "metadata_id", F.TYPE_INT64, None),
            (5, "str_value", F.TYPE_STRING, None),
            (7, "ref_value", F.TYPE_UINT64, None))
        msg("XEventMetadata", (2, "name", F.TYPE_STRING, None),
            (5, "stats", F.TYPE_MESSAGE, "*XStat"))
        msg("XStatMetadata", (2, "name", F.TYPE_STRING, None))
        msg("EventMetadataEntry", (1, "key", F.TYPE_INT64, None),
            (2, "value", F.TYPE_MESSAGE, "XEventMetadata"))
        msg("StatMetadataEntry", (1, "key", F.TYPE_INT64, None),
            (2, "value", F.TYPE_MESSAGE, "XStatMetadata"))
        msg("XPlane", (2, "name", F.TYPE_STRING, None),
            (4, "event_metadata", F.TYPE_MESSAGE, "*EventMetadataEntry"),
            (5, "stat_metadata", F.TYPE_MESSAGE, "*StatMetadataEntry"))
        msg("XSpace", (1, "planes", F.TYPE_MESSAGE, "*XPlane"))
        pool = descriptor_pool.DescriptorPool()
        pool.Add(f)
        _XSPACE = message_factory.GetMessageClass(
            pool.FindMessageTypeByName("chipbench_xplane.XSpace"))
    return _XSPACE
