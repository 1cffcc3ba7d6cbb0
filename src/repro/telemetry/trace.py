"""Span-based structured tracing for the whole stack.

One `Tracer` per run collects host-side *spans* — named wall-clock
intervals opened at jit boundaries (window seam, policy decision, env
advance, model load, prefill, decode, replay push, gradient update) — and
writes them as Chrome trace-event JSON (loadable in perfetto /
chrome://tracing) plus a line-per-event JSONL sidecar. Spans are recorded
strictly OUTSIDE compiled code: the tracer never enters a `jit`-traced
region, so enabling it cannot perturb a single compiled program, and with
`TraceConfig(enabled=False)` (the default) every call site hits the
shared `NULL_TRACER` no-op — zero allocations, zero behavioural change
(`tests/test_telemetry.py` pins summaries bitwise-identical on vs off).

An enabled tracer also opens a `jax.profiler.TraceAnnotation` named
``PROFILER_PREFIX + name`` around every span, with the span's args as its
keyword arguments. While a JAX profiler session runs (`jax_profile`, or any
`jax.profiler.start_trace`), each span is then a host event in the same
profile as the device's operations, on one clock with them; otherwise the
annotation is a no-op. Each span records its own `id`, the `parent` span
open on the same thread, and its `depth` on that thread.

The front door is `ExecSpec(trace=TraceConfig(enabled=True, path=...))`:
`Simulator`, `StreamRunner`, `train_stream_sac/ppo`, and the serving
backend all resolve the SAME `TraceConfig` to the SAME `Tracer` (live
tracers are cached per config), so one run emits one trace file no matter
how many layers touch it.

    with tracer.span("window", window=w):
        ...host work wrapping one jitted window rollout...
    tracer.write()          # idempotent full rewrite; safe to call often

Span names and their argument keys are documented in
`docs/telemetry_schema.md`; `telemetry.schema.validate_trace` checks an
emitted file against the machine-readable schema.
"""
from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax

#: schema version stamped into every trace file (bump on breaking changes)
TRACE_SCHEMA_VERSION = 1

#: name prefix of every span's annotation in a JAX profiler trace
PROFILER_PREFIX = "eat:"


@dataclass(frozen=True)
class TraceConfig:
    """Declarative tracing knobs, hashable so it can ride on `ExecSpec`.

    * ``enabled`` — master switch; False (default) resolves to the no-op
      `NULL_TRACER` everywhere.
    * ``path`` — Chrome trace JSON output (default ``trace.json``); the
      JSONL sidecar lands next to it as ``<path>.jsonl``.
    * ``jsonl`` — also write the JSONL sidecar (one event per line).
    * ``metrics_path`` — when set, consumers snapshot the unified metrics
      registry here (Prometheus text; ``<path>.jsonl`` gets the JSONL
      snapshot) at run end.
    * ``profile_decisions`` — time per-decision policy inference after a
      `Simulator.run` (`telemetry.profile`) and surface p50/p95/p99 in
      the result summary/sweep rows.
    * ``profile_iters`` — decisions timed by the profiler probe.
    * ``jax_profiler_dir`` — opt-in `jax.profiler.start_trace` capture
      directory: one profile holding the device's operations and every
      span (as ``PROFILER_PREFIX + name`` host events) on one clock.
    """
    enabled: bool = False
    path: str = "trace.json"
    jsonl: bool = True
    metrics_path: Optional[str] = None
    profile_decisions: bool = False
    profile_iters: int = 50
    jax_profiler_dir: Optional[str] = None


class _NullSpan:
    """No-op context manager shared by every disabled call site."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """The disabled tracer: every operation is a constant-time no-op."""
    enabled = False
    config: Optional[TraceConfig] = None

    def span(self, name: str, cat: str = "phase", **args):
        return _NULL_SPAN

    def instant(self, name: str, cat: str = "phase", **args) -> None:
        pass

    def counter(self, name: str, value: float, **args) -> None:
        pass

    def write(self) -> Optional[str]:
        return None


NULL_TRACER = NullTracer()


class _Span:
    __slots__ = ("tracer", "name", "cat", "args", "t0", "id", "parent",
                 "depth", "annotation")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: Dict):
        self.tracer, self.name, self.cat, self.args = tracer, name, cat, args

    def __enter__(self):
        self.id, self.parent, self.depth = self.tracer._enter()
        self.annotation = jax.profiler.TraceAnnotation(
            PROFILER_PREFIX + self.name, **self.args)
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self.t0
        self.annotation.__exit__(*exc)
        self.tracer._exit(self, dur)
        return False


class Tracer:
    """Collects spans/instants/counters; writes Chrome JSON + JSONL.

    Events are buffered on the host (a 10^5-span run is a few MB) and the
    output files are fully rewritten on every `write()` — callers flush at
    natural boundaries (run end, round end) and a crash mid-run still
    leaves the last consistent file behind.
    """

    enabled = True

    def __init__(self, config: TraceConfig):
        self.config = config
        self.events: List[Dict[str, Any]] = []
        self._t0 = time.perf_counter()
        self._epoch = time.time()
        self._ids = 0
        self._thread = threading.local()     # .stack: ids of open spans
        self._lock = threading.Lock()
        self._pid = os.getpid()

    # -- recording -----------------------------------------------------
    def span(self, name: str, cat: str = "phase", **args) -> _Span:
        """Context manager: one complete ("X") event on exit."""
        return _Span(self, name, cat, args)

    def _stack(self) -> List[int]:
        stack = getattr(self._thread, "stack", None)
        if stack is None:
            stack = self._thread.stack = []
        return stack

    def _enter(self):
        """(id, parent id or None, depth) of a span opening on this
        thread."""
        with self._lock:
            self._ids += 1
            sid = self._ids
        stack = self._stack()
        parent = stack[-1] if stack else None
        stack.append(sid)
        return sid, parent, len(stack) - 1

    def _exit(self, span: _Span, dur: float) -> None:
        self._stack().pop()
        ev = {"name": span.name, "cat": span.cat, "ph": "X",
              "ts": (span.t0 - self._t0) * 1e6, "dur": dur * 1e6,
              "pid": self._pid, "tid": 0,
              "args": dict(span.args, depth=span.depth, id=span.id,
                           parent=span.parent)}
        with self._lock:
            self.events.append(ev)

    def instant(self, name: str, cat: str = "phase", **args) -> None:
        ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
              "ts": (time.perf_counter() - self._t0) * 1e6,
              "pid": self._pid, "tid": 0, "args": args}
        with self._lock:
            self.events.append(ev)

    def counter(self, name: str, value: float, **args) -> None:
        ev = {"name": name, "cat": "counter", "ph": "C",
              "ts": (time.perf_counter() - self._t0) * 1e6,
              "pid": self._pid, "tid": 0,
              "args": dict(args, value=float(value))}
        with self._lock:
            self.events.append(ev)

    # -- output --------------------------------------------------------
    def _ordered(self) -> List[Dict[str, Any]]:
        # completion order == append order; presentation order is by start
        # time so nesting reads top-down in the file and in `trace_summary`
        return sorted(self.events, key=lambda e: e["ts"])

    def write(self) -> str:
        """(Re)write the trace files; returns the Chrome JSON path."""
        path = self.config.path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        events = self._ordered()
        doc = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "schema_version": TRACE_SCHEMA_VERSION,
                "epoch_unix_s": self._epoch,
            },
        }
        with open(path, "w") as f:
            json.dump(doc, f)
        if self.config.jsonl:
            with open(path + ".jsonl", "w") as f:
                for ev in events:
                    f.write(json.dumps(ev) + "\n")
        return path


# ----------------------------------------------------------------------
# one live tracer per TraceConfig: every layer that threads the same
# config (Simulator, StreamRunner, trainers, serving backend) shares one
# event buffer, hence one trace file per run.
_LIVE: Dict[TraceConfig, Tracer] = {}
_LIVE_LOCK = threading.Lock()


def tracer_for(config: Optional[TraceConfig]):
    """Resolve a TraceConfig to a tracer (NULL_TRACER when disabled)."""
    if config is None or not config.enabled:
        return NULL_TRACER
    with _LIVE_LOCK:
        t = _LIVE.get(config)
        if t is None:
            t = _LIVE[config] = Tracer(config)
        return t


def reset_tracers() -> None:
    """Drop every cached live tracer (tests; fresh files per scenario)."""
    with _LIVE_LOCK:
        _LIVE.clear()


# ----------------------------------------------------------------------
class jax_profile:
    """Opt-in device-side capture: wraps a region in
    `jax.profiler.start_trace(dir)` when `TraceConfig.jax_profiler_dir`
    is set (and tracing is enabled), no-op otherwise."""

    def __init__(self, config: Optional[TraceConfig]):
        self._dir = (config.jax_profiler_dir
                     if config is not None and config.enabled else None)

    def __enter__(self):
        if self._dir:
            jax.profiler.start_trace(self._dir)
        return self

    def __exit__(self, *exc):
        if self._dir:
            jax.profiler.stop_trace()
        return False
