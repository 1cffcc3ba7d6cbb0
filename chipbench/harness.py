"""What every cell shares: set-up of JAX and the program, the profiled
window, the device's memory peak, and the result line.

A driver (`chipbench/drivers/<name>.py`, named by the traffic file's
`driver` or else by the configuration's) defines `run(ctx) -> Outcome`. It
builds the cell from the configuration and traffic files, warms every shape
the window uses, calls `ctx.start_window()`, runs the measured window, reads
`ctx.memory_peak()`, and then checks what the window produced against the
configuration's plain reference. In a `--trace 1` run it wraps a short
extra window in `ctx.profiled(...)`, or between `ctx.profile_begin()` and
`ctx.profile_end()` where the program's own loop runs it. The profile is
reduced by `spantrace`: `devtrace`'s numbers, with each idle gap named by
the program's innermost span and the device's idle and busy time per span.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

from chipbench import devtrace, spantrace

ROOT = Path(__file__).resolve().parents[1]


@dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit      # NaN fails


@dataclass
class Outcome:
    """What a driver hands back: the end-to-end readings besides `setup_s`,
    the counts and host spans the per-layer readers use, and the checks."""
    e2e: Dict[str, float]
    attempted: int
    failed: int
    checks: List[Check]
    counts: Dict = field(default_factory=dict)
    spans: List[Dict] = field(default_factory=list)


class Context:
    def __init__(self, cell, seed: int, seconds: float, trace: bool, devs,
                 t_process: float, peaks: Optional[Dict] = None):
        self.cell, self.seed, self.seconds, self.trace = cell, seed, seconds, trace
        self.config, self.traffic = cell.config, cell.traffic
        self.devs = devs[:cell.chips]
        self.t_process = t_process
        self.setup_s: Optional[float] = None
        self.peak_bytes: Optional[int] = None
        self.device: Optional[spantrace.SpanTrace] = None
        self.peaks = peaks or peaks_for(devs[0].device_kind)
        # the control: the reference, in the next precision below the one
        # the configuration states, checked in the program's place
        # (chipbench/control.py); never set in a benchmark run
        self.control = False
        self.marks: List[Tuple[str, float]] = []
        self._profile: Optional[Tuple[str, object]] = None

    def mark(self, what: str) -> None:
        """Note a step of set-up, in seconds from process start."""
        self.marks.append((what, time.perf_counter() - self.t_process))

    def start_window(self) -> float:
        """Set-up ends here; returns the window's start on the host clock."""
        now = time.perf_counter()
        self.setup_s = now - self.t_process
        self.mark("window starts")
        return now

    def memory_peak(self) -> int:
        """Peak bytes in use on the fullest chip so far; read once the
        window has closed and before the reference runs."""
        self.peak_bytes = max(int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in self.devs)
        return self.peak_bytes

    def profiled(self, fn: Callable[[], None]) -> None:
        """Run `fn` under the JAX profiler, reduce the trace, delete it."""
        self.profile_begin()
        try:
            fn()
        finally:
            self.profile_end()

    def profile_begin(self) -> None:
        """Start the JAX profiler and the `bench:profiled` window."""
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        d = tempfile.mkdtemp(prefix="chipbench-trace-")
        try:
            jax.profiler.start_trace(d, profiler_options=opts)
        except BaseException:
            shutil.rmtree(d, ignore_errors=True)
            raise
        mark = jax.profiler.TraceAnnotation(devtrace.MARK + "profiled")
        mark.__enter__()
        self._profile = (d, mark)

    def profile_end(self) -> None:
        """Close the window, stop the profiler, reduce the trace with the
        program's spans (`spantrace`) and delete it."""
        import jax
        d, mark = self._profile
        self._profile = None
        try:
            mark.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.device = spantrace.reduce(*spantrace.load(d))
        finally:
            shutil.rmtree(d, ignore_errors=True)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod          # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, root: Path = ROOT):
    """The per-layer metric's own reader, `chipbench/metrics/<metric>.py`."""
    return load_module(root / "chipbench" / "metrics" / f"{metric}.py",
                       "chipbench_metric_" + metric.replace(".", "_"))


_REFERENCES: Dict[Path, object] = {}


def reference(cfg: Dict, role: str, root: Path = ROOT):
    """The configuration's plain reference for `role`: the file its
    `reference` group names (`scheduler`, `model`), loaded once."""
    path = (root / cfg["reference"][role]).resolve()
    if path not in _REFERENCES:
        _REFERENCES[path] = load_module(path, "chipbench_reference_"
                                        + path.stem)
    return _REFERENCES[path]


def derive_seed(seed: int, salt: int) -> int:
    """A 31-bit seed for part `salt` of a run, from the run's seed."""
    import numpy as np
    return int(np.random.SeedSequence([seed % 2 ** 64, salt])
               .generate_state(1)[0] >> 1)


def peaks_for(kind: str) -> Dict:
    table = json.loads((ROOT / "chipbench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in chipbench/peaks.json; "
                       "add its published peaks before measuring on it")
    return table["devices"][kind]


def setup_jax() -> None:
    """Compile cache in the checkout (or where JAX_COMPILATION_CACHE_DIR
    says), with every program cached."""
    import jax
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(cell, seed: int, seconds: float, trace: bool, devs,
             t_process: float):
    setup_jax()
    ctx = Context(cell, seed, seconds, trace, devs, t_process)
    ctx.mark("devices found")
    driver = importlib.import_module(f"chipbench.drivers.{cell.driver}")
    out = driver.run(ctx)
    return ctx, out


def result_line(cell, run: Tuple, trace: bool, devs) -> Dict:
    ctx, out = run
    d = devs[0]
    metrics: Dict[str, Dict] = {}
    if not trace:
        values = dict(out.e2e, setup_s=ctx.setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            v = reader(m["name"]).read(ctx, out)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs), "memory_peak_bytes": ctx.peak_bytes}
    line = {"correct": all(c.ok for c in out.checks) and bool(out.checks),
            "attempted": out.attempted, "failed": out.failed,
            "metrics": metrics, "device": device}
    if trace and ctx.device is not None:
        device["busy_s"] = ctx.device.busy_s
        device["window_s"] = ctx.device.window_s
        line["breakdown"] = ctx.device.breakdown()
    line["checks"] = [{"name": c.name, "value": c.value, "limit": c.limit}
                      for c in out.checks]
    return line
