"""Trace reduction, on a hand-made trace and on a recorded one."""
import json
from pathlib import Path

import pytest

from chipbench import devtrace
from chipbench.devtrace import Event

DATA = Path(__file__).parent / "data"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def op(name, s, e, plane=DEV):
    return Event(plane, devtrace.OPS_LINE, name, s * 1e6, e * 1e6)


def mark(name, s, e):
    return Event(HOST, "python", devtrace.MARK + name, s * 1e6, e * 1e6)


def test_busy_idle_ops_and_gap_labels():
    ev = [mark("profiled", 0, 100), mark("window", 0, 100),
          mark("rollout", 0, 60),
          op("while.1", 5, 40), op("fusion.1", 5, 20),
          op("kernel_env_step", 20, 40),
          op("fusion.1", 70, 80), op("outside", 150, 160)]
    d = devtrace.reduce(ev)
    assert d.window_s == pytest.approx(0.1)
    assert d.busy_s == pytest.approx(0.045)          # 5-40 and 70-80 ms
    assert d.op_calls == {"fusion.1": 2, "kernel_env_step": 1}
    assert d.op_seconds["fusion.1"] == pytest.approx(0.025)
    assert d.ops_matching("env_step") == (pytest.approx(0.02), 1)
    gaps = sorted(d.idle_gaps)
    assert gaps == [("rollout", pytest.approx(0.005)),
                    ("rollout", pytest.approx(0.030)),
                    ("window", pytest.approx(0.020))]
    assert d.breakdown(1) == {
        "device_ops": [["fusion.1", pytest.approx(0.025)]],
        "idle_gaps": [["rollout", pytest.approx(0.03)]]}


def test_no_window_or_no_device_ops_reads_nothing():
    assert devtrace.reduce([op("x", 0, 1)]) is None
    assert devtrace.reduce([mark("profiled", 0, 10)]) is None


def test_recorded_trace_excerpt():
    """40 ms of a profiled sim-paper8-eat window on one v5e: every device
    op inside the window, busy equal to the union of the ops, each gap
    between ops labelled."""
    ev = [Event(*e) for e in json.loads((DATA / "trace_excerpt.json")
                                        .read_text())]
    d = devtrace.reduce(ev)
    ops = sorted((e.start, e.end) for e in ev if e.plane.startswith(DEV)
                 and e.line == devtrace.OPS_LINE)
    lo = min(e.start for e in ev if e.name == devtrace.MARK + "profiled")
    hi = max(e.end for e in ev if e.name == devtrace.MARK + "profiled")
    covered, end = 0.0, lo
    for s, e in ops:                         # naive sweep of the union
        s, e = max(s, end), min(e, hi)
        if e > s:
            covered += e - s
            end = e
    assert d.busy_s == pytest.approx(covered * 1e-9, rel=1e-9)
    assert 0 < d.busy_s <= d.window_s
    assert sum(d.op_calls.values()) == len(devtrace.leaves(
        [e for e in ev if e.plane.startswith(DEV)]))
    assert sum(g for _, g in d.idle_gaps) == pytest.approx(
        d.window_s - d.busy_s, rel=1e-9)
