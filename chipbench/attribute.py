#!/usr/bin/env python3
"""Run one cell as `run.py --trace 1` does, and say where the device's idle
time went, by the program's own spans.

    python3 chipbench/attribute.py --workload <name> --seed <n> --seconds <s>

The run is `run.py`'s traced run: the measured window with the program's
host tracer on, then a profiled extra window, then the check. The profile
is reduced by `chipbench/spantrace.py`, so each idle gap is named by the
innermost program span around it.
It prints one JSON line:

* `e2e_traced`: the measured window's end-to-end metrics with the tracer
  on. Against `run.py --trace 0` on the same seed, the cost of tracing;
* `line`: the traced result line (`harness.result_line`), its breakdown's
  gaps named by program spans;
* `idle_by_span`, `span_calls`: idle seconds under each innermost span,
  and the program spans' counts and summed numeric args;
* `clock_offset_s`: host clock minus device clock in the profile;
* `scopes`, `kernels`: device seconds and calls under the `policy` and
  `env_step` scopes and of each named Pallas kernel;
* `span_metrics`: what the readers that need the program's spans read.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402
from chipbench.run import Cell, device_or_exit  # noqa: E402

SPAN_METRICS = ("seam_idle_ms", "policy_device_us", "decision_idle_ms",
                "decode_idle_ms_per_token", "model_load_ms")
SCOPES = ("policy", "env_step")
KERNELS = ("env_step_pallas", "denoiser_chain", "denoiser_step")


def report(cell, ctx, out) -> dict:
    d = ctx.device
    return {
        "workload": cell.name, "seed": ctx.seed,
        "e2e_traced": out.e2e, "setup_s": ctx.setup_s,
        "line": harness.result_line(cell, (ctx, out), True, ctx.devs),
        "idle_by_span": sorted(d.idle_by_span.items(), key=lambda kv: -kv[1]),
        "span_calls": d.span_calls,
        "clock_offset_s": d.clock_offset_s,
        "scopes": {s: d.scope_seconds(s) for s in SCOPES},
        "kernels": {k: d.ops_matching(k) for k in KERNELS},
        "span_metrics": {m: harness.reader(m).read(ctx, out)
                         for m in SPAN_METRICS}}


def run(argv=None):
    """(cell, context, outcome) of one traced run of a cell."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = Cell(bench, args.workload)
    devs = device_or_exit(cell.chips)
    harness.setup_jax()
    ctx = harness.Context(cell, args.seed, args.seconds, True, devs,
                          T_PROCESS)
    ctx.mark("devices found")
    driver = importlib.import_module(f"chipbench.drivers.{cell.driver}")
    return cell, ctx, driver.run(ctx)


def main(argv=None) -> int:
    cell, ctx, out = run(argv)
    print(json.dumps(report(cell, ctx, out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
