"""Benchmark harness entry point — one benchmark per paper table.

    PYTHONPATH=src python -m benchmarks.run [--quick|--full] [--only NAME]

Light benchmarks (time model, patch acceleration, trace example, decision
latency, roofline report) always run live. The scheduling grid behind
Tables IX/X/XI is expensive (DRL training on one CPU core); by default it
REUSES the artifact cache under ``artifacts/scheduling/`` and only computes
missing cells with a reduced budget. ``--full`` recomputes the entire paper
grid at full budget.
"""
from __future__ import annotations

import argparse
import json
import os
import time
import traceback

from benchmarks import common as C

LIGHT = ("time_model", "patch_accel", "trace_example", "decision_latency",
         "roofline")


def run_light(name: str):
    mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
    return mod.run(verbose=True)


def run_scheduling(mode: str):
    if mode == "cache-only":
        grid, episodes, n_eval, algos = None, 0, 0, ()
        missing = False
    elif mode == "quick":
        # paper's headline cells only: one rate per cluster size
        grid = {4: (0.05,), 8: (0.10,), 12: (0.15,)}
        episodes, n_eval = 10, 3
        algos = C.ALL_ALGOS
        missing = True
    else:  # full
        grid, episodes, n_eval = C.PAPER_GRID, 20, 5
        algos = C.ALL_ALGOS
        missing = True
    if missing:
        C.run_grid(algos, grid, episodes=episodes, n_eval=n_eval)
    for t in ("quality", "latency", "reload", "efficiency"):
        print()
        run_light(t)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="compute missing scheduling cells at reduced budget")
    ap.add_argument("--full", action="store_true",
                    help="recompute the full paper grid (hours on 1 CPU)")
    ap.add_argument("--only", default=None,
                    help=f"run one benchmark: {LIGHT + ('scheduling',)}")
    args = ap.parse_args()

    t0 = time.time()
    failures = []
    names = [args.only] if args.only else list(LIGHT) + ["scheduling"]
    for name in names:
        print(f"\n=== bench: {name} " + "=" * max(0, 50 - len(name)))
        try:
            if name == "scheduling":
                mode = ("full" if args.full else
                        "quick" if args.quick else "cache-only")
                run_scheduling(mode)
            else:
                run_light(name)
        except Exception:  # noqa: BLE001 — report all failures at the end
            failures.append(name)
            traceback.print_exc()
    print(f"\nbenchmarks done in {time.time() - t0:.0f}s; "
          f"{len(failures)} failures{': ' + str(failures) if failures else ''}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
