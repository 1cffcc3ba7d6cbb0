#!/usr/bin/env python3
"""Readings that set the limits of `correct`: the program's and the
control's, for one cell, over many seeds, in one process.

    python3 chipbench/control.py --workload <name> --seeds <n> [--first <seed>]
        [--seconds <s>] [--faults half_batch] [--out chiprun_out/control_<name>.json]

For each seed it runs the cell once as the benchmark does (a short window
at the cell's own load, the check's usual sample) and records the numbers
the check compares; then runs it again with the control in the program's
place: the plain reference computed in the next precision below the one
the configuration states (`reference/scheduler.py` `arith(...,
control=True)`: environment and actor activations in bfloat16;
`reference/qwen2.py`: float8 weights and matmul inputs;
`reference/sac.py`: every value in bfloat16). `--faults` names faults a
driver can plant in the reference put in the program's place (the train
driver: `half_batch`, each update over the first half of its batch), run
on the same seeds as the control. A limit lies above the program's readings
and below the control's and the faults'. The benchmark's own runs never run
the control or a fault.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first", type=int, default=4_000_000_000)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--controls", type=int, default=3,
                    help="seeds (the first ones) that also run the control")
    ap.add_argument("--faults", nargs="*", default=[],
                    help="faults to plant on the control's seeds")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from chipbench import harness
    from chipbench.run import Cell, device_or_exit
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = Cell(bench, args.workload)
    devs = device_or_exit(cell.chips)
    harness.setup_jax()
    import importlib
    driver = importlib.import_module(f"chipbench.drivers.{cell.driver}")
    rows = []
    for i in range(args.seeds):
        seed = args.first + i
        modes = [False] + ([True] + args.faults if i < args.controls else [])
        for control in modes:
            ctx = harness.Context(cell, seed, args.seconds, False, devs,
                                  time.perf_counter())
            ctx.control = control
            out = driver.run(ctx)
            row = {"seed": seed, "control": control,
                   "readings": {c.name: c.value for c in out.checks},
                   "correct": all(c.ok for c in out.checks),
                   "set-up": ctx.marks}
            print(json.dumps(row), flush=True)
            rows.append(row)
    path = Path(args.out or ROOT / "chiprun_out" / f"control_{cell.name}.json")
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
