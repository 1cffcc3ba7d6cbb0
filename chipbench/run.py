#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout. Everything about the cell is data,
found by name: the entry of `workloads` in `BENCHMARK.json`, the
configuration file it names, the traffic file
`chipbench/traffic/<traffic>.json`, the driver `chipbench/drivers/
<driver>.py` (named by the traffic file's `driver` key, or else by the
configuration's), and one reader `chipbench/metrics/<metric>.py` per
per-layer metric.

With `--trace 0` the result carries the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, device busy time and a breakdown from a
profiler trace of a short extra window. Either way the run checks what the
timed path produced against the configuration's plain reference and prints
each compared number beside its limit, last on stderr and last in the
result line, the last line of stdout. There is no CPU fallback: without a
TPU, or with fewer chips than the cell asks for, the run exits with code 3
and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
NO_DEVICE = 3


class Cell:
    """Everything one run needs, resolved from the files by name."""

    def __init__(self, bench: dict, workload: str, root: Path = ROOT):
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                             f"has {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfgs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = cfgs[self.entry["config"]]
        self.config = json.loads((root / self.config_entry["file"]).read_text())
        self.traffic = json.loads((root / "chipbench" / "traffic"
                                   / f"{self.entry['traffic']}.json").read_text())
        self.driver = self.traffic.get("driver", self.config["driver"])
        applies = lambda m: workload in m.get("workloads", [workload])  # noqa: E731
        self.end_to_end = [m for m in bench["end_to_end"] if applies(m)]
        self.per_layer = [m for m in bench["per_layer"] if applies(m)]


def device_or_exit(chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" or len(devs) < chips:
        print(f"no result: JAX sees {len(devs)} {d.platform} device(s); this "
              f"cell needs {chips} TPU chip(s) and has no fallback",
              file=sys.stderr)
        sys.exit(NO_DEVICE)
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = Cell(bench, args.workload)
    devs = device_or_exit(cell.chips)

    from chipbench import harness
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           devs, T_PROCESS)
    line = harness.result_line(cell, out, bool(args.trace), devs)
    print("set-up, seconds from process start: " + ", ".join(
        f"{what} {t:.3f}" for what, t in out[0].marks), file=sys.stderr)
    for c in line["checks"]:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
