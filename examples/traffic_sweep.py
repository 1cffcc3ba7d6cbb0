"""Streaming traffic sweep: unbounded-horizon QoS telemetry per policy.

    PYTHONPATH=src python examples/traffic_sweep.py            # default run
    PYTHONPATH=src python examples/traffic_sweep.py \
        --cells bursty,diurnal,flashcrowd --policies random,fifo,greedy \
        --streams 32 --window-tasks 64 --windows 50

The default invocation streams >= 100k tasks per policy through the
windowed engine (32 parallel streams x 64-task windows x 100 windows) on
CPU at O(window) memory, and reports p50/p95/p99 latency, QoS-violation
rate, server utilization, cold-start rate, and goodput per policy. Rows go
to --out as JSON (schema: traffic/sweep.py run_cell).

Named cells: poisson (paper rate), bursty (MMPP), diurnal, flashcrowd,
coldstart; or pass --rate to override the Poisson rate. Use --checkpoint to
evaluate trained EAT weights with --policies eat (without it, learned
policies run untrained and rows carry trained=false). --backend picks the
`repro.api` execution backend; `--backend sharded` splits the stream axis
over the local device mesh (bitwise-identical telemetry; on CPU force
devices with XLA_FLAGS=--xla_force_host_platform_device_count=8).
"""
from __future__ import annotations

import argparse

import jax

from repro.api import BACKENDS, ExecSpec
from repro.core import scenarios as SC
from repro.traffic.stream import StreamConfig
from repro.traffic.sweep import run_sweep


def named_cells(names, servers):
    grid = {
        "poisson": SC.poisson_scenario(servers),
        "bursty": SC.bursty_traffic(servers),
        "diurnal": SC.diurnal_traffic(servers),
        "flashcrowd": SC.flash_crowd(servers),
        "coldstart": SC.cold_start_heavy(servers),
    }
    unknown = [n for n in names if n not in grid]
    if unknown:
        raise SystemExit(f"unknown cells {unknown}; choose from {sorted(grid)}")
    return [grid[n] for n in names]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="poisson",
                    help="comma list: poisson,bursty,diurnal,flashcrowd,"
                         "coldstart")
    ap.add_argument("--policies", default="random,fifo,greedy")
    ap.add_argument("--servers", type=int, default=8)
    ap.add_argument("--streams", type=int, default=32,
                    help="parallel independent streams per run (batch axis)")
    ap.add_argument("--window-tasks", type=int, default=64,
                    help="tasks per window per stream (device memory bound)")
    ap.add_argument("--windows", type=int, default=100,
                    help="windows per run; 100 keeps >= 100k tasks per "
                         "policy even when overload caps injection at "
                         "window_tasks - max_carry per window")
    ap.add_argument("--max-steps-per-window", type=int, default=0)
    ap.add_argument("--resp-sla", type=float, default=120.0)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="override the Poisson cell's arrival rate")
    ap.add_argument("--checkpoint", default=None,
                    help="actor checkpoint dir for --policies eat/ppo")
    ap.add_argument("--backend", default="fused", choices=BACKENDS,
                    help="execution backend (sharded = device-mesh split "
                         "of the stream axis, bitwise-identical)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="artifacts/traffic_sweep.json")
    args = ap.parse_args()

    cells = named_cells(args.cells.split(","), args.servers)
    if args.rate:
        cells = [SC.poisson_scenario(args.servers, args.rate)
                 if c.name.startswith("poisson") else c for c in cells]
    stream = StreamConfig(
        num_windows=args.windows, num_streams=args.streams,
        max_steps_per_window=args.max_steps_per_window or None,
        resp_sla=args.resp_sla)
    total = args.streams * args.window_tasks * args.windows
    print(f"streaming <= {total} tasks per (cell, policy): "
          f"{args.streams} streams x {args.window_tasks}-task windows "
          f"x {args.windows} windows, {args.servers} servers")
    run_sweep(cells, args.policies.split(","), jax.random.PRNGKey(args.seed),
              stream=stream, window_tasks=args.window_tasks,
              checkpoint=args.checkpoint,
              exec_spec=ExecSpec(backend=args.backend), out=args.out)


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
