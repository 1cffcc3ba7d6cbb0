"""Streaming traffic engine throughput (wall-clock tasks/sec + sim QoS).

    PYTHONPATH=src python benchmarks/bench_traffic.py --streams 32 \
        --window-tasks 64 --windows 20

Streams one window-chained run per policy through the `repro.api` facade
(`Simulator` with a streaming WorkloadSpec, Poisson at the paper rate) and
records wall-clock tasks/sec, per-window latency, and the simulated
p50/p95/p99 / QoS numbers. `--backend` picks the execution backend
(reference / fused / sharded — bitwise-identical QoS); writes
BENCH_traffic.json at the repo root so the perf trajectory is tracked
across PRs (`make bench-traffic`).
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax

from common import write_bench_json
from repro.api import BACKENDS, ExecSpec, PolicySpec, Simulator, WorkloadSpec
from repro.core.scenarios import poisson_scenario
from repro.core.workload import paper_rate_for


def bench_policy(name: str, wl: WorkloadSpec, exec_spec: ExecSpec, *,
                 warm_windows: int = 2):
    spec = PolicySpec(name)

    def one(num_windows, key_seed):
        w = dataclasses.replace(wl, num_windows=num_windows)
        return Simulator(w, exec_spec).run(spec, jax.random.PRNGKey(key_seed))

    warm = one(warm_windows, 0)                   # compile + warm windows
    res = one(wl.num_windows, 0)
    s = res.summary
    tasks = s["tasks_injected"]
    return {
        "policy": name,
        "trained": res.trained,
        "tasks": tasks,
        "wall_s": res.wall_s,
        "warm_s": warm.wall_s,
        "tasks_per_s": tasks / res.wall_s,
        "windows_per_s": wl.num_windows / res.wall_s,
        "latency_p50": s["latency_p50"],
        "latency_p99": s["latency_p99"],
        "qos_violation_rate": s["qos_violation_rate"],
        "utilization": s["utilization"],
        "goodput_per_s": s["goodput_per_s"],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--servers", type=int, default=8)
    ap.add_argument("--streams", type=int, default=32)
    ap.add_argument("--window-tasks", type=int, default=64)
    ap.add_argument("--windows", type=int, default=20)
    ap.add_argument("--policies", default="random,fifo,greedy")
    ap.add_argument("--backend", default="fused", choices=BACKENDS,
                    help="api execution backend (bitwise-identical QoS; "
                         "sharded splits streams over the device mesh)")
    ap.add_argument("--fused", type=int, default=None,
                    help="legacy alias: 1 = --backend fused, 0 = "
                         "--backend reference")
    ap.add_argument("--json-out", default="",
                    help="BENCH json path ('' = repo-root default, "
                         "'none' = skip)")
    args = ap.parse_args()
    backend = args.backend
    if args.fused is not None:
        backend = "fused" if args.fused else "reference"
    exec_spec = ExecSpec(backend=backend)

    sc = poisson_scenario(args.servers, paper_rate_for(args.servers))
    wl = WorkloadSpec.streaming(sc, streams=args.streams,
                                num_windows=args.windows,
                                window_tasks=args.window_tasks)

    rows = []
    for name in args.policies.split(","):
        row = bench_policy(name, wl, exec_spec)
        rows.append(row)
        print(f"{name:>8s}: {row['tasks']:7d} tasks in {row['wall_s']:6.1f}s "
              f"= {row['tasks_per_s']:8.0f} tasks/s | "
              f"p99 {row['latency_p99']:8.1f}s "
              f"viol {row['qos_violation_rate']:.3f} "
              f"util {row['utilization']:.2f}")

    payload = {"servers": args.servers, "streams": args.streams,
               "window_tasks": args.window_tasks, "windows": args.windows,
               "comparability_note":
                   "absolute tasks/s depend on machine load at record time "
                   "and are NOT comparable across records; for engine "
                   "comparisons use BENCH_env_step.json / "
                   "BENCH_sharded_rollout.json, which measure side-by-side "
                   "in one run",
               "policies": rows}
    print(json.dumps(payload, indent=1))
    if args.json_out != "none":
        write_bench_json("traffic", payload, out=args.json_out or None,
                         fused=backend != "reference", exec_backend=backend)


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
