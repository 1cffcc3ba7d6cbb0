"""Serve the stream: a Poisson arrival stream scheduled onto the REAL
serving cluster through the unified facade.

    PYTHONPATH=src python examples/serve_stream.py [--policy eat|greedy|fifo|random]
        [--servers 4] [--windows 3] [--window-tasks 8] [--rate 2.0]
        [--archs tinyllama-1.1b] [--wall-clock] [--checkpoint DIR]

One spec triple drives everything:

    Simulator(WorkloadSpec.streaming(cell, streams=1, ...),
              ExecSpec(backend="serving", ...)).run(PolicySpec(name), key)

Every scheduling decision advances the shared env decision step on a mirror
of the physical pool; every scheduled task REALLY loads weights (or reuses
a warm gang) and runs patch-parallel prefill + greedy decode on reduced-
config zoo models. Default virtual time keeps Table-VI latency economics
(the decision process is bitwise-identical to the fused simulator —
`tests/test_serving_backend.py` pins this); `--wall-clock` feeds measured
execution seconds into latencies, rewards, and observations instead.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax

from repro import api
from repro.core import agent as AG
from repro.core import env as EV
from repro.core.scenarios import Scenario
from repro.core.workload import TraceConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="greedy",
                    choices=["eat", "greedy", "fifo", "random"])
    ap.add_argument("--servers", type=int, default=4)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--window-tasks", type=int, default=8)
    ap.add_argument("--rate", type=float, default=2.0)
    ap.add_argument("--archs", default="tinyllama-1.1b")
    ap.add_argument("--wall-clock", action="store_true")
    ap.add_argument("--checkpoint", default=None,
                    help="restore EAT actor weights from a saved run")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    ecfg = EV.EnvConfig(num_servers=args.servers,
                        max_tasks=args.window_tasks)
    cell = Scenario(
        name=f"poisson-{args.servers}srv",
        ecfg=ecfg,
        tcfg=TraceConfig(num_tasks=args.window_tasks,
                         arrival_rate=args.rate,
                         max_servers=args.servers))
    wl = api.WorkloadSpec.streaming(
        cell, streams=1, num_windows=args.windows,
        window_tasks=args.window_tasks,
        max_steps_per_window=4 * args.window_tasks)
    spec = api.ExecSpec(backend="serving",
                        serving_archs=tuple(args.archs.split(",")),
                        serving_wall_clock=args.wall_clock,
                        serving_prompt_len=8, serving_max_new_tokens=8,
                        serving_seed=args.seed)
    options = ({"acfg": AG.AgentConfig(variant="eat-da", T=2)}
               if args.policy == "eat" else {})
    pol = api.PolicySpec(args.policy, checkpoint=args.checkpoint,
                         options=options)

    res = api.Simulator(wl, spec).run(pol, jax.random.PRNGKey(args.seed))
    s = res.summary
    mode = "wall-clock" if s["wall_clock"] else "virtual (Table-VI)"
    print(f"\npolicy={res.policy} trained={res.trained} time={mode}")
    print(f"windows={args.windows} injected={s['tasks_injected']} "
          f"scheduled={s['tasks_scheduled']} executed={s['tasks_executed']} "
          f"dropped={s['tasks_dropped']}")
    print(f"latency p50/p95/p99 = {s['latency_p50']:.2f}/"
          f"{s['latency_p95']:.2f}/{s['latency_p99']:.2f}s  "
          f"violation={s['qos_violation_rate']:.3f}  "
          f"goodput={s['goodput_per_s']:.3f}/s")
    print(f"model loads={s['model_loads']} reuses={s['model_reuses']} "
          f"cold-start rate={s['cold_start_rate']:.2f} "
          f"utilization={s['utilization']:.2f}")
    if args.wall_clock and "measured_busy_mean_s" in s:
        print(f"measured busy mean = {s['measured_busy_mean_s']:.3f}s/task")


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
