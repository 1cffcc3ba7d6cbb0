"""Serving-backend QoS bench: stream-trained EAT vs baselines on the REAL
cluster -> BENCH_serving.json.

    PYTHONPATH=src python benchmarks/bench_serving.py
        [--servers 4] [--window-tasks 8] [--windows 3] [--rounds 6]
        [--rate 2.0] [--archs tinyllama-1.1b]

Three stages:
  1. train EAT in the stream (`train_stream_sac`, fused backend — the
     decision process is bitwise-identical to virtual-time serving, so the
     policy transfers exactly);
  2. evaluate the trained actor + baselines on `ExecSpec(backend="serving")`
     with real reduced-config models in virtual (Table-VI) time — the
     paper-comparable QoS numbers, plus real pool economics;
  3. re-evaluate everything under `serving_wall_clock=True` — measured
     prefill+decode seconds feed the latencies, the sim-to-real row.

Every row is the shared StreamAggregator schema (drop-inclusive p50/p95/p99,
violation, goodput, cold-start, utilization) + model_loads/model_reuses/
tasks_executed, so simulated and measured runs land in one table.
"""
from __future__ import annotations

import argparse
import warnings

import jax

from common import write_bench_json
from repro import api
from repro.core import agent as AG
from repro.core import env as EV
from repro.core import sac as SAC
from repro.core.scenarios import Scenario
from repro.core.workload import TraceConfig
from repro.training import stream_train as ST

BASELINES = ("greedy", "fifo", "random")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--servers", type=int, default=4)
    ap.add_argument("--window-tasks", type=int, default=8)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--rate", type=float, default=2.0)
    ap.add_argument("--archs", default="tinyllama-1.1b")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    ecfg = EV.EnvConfig(num_servers=args.servers,
                        max_tasks=args.window_tasks)
    acfg = AG.AgentConfig(variant="eat-da", T=2)
    cell = Scenario(
        name=f"poisson-{args.servers}srv",
        ecfg=ecfg,
        tcfg=TraceConfig(num_tasks=args.window_tasks,
                         arrival_rate=args.rate,
                         max_servers=args.servers))

    # -- 1. train EAT in the stream (simulated, bitwise == virtual serving)
    print(f"[1/3] stream-training EAT ({args.rounds} rounds)...")
    tres = ST.train_stream_sac(
        ecfg, acfg, SAC.SACConfig(warmup_steps=64, batch_size=32),
        ST.StreamTrainConfig(rounds=args.rounds, streams=4,
                             max_steps_per_window=4 * args.window_tasks,
                             max_updates_per_round=16),
        scenario=cell, seed=args.seed,
        exec_spec=api.ExecSpec(backend="fused"))
    policies = {"eat": api.PolicySpec("eat", params=tres.state.actor,
                                      options={"acfg": acfg})}
    policies.update({b: api.PolicySpec(b) for b in BASELINES})

    # -- 2+3. evaluate on the real cluster, virtual then wall-clock -------
    wl = api.WorkloadSpec.streaming(
        cell, streams=1, num_windows=args.windows,
        window_tasks=args.window_tasks,
        max_steps_per_window=4 * args.window_tasks)
    keep = ("latency_p50", "latency_p95", "latency_p99", "latency_mean",
            "qos_violation_rate", "drop_rate", "goodput_per_s",
            "cold_start_rate", "reuse_rate", "utilization", "avg_quality",
            "tasks_injected", "tasks_scheduled", "tasks_executed",
            "tasks_dropped", "model_loads", "model_reuses", "wall_clock",
            "measured_busy_mean_s")
    rows = {}
    for stage, wall in (("virtual", False), ("wall_clock", True)):
        print(f"[{2 + int(wall)}/3] serving eval ({stage} time)...")
        spec = api.ExecSpec(backend="serving",
                            serving_archs=tuple(args.archs.split(",")),
                            serving_wall_clock=wall,
                            serving_prompt_len=8, serving_max_new_tokens=8,
                            serving_seed=args.seed)
        sim = api.Simulator(wl, spec)
        for name, pol in policies.items():
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", api.UntrainedPolicyWarning)
                r = sim.run(pol, jax.random.PRNGKey(args.seed))
            rows[f"{stage}/{name}"] = {
                **{k: r.summary[k] for k in keep if k in r.summary},
                "trained": r.trained, "wall_s": round(r.wall_s, 2)}
            print(f"    {stage:10s} {name:8s} p95="
                  f"{r.summary['latency_p95']:8.2f}s "
                  f"viol={r.summary['qos_violation_rate']:.3f} "
                  f"goodput={r.summary['goodput_per_s']:.4f}/s "
                  f"loads={r.summary['model_loads']}")

    write_bench_json("serving", {
        "servers": args.servers, "window_tasks": args.window_tasks,
        "windows": args.windows, "train_rounds": args.rounds,
        "arrival_rate": args.rate, "archs": args.archs.split(","),
        "final_train_return": tres.history[-1]["episode_return_mean"],
        "rows": rows,
    }, exec_backend="serving")


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
