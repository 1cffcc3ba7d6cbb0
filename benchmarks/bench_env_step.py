"""Fused vs unfused environment decision step, measured end-to-end.

    PYTHONPATH=src python benchmarks/bench_env_step.py

Two measurements per (E servers, B envs) cell, both end-to-end through the
public engines so the numbers are what training/baselines/streaming
actually see:

* batched episodes/sec: `rollout.batch_rollout` with `fused=True` (one
  fused decision op advances all B envs per step) vs `fused=False` (the
  legacy vmap-of-scans engine on the compositional `env.step`);
* streaming tasks/sec: `traffic.run_stream` (open-loop Poisson arrivals at
  the paper rate) with `StreamConfig(fused=...)`.

Writes BENCH_env_step.json at the repo root (`make bench-env-step`). On
CPU the fused path runs the jnp reference; pass `--impl pallas` to time the
kernel itself (compiled on gpu/tpu, interpret-mode — slow, parity only —
on CPU). Both paths are bitwise-identical, so every speedup is free.
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from common import write_bench_json
from repro.api import ExecSpec, rollout_fn_for
from repro.core import env as EV
from repro.core import rollout as RO
from repro.core.workload import TraceConfig, make_trace_batch, paper_rate_for
from repro.traffic.arrivals import PoissonArrivals
from repro.traffic.stream import ProcessTaskSource, StreamConfig, run_stream

# fused/unfused measured through the api backends ("reference" is the legacy
# vmap-of-scans engine, "fused" the fused env-step op — bitwise-identical)
_ENGINES = (("unfused", "reference"), ("fused", "fused"))


def _policy(name, ecfg):
    return {"fifo": RO.fifo_policy, "random": RO.uniform_policy}[name](ecfg)


def bench_rollout_cell(E, B, *, policy, window_tasks, num_steps, impl,
                       min_s=2.0):
    ecfg = EV.EnvConfig(num_servers=E, max_tasks=window_tasks, queue_window=8,
                        max_steps=num_steps)
    tc = TraceConfig(num_tasks=window_tasks, arrival_rate=paper_rate_for(E),
                     max_servers=E)
    traces = make_trace_batch(jax.random.PRNGKey(0), tc, B)
    keys = jax.random.split(jax.random.PRNGKey(1), B)
    pol = _policy(policy, ecfg)
    out = {}
    for label, backend in _ENGINES:
        rollout = rollout_fn_for(ExecSpec(backend=backend, fused_impl=impl))

        def run():
            r = rollout(ecfg, traces, pol, {}, keys)
            jax.block_until_ready(r.metrics["episode_return"])
        t0 = time.perf_counter()
        run()                                  # compile
        compile_s = time.perf_counter() - t0
        n, t0 = 0, time.perf_counter()
        while time.perf_counter() - t0 < min_s:
            run()
            n += 1
        eps = B * n / (time.perf_counter() - t0)
        out[label] = {
            "eps_per_s": round(eps, 1), "compile_s": round(compile_s, 2)}
    out["speedup"] = round(out["fused"]["eps_per_s"]
                           / out["unfused"]["eps_per_s"], 2)
    return out


def bench_stream_cell(E, B, *, policy, window_tasks, windows, impl):
    ecfg = EV.EnvConfig(num_servers=E, max_tasks=window_tasks, queue_window=8)
    tc = TraceConfig(num_tasks=window_tasks, arrival_rate=paper_rate_for(E),
                     max_servers=E)
    pol = _policy(policy, ecfg)
    out = {}
    for label, backend in _ENGINES:
        rollout = rollout_fn_for(ExecSpec(backend=backend, fused_impl=impl))

        def run(num_windows):
            src = ProcessTaskSource(PoissonArrivals(tc.arrival_rate), tc,
                                    jax.random.PRNGKey(0), num_streams=B)
            cfg = StreamConfig(num_windows=num_windows, num_streams=B)
            t0 = time.perf_counter()
            res = run_stream(ecfg, pol, {}, src, jax.random.PRNGKey(1), cfg,
                             rollout_fn=rollout)
            return time.perf_counter() - t0, res
        run(1)                                 # compile + warm
        wall, res = run(windows)
        tasks = res.summary["tasks_injected"]
        out[label] = {
            "tasks": int(tasks), "wall_s": round(wall, 2),
            "tasks_per_s": round(tasks / wall, 1)}
    out["speedup"] = round(out["fused"]["tasks_per_s"]
                           / out["unfused"]["tasks_per_s"], 2)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--servers", default="8,16,32")
    ap.add_argument("--batches", default="32,256")
    ap.add_argument("--policy", default="fifo", choices=["fifo", "random"])
    ap.add_argument("--window-tasks", type=int, default=32)
    ap.add_argument("--num-steps", type=int, default=256)
    ap.add_argument("--stream-windows", type=int, default=8)
    ap.add_argument("--impl", default="auto",
                    choices=["auto", "ref", "pallas"],
                    help="fused implementation (auto: pallas on gpu/tpu, "
                         "jnp reference on cpu)")
    ap.add_argument("--json-out", default="",
                    help="BENCH json path ('' = repo-root default, "
                         "'none' = skip)")
    args = ap.parse_args()

    servers = [int(s) for s in args.servers.split(",")]
    batches = [int(b) for b in args.batches.split(",")]
    rollout_cells, stream_cells = [], []
    for E in servers:
        for B in batches:
            r = bench_rollout_cell(E, B, policy=args.policy,
                                   window_tasks=args.window_tasks,
                                   num_steps=args.num_steps, impl=args.impl)
            r.update(servers=E, batch=B)
            rollout_cells.append(r)
            print(f"rollout E={E:2d} B={B:3d}: "
                  f"unfused {r['unfused']['eps_per_s']:8.1f} eps/s  "
                  f"fused {r['fused']['eps_per_s']:8.1f} eps/s  "
                  f"({r['speedup']:.2f}x)", flush=True)
            s = bench_stream_cell(E, B, policy=args.policy,
                                  window_tasks=args.window_tasks,
                                  windows=args.stream_windows, impl=args.impl)
            s.update(servers=E, streams=B)
            stream_cells.append(s)
            print(f"stream  E={E:2d} B={B:3d}: "
                  f"unfused {s['unfused']['tasks_per_s']:8.1f} tasks/s  "
                  f"fused {s['fused']['tasks_per_s']:8.1f} tasks/s  "
                  f"({s['speedup']:.2f}x)", flush=True)

    payload = {
        "policy": args.policy,
        "window_tasks": args.window_tasks,
        "num_steps": args.num_steps,
        "impl": args.impl,
        "rollout": rollout_cells,
        "stream": stream_cells,
        "min_speedup_rollout": min(r["speedup"] for r in rollout_cells),
        "max_speedup_rollout": max(r["speedup"] for r in rollout_cells),
    }
    print(json.dumps(payload, indent=1))
    if args.json_out != "none":
        write_bench_json("env_step", payload, out=args.json_out or None,
                         fused=True, exec_backend="fused+reference")


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
