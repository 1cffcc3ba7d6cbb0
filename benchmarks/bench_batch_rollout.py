"""Batched rollout engine benchmarks: host loop vs batched, fused vs sharded.

    PYTHONPATH=src python benchmarks/bench_batch_rollout.py --batch 32
    PYTHONPATH=src python benchmarks/bench_batch_rollout.py \
        --sharded --devices 8            # -> BENCH_sharded_rollout.json

Default mode rolls the same B (trace, key) pairs through (a)
`baselines.evaluate_policy` — the per-step host Python loop — and (b) the
`repro.api` "fused" backend — one jitted program — and reports warm
episodes/sec for both (identical metrics asserted; the engine is
bit-compatible with the host loop).

`--sharded` mode compares the "fused" backend (single device) against the
"sharded" backend (batch axis shard_map'd over the device mesh) at equal
batch sizes (default B in {256, 1024}) and writes BENCH_sharded_rollout.json.
`--devices N` forces N host CPU devices through XLA_FLAGS before jax starts
its backend; results are bitwise-identical across backends, so the
speedup column is a free win.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def bench_host_vs_batched(args):
    import jax
    import numpy as np

    from repro.api import ExecSpec, rollout_fn_for
    from repro.core import baselines as BL
    from repro.core import env as EV
    from repro.core import rollout as RO
    from repro.core.workload import (TraceConfig, make_trace_batch,
                                     paper_rate_for)

    ecfg = EV.EnvConfig(num_servers=args.servers, max_tasks=args.tasks,
                        max_steps=args.max_steps)
    tc = TraceConfig(num_tasks=args.tasks,
                     arrival_rate=paper_rate_for(args.servers),
                     max_servers=args.servers)
    traces = make_trace_batch(jax.random.PRNGKey(1), tc, args.batch)
    keys = jax.random.split(jax.random.PRNGKey(2), args.batch)
    trace_list = [jax.tree_util.tree_map(lambda x, b=b: x[b], traces)
                  for b in range(args.batch)]
    if args.policy == "random":
        policy = RO.uniform_policy(ecfg)
        host_act = lambda tr: lambda k, s, o: BL.random_policy(k, ecfg)  # noqa: E731
    else:
        policy = RO.greedy_policy(ecfg)
        host_act = lambda tr: lambda k, s, o: BL.greedy_act(ecfg, tr, s)  # noqa: E731
    rollout = rollout_fn_for(ExecSpec(backend="fused"))

    # ---- host loop (warm its jitted step first) ----------------------
    BL.evaluate_policy(ecfg, trace_list[0], host_act(trace_list[0]), keys[0])
    t0 = time.perf_counter()
    host_metrics = [BL.evaluate_policy(ecfg, tr, host_act(tr), k)
                    for tr, k in zip(trace_list, keys)]
    host_s = time.perf_counter() - t0

    # ---- batched engine (api "fused" backend) ------------------------
    t0 = time.perf_counter()
    res = rollout(ecfg, traces, policy, {}, keys)
    jax.block_until_ready(res.metrics)
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(args.repeat):
        t0 = time.perf_counter()
        res = rollout(ecfg, traces, policy, {}, keys)
        jax.block_until_ready(res.metrics)
        times.append(time.perf_counter() - t0)
    batch_s = min(times)

    # metrics parity between the two paths: state-derived metrics are
    # bitwise; the return accumulation can differ by a float32 ulp when the
    # policy itself reduces over candidates (greedy under double-vmap).
    for k, rtol in (("num_scheduled", 0), ("avg_quality", 0),
                    ("avg_steps", 0), ("episode_return", 1e-6)):
        host_v = np.asarray([m[k] for m in host_metrics], np.float32)
        np.testing.assert_allclose(np.asarray(res.metrics[k], np.float32),
                                   host_v, rtol=rtol, atol=0)

    out = {
        "policy": args.policy, "batch": args.batch, "servers": args.servers,
        "max_steps": args.max_steps,
        "host_eps_per_s": args.batch / host_s,
        "batch_eps_per_s": args.batch / batch_s,
        "batch_tasks_per_s": args.batch * args.tasks / batch_s,
        "batch_compile_s": compile_s,
        "speedup": host_s / batch_s,
    }
    print(json.dumps(out, indent=1))
    print(f"\n{args.policy}: host {out['host_eps_per_s']:8.2f} eps/s | "
          f"batched {out['batch_eps_per_s']:8.2f} eps/s | "
          f"speedup x{out['speedup']:.1f} (compile {compile_s:.1f}s)")
    if args.json_out != "none":
        from common import write_bench_json
        write_bench_json(f"batch_rollout_{args.policy}", out,
                         out=args.json_out or None, fused=None,
                         exec_backend="fused")
    return out


def bench_sharded_vs_fused(args):
    """Equal-batch eps/s: "fused" on one device vs "sharded" over the mesh.
    Both are bitwise-identical programs, so speedup is pure scaling."""
    import jax
    import numpy as np

    from repro.api import ExecSpec, resolve_shards, rollout_fn_for
    from repro.core import env as EV
    from repro.core import rollout as RO
    from repro.core.workload import (TraceConfig, make_trace_batch,
                                     paper_rate_for)

    ecfg = EV.EnvConfig(num_servers=args.servers, max_tasks=args.tasks,
                        max_steps=args.max_steps)
    tc = TraceConfig(num_tasks=args.tasks,
                     arrival_rate=paper_rate_for(args.servers),
                     max_servers=args.servers)
    policy = RO.fifo_policy(ecfg)
    cells = []
    for B in [int(b) for b in args.sharded_batches.split(",")]:
        traces = make_trace_batch(jax.random.PRNGKey(1), tc, B)
        keys = jax.random.split(jax.random.PRNGKey(2), B)
        cell = {"batch": B, "servers": args.servers,
                "shards": resolve_shards(B, ExecSpec(backend="sharded"))}
        ref = None
        for backend in ("fused", "sharded"):
            rollout = rollout_fn_for(ExecSpec(backend=backend))

            def run():
                r = rollout(ecfg, traces, policy, {}, keys,
                            num_steps=args.max_steps)
                jax.block_until_ready(r.metrics["episode_return"])
                return r
            t0 = time.perf_counter()
            r = run()                              # compile
            compile_s = time.perf_counter() - t0
            if ref is None:
                ref = np.asarray(r.metrics["episode_return"])
            else:                                  # bitwise across backends
                np.testing.assert_array_equal(
                    ref, np.asarray(r.metrics["episode_return"]))
            n, t0 = 0, time.perf_counter()
            while time.perf_counter() - t0 < args.min_s:
                run()
                n += 1
            eps = B * n / (time.perf_counter() - t0)
            cell[backend] = {"eps_per_s": round(eps, 1),
                             "compile_s": round(compile_s, 2)}
        cell["speedup"] = round(cell["sharded"]["eps_per_s"]
                                / cell["fused"]["eps_per_s"], 2)
        cell["bitwise_identical"] = True
        cells.append(cell)
        print(f"B={B:5d} shards={cell['shards']}: "
              f"fused {cell['fused']['eps_per_s']:9.1f} eps/s  "
              f"sharded {cell['sharded']['eps_per_s']:9.1f} eps/s  "
              f"({cell['speedup']:.2f}x)", flush=True)

    payload = {"policy": "fifo", "tasks": args.tasks,
               "max_steps": args.max_steps, "cells": cells,
               "min_speedup": min(c["speedup"] for c in cells)}
    print(json.dumps(payload, indent=1))
    if args.json_out != "none":
        from common import write_bench_json
        write_bench_json("sharded_rollout", payload,
                         out=args.json_out or None, fused=True,
                         exec_backend="sharded")
    return payload


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--servers", type=int, default=8)
    ap.add_argument("--tasks", type=int, default=32)
    ap.add_argument("--max-steps", type=int, default=256)
    ap.add_argument("--policy", choices=("random", "greedy"), default="random")
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--sharded", action="store_true",
                    help="bench the sharded vs fused api backends instead "
                         "of host-loop vs batched")
    ap.add_argument("--sharded-batches", default="256,1024")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host CPU devices (XLA_FLAGS, set "
                         "before jax starts its backend)")
    ap.add_argument("--min-s", type=float, default=2.0)
    ap.add_argument("--json-out", default="",
                    help="BENCH json path ('' = repo-root default, "
                         "'none' = skip)")
    a = ap.parse_args()
    if a.devices:
        from repro.launch.mesh import force_host_devices
        force_host_devices(a.devices)
    sys.path.insert(0, os.path.dirname(__file__))
    if a.sharded:
        bench_sharded_vs_fused(a)
    else:
        bench_host_vs_batched(a)
