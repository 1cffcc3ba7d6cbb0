"""Fault-tolerance frontier: QoS vs fault rate, retry+degrade vs naive drop.

    PYTHONPATH=src python benchmarks/bench_faults.py --streams 8 --windows 10

Sweeps the per-server outage rate (MTBF) over a deterministic seeded fault
timeline and runs the same streaming workload twice per point:

* ``retry``  — the fault-tolerant policy this repo ships: crashed gangs
  requeue with capped exponential backoff under a deadline-aware retry
  budget (`FaultSpec.max_retries > 0`);
* ``drop``   — the naive baseline: a crashed gang's task is lost
  (`max_retries=0` exhausts the budget on the first failure).

Both see the *same* outages (same FaultSpec seed => same timeline), so the
difference is purely the recovery policy. Each scheduling policy on the
grid (greedy + the fifo/random baselines) is swept with both strategies.
Writes BENCH_faults.json at the repo root (`make bench-faults`) and
asserts that under the shipped default policy (greedy) the retry
strategy's goodput is never below naive drop at any fault rate — the
acceptance gate for the fault-tolerance PR. Baselines are recorded
ungated: random placement can waste retry capacity, and the frontier
shows it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax

from common import write_bench_json
from repro.api import ExecSpec, PolicySpec, Simulator, WorkloadSpec
from repro.core.scenarios import poisson_scenario
from repro.core.workload import paper_rate_for
from repro.faults import FaultSpec

#: swept outage severities: mean seconds between per-server failures
#: (0 = faults off — the bitwise-identical baseline row)
MTBF_GRID = (0.0, 600.0, 300.0, 150.0, 75.0, 40.0)


def _spec(mtbf: float, retries: int, seed: int) -> FaultSpec | None:
    if mtbf <= 0.0:
        return None
    return FaultSpec(seed=seed, mtbf=mtbf, mttr=20.0, straggler_prob=0.05,
                     straggler_factor=3.0, max_retries=retries,
                     backoff_base=1.0, backoff_cap=5.0,
                     retry_deadline=900.0)


def run_point(wl: WorkloadSpec, backend: str, policy: str, mtbf: float,
              retries: int, seed: int):
    faults = _spec(mtbf, retries, seed)
    sim = Simulator(wl, ExecSpec(backend=backend, faults=faults))
    res = sim.run(PolicySpec(policy), jax.random.PRNGKey(0))
    s = res.summary
    return {
        "policy": policy,
        "mtbf": mtbf,
        "strategy": "off" if faults is None else (
            "retry" if retries > 0 else "drop"),
        "max_retries": 0 if faults is None else retries,
        "wall_s": res.wall_s,
        "tasks_injected": s["tasks_injected"],
        "tasks_scheduled": s["tasks_scheduled"],
        "tasks_failed": s.get("tasks_failed", 0),
        "tasks_retried": s.get("tasks_retried", 0),
        "tasks_dropped": s["tasks_dropped"],
        "tasks_dropped_retry_exhausted":
            s.get("tasks_dropped_retry_exhausted", 0),
        "tasks_pending_retry": s.get("tasks_failed_pending_retry", 0),
        "goodput_rate": s["goodput_rate"],
        "goodput_per_s": s["goodput_per_s"],
        "qos_violation_rate": s["qos_violation_rate"],
        "drop_rate": s["drop_rate"],
        "latency_p99": s["latency_p99"],
        "utilization": s["utilization"],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--servers", type=int, default=8)
    ap.add_argument("--streams", type=int, default=8)
    ap.add_argument("--window-tasks", type=int, default=8,
                    help="small windows keep the retry re-admission "
                         "granularity (one window) well under the SLA")
    ap.add_argument("--windows", type=int, default=24)
    ap.add_argument("--backend", default="fused")
    ap.add_argument("--retries", type=int, default=3)
    ap.add_argument("--fault-seed", type=int, default=1)
    ap.add_argument("--policies", default="greedy,fifo,random",
                    help="comma-separated registry policies; each is swept "
                         "over the MTBF grid with both recovery strategies")
    ap.add_argument("--rate-scale", type=float, default=0.35,
                    help="offered load as a fraction of the paper rate: "
                         "the frontier needs headroom for recovered tasks "
                         "to finish inside the SLA (1.0 saturates the "
                         "cluster even fault-free)")
    ap.add_argument("--resp-sla", type=float, default=600.0)
    ap.add_argument("--json-out", default="",
                    help="BENCH json path ('' = repo-root default, "
                         "'none' = skip)")
    args = ap.parse_args()

    rate = paper_rate_for(args.servers) * args.rate_scale
    sc = poisson_scenario(args.servers, rate)
    wl = WorkloadSpec.streaming(sc, streams=args.streams,
                                num_windows=args.windows,
                                window_tasks=args.window_tasks,
                                resp_sla=args.resp_sla)

    rows = []
    for policy in args.policies.split(","):
        for mtbf in MTBF_GRID:
            pt_retry = run_point(wl, args.backend, policy, mtbf,
                                 args.retries, args.fault_seed)
            rows.append(pt_retry)
            print(json.dumps(pt_retry))
            if mtbf > 0.0:
                pt_drop = run_point(wl, args.backend, policy, mtbf, 0,
                                    args.fault_seed)
                rows.append(pt_drop)
                print(json.dumps(pt_drop))
                # the gate applies to the shipped default policy: under
                # greedy placement, retry+degrade must never lose to
                # naive drop. Baseline policies (fifo/random) are
                # recorded ungated — random placement can waste retry
                # capacity, which is exactly what the frontier shows.
                if policy != "greedy":
                    continue
                for gate in ("goodput_rate", "goodput_per_s"):
                    assert pt_retry[gate] >= pt_drop[gate], (
                        f"retry+degrade lost to naive drop for "
                        f"{policy} at mtbf={mtbf}: {gate} "
                        f"{pt_retry[gate]:.4f} < {pt_drop[gate]:.4f}")

    payload = {
        "workload": {"servers": args.servers, "streams": args.streams,
                     "window_tasks": args.window_tasks,
                     "windows": args.windows, "rate": rate,
                     "resp_sla": args.resp_sla},
        "fault_model": {"mttr": 20.0, "straggler_prob": 0.05,
                        "retries": args.retries, "seed": args.fault_seed},
        "frontier": rows,
        "gate": "greedy: retry goodput >= drop goodput at every MTBF "
                "(rate and per_s); baselines recorded ungated",
    }
    if args.json_out != "none":
        path = write_bench_json("faults", payload,
                                out=args.json_out or None,
                                exec_backend=args.backend)
        print(f"wrote {path}")


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
