"""Placement frontier: QoS with vs without the slow timescale.

    PYTHONPATH=src python benchmarks/bench_placement.py --streams 4 --windows 10

Runs the same streaming workload once per placement policy — none (the
reactive baseline), static (demand-blind prior), lfu (trailing window),
forecast (EWMA + trend) — on two non-stationary multi-model cells:

* ``modelskew-flashcrowd``: Zipf model popularity under periodic arrival
  spikes (`core.scenarios.model_skew_flashcrowd`) — reactive loading
  degenerates into cold-start storms at every spike;
* ``diurnal-skew``: Zipf popularity under sinusoidal day/night load.

Placement never perturbs demand, so all four runs of a cell see the
*identical* seeded arrival stream (asserted); the difference is purely the
layout the fast scheduler finds at each window start. Writes
BENCH_placement.json at the repo root (`make bench-placement`) and asserts
the acceptance gate for the two-timescale PR: on each cell, the best
demand-following policy (lfu or forecast) must beat placement-free on
cold-start rate AND p99 latency.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax

from common import write_bench_json
from repro.api import ExecSpec, PolicySpec, Simulator, WorkloadSpec
from repro.core.scenarios import model_skew_flashcrowd, zipf_probs
from repro.placement import PlacementSpec
from repro.traffic.arrivals import DiurnalArrivals

POLICIES = ("none", "static", "lfu", "forecast")


def _spec(policy: str, model_probs) -> PlacementSpec | None:
    if policy == "none":
        return None
    if policy == "static":
        return PlacementSpec(policy="static", model_probs=model_probs)
    return PlacementSpec(policy=policy)


def diurnal_skew(num_servers: int, num_models: int, zipf_a: float):
    """Zipf-skewed popularity under sinusoidal day/night arrivals."""
    sc = model_skew_flashcrowd(num_servers, num_models, zipf_a=zipf_a)
    base = sc.tcfg.arrival_rate
    return dataclasses.replace(
        sc, name=f"diurnal-skew-{num_models}x{num_servers}srv",
        arrival=DiurnalArrivals(base_rate=base, amplitude=0.6, period=800.0))


def run_point(wl: WorkloadSpec, backend: str, sched: str, policy: str,
              model_probs):
    sim = Simulator(wl, ExecSpec(backend=backend,
                                 placement=_spec(policy, model_probs)))
    res = sim.run(PolicySpec(sched), jax.random.PRNGKey(0))
    s = res.summary
    pc = res.raw.placement_counters
    return {
        "placement": policy,
        "scheduler": sched,
        "wall_s": res.wall_s,
        "tasks_injected": s["tasks_injected"],
        "tasks_scheduled": s["tasks_scheduled"],
        "cold_start_rate": s["cold_start_rate"],
        "reuse_rate": s["reuse_rate"],
        "latency_p50": s["latency_p50"],
        "latency_p99": s["latency_p99"],
        "qos_violation_rate": s["qos_violation_rate"],
        "goodput_rate": s["goodput_rate"],
        "utilization": s["utilization"],
        "placement_decisions": pc.get("placement_decisions", 0),
        "placement_prefetches": pc.get("placement_prefetches", 0),
        "placement_gangs_kept": pc.get("placement_gangs_kept", 0),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--servers", type=int, default=8)
    ap.add_argument("--models", type=int, default=3)
    ap.add_argument("--zipf-a", type=float, default=1.5)
    ap.add_argument("--streams", type=int, default=4)
    ap.add_argument("--windows", type=int, default=10)
    ap.add_argument("--window-tasks", type=int, default=8)
    ap.add_argument("--backend", default="fused")
    ap.add_argument("--scheduler", default="greedy",
                    help="fast-timescale registry policy; the placement "
                         "sweep holds it fixed")
    ap.add_argument("--resp-sla", type=float, default=600.0)
    ap.add_argument("--json-out", default="",
                    help="BENCH json path ('' = repo-root default, "
                         "'none' = skip)")
    args = ap.parse_args()

    probs = zipf_probs(args.models, args.zipf_a)
    cells = [model_skew_flashcrowd(args.servers, args.models,
                                   zipf_a=args.zipf_a),
             diurnal_skew(args.servers, args.models, args.zipf_a)]

    rows = []
    for sc in cells:
        wl = WorkloadSpec.streaming(sc, streams=args.streams,
                                    num_windows=args.windows,
                                    window_tasks=args.window_tasks,
                                    resp_sla=args.resp_sla)
        cell_rows = {}
        for policy in POLICIES:
            pt = run_point(wl, args.backend, args.scheduler, policy, probs)
            pt["cell"] = sc.name
            cell_rows[policy] = pt
            rows.append(pt)
            print(json.dumps(pt))
        # identical arrivals: the slow timescale never perturbs demand
        injected = {p: r["tasks_injected"] for p, r in cell_rows.items()}
        assert len(set(injected.values())) == 1, \
            f"arrival streams diverged across placement policies: {injected}"
        # acceptance gate: the best demand-following policy beats reactive
        # loading on cold starts AND tail latency
        none_row = cell_rows["none"]
        best = min((cell_rows["lfu"], cell_rows["forecast"]),
                   key=lambda r: (r["cold_start_rate"], r["latency_p99"]))
        for gate, better in (("cold_start_rate", "lower"),
                             ("latency_p99", "lower")):
            assert best[gate] < none_row[gate], (
                f"{sc.name}: demand-following placement did not improve "
                f"{gate}: best({best['placement']})={best[gate]:.4f} vs "
                f"none={none_row[gate]:.4f}")
        print(f"# {sc.name}: {best['placement']} beats none — cold_start "
              f"{none_row['cold_start_rate']:.4f} -> "
              f"{best['cold_start_rate']:.4f}, p99 "
              f"{none_row['latency_p99']:.1f} -> {best['latency_p99']:.1f}")

    payload = {
        "workload": {"servers": args.servers, "models": args.models,
                     "zipf_a": args.zipf_a, "streams": args.streams,
                     "windows": args.windows,
                     "window_tasks": args.window_tasks,
                     "scheduler": args.scheduler,
                     "resp_sla": args.resp_sla},
        "frontier": rows,
        "gate": "per cell: min(lfu, forecast) beats none on "
                "cold_start_rate and latency_p99 on identical arrivals",
    }
    if args.json_out != "none":
        path = write_bench_json("placement", payload,
                                out=args.json_out or None,
                                exec_backend=args.backend)
        print(f"wrote {path}")


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
