#!/usr/bin/env python
"""CI placement smoke: the slow timescale's two load-bearing promises.

    PYTHONPATH=src python scripts/placement_smoke.py

Gates two contracts on short streaming runs (`make placement-smoke`):

1. **Off means off.** ``placement=None`` and ``PlacementSpec.none()`` must
   produce *identical* summaries on the fused, sharded, and serving
   backends — placement rewrites the host carry between windows, so an
   inactive spec changes no compiled program and no result.
2. **Placement acts.** An active demand-following policy (lfu) on a
   Zipf-skewed multi-model cell sees the exact arrival stream of the
   placement-free run (`tasks_injected` parity), issues decisions every
   seam, and pre-warms gangs (prefetches > 0); on the serving backend the
   real-weight prefetch/evict ledger accrues off the timed path.
"""
from __future__ import annotations

import re
import sys

_MEASURED = re.compile(
    r"(_latency_(p\d+|mean)_s$|_decisions$|^decision_latency_n$"
    r"|measured_busy|^wall_s$)")


def _det(summary):
    return {k: v for k, v in summary.items()
            if isinstance(v, (int, float, bool)) and not _MEASURED.search(k)}


def main() -> int:
    import jax

    from repro.api import ExecSpec, PolicySpec, Simulator, WorkloadSpec
    from repro.core import env as EV
    from repro.core.scenarios import Scenario, zipf_probs
    from repro.core.workload import TraceConfig
    from repro.placement import PlacementSpec

    ecfg = EV.EnvConfig(num_servers=4, max_tasks=8, num_models=3)
    cell = Scenario(
        name="placement-smoke-cell", ecfg=ecfg,
        tcfg=TraceConfig(num_tasks=8, arrival_rate=2.0, max_servers=4,
                         num_models=3, model_probs=zipf_probs(3)))
    key = jax.random.PRNGKey(0)

    def run(backend, placement, **es_kw):
        wl = WorkloadSpec.streaming(
            cell, streams=1 if backend == "serving" else 4,
            num_windows=3, window_tasks=8)
        sim = Simulator(wl, ExecSpec(backend=backend, placement=placement,
                                     **es_kw))
        return sim.run(PolicySpec("greedy"), key)

    # 1. inactive spec == no spec, byte for byte, on every backend --------
    for backend, kw in (("fused", {}), ("sharded", {}),
                        ("serving", {"serving_execute": False})):
        print(f"[placement-smoke] placement=None == PlacementSpec.none() "
              f"({backend})")
        a = run(backend, None, **kw)
        b = run(backend, PlacementSpec.none(), **kw)
        da, db = _det(a.summary), _det(b.summary)
        assert da == db, (
            f"{backend}: PlacementSpec.none() changed results: "
            f"{ {k: (da[k], db[k]) for k in da if da[k] != db[k]} }")
        assert a.raw.placement_counters == b.raw.placement_counters == {}
        print("  bitwise-identical summaries")

    # 2. an active policy acts without perturbing arrivals ----------------
    print("[placement-smoke] lfu placement on the fused backend")
    base = run("fused", None)
    lfu = run("fused", PlacementSpec(policy="lfu"))
    assert lfu.summary["tasks_injected"] == base.summary["tasks_injected"], \
        "placement perturbed the arrival stream"
    pc = lfu.raw.placement_counters
    assert pc["placement_decisions"] == 3, pc
    assert pc["placement_gangs_planned"] > 0, pc
    print(f"  decisions={pc['placement_decisions']} "
          f"planned={pc['placement_gangs_planned']} "
          f"prefetches={pc['placement_prefetches']}")

    print("[placement-smoke] lfu placement on the serving backend")
    slfu = run("serving", PlacementSpec(policy="lfu"),
               serving_execute=False)
    spc = slfu.raw.placement_counters
    assert spc["placement_decisions"] == 3, spc
    assert "placement_weight_prefetches" in slfu.summary
    print(f"  weight_prefetches={slfu.summary['placement_weight_prefetches']} "
          f"weight_evictions={slfu.summary['placement_weight_evictions']}")
    print("[placement-smoke] OK")
    return 0


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
