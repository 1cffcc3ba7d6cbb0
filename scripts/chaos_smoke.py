#!/usr/bin/env python
"""CI chaos smoke: the fault-injection stack under an aggressive FaultSpec.

    PYTHONPATH=src python scripts/chaos_smoke.py

Gates three contracts on short streaming runs (`make chaos-smoke`):

1. **No silent loss.** On the fused backend under heavy crashes/stragglers
   the stream ledger must balance exactly:
   ``injected == scheduled + dropped + failed_pending_retry + leftover``
   (dropped = backlog-shed + retry-exhausted), and the run must be
   bit-for-bit repeatable (same FaultSpec + key => same summary).
2. **Fault-free identity.** ``faults=None`` and ``FaultSpec.none()`` must
   produce *identical* summaries — the fault branch compiles away.
3. **Serving tolerance.** The serving backend under the same FaultSpec must
   skip crashed gangs (mirror says the server dies mid-run), retry injected
   executor errors, degrade the final attempt, and keep its own ledger
   consistent with the stream's.
"""
from __future__ import annotations

import re
import sys

_MEASURED = re.compile(
    r"(_latency_(p\d+|mean)_s$|_decisions$|^decision_latency_n$"
    r"|measured_busy|^wall_s$)")

CHAOS = dict(seed=2, mtbf=60.0, mttr=15.0, straggler_prob=0.3,
             straggler_factor=3.0, max_retries=3, backoff_base=2.0,
             backoff_cap=20.0, retry_deadline=600.0)


def _det(summary):
    return {k: v for k, v in summary.items()
            if isinstance(v, (int, float, bool)) and not _MEASURED.search(k)}


def _assert_ledger(s, ctx):
    lhs = s["tasks_injected"]
    rhs = (s["tasks_scheduled"] + s["tasks_dropped"]
           + s["tasks_failed_pending_retry"] + s["tasks_leftover"])
    assert lhs == rhs, f"{ctx}: ledger leak — {lhs} != {rhs} ({s})"
    assert s["tasks_dropped"] == (s["tasks_dropped_shed"]
                                  + s["tasks_dropped_retry_exhausted"]), ctx
    print(f"  {ctx}: ledger balances ({lhs} == {rhs}), "
          f"failed={s['tasks_failed']} retried={s['tasks_retried']}")


def main() -> int:
    import jax

    from repro.api import ExecSpec, PolicySpec, Simulator, WorkloadSpec
    from repro.core.scenarios import poisson_scenario
    from repro.faults import FaultSpec

    sc = poisson_scenario(num_servers=4, rate=2.0)
    key = jax.random.PRNGKey(0)

    def run(backend, faults, **es_kw):
        wl = WorkloadSpec.streaming(
            sc, streams=1 if backend == "serving" else 4,
            num_windows=3, window_tasks=8)
        sim = Simulator(wl, ExecSpec(backend=backend, faults=faults,
                                     **es_kw))
        res = sim.run(PolicySpec("greedy"), key)
        fc = (sim._rollout.fault_counters()
              if hasattr(sim._rollout, "fault_counters") else {})
        return res, fc

    chaos = FaultSpec(**CHAOS)

    # 1. fused chaos: conservation + determinism + visible faults ---------
    print("[chaos-smoke] fused backend under chaos")
    r1, _ = run("fused", chaos)
    _assert_ledger(r1.summary, "fused chaos")
    assert r1.summary["tasks_failed"] > 0, "chaos produced zero crashes"
    r2, _ = run("fused", chaos)
    d1, d2 = _det(r1.summary), _det(r2.summary)
    assert d1 == d2, ("fused chaos not deterministic: "
                      f"{ {k: (d1[k], d2[k]) for k in d1 if d1[k] != d2[k]} }")
    print("  deterministic: identical summary on repeat")

    # 2. fault-free identity ---------------------------------------------
    print("[chaos-smoke] faults=None == FaultSpec.none() (fused)")
    b1, _ = run("fused", None)
    b2, _ = run("fused", FaultSpec.none())
    db1, db2 = _det(b1.summary), _det(b2.summary)
    assert db1 == db2, ("FaultSpec.none() changed results: "
                        f"{ {k: (db1[k], db2[k]) for k in db1 if db1[k] != db2[k]} }")
    assert b1.summary["tasks_failed"] == 0
    print("  bitwise-identical summaries")

    # 3. serving under chaos + injected executor errors -------------------
    print("[chaos-smoke] serving backend under chaos + executor faults")
    schaos = FaultSpec(**{**CHAOS, "exec_error_prob": 0.5,
                          "exec_max_attempts": 2})
    s1, fc1 = run("serving", schaos)
    _assert_ledger(s1.summary, "serving chaos")
    print(f"  serving fault counters: {fc1}")
    assert fc1.get("crashed_tasks", 0) + s1.summary["tasks_failed"] > 0
    s2, fc2 = run("serving", schaos)
    assert fc1 == fc2, f"serving fault ledger not deterministic: {fc1} {fc2}"
    assert _det(s1.summary) == _det(s2.summary), "serving chaos summary drift"
    print("  deterministic: identical ledger + summary on repeat")

    sn1, _ = run("serving", None)
    sn2, _ = run("serving", FaultSpec.none())
    assert _det(sn1.summary) == _det(sn2.summary), \
        "serving FaultSpec.none() changed results"
    print("  serving fault-free identity holds")
    print("[chaos-smoke] OK")
    return 0


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
