"""Tables II–IV — the motivating 4-task example.

Reproduces the paper's §II experiment: tasks 1-4 arrive 10 s apart on a
4-GPU cluster (tasks 1,2,4 need 2 patches; task 3 needs 4).  The
*traditional* policy runs a fixed 20 steps, schedules tasks in arrival
order onto the first free servers, and never reuses loaded models across
gang sizes — reproducing Table III's repeated inits.  The *EAT-style*
policy trades a few steps away on the queued tasks and reuses the 2-patch
gangs — reproducing Table II.  We report both event logs and the Table-IV
summary (quality / mean inference latency) from the same latency+quality
models used everywhere else.
"""
from __future__ import annotations

from typing import Dict, List

import jax.numpy as jnp
import numpy as np

from repro.core import timemodel as TM
from repro.core.quality import quality_of

ARRIVALS = [0.0, 10.0, 20.0, 30.0]
PATCHES = [2, 2, 4, 2]


def _simulate(policy: str) -> Dict:
    """Event-driven simulation of the 4-task example on 4 servers."""
    free_at = np.zeros(4)
    gang_model = [None] * 4          # loaded gang signature per server
    log: List[Dict] = []
    responses, qualities = [], []

    if policy == "traditional":
        steps_for = {0: 20, 1: 20, 2: 20, 3: 20}
    else:  # eat: shave steps on queued tasks, reuse gangs
        steps_for = {0: 18, 1: 17, 2: 17, 3: 25}
        # proactive init (paper Table II: Init 1 + Init 2 both start at t=0,
        # before any task is scheduled — the agent warms two 2-patch gangs)
        for pair in ([0, 1], [2, 3]):
            init = float(TM.init_time(jnp.asarray(2)))
            for i in pair:
                free_at[i] = init
                gang_model[i] = ("gang", 2)
            log.append({"task": f"Init {len(log)+1}", "gpu": pair,
                        "time": round(init, 1)})

    order = [0, 1, 2, 3] if policy == "traditional" else [0, 1, 3, 2]
    for k in order:
        c = PATCHES[k]
        arr = ARRIVALS[k]
        # earliest time c servers are simultaneously free
        t_sorted = np.sort(free_at)
        start = max(arr, t_sorted[c - 1])
        sig = ("gang", c)
        # pick servers: prefer an idle gang with the same signature
        idle = [i for i in range(4) if free_at[i] <= start]
        reuse = (policy == "eat"
                 and sum(gang_model[i] == sig for i in idle) >= c)
        if reuse:
            sel = [i for i in idle if gang_model[i] == sig][:c]
            init = 0.0
        else:
            sel = sorted(idle, key=lambda i: free_at[i])[:c]
            init = float(TM.init_time(jnp.asarray(c)))
            log.append({"task": f"Init {len(log)+1}", "gpu": sel,
                        "time": round(init, 1)})
        s = steps_for[k]
        texe = float(TM.exec_time(jnp.asarray(c), jnp.asarray(s)))
        finish = start + init + texe
        for i in sel:
            free_at[i] = finish
            gang_model[i] = sig
        q = float(quality_of(jnp.asarray(s)))
        responses.append(finish - arr)
        qualities.append(q)
        log.append({"task": f"Task {k+1}", "patches": c, "gpu": sel,
                    "steps": s, "exec_s": round(texe, 1),
                    "inference_s": round(finish - arr, 1),
                    "quality": round(q, 2)})
    return {"log": log, "avg_quality": float(np.mean(qualities)),
            "avg_inference_latency": float(np.mean(responses))}


def run(verbose: bool = True) -> Dict:
    eat = _simulate("eat")
    trad = _simulate("traditional")
    out = {"eat": eat, "traditional": trad,
           "paper_table_iv": {"eat": {"quality": 2.4 / 10, "latency": 22.64},
                              "traditional": {"quality": 2.51 / 10,
                                              "latency": 52.00}}}
    if verbose:
        for name, res in (("EAT (Table II)", eat),
                          ("Traditional (Table III)", trad)):
            print(f"\n{name}:")
            for e in res["log"]:
                print("  ", e)
            print(f"  avg quality {res['avg_quality']:.3f}, "
                  f"avg inference latency {res['avg_inference_latency']:.2f} s")
        speedup = trad["avg_inference_latency"] / eat["avg_inference_latency"]
        print(f"\nTable IV: EAT latency {eat['avg_inference_latency']:.1f}s vs "
              f"traditional {trad['avg_inference_latency']:.1f}s "
              f"({speedup:.2f}x; paper: 22.6 vs 52.0 = 2.30x)")
    return out


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
