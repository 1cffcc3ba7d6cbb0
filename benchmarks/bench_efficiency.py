"""Fig. 8 — generation efficiency (quality per second of response time).

Computed from the shared scheduling grid as quality / avg_response, the
paper's definition. The paper excludes Random and the meta-heuristics
(below the basic quality bar) and ranks EAT > EAT-A > EAT-DA > EAT-D >
PPO > Greedy on time utilization.
"""
from __future__ import annotations

from benchmarks import common as C

INCLUDED = ("eat", "eat-a", "eat-d", "eat-da", "ppo", "greedy")


def run(verbose: bool = True):
    results = [r for r in C.load_grid() if r["algo"] in INCLUDED]
    if not results:
        print("no cached scheduling runs; run `python -m benchmarks.run` first")
        return None
    for r in results:
        r["efficiency"] = r["avg_quality"] / max(r["avg_response"], 1e-9)
    table = C.format_table(results, "efficiency", fmt="{:.4f}")
    if verbose:
        print("Fig. 8 — generation efficiency (quality / response second)")
        print(table)
    return table


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
