"""Table XI — model reload rate across algorithms / cluster sizes / rates.

Lower is better (fewer cold starts). Paper anchors at 4 servers / 0.05:
EAT 0.633 < EAT-A 0.667 < PPO 0.688 < EAT-DA 0.700 < Harmony 0.726 <
Random 0.800 < Genetic 0.850; Greedy's backlog artificially lowers its rate.
"""
from __future__ import annotations

from benchmarks import common as C


def run(verbose: bool = True):
    results = C.load_grid()
    if not results:
        print("no cached scheduling runs; run `python -m benchmarks.run` first")
        return None
    table = C.format_table(results, "reload_rate")
    if verbose:
        print("Table XI — model reload rate")
        print(table)
    return table


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
