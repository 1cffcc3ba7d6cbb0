"""Table X — response latency across algorithms / cluster sizes / rates.

Paper headline (4 servers, rate 0.05): EAT 39.7 s beats EAT-A by 28.7%,
EAT-DA by 58.2%, PPO by 68.8%, Greedy by 74.3%, Random by 30.0%.
We assert the *ordering* (EAT < ablations < Greedy) rather than absolute
seconds — see DESIGN.md §6 (calibrated latency model, smaller training
budget).
"""
from __future__ import annotations

from benchmarks import common as C


def run(verbose: bool = True):
    results = C.load_grid()
    if not results:
        print("no cached scheduling runs; run `python -m benchmarks.run` first")
        return None
    table = C.format_table(results, "avg_response", fmt="{:.1f}")
    if verbose:
        print("Table X — response latency (s)")
        print(table)
        # headline comparison at the paper's real-machine cell
        cell = {r["algo"]: r for r in results
                if r["servers"] == 4 and abs(r["rate"] - 0.05) < 1e-9}
        if "eat" in cell:
            eat = cell["eat"]["avg_response"]
            for other in ("eat-a", "eat-da", "ppo", "greedy", "random"):
                if other in cell:
                    o = cell[other]["avg_response"]
                    print(f"  EAT vs {other}: {eat:.1f} vs {o:.1f} "
                          f"({100 * (o - eat) / max(o, 1e-9):+.1f}%)")
    return table


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
