"""Table IX — generation quality across algorithms / cluster sizes / rates.

Reads the shared scheduling-run cache (populated by ``benchmarks.common
.run_grid``; ``benchmarks.run`` orchestrates it) and prints the paper-style
table. Paper anchors: Greedy pins the 0.270 ceiling; SAC-family ~0.26;
PPO fixed 0.228; meta-heuristics ~0.18-0.22; Random lowest.
"""
from __future__ import annotations

from benchmarks import common as C


def run(verbose: bool = True):
    results = C.load_grid()
    if not results:
        print("no cached scheduling runs; run `python -m benchmarks.run` first")
        return None
    table = C.format_table(results, "avg_quality")
    if verbose:
        print("Table IX — quality (CLIP-proxy score)")
        print(table)
    return table


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
