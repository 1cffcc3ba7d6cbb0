"""Roofline report — assignment deliverable (g).

Reads the dry-run artifacts (``artifacts/dryrun/*.json``, produced by
``repro.launch.dryrun``) and prints, per (arch x shape) on the single-pod
mesh: the three roofline terms in seconds, the dominant bottleneck,
MODEL_FLOPS / HLO_FLOPs (useful-compute ratio), and per-device memory.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")


def load(mesh: str = "single") -> List[Dict]:
    out = []
    if not os.path.isdir(ART):
        return out
    for fn in sorted(os.listdir(ART)):
        if not fn.endswith(".json"):
            continue
        with open(os.path.join(ART, fn)) as f:
            r = json.load(f)
        if r.get("mesh") == mesh:
            out.append(r)
    return out


def fmt_row(r: Dict) -> str:
    if r["status"] == "skipped":
        return f"| {r['arch']} | {r['shape']} | skipped |" + " - |" * 8
    if r["status"] != "ok":
        return f"| {r['arch']} | {r['shape']} | ERROR |" + " - |" * 8
    tc, tm, tl = (r.get("a_compute_s", 0), r.get("a_memory_s", 0),
                  r.get("a_collective_s", 0))
    dom = r.get("a_bottleneck", "?")
    mdom = r.get("bottleneck", "?")
    ratio = r.get("useful_flop_ratio", 0.0)
    peak_gb = r.get("peak_device_bytes", 0) / 1e9
    fits = "Y" if peak_gb < 15.2 else "N"   # v5e: 16 GB HBM, 5% headroom
    return (f"| {r['arch']} | {r['shape']} | {tc:.2e} | {tm:.2e} | {tl:.2e} "
            f"| {dom} | {mdom} | {ratio:.2f} | {peak_gb:.1f} | {fits} |")


def run(verbose: bool = True, mesh: str = "single") -> Optional[str]:
    rows = load(mesh)
    if not rows:
        print("no dry-run artifacts; run `python -m repro.launch.dryrun --all`")
        return None
    head = ("| arch | shape | compute_s | memory_s | collective_s | "
            "bottleneck | HLO-bneck | useful/HLO flops | HLO peak GB/dev "
            "| fits 16GB |")
    lines = [head, "|" + "---|" * 10]
    lines += [fmt_row(r) for r in rows]
    table = "\n".join(lines)
    if verbose:
        n_ok = sum(r["status"] == "ok" for r in rows)
        n_skip = sum(r["status"] == "skipped" for r in rows)
        print(f"Roofline ({mesh}-pod mesh): {n_ok} ok, {n_skip} skipped, "
              f"{len(rows) - n_ok - n_skip} errors")
        print("(compute/memory/collective = analytic model per device; "
              "HLO columns = measured, scan-body-once caveat — see "
              "EXPERIMENTS.md §Roofline)")
        print(table)
        census: Dict[str, int] = {}
        for r in rows:
            if r["status"] == "ok":
                b = r.get("a_bottleneck", "?")
                census[b] = census.get(b, 0) + 1
        print("analytic bottleneck census:", census)
    return table


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    import sys
    run(mesh=sys.argv[1] if len(sys.argv) > 1 else "single")
