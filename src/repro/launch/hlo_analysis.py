"""Extract roofline terms from a compiled dry-run artifact.

cost_analysis() gives PER-DEVICE HLO FLOPs / bytes accessed (verified: a
512-way sharded matmul reports 1/512 of the global FLOPs). Collective bytes
are not in cost_analysis, so we parse the optimized HLO text and sum the
output-shape bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute op (a standard per-device bytes-moved proxy).

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import re
from typing import Dict

from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\][^ ]*))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device bytes moved by collectives, by op kind."""
    out: Dict[str, float] = {}
    count: Dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        ty, op = m.group(1), m.group(2)
        b = _shape_bytes(ty)
        out[op] = out.get(op, 0.0) + b
        count[op] = count.get(op, 0) + 1
    out["total"] = sum(v for k, v in out.items())
    out["ops"] = sum(count.values())
    return out


def roofline_terms(cost: Dict, coll: Dict, *, num_links: int = 4) -> Dict:
    """Three roofline terms in seconds (per device / per chip)."""
    flops = float(cost.get("flops", 0.0))
    bytes_hbm = float(cost.get("bytes accessed", 0.0))
    bytes_coll = float(coll.get("total", 0.0))
    t_compute = flops / PEAK_FLOPS_BF16
    t_memory = bytes_hbm / HBM_BW
    t_coll = bytes_coll / (ICI_BW * num_links)
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll, "hlo_flops": flops,
             "hlo_bytes": bytes_hbm, "collective_bytes": bytes_coll}
    dominant = max(("compute_s", "memory_s", "collective_s"),
                   key=lambda k: terms[k])
    terms["bottleneck"] = dominant.replace("_s", "")
    return terms


def analyze_compiled(compiled) -> Dict:
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    mem = compiled.memory_analysis()
    out = roofline_terms(cost, coll)
    out["memory"] = {
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "code_bytes": mem.generated_code_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
    }
    out["peak_device_bytes"] = (mem.argument_size_in_bytes
                                + mem.output_size_in_bytes
                                + mem.temp_size_in_bytes
                                - mem.alias_size_in_bytes)
    out["collectives"] = {k: v for k, v in coll.items()}
    return out
