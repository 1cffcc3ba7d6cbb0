"""Cells at a size a CPU test can hold, driven without the chip check.

`tiny_run` builds a cell from the files by name, shrinks its load (streams,
sample, prompt, model) and runs its driver once on whatever JAX sees,
without the persistent compile cache. The numbers compared are the ones a
chip run compares; the sizes are not.
"""
from __future__ import annotations

import copy
import importlib
import json
import time
from pathlib import Path
from typing import Dict, Optional

from chipbench import harness

ROOT = Path(__file__).resolve().parents[1]
CPU_PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}

# qwen2-1.5b as the program's reduced preset builds it: 2 layers, width 256
TINY_QWEN2 = {"hidden_size": 256, "intermediate_size": 512,
              "num_hidden_layers": 2, "num_attention_heads": 4,
              "num_key_value_heads": 2, "vocab_size": 1000}


def tiny_cell(workload: str, bench: Optional[Dict] = None, root: Path = ROOT):
    from chipbench.run import Cell
    bench = bench or json.loads((root / "BENCHMARK.json").read_text())
    cell = Cell(bench, workload, root)
    cfg = copy.deepcopy(cell.config)
    tr = dict(cell.traffic)
    if cell.driver == "sim":
        tr["streams"] = 4
        cfg["check"].update(streams=4, min_decisions=40)
    elif cell.driver == "train":
        # 4 streams, batches of 32 and 4 updates a round: round 0 passes
        # the warm-up, rounds 1 and 2 collect with the actor
        tr["streams"] = 4
        tn = tr["train"] = copy.deepcopy(tr["train"])
        tn["sac"].update(batch_size=32, warmup_steps=32,
                         buffer_capacity=4096)
        tn.update(max_updates_per_round=4, setup_updates=4, profile_updates=4)
        tn["check"].update(updates=4, collect_streams=4, min_decisions=40)
    else:
        cfg.update(TINY_QWEN2)
        cfg["serving"].update(reduced=True, dtype="float32")
        cfg["assumed"].update(prompt_len=16, max_new_tokens=8)
        # the reduced model serves in float32, as its reference computes:
        # sound runs read ~0 (CPU), float8 ~0.1, a swapped token ~1
        cfg["check"].update(served_tokens=40, min_decisions=40)
        cfg["check"]["limits"]["token_logit_gap"] = 0.01
    cell.config, cell.traffic = cfg, tr
    return cell


def tiny_run(cell, seed: int = 2 ** 31 + 12345, seconds: float = 0.5,
             control: bool = False, trace: bool = False):
    """(context, outcome) of one run of the cell's driver."""
    import jax
    ctx = harness.Context(cell, seed, seconds, trace, jax.devices(),
                          time.perf_counter(), peaks=CPU_PEAKS)
    ctx.control = control
    driver = importlib.import_module(f"chipbench.drivers.{cell.driver}")
    return ctx, driver.run(ctx)


def correct(out) -> bool:
    return all(c.ok for c in out.checks) and bool(out.checks)
