"""Table I — task acceleration with different numbers of patches.

Two measurements:
  1. the calibrated latency model's speedup curve (paper Table I anchors:
     x1.8 @ 2 patches, x3.1 @ 4, x4.9 @ 8 for a 45-step generation);
  2. a REAL patch-parallel measurement on this host: a reduced LM service
     prefills a prompt split into c patches (the TPU mapping of
     DistriFusion's spatial patches — each patch is a sequence chunk on one
     mesh slice; here they run as a batched call), wall-clocked vs the
     single-patch run. On one CPU device the batched call has no real
     parallelism, so we report the *work-per-patch* scaling which on a
     c-wide mesh slice converts to the Table-I speedup.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import get_config
from repro.core import timemodel as TM
from repro.models.zoo import build_model

PAPER_TABLE_I = {1: 1.0, 2: 1.8, 4: 3.1, 8: 4.9}


def model_speedups(steps: int = 45) -> dict:
    t1 = float(TM.exec_time(jnp.asarray(1), jnp.asarray(steps)))
    out = {}
    for c in (1, 2, 4, 8):
        tc = float(TM.exec_time(jnp.asarray(c), jnp.asarray(steps)))
        out[c] = t1 / tc
    return out


def real_patch_prefill(arch: str = "tinyllama-1.1b", seq: int = 512,
                       iters: int = 3) -> dict:
    """Prefill a seq-token prompt as c patches of seq/c; time per patch-chunk."""
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    results = {}
    for c in (1, 2, 4):
        chunk = seq // c
        toks = jnp.zeros((c, chunk), jnp.int32)
        cache = model.make_cache(c, chunk, jnp.float32)

        fn = jax.jit(lambda p, b, ca: model.prefill(p, b, ca))
        out = fn(params, {"tokens": toks}, cache)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(params, {"tokens": toks}, cache)
            jax.block_until_ready(out)
        results[c] = (time.perf_counter() - t0) / iters
    return results


def run(verbose: bool = True, with_real: bool = True) -> dict:
    speedups = model_speedups()
    out = {"model_speedup": speedups, "paper": PAPER_TABLE_I}
    if with_real:
        real = real_patch_prefill()
        # on-a-real-mesh speedup = t(1 patch of S) / t(1 chunk of S/c):
        out["real_chunk_times_s"] = real
        out["real_projected_speedup"] = {c: real[1] / real[c] for c in real}
    if verbose:
        print("Table I — patch acceleration")
        print("| patches | model x | paper x |", "projected x |" if with_real else "")
        for c in (1, 2, 4, 8):
            line = f"| {c} | {speedups[c]:.1f} | {PAPER_TABLE_I[c]} |"
            if with_real and c in out.get("real_projected_speedup", {}):
                line += f" {out['real_projected_speedup'][c]:.1f} |"
            print(line)
    return out


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
