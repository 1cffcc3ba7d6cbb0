"""Executor, whole task: the least time the chip could take for each
prefill and each decode step the window's tasks needed (each the larger of
its operations over peak FLOP/s and its bytes over HBM bandwidth), summed,
over the window's wall seconds, in percent. Decoding at batch 1 is bound by
bytes: every weight is read once per token."""
from chipbench import costs
from chipbench.reference.qwen2 import dims


def read(ctx, out):
    calls, s = out.counts.get("calls"), out.counts.get("window_s")
    if not calls or not s:
        return None
    m, peaks, total = dims(ctx.config), ctx.peaks, 0.0
    for c, prompt_len, steps in calls:
        S = prompt_len + (-prompt_len) % c
        total += costs.least_seconds(*costs.prefill_cost(m, c, S), peaks)[0]
        for i in range(steps - 1):
            total += costs.least_seconds(*costs.decode_cost(m, S + i), peaks)[0]
    return total / s * 100.0
