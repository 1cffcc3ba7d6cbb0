"""Env-step kernel: the least time one call could take on this chip (its
bytes over HBM bandwidth; its operations are far below the compute bound)
over the measured time of a call, in percent."""
from chipbench import costs

KERNEL = "env_step"


def read(ctx, out):
    if ctx.device is None:
        return None
    seconds, calls = ctx.device.ops_matching(KERNEL)
    if not calls:
        return None
    g = ctx.config["cluster"]
    B = out.counts["B"] // ctx.device.devices
    least, _bound = costs.least_seconds(
        B * costs.env_step_ops(g["E"], g["K"], g["l"]),
        costs.env_step_bytes(B, g["E"], g["K"], 2 + g["l"], g["l"]),
        ctx.peaks)
    return least / (seconds / calls) * 100.0
