"""Env-step kernel: device time of the fused env-step Pallas call in the
profiled window, over its calls, in microseconds."""

KERNEL = "env_step"


def read(ctx, out):
    if ctx.device is None:
        return None
    seconds, calls = ctx.device.ops_matching(KERNEL)
    return seconds / calls * 1e6 if calls else None
