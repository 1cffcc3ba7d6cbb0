"""Device: the share of the profiled window in which no operation ran on
the device, in percent."""


def read(ctx, out):
    d = ctx.device
    if d is None or d.window_s <= 0:
        return None
    return (1.0 - d.busy_s / d.window_s) * 100.0
