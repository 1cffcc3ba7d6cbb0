"""Learner: device busy time whose innermost program span is
`gradient_update`, over the updates the profiled round ran, in
microseconds. Reads the program's spans in the device trace
(`chipbench/spantrace.py`)."""


def read(ctx, out):
    busy = getattr(ctx.device, "busy_by_span", None)
    n = out.counts.get("profiled_updates")
    if not busy or not n or not busy.get("gradient_update"):
        return None
    return busy["gradient_update"] / n * 1e6
