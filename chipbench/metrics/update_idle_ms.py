"""Learner: device idle time whose innermost program span is
`gradient_update`, over the updates the profiled round ran, in ms. Reads
the program's spans in the device trace (`chipbench/spantrace.py`)."""


def read(ctx, out):
    idle = getattr(ctx.device, "idle_by_span", None)
    n = out.counts.get("profiled_updates")
    if idle is None or not n or "gradient_update" not in getattr(
            ctx.device, "span_calls", {}):
        return None
    return idle.get("gradient_update", 0.0) / n * 1e3
