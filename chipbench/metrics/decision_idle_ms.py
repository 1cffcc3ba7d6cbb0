"""Serving decision seam: device idle time whose innermost program span is
`decision` or `env_advance`, over the `decision` spans of the profiled
window, in ms. Reads the program's spans in the device trace
(`chipbench/spantrace.py`)."""


def read(ctx, out):
    idle = getattr(ctx.device, "idle_by_span", None)
    calls = getattr(ctx.device, "span_calls", {}).get("decision", {})
    if idle is None or not calls.get("calls"):
        return None
    return (idle.get("decision", 0.0) + idle.get("env_advance", 0.0)) \
        / calls["calls"] * 1e3
