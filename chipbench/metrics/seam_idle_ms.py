"""Stream window engine: device idle time whose innermost program span is
`window`, `build_window`, `window_seam` or `window_record` (the host seam
between two window rollouts), over the `window` spans of the profiled
window, in ms. Reads the program's spans in the device trace
(`chipbench/spantrace.py`)."""

SEAM = ("window", "build_window", "window_seam", "window_record")


def read(ctx, out):
    idle = getattr(ctx.device, "idle_by_span", None)
    calls = getattr(ctx.device, "span_calls", {}).get("window", {})
    if idle is None or not calls.get("calls"):
        return None
    return sum(idle.get(k, 0.0) for k in SEAM) / calls["calls"] * 1e3
