"""Executor: device idle time whose innermost program span is `decode`
(the decode loop reads every token back to the host), over the tokens the
profiled window's `decode` spans asked for (their summed `steps`), in ms.
Reads the program's spans in the device trace (`chipbench/spantrace.py`)."""


def read(ctx, out):
    idle = getattr(ctx.device, "idle_by_span", None)
    steps = getattr(ctx.device, "span_calls", {}).get("decode", {}).get(
        "steps")
    if idle is None or not steps:
        return None
    return idle.get("decode", 0.0) / steps * 1e3
