"""Executor, cold load: mean host time of the program's `model_load` span
over the measured window's cold loads, in ms. With tracing on the span
ends in `block_until_ready` on the loaded weights."""


def read(ctx, out):
    ev = [e for e in out.spans if e["name"] == "model_load"]
    if not ev:
        return None
    return sum(e["dur"] for e in ev) / len(ev) / 1e3
