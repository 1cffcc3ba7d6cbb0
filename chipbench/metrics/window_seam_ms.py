"""Stream window engine: mean host time per window of the program's
`build_window` and `window_seam` spans (neither has child spans), in ms."""


def read(ctx, out):
    ev = [e for e in out.spans if e["name"] in ("build_window", "window_seam")]
    n = out.counts.get("windows", 0)
    if not ev or not n:
        return None
    return sum(e["dur"] for e in ev) / n / 1e3
