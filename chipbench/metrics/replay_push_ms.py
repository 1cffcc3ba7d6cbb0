"""Replay buffer: host time of the program's `replay_push` spans (the
window's valid transitions flattened and scattered into the ring) per
round of the measured window, in ms."""


def read(ctx, out):
    ev = [e for e in out.spans if e["name"] == "replay_push"]
    n = out.counts.get("rounds")
    if not ev or not n:
        return None
    return sum(e["dur"] for e in ev) / n / 1e3
