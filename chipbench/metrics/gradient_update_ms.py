"""Learner: host time of the program's `gradient_update` spans (each a
round's update schedule: replay sampling, uploads and update dispatches)
over the updates they ran in the measured window, in ms."""


def read(ctx, out):
    ev = [e for e in out.spans if e["name"] == "gradient_update"]
    n = out.counts.get("updates")
    if not ev or not n:
        return None
    return sum(e["dur"] for e in ev) / n / 1e3
