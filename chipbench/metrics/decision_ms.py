"""Serving decision seam: mean host time per decision of the program's
`decision` and `env_advance` spans (both end in `block_until_ready`), ms."""


def read(ctx, out):
    dec = [e for e in out.spans if e["name"] == "decision"]
    if not dec:
        return None
    adv = [e for e in out.spans if e["name"] == "env_advance"]
    return (sum(e["dur"] for e in dec) + sum(e["dur"] for e in adv)) \
        / len(dec) / 1e3
