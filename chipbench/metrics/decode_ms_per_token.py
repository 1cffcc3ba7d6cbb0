"""Executor: the program's `decode` span time over the tokens decoded
(the decode loop reads every token back to the host), in ms."""


def read(ctx, out):
    ev = [e for e in out.spans if e["name"] == "decode"]
    tokens = sum(int(e["args"]["steps"]) for e in ev)
    if not tokens:
        return None
    return sum(e["dur"] for e in ev) / tokens / 1e3
