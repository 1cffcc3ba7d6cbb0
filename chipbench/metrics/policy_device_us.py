"""Policy (the EAT actor, or greedy's candidate search): device time of
the ops under the program's `policy` named scope, over the scan steps of
the profiled window (`window_rollout` spans times T decisions), in
microseconds. An op is counted by the scope of its event metadata's
`tf_op` (a fusion carries its root's), read by `chipbench/spantrace.py`."""


def read(ctx, out):
    d = ctx.device
    calls = getattr(d, "span_calls", {}).get("window_rollout", {})
    if not calls.get("calls") or not out.counts.get("T"):
        return None
    seconds, n = d.scope_seconds("policy")
    if not n:
        return None
    return seconds / (calls["calls"] * out.counts["T"]) * 1e6
