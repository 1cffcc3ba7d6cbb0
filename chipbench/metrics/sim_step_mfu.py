"""Actor and env step together: the operations one stream-decision needs
(the EAT actor or the greedy baseline's candidate search, plus the env
step), times stream-decisions per second over the measured window, over
the chip's peak bf16 FLOP/s, in percent."""
from chipbench import costs


def read(ctx, out):
    n, s = out.counts.get("stream_decisions"), out.counts.get("window_s")
    if not n or not s:
        return None
    g, a = ctx.config["cluster"], ctx.config["actor"]
    E, K, l = g["E"], g["K"], g["l"]
    if ctx.traffic["policy"] == "greedy":
        ops = costs.greedy_ops(E, K, l)
    else:
        ops = costs.actor_ops(3, E + l, 2 + l, a["d_attn"], a["hidden"],
                              a["t_dim"], a["T"])
    ops += costs.env_step_ops(E, K, l)
    return ops * n / s / (ctx.peaks["bf16_flops"] * len(ctx.devs)) * 100.0
