"""Learner and collection together (a whole round): the operations of every
update (`costs.sac_update_ops`) and of every stream-decision of collection
(the EAT actor and the env step) in the measured window, over its wall
seconds, over the chip's peak bf16 FLOP/s, in percent."""
from chipbench import costs


def read(ctx, out):
    c = out.counts
    s = c.get("window_s")
    if not s or not c.get("updates"):
        return None
    g, a = ctx.config["cluster"], ctx.config["actor"]
    tn = ctx.traffic["train"]
    E, K, l = g["E"], g["K"], g["l"]
    A = 2 + l
    update = costs.sac_update_ops(tn["sac"]["batch_size"], 3, E + l, A,
                                  a["d_attn"], a["hidden"], a["t_dim"],
                                  a["T"], tn["critic_hidden"])
    decision = costs.actor_ops(3, E + l, A, a["d_attn"], a["hidden"],
                               a["t_dim"], a["T"]) + costs.env_step_ops(E, K, l)
    ops = update * c["updates"] + decision * c["stream_decisions"]
    return ops / s / (ctx.peaks["bf16_flops"] * len(ctx.devs)) * 100.0
