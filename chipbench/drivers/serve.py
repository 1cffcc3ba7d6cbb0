"""Driver of the served cells: one edge cluster whose servers run a real
model for every task the scheduler places (`ServingRollout` under the
program's stream engine, virtual time).

Each scheduled task loads the model on a cold gang (weights made by the
benchmark's own generator, from a key the program's loader draws), prefills
the prompt in c patches and decodes one greedy token per inference step the
policy chose. The window runs round(`--seconds` / `window_seconds`) whole
stream windows, `window_seconds` being the traffic file's nominal length of
one. A task's time runs, on the benchmark's clock, from the start of the
decision that placed it to the return of its last decode step; the
benchmark wraps the program's decision and generate calls to read it. The
program's host tracer is on in the `--trace 1` run only.

The check, once the window has closed, covers both halves of the timed
path. The decisions: every decision of the window is replayed against the
plain scheduling reference (the configuration's `reference.scheduler`):
the state and observation the policy saw, the action it owes them, the
window's end and the seam between windows; the tasks the reference places
must be the tasks executed, in order, with the same gang size, as many
decode steps as it chose and a cold load for every cold gang. The model:
with the pool's weights freed, a sample of the executed tasks drawn from
the seed, the longest among them, goes through the plain float32 model
(`reference.model`) over each prompt and its served tokens; the widest gap
by which a served token's logit lies below the reference's best is
compared with its limit.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time
from typing import Dict, List

import numpy as np

from chipbench import arrivals
from chipbench.drivers.sim import (env_config, host_state, policy_checks,
                                   policy_for, seam_check)
from chipbench.harness import Check, Outcome, derive_seed, reference


def served_arch(cfg: Dict) -> str:
    """The program's architecture for the configuration, registered under a
    name of its own with the norm epsilon the configuration states (the
    program's `ArchConfig.norm_eps`)."""
    from repro.common.config import get_config, register
    base, eps = cfg["serving"]["arch"], float(cfg["rms_norm_eps"])
    name = f"{base}.eps{eps:g}"
    register(name)(lambda: dataclasses.replace(get_config(base), name=name,
                                               norm_eps=eps))
    return name


class Windows:
    """The serving rollout the runner calls, unchanged, keeping of each
    measured window its inputs and result, and which of the recorded
    decisions, executions and loads fell in it."""

    backend = "serving"

    def __init__(self, inner, rec: Dict[str, List]):
        self.inner, self.rec = inner, rec
        self.log: List[Dict] = []
        self.on = False

    def __call__(self, ecfg, traces, policy, params, keys, **kw):
        at = {k: len(v) for k, v in self.rec.items()}
        res = self.inner(ecfg, traces, policy, params, keys, **kw)
        if self.on:
            self.log.append({"traces": traces, "keys": keys,
                             "init": kw["init_state"], "res": res,
                             "span": {k: (at[k], len(v))
                                      for k, v in self.rec.items()}})
        return res


def run(ctx) -> Outcome:
    import jax
    from repro.actors.program import actor_program
    from repro.serving.backend import ServingRollout
    from repro.telemetry.trace import TraceConfig, Tracer
    from repro.traffic.stream import StreamConfig, StreamRunner

    cfg, tr = ctx.config, ctx.traffic
    MODEL, SCHED = reference(cfg, "model"), reference(cfg, "scheduler")
    ecfg = env_config(cfg)
    E = ecfg.num_servers
    arch, dims = served_arch(cfg), MODEL.dims(cfg)
    prompt_len = int(cfg["assumed"]["prompt_len"])
    max_new = int(cfg["assumed"]["max_new_tokens"])
    policy, params = policy_for(cfg, ecfg, tr["policy"])
    tracer = Tracer(TraceConfig(enabled=True)) if ctx.trace else None

    reduced = bool(cfg["serving"].get("reduced", False))
    dtype = cfg["serving"]["dtype"]

    def rollout(execute: bool, seed: int) -> ServingRollout:
        return ServingRollout(E, archs=(arch,), reduced=reduced,
                              wall_clock=False, execute=execute,
                              prompt_len=prompt_len, max_new_tokens=max_new,
                              seed=seed, warmup=True,
                              tracer=tracer if execute else None)

    roll = rollout(True, derive_seed(ctx.seed, 5))
    ex = roll.executor
    made: Dict[int, object] = {}
    # seen: (state, obs, action) per decision; served: (weights key,
    # prompt, c, steps asked, tokens, seconds since the decision) per
    # executed task; loads: one per cold load
    rec: Dict[str, List] = {"seen": [], "served": [], "loads": []}
    decided: List[float] = []
    program_init, program_generate = ex.init_params, ex.generate
    actor = actor_program(ecfg, policy)             # cached per process
    program_act = type(actor).act.__get__(actor)

    def act(trace, state, obs, key, p):
        decided.append(time.perf_counter())
        out = program_act(trace, state, obs, key, p)
        rec["seen"].append((state, obs, out[1]))
        return out

    def init_params(a, key):
        if ctx.trace:
            with jax.profiler.TraceAnnotation("bench:load"):
                p = MODEL.program_tree(MODEL.make_weights(dims, key, dtype))
        else:
            p = MODEL.program_tree(MODEL.make_weights(dims, key, dtype))
        made[id(p)] = key
        rec["loads"].append(key)
        return p

    def generate(a, p, prompt, c, steps, max_new_tokens=16, **kw):
        if ctx.trace:
            with jax.profiler.TraceAnnotation("bench:generate"):
                toks = program_generate(a, p, prompt, c, steps,
                                        max_new_tokens, **kw)
        else:
            toks = program_generate(a, p, prompt, c, steps, max_new_tokens,
                                    **kw)
        rec["served"].append((made[id(p)], np.asarray(prompt), int(c),
                              int(steps), toks,
                              time.perf_counter() - decided[-1]))
        return toks

    # ---- set-up: the program's shapes, then our weights in them ---------
    want = jax.eval_shape(lambda k: program_init(arch, k),
                          jax.random.PRNGKey(0))
    have = jax.eval_shape(
        lambda k: MODEL.program_tree(MODEL.make_weights(dims, k, dtype)),
        jax.random.PRNGKey(0))
    if jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(have) \
            or jax.tree_util.tree_leaves(want) != jax.tree_util.tree_leaves(have):
        raise RuntimeError("the benchmark's weights do not fit the program's "
                           f"parameter tree for {arch}")
    ex.init_params, ex.generate, actor.act = init_params, generate, act
    cs = sorted({int(c) for c in tr["c_support"] if int(c) <= E})
    for c in cs:
        ex.warm(arch, prompt_len, c, max_new, max_new)
    ctx.mark("executor warmed")
    jax.block_until_ready(MODEL.make_weights(dims, jax.random.PRNGKey(1),
                                             dtype))
    ctx.mark("weight generator")
    # the decision, mirror and seam programs at this cluster's shapes
    mirror = rollout(False, 0)
    mirror.executor.init_params = lambda a, k: None
    warm_src = arrivals.StreamSource(tr, E, derive_seed(ctx.seed, 6), 1)
    StreamRunner(ecfg, policy, params, warm_src, jax.random.PRNGKey(0),
                 StreamConfig(num_streams=1), rollout_fn=mirror).run_window()
    del mirror
    gc.collect()
    ctx.mark("decision programs")

    windows = Windows(roll, rec)
    source = arrivals.StreamSource(tr, E, derive_seed(ctx.seed, 1), 1)
    runner = StreamRunner(ecfg, policy, params, source,
                          jax.random.PRNGKey(derive_seed(ctx.seed, 2)),
                          StreamConfig(num_streams=1), rollout_fn=windows,
                          tracer=tracer)

    def window():
        if ctx.trace:
            with jax.profiler.TraceAnnotation("bench:window"):
                return runner.run_window()
        return runner.run_window()

    for v in rec.values():
        v.clear()
    windows.on = True
    ptrs, stats = [], []
    # ---- the measured window: a fixed number of whole stream windows,
    # --seconds over the traffic's nominal stream window (a stream window
    # lasts seconds, and a count that followed the clock would change the
    # work a seed brings from one run to the next)
    n_windows = max(1, round(ctx.seconds / float(tr["window_seconds"])))
    t_start = ctx.start_window()
    scheduled, lasted = 0, []
    for _ in range(n_windows):
        t0 = time.perf_counter()
        ptrs.append(source.ptr.copy())
        res = window()
        stats.append(res.stats)
        scheduled += res.record["scheduled"]
        lasted.append(time.perf_counter() - t0)
    elapsed = time.perf_counter() - t_start
    print("stream windows, seconds: " + ", ".join(f"{t:.4f}" for t in lasted),
          file=sys.stderr)
    windows.on = False
    ptrs.append(source.ptr.copy())
    executed = roll.tasks_executed
    ctx.memory_peak()
    spans = list(tracer.events) if tracer is not None else []
    window_tasks = list(rec["served"])
    lat = [s[5] for s in window_tasks]

    if ctx.trace:                   # device metrics: a short extra window
        ctx.profiled(lambda: window())

    chk = cfg["check"]
    checks = decision_checks(ctx, SCHED, windows.log, rec, ptrs, stats,
                             source, params, runner.T)
    # ---- the model, with the pool's weights freed -----------------------
    roll.pool.reset()
    del runner
    gc.collect()
    pick = np.random.default_rng([ctx.seed % 2 ** 64, 4])
    order = [int(i) for i in pick.permutation(len(window_tasks))]
    if order:                                   # the longest task first
        longest = int(np.argmax([len(s[4]) for s in window_tasks]))
        order.remove(longest)
        order.insert(0, longest)
    sample, tokens = [], 0
    for i in order:
        if tokens >= chk["served_tokens"]:
            break
        sample.append(window_tasks[i])
        tokens += len(window_tasks[i][4])
    rows = prompt_len + max(max_new, int(cfg["cluster"]["s_max"]))
    worst, weights, wkey = 0.0, None, None
    for key, prompt, c, _, toks, _ in sorted(sample, key=lambda s: id(s[0])):
        if key is not wkey:
            weights, wkey = None, key
            gc.collect()
            weights = MODEL.make_weights(dims, key, dtype)
        lg = MODEL.logits(dims, weights, prompt, c, toks, rows)
        if ctx.control:   # float8's own first choice at each position
            toks = MODEL.logits(dims, weights, prompt, c, toks, rows,
                                quant=True).argmax(axis=1)
        worst = max(worst, float(MODEL.gaps(lg, toks).max()))
    del weights
    checks = [Check("token_logit_gap", worst,
                    chk["limits"]["token_logit_gap"])] + checks + [
        Check("tasks_not_executed", scheduled - executed, 0),
        Check("served_tokens_short", max(0, chk["served_tokens"] - tokens),
              0)]
    counts = {"window_s": elapsed, "tasks": executed,
              "calls": [(int(c), len(p), len(t)) for _, p, c, _, t, _ in
                        window_tasks]}
    p90 = float(np.quantile(lat, 0.9, method="inverted_cdf")) if lat \
        else float("nan")
    return Outcome(e2e={"serve_tasks_per_s": executed / elapsed,
                        "serve_task_p90_s": p90},
                   attempted=scheduled, failed=scheduled - executed,
                   checks=checks, counts=counts, spans=spans)


def decision_checks(ctx, SCHED, log, rec, ptrs, stats, source, params,
                    T: int) -> List[Check]:
    """Every decision of the measured windows against the scheduling
    reference, and what the pool executed against what it placed."""
    import jax
    cfg, chk = ctx.config, ctx.config["check"]
    policy = ctx.traffic["policy"]
    cl = SCHED.Cluster.from_config(cfg)
    ar = SCHED.arith(ctx.devs[0].platform,
                     exact_dots=tuple(cfg["actor"].get("exact_dots", ())))
    host_params = jax.tree_util.tree_map(np.asarray, params)
    rd = SCHED.Readings()
    mismatched = short = cold = 0
    for w, now in enumerate(log):
        trace = {c: np.asarray(v[0]) for c, v in now["traces"].items()}
        d0, d1 = now["span"]["seen"]
        seen = {"state": [host_state(s) for s, _, _ in rec["seen"][d0:d1]],
                "obs": [np.asarray(o) for _, o, _ in rec["seen"][d0:d1]],
                "action": [np.asarray(a) for _, _, a in rec["seen"][d0:d1]]}
        noise = None
        if policy != "greedy":
            nz = SCHED.noise_for(jax.numpy.asarray(now["keys"]), T,
                                 cfg["actor"]["T"], cl.A)
            noise = {k: v[0] for k, v in nz.items()}
        final, placed = SCHED.replay_decisions(
            cl, ar, policy, trace, host_state(now["init"], 0), seen,
            host_state(now["res"].final_state, 0), T, rd, actor=cfg["actor"],
            params=host_params, noise=noise)
        if w + 1 < len(log):
            seam_check(SCHED, rd, cl, trace, final, stats[w], 0, log[w + 1],
                       ptrs[w + 1], source)
        s0, s1 = now["span"]["served"]
        ran = rec["served"][s0:s1]
        mismatched += abs(len(ran) - len(placed))
        for p, (_, _, c, steps, toks, _) in zip(placed, ran):
            mismatched += int(c != p["c"]) + int(steps != p["steps"])
            short += abs(len(toks) - p["steps"])
        l0, l1 = now["span"]["loads"]
        cold += abs((l1 - l0) - sum(not p["reuse"] for p in placed))
    return policy_checks(rd, chk, policy) + [
        Check("placements_mismatched", mismatched, 0),
        Check("decode_steps_short", short, 0),
        Check("cold_loads_mismatched", cold, 0)]
