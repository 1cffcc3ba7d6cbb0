"""Driver of the simulated scheduling cells: B parallel edge clusters
(streams) scheduled window by window on the program's stream engine.

The window drives `StreamRunner.run_window(collect=True)`, as stream
training's collection does: one jitted rollout of T decisions over B
streams (actor + env-step kernel per decision), then the window seam. It
runs whole windows until `--seconds` have passed. `sim_tasks_per_s` is the
tasks scheduled in those windows over their wall seconds.

The check takes, once the window has closed, a few windows drawn from the
seed and a sample of their streams, and replays each against the plain
reference (`chipbench/reference/scheduler.py`): every observation, action,
reward and done flag the program produced, its final state, and the seam
into the next window (carried state, leftover tasks, fresh tasks, stats).
"""
from __future__ import annotations

import sys
import time
from typing import Dict, List

import numpy as np

from chipbench import arrivals
from chipbench.harness import Check, Outcome, derive_seed, reference
from chipbench.weights import make_actor

STATE_FIELDS = {"time": "time", "server_free_at": "free",
                "server_model": "smodel", "server_gang": "sgang",
                "server_gang_size": "sgsize", "task_status": "status",
                "task_start": "start", "task_finish": "finish",
                "task_steps": "steps", "task_quality": "quality",
                "task_reload": "reload", "steps_taken": "taken"}


def env_config(cfg: Dict):
    from repro.core import env as EV
    g = cfg["cluster"]
    return EV.EnvConfig(
        num_servers=g["E"], queue_window=g["l"], s_min=g["s_min"],
        s_max=g["s_max"], max_tasks=g["K"], time_limit=g["time_limit"],
        max_steps=g["max_steps"], alpha_q=g["alpha_q"], beta_t=g["beta_t"],
        mu_t=g["mu_t"], k_time=g["k_time"], lambda_q=g["lambda_q"],
        p_quality=g["p_quality"], q_min=g["q_min"],
        time_scale=g["time_scale"], num_models=g["num_models"])


def policy_for(cfg: Dict, ecfg, name: str):
    """The program's policy callable and the benchmark's weights for it."""
    import jax
    if name == "greedy":
        from repro.core.rollout import greedy_policy
        return greedy_policy(ecfg), {}
    from repro.actors.policies import actor_policy
    from repro.core.agent import AgentConfig
    a = cfg["actor"]
    acfg = AgentConfig(variant="eat", T=a["T"], hidden=a["hidden"],
                       d_attn=a["d_attn"], log_sigma_min=a["log_sigma_min"],
                       log_sigma_max=a["log_sigma_max"])
    rows, cols = ecfg.obs_shape
    params = make_actor(a, rows, cols, ecfg.action_dim,
                        jax.random.PRNGKey(a["weights_seed"]))
    return actor_policy(ecfg, acfg, deterministic=False), params


def host_state(state, b=None) -> Dict:
    """The reference's names for a program state, of stream `b` of a batch
    or of an unbatched state."""
    pick = (lambda x: x) if b is None else (lambda x: x[b])
    return {mine: np.asarray(pick(getattr(state, f)))
            for f, mine in STATE_FIELDS.items()}


class Recorder:
    """The rollout backend the runner calls, unchanged, keeping references
    to the inputs and outputs of the windows the check will read."""

    def __init__(self, inner, trace: bool):
        self.inner, self.trace = inner, trace
        self.backend = getattr(inner, "backend", "fused")
        self.window = 0
        self.keep: set = set()
        self.log: Dict[int, Dict] = {}

    def __call__(self, ecfg, traces, policy, params, keys, **kw):
        if self.trace:
            import jax
            with jax.profiler.TraceAnnotation("bench:rollout"):
                res = self.inner(ecfg, traces, policy, params, keys, **kw)
        else:
            res = self.inner(ecfg, traces, policy, params, keys, **kw)
        if self.window in self.keep:
            self.log[self.window] = {"traces": traces, "keys": keys,
                                     "init": kw["init_state"], "res": res}
        self.window += 1
        return res


def run(ctx) -> Outcome:
    import jax
    from repro.api.backends import rollout_fn_for
    from repro.api.specs import ExecSpec
    from repro.telemetry.trace import TraceConfig, Tracer
    from repro.traffic.stream import StreamConfig, StreamRunner

    cfg, tr = ctx.config, ctx.traffic
    REF = reference(cfg, "scheduler")
    cl = REF.Cluster.from_config(cfg)
    ecfg = env_config(cfg)
    B = int(tr["streams"])
    policy, params = policy_for(cfg, ecfg, tr["policy"])
    rec = Recorder(rollout_fn_for(ExecSpec(backend="fused")), ctx.trace)
    source = arrivals.StreamSource(tr, cl.E, derive_seed(ctx.seed, 1), B)
    key = jax.random.PRNGKey(derive_seed(ctx.seed, 2))
    tracer = Tracer(TraceConfig(enabled=True)) if ctx.trace else None
    runner = StreamRunner(ecfg, policy, params, source, key,
                          StreamConfig(num_streams=B), rollout_fn=rec,
                          tracer=tracer)
    T = runner.T

    def window():
        if ctx.trace:
            with jax.profiler.TraceAnnotation("bench:window"):
                return runner.run_window(collect=True)
        return runner.run_window(collect=True)

    # ---- set-up: compile and warm every program the window runs --------
    for i in range(2):
        t0 = time.perf_counter()
        window()
        ctx.mark(f"warm window {i + 1}")
    t_window = time.perf_counter() - t0
    # draw the window's traffic now, for 1.5 times the windows it should run
    source.reserve(int(source.ptr.max())
                   + 3 * cl.K * int(ctx.seconds / t_window + 2) // 2)
    ctx.mark("traffic drawn")
    chk = cfg["check"]
    p_keep = min(1.0, chk["windows"] * t_window / max(ctx.seconds, 1e-9))
    pick = np.random.default_rng([ctx.seed % 2 ** 64, 3])
    first = runner.window
    rec.keep = {first, first + 1}
    ptrs: Dict[int, np.ndarray] = {}
    stats: Dict[int, Dict] = {}
    if tracer is not None:
        tracer.events.clear()

    # ---- the measured window ------------------------------------------
    t_start = ctx.start_window()
    scheduled = injected = dropped = windows = 0
    while True:
        w = runner.window
        if w in rec.keep:
            ptrs[w] = source.ptr.copy()
        if w != first and pick.random() < p_keep:
            rec.keep |= {w, w + 1}
        res = window()
        if w in rec.keep:
            stats[w] = res.stats
        scheduled += res.record["scheduled"]
        injected += res.record["injected"]
        dropped += res.record["dropped"]
        windows += 1
        if time.perf_counter() - t_start >= ctx.seconds:
            break
    jax.block_until_ready(res.transitions)
    elapsed = time.perf_counter() - t_start
    ctx.memory_peak()
    spans = list(tracer.events) if tracer is not None else []

    if ctx.trace:                   # device metrics: a short extra window
        def extra():
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < cfg["profile_seconds"]:
                window()
        rec.keep = set()
        ctx.profiled(extra)

    checks = check(ctx, REF, cl, rec, stats, ptrs, source, params, T)
    counts = {"stream_decisions": windows * T * B, "window_s": elapsed,
              "windows": windows, "tasks": scheduled, "B": B, "T": T}
    return Outcome(e2e={"sim_tasks_per_s": scheduled / elapsed},
                   attempted=injected, failed=dropped, checks=checks,
                   counts=counts, spans=spans)


def check(ctx, REF, cl, rec, stats, ptrs, source, params, T) -> List[Check]:
    import jax
    cfg, chk = ctx.config, ctx.config["check"]
    policy = ctx.traffic["policy"]
    rd = REF.Readings()
    platform = ctx.devs[0].platform
    exact_dots = tuple(cfg["actor"].get("exact_dots", ()))
    ar = REF.arith(platform, exact_dots=exact_dots)
    host_params = jax.tree_util.tree_map(np.asarray, params)
    pick = np.random.default_rng([ctx.seed % 2 ** 64, 4])
    checked = [w for w in sorted(rec.log)
               if w + 1 in rec.log and w in stats and w + 1 in ptrs]
    for w in checked:
        now, nxt = rec.log[w], rec.log[w + 1]
        B = int(now["keys"].shape[0])
        streams = np.sort(pick.choice(B, min(chk["streams"], B), replace=False))
        noise = (REF.noise_for(jax.numpy.asarray(now["keys"])[streams], T,
                               cfg["actor"]["T"], cl.A)
                 if policy != "greedy" else None)
        tx = now["res"].transitions
        got = {f: np.asarray(getattr(tx, f)[streams])
               for f in ("obs", "action", "reward", "done", "valid")}
        for i, b in enumerate(streams):
            trace = {c: np.asarray(v[b]) for c, v in now["traces"].items()}
            carry = host_state(now["init"], b)
            nz = None if noise is None else {k: v[i] for k, v in noise.items()}
            if ctx.control:
                out = REF.run_free(cl, REF.arith(platform, control=True,
                                                 exact_dots=exact_dots),
                                   policy, trace, carry, T, cfg["actor"],
                                   host_params, nz)
            else:
                out = {f: v[i] for f, v in got.items()}
                out["state"] = host_state(now["res"].final_state, b)
            final = REF.replay(cl, ar, policy, trace, carry, out, rd,
                               actor=cfg["actor"], params=host_params,
                               noise=nz)
            if not ctx.control:
                seam_check(REF, rd, cl, trace, final, stats[w], b, nxt,
                           ptrs[w + 1], source)
    return policy_checks(rd, chk, policy)


def policy_checks(rd, chk: Dict, policy: str) -> List[Check]:
    """The numbers a replay read, each with its limit from the
    configuration's `check` group."""
    print(f"env_max_rel_err worst at: {rd.worst}; decisions checked: "
          f"{rd.decisions}", file=sys.stderr)
    checks = [Check("env_max_rel_err", rd.env_max_rel_err,
                    chk["limits"]["env_max_rel_err"]),
              Check("int_mismatches", rd.int_mismatches,
                    chk["limits"]["int_mismatches"]),
              Check("decisions_checked_short", max(0, chk["min_decisions"]
                                                   - rd.decisions), 0)]
    if policy == "greedy":
        checks.insert(0, Check("greedy_score_gap", rd.greedy_score_gap,
                               chk["limits"]["greedy_score_gap"]))
    else:
        print(f"actor_max_abs_diff (not compared): {rd.actor_max_abs_diff!r}",
              file=sys.stderr)
        checks.insert(0, Check("actor_mean_abs_diff",
                               rd.actor_abs_diff_sum / max(rd.actor_values, 1),
                               chk["limits"]["actor_mean_abs_diff"]))
    return checks


def seam_check(REF, rd, cl, trace, final, stats, b, nxt, ptr,
               source) -> None:
    """The window's stats, the state carried into the next window, and the
    next window's tasks: the leftovers (the newest `max_carry` of them,
    oldest first, clocks rebased) and then fresh tasks from the source."""
    want, carry, lo = REF.seam(cl, trace, final)
    for k, v in want.items():
        if k == "sum_resp":
            rd.rel(stats[k][b], v, "seam sum_resp")
        else:
            rd.same(stats[k][b], v)
    got_carry = host_state(nxt["init"], b)
    for f in REF.INT_FIELDS:
        rd.same(got_carry[f], carry[f])
    for f in REF.FLOAT_FIELDS:
        rd.rel(got_carry[f], carry[f], "carry " + f)
    nl = len(lo["arr_time"])
    kept = min(nl, cl.max_carry)
    got = {c: np.asarray(v[b]) for c, v in nxt["traces"].items()}
    for c in arrivals.COLS:
        if c == "arr_time":
            rd.rel(got[c][:kept], lo[c][nl - kept:], "leftover arr_time")
        else:
            rd.same(got[c][:kept], lo[c][nl - kept:])
    fresh = source.tasks(b, int(ptr[b]), cl.K - kept)
    for c in ("c", "model", "noise"):
        rd.same(got[c][kept:], fresh[c])
    if cl.K > kept:   # window-local clock: absolute minus the window's epoch
        epoch = fresh["arr_time"][0] - float(got["arr_time"][kept])
        rd.rel(got["arr_time"][kept:],
               (fresh["arr_time"] - epoch).astype(np.float32), "fresh arrivals")
