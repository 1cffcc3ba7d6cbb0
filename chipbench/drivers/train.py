"""Driver of the stream-training cell: the program's own stream SAC loop
(`training/stream_train.py` `train_stream_sac`, the paper's Algorithm 2) on
B simulated clusters at once.

A round collects one window of T decisions over B streams with the current
policy (`StreamRunner.run_window(collect=True)`), pushes its valid
transitions into the replay buffer, and runs the update schedule over them:
one SAC update per new transition at the traffic file's settings. Round 0
collects with the uniform warm-up policy and runs the traffic file's
`setup_updates` updates, the first ones the check follows; it and every
compile belong to set-up. The window is a fixed number of whole
rounds after it, from the traffic file's `round_seconds`, so a seed brings
the same work to every run. `train_transitions_per_s` is the transitions
pushed in those rounds over their wall seconds.

The loop is the program's. The driver hands it the benchmark's task source
(`chipbench/arrivals.py`), its initial weights, made from the seed on the
device, and with `--trace 1` a tracer, by standing its own objects in for
the ones `train_stream_sac` builds (`StreamRunner`, `ReplayBuffer`,
`init_train_state`); it wraps the update step and the rollout to keep what
the check reads, and leaves them as they are.

The check, once the window has closed, against plain references
(`reference/sac.py`, `reference/scheduler.py`):

* the first updates of round 0: the reference follows them from the same
  initial weights on the same batches and keys, and each step's losses, the
  first step's gradient (from the program's Adam moments) and the
  parameters' change after them are compared by the worst leaf's norm;
* a sample of the window's updates, drawn from the seed (the first of round
  1 always among them): each recomputed from the program's state before it,
  the batch it drew and its key, every leaf of the state after it compared;
* one measured window of a sample of streams, replayed as the sim cells
  replay theirs, with the actor weights of that round;
* every measured round's replay-buffer rows against that window's valid
  transitions.
"""
from __future__ import annotations

import contextlib
import functools
import math
import sys
import time
from typing import Dict, List

import numpy as np

from chipbench import arrivals
from chipbench.drivers import sim
from chipbench.harness import Check, Outcome, derive_seed, reference

OPT = ("opt_actor", "opt_critic1", "opt_critic2")
NETS = ("actor", "critic1", "critic2")


@contextlib.contextmanager
def standing_in(obj, **attrs):
    """Set attributes of a module for the length of the block."""
    old = {k: getattr(obj, k) for k in attrs}
    for k, v in attrs.items():
        setattr(obj, k, v)
    try:
        yield
    finally:
        for k, v in old.items():
            setattr(obj, k, v)


@functools.lru_cache(maxsize=None)
def _weights_maker(rows: int, cols: int, A: int, d_attn: int, hidden: int,
                   t_dim: int, sigma_std: float, sigma_bias: float,
                   critic_hidden: int):
    """Actor and two critics in one jitted call from a key. The actor as
    `chipbench/weights.py` makes it; a critic is the Mish MLP [rows * cols
    + A, h, h, 1] with N(0, 1/fan_in) weights and N(0, 0.01^2) biases."""
    import jax
    from chipbench.weights import _maker
    actor_of = _maker(rows, cols, A, d_attn, hidden, t_dim, sigma_std,
                      sigma_bias)
    dims = [rows * cols + A, critic_hidden, critic_hidden, 1]

    def critic(key):
        ks = jax.random.split(key, 2 * len(dims))
        return {"layers": [
            {"w": jax.random.normal(ks[2 * i], (a, b)) / math.sqrt(a),
             "b": 0.01 * jax.random.normal(ks[2 * i + 1], (b,))}
            for i, (a, b) in enumerate(zip(dims[:-1], dims[1:]))]}

    @jax.jit
    def make(key):
        ka, k1, k2 = jax.random.split(key, 3)
        return actor_of(ka), critic(k1), critic(k2)
    return make


def make_weights(cfg: Dict, train: Dict, rows: int, cols: int, A: int,
                 key):
    a = cfg["actor"]
    return _weights_maker(rows, cols, A, int(a["d_attn"]), int(a["hidden"]),
                          int(a["t_dim"]), float(a["sigma_std"]),
                          float(a["sigma_bias"]),
                          int(train["critic_hidden"]))(key)


def as_dict(ts) -> Dict:
    """The program's train state in the reference's layout."""
    d = dict(ts._asdict())
    for k in OPT:
        d[k] = dict(d[k]._asdict())
    return d


class Learner:
    """The program's update step, called unchanged; keeps the state before,
    batch, key and outputs of the updates in `keep`."""

    def __init__(self, real, keep):
        self.real, self.keep = real, set(keep)
        self.count = 0
        self.log: Dict[int, tuple] = {}

    def __call__(self, ts, batch, key, **kw):
        i = self.count
        self.count += 1
        out = self.real(ts, batch, key, **kw)
        if i in self.keep:
            self.log[i] = (ts, batch, key, out)
        return out


class Recorder(sim.Recorder):
    """The rollout, unchanged; also keeps the actor weights of the windows
    the check replays."""

    def __init__(self, inner, trace: bool):
        super().__init__(inner, trace)
        self.params: Dict[int, Dict] = {}

    def __call__(self, ecfg, traces, policy, params, keys, **kw):
        if self.window in self.keep:
            self.params[self.window] = params
        return super().__call__(ecfg, traces, policy, params, keys, **kw)


def runner_type():
    from repro.traffic.stream import StreamRunner

    class Runner(StreamRunner):
        """The program's stream runner; keeps each window's source
        pointers, stats and transitions."""
        ptrs: Dict[int, np.ndarray]
        stats: Dict[int, Dict]
        transitions: Dict[int, object]
        record: set

        def run_window(self, **kw):
            w = self.window
            self.ptrs[w] = self.source.ptr.copy()
            res = super().run_window(**kw)
            if w in self.record:
                self.stats[w] = res.stats
                self.transitions[w] = res.transitions
            return res
    return Runner


def updates_for(scfg, n_new: int, size: int, cap) -> int:
    """Updates the program's schedule runs over `n_new` new transitions
    with `size` in the buffer (`sac.run_update_schedule`'s rule)."""
    if size < scfg.warmup_steps:
        return 0
    n = (n_new // scfg.update_every) * scfg.updates_per_step
    return n if cap is None else min(n, cap)


def run(ctx) -> Outcome:
    import jax
    from repro.api.backends import rollout_fn_for
    from repro.api.specs import ExecSpec
    from repro.core import sac as SAC
    from repro.core.agent import AgentConfig
    from repro.core.replay import ReplayBuffer
    from repro.telemetry.trace import TraceConfig, Tracer
    from repro.training import stream_train as ST
    from repro.training.optimizer import adam_init

    cfg, tr = ctx.config, ctx.traffic
    tn, chk = tr["train"], tr["train"]["check"]
    REF = reference(cfg, "scheduler")
    cl = REF.Cluster.from_config(cfg)
    ecfg = sim.env_config(cfg)
    B = int(tr["streams"])
    a = cfg["actor"]
    sac = dict(tn["sac"])
    acfg = AgentConfig(variant="eat", T=a["T"], hidden=a["hidden"],
                       d_attn=a["d_attn"],
                       entropy_alpha=sac.pop("entropy_alpha"),
                       log_sigma_min=a["log_sigma_min"],
                       log_sigma_max=a["log_sigma_max"])
    scfg = SAC.SACConfig(**sac)
    n = max(1, int(round(ctx.seconds / float(tn["round_seconds"]))))
    cap = tn["max_updates_per_round"]
    # round 0 is set-up: it runs `setup_updates` updates (the first the
    # check follows, and the update's compile), not one per transition
    stcfg = ST.StreamTrainConfig(rounds=1 + n + int(ctx.trace),
                                 windows_per_round=1, streams=B,
                                 max_updates_per_round=int(
                                     tn["setup_updates"]))
    rows, cols = ecfg.obs_shape
    pick = np.random.default_rng([ctx.seed % 2 ** 64, 5])

    # ---- set-up: weights, traffic, the stand-ins -----------------------
    weights = make_weights(cfg, tn, rows, cols, ecfg.action_dim,
                           jax.random.PRNGKey(derive_seed(ctx.seed, 2)))
    jax.block_until_ready(weights)
    ctx.mark("weights made")
    actor0, c1, c2 = weights
    source = arrivals.StreamSource(tr, cl.E, derive_seed(ctx.seed, 1), B)
    source.reserve(cl.K * (stcfg.rounds + 4))
    ctx.mark("traffic drawn")
    tracer = None
    if ctx.trace:
        tracer = Tracer(TraceConfig(enabled=True))
        tracer.write = lambda: None            # spans stay in memory
    rec = Recorder(rollout_fn_for(ExecSpec(backend="fused")), ctx.trace)
    w_check = 1 + int(pick.integers(n))        # a measured window and
    rec.keep = {w_check, w_check + 1}          # the next, for the seam
    Runner = runner_type()
    first = int(chk["first_steps"])
    learner = Learner(SAC.update_step, range(first))
    held: Dict = {}

    def make_runner(ecfg_, policy, params, _source, key, scfg_, **_kw):
        r = held["runner"] = Runner(ecfg_, policy, params, source, key,
                                    scfg_, rollout_fn=rec, tracer=tracer)
        r.ptrs, r.stats, r.transitions = {}, {}, {}
        r.record = set(range(1, n + 1))
        return r

    def make_buffer(*args):
        held["buffer"] = ReplayBuffer(*args)
        return held["buffer"]

    def init_state(key, ecfg_, acfg_):
        return SAC.TrainState(
            actor=actor0, critic1=c1, critic2=c2, target1=c1, target2=c2,
            opt_actor=adam_init(actor0), opt_critic1=adam_init(c1),
            opt_critic2=adam_init(c2), step=jax.numpy.zeros((), "int32"))

    # compile the actor's collection now: round 1 is measured
    warm_collection(ecfg, B, rec.inner, acfg, actor0, tr, cl)
    ctx.mark("actor rollout warmed")

    per_round = -(-int(chk["updates"]) // n)
    ptr = {"prev": 0}
    rows_of: Dict[int, tuple] = {}
    counts = {"transitions": 0, "updates": 0, "rounds": 0,
              "stream_decisions": 0}
    times: Dict[str, float] = {}
    spans: List[Dict] = []

    def hook(r, flat):
        buf = held["buffer"]
        rows_of[r] = (ptr["prev"], buf.ptr)
        ptr["prev"] = buf.ptr
        if 1 <= r <= n:
            k = updates_for(scfg, len(flat[2]), buf.size, cap)
            got = list(pick.choice(k, min(per_round, k), replace=False)) \
                if k else []
            if r == 1 and k:
                got.append(0)
            learner.keep |= {learner.count + int(i) for i in got}

    def callback(r, row, ts):
        if r == 0:
            # the loop reads its cap afresh each round
            object.__setattr__(stcfg, "max_updates_per_round", cap)
            jax.block_until_ready(ts)
            if tracer is not None:
                tracer.events.clear()
            times["start"] = ctx.start_window()
            return
        if r <= n:
            counts["transitions"] += row["transitions"]
            counts["updates"] += row["updates"]
            counts["rounds"] += 1
            counts["stream_decisions"] += B * held["runner"].T
        if r == n:
            jax.block_until_ready(ts)
            times["elapsed"] = time.perf_counter() - times["start"]
            ctx.memory_peak()
            if tracer is not None:
                spans.extend(tracer.events)
            if ctx.trace:
                # the profiled round: its collection, push and its first
                # `profile_updates` updates, so the trace stays small
                object.__setattr__(stcfg, "max_updates_per_round",
                                   int(tn["profile_updates"]))
                ctx.profile_begin()
        elif r == n + 1:
            jax.block_until_ready(ts)
            ctx.profile_end()
            counts["profiled_updates"] = row["updates"]

    with standing_in(ST, StreamRunner=make_runner, ReplayBuffer=make_buffer), \
            standing_in(SAC, update_step=learner, init_train_state=init_state):
        res = ST.train_stream_sac(
            ecfg, acfg, scfg, stcfg, seed=derive_seed(ctx.seed, 3),
            exec_spec=ExecSpec(backend="fused"), callback=callback,
            transition_hook=hook)
    runner = held["runner"]
    counts.update(window_s=times["elapsed"], B=B, T=runner.T)
    print("window: " + ", ".join(f"{k} {v}" for k, v in counts.items()),
          file=sys.stderr)
    del res
    if w_check + 1 not in rec.log:      # the last window's seam: one more
        runner.run_window(collect=True)  # collection, after the window

    checks = check(ctx, REF, cl, learner, weights, runner, rec, source,
                   held["buffer"], rows_of, n)
    injected = sum(r["injected"] for r in runner.per_window[1:n + 1])
    dropped = sum(r["dropped"] for r in runner.per_window[1:n + 1])
    return Outcome(e2e={"train_transitions_per_s":
                        counts["transitions"] / times["elapsed"]},
                   attempted=injected, failed=dropped, checks=checks,
                   counts=counts, spans=spans)


def warm_collection(ecfg, B, rollout, acfg, actor, tr, cl) -> None:
    """One throwaway window of the actor's collection at the cell's shapes,
    on traffic of its own: it compiles the programs round 1 runs."""
    import jax
    from repro.actors.policies import actor_policy
    from repro.traffic.stream import StreamConfig, StreamRunner
    src = arrivals.StreamSource(tr, cl.E, 0, B)
    r = StreamRunner(ecfg, actor_policy(ecfg, acfg), actor, src,
                     jax.random.PRNGKey(0), StreamConfig(num_streams=B),
                     rollout_fn=rollout)
    jax.block_until_ready(r.run_window(collect=True).transitions)


# ------------------------------------------------------------------ check
def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return math.inf
    if not np.issubdtype(np.asarray(want).dtype, np.floating):
        return 0.0 if np.array_equal(got, want) else 1.0
    err = np.abs(got - want) / (1.0 + np.abs(want))
    return float(np.max(np.where(np.isfinite(err), err, np.inf),
                        initial=0.0))


def leaves(tree) -> List[np.ndarray]:
    import jax
    return [np.asarray(x, np.float64) for x in jax.tree_util.tree_leaves(tree)]


def norm_gap(got, want, keep=None) -> float:
    """Worst leaf: |‖program leaf‖ − ‖reference leaf‖| over the larger of
    that reference leaf's norm and the median leaf's."""
    g = [float(np.linalg.norm(x)) for x in leaves(got)]
    w = [float(np.linalg.norm(x)) for x in leaves(want)]
    if len(g) != len(w):
        return math.inf
    med = float(np.median(w))
    idx = range(len(w)) if keep is None else keep
    return max((abs(g[i] - w[i]) / max(w[i], med, 1e-30) for i in idx),
               default=0.0)


def check(ctx, REF, cl, learner, weights, runner, rec, source, buffer,
          rows_of, n) -> List[Check]:
    import jax
    tn, chk = ctx.traffic["train"], ctx.traffic["train"]["check"]
    S = reference({"reference": {"learner": tn["reference"]}}, "learner")
    lim = chk["limits"]
    platform = ctx.devs[0].platform
    hp = S.hyper(ctx.config, tn, platform)
    mode = ctx.control
    if mode == "half_batch":           # a fault, planted in the reference
        half = lambda b: {k: v[:len(v) // 2] for k, v in b.items()}  # noqa
        stand_in = lambda st, b, k: S.run(st, half(b), k, hp)  # noqa: E731
    elif mode:
        hpc = S.hyper(ctx.config, tn, platform, control=True)
        stand_in = lambda st, b, k: S.run(st, b, k, hpc)  # noqa: E731
    else:
        stand_in = None
    r = {"loss_rel_err": 0.0, "update_max_rel_err": 0.0}
    worst = "none"

    def losses(prog, ref):
        for k in ("critic_loss", "actor_loss"):
            r["loss_rel_err"] = max(r["loss_rel_err"],
                                    rel_err(prog[k], ref[k]))

    # -- the first updates: the reference follows them from the weights --
    first = int(chk["first_steps"])
    ref = S.initial_state(*weights)
    mine = ref
    ref_grads = None
    for i in range(first):
        if i not in learner.log:
            break
        _, batch, key, (ts_out, met) = learner.log[i]
        new, out = S.run(ref, batch, key, hp)
        if stand_in is None:
            prog = as_dict(ts_out)
            prog_loss = met
        else:
            prog, prog_loss = stand_in(mine, batch, key)
        losses(prog_loss, out)
        if i == 0:
            ref_grads = out["grads"]
            b1 = hp.b1
            got_grads = {k: jax.tree_util.tree_map(
                lambda m: np.asarray(m) / (1 - b1), prog["opt_" + k]["mu"])
                for k in NETS}
        ref, mine = new, prog
    steps_seen = min(first, len(learner.log))
    if ref_grads is not None:
        grad_gap = norm_gap(got_grads, ref_grads)
        g = [float(np.linalg.norm(x)) for x in leaves(ref_grads)]
        moved = [i for i, x in enumerate(g) if x >= 1e-3 * np.median(g)]
        d = lambda s: {k: jax.tree_util.tree_map(  # noqa: E731
            lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64),
            s[k], w) for k, w in zip(NETS, weights)}
        change_gap = norm_gap(d(mine), d(ref), moved)
    else:
        grad_gap = change_gap = math.inf

    # -- a sample of the window's updates, each from the program's state --
    sampled = sorted(i for i in learner.log if i >= first)
    for i in sampled:
        ts_in, batch, key, (ts_out, met) = learner.log[i]
        st = as_dict(ts_in)
        new, out = S.run(st, batch, key, hp)
        if stand_in is None:
            prog, prog_loss = as_dict(ts_out), met
        else:
            prog, prog_loss = stand_in(st, batch, key)
        losses(prog_loss, out)
        paths = jax.tree_util.tree_flatten_with_path(new)[0]
        for (path, _), got, want in zip(paths,
                                        leaves({k: prog[k] for k in new}),
                                        leaves(new)):
            e = rel_err(got, want)
            if e > r["update_max_rel_err"]:
                r["update_max_rel_err"] = e
                worst = f"update {i} {jax.tree_util.keystr(path)}"
    print(f"updates checked: first {steps_seen}, window {len(sampled)} "
          f"({sampled[:12]}); first-step gradient and change norms of "
          f"{len(leaves(weights))} leaves", file=sys.stderr)
    if sampled:
        print(f"update_max_rel_err worst at: {worst}", file=sys.stderr)

    # -- the replay buffer against the windows it was filled from ------
    mismatches = 0
    for w in range(1, n + 1):
        want = valid_rows(runner.transitions[w])
        lo, hi = rows_of[w]
        got = (buffer.obs[lo:hi], buffer.action[lo:hi], buffer.reward[lo:hi],
               buffer.next_obs[lo:hi], buffer.done[lo:hi])
        if hi - lo != len(want[2]):
            mismatches += abs(hi - lo - len(want[2]))
            continue
        mismatches += sum(int(np.sum(np.asarray(g) != np.asarray(x)))
                          for g, x in zip(got, want))

    # -- one measured window of collection, replayed ---------------------
    w = min(rec.log)
    view = CollectView(ctx, chk)
    coll = sim.check(view, REF, cl, rec, runner.stats, runner.ptrs,
                     source, rec.params[w], runner.T)

    checks = [Check("update_max_rel_err", r["update_max_rel_err"],
                    lim["update_max_rel_err"]),
              Check("loss_rel_err", r["loss_rel_err"], lim["loss_rel_err"]),
              Check("grad_norm_gap", grad_gap, lim["grad_norm_gap"]),
              Check("change_norm_gap", change_gap, lim["change_norm_gap"]),
              Check("updates_checked_short",
                    max(0, int(chk["updates"]) - len(sampled))
                    + (first - steps_seen), 0),
              Check("replay_mismatches", mismatches,
                    lim["replay_mismatches"])]
    return checks + coll


def valid_rows(tx):
    """A window's valid steps, stream by stream in step order, in the
    buffer's layout: obs, agent-space action, reward, next obs, done."""
    valid = np.asarray(tx.valid).reshape(-1).astype(bool)

    def flat(x):
        x = np.asarray(x)
        return x.reshape((-1,) + x.shape[2:])[valid]
    return (flat(tx.obs), flat(tx.extras["agent_action"]), flat(tx.reward),
            flat(tx.next_obs), flat(tx.done).astype(np.float32))


class CollectView:
    """What `sim.check` reads of a context, with the train cell's sample:
    one window, `collect_streams` streams of it, the EAT actor."""

    def __init__(self, ctx, chk: Dict):
        self.seed, self.devs = ctx.seed, ctx.devs
        self.control = bool(ctx.control) and ctx.control != "half_batch"
        self.traffic = dict(ctx.traffic, policy="eat")
        self.config = dict(ctx.config, check=dict(
            ctx.config["check"], streams=chk["collect_streams"],
            min_decisions=chk["min_decisions"]))
