"""Plain reference of the EAT scheduling semantics (arXiv:2507.10026 §IV-V).

Written from the paper and the configuration file alone, in numpy, one
stream at a time: the Eq.-6 observation, the event-driven gang-scheduling
step (reuse of a complete idle gang, fragmentation-aware fresh selection,
the Table-VI latency model, the Eq.-2/3 quality and penalty, the reward),
the attention encoder and T-step DDPM actor with its Gaussian head, the
quality-first greedy baseline, and the window seam (clock rebase, gang
relabel, leftover compaction). It imports nothing of the program.

Arithmetic. The environment runs in float32, as the configuration states;
the actor in float64 except for its matrix products, which take the
platform's default precision (`arith`). The control (`arith(...,
control=True)`) rounds every environment value and every actor activation
to bfloat16.

Random numbers. The actor's noise is drawn with JAX's public PRNG from the
keys the benchmark hands the program, split as the paper's Algorithm 1 and
the rollout protocol split them (`noise_for`).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict

import numpy as np

INF = np.float32(1e30)
F32 = np.float32


def to_bf16(x) -> np.ndarray:
    """Round float32 values to the nearest bfloat16 (ties to even), kept as
    float32."""
    x = np.asarray(x, np.float32)
    b = x.view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32)


@dataclass(frozen=True)
class Arith:
    env: str = "float32"          # float32 | bfloat16
    matmul: str = "exact"         # exact | one_pass (bfloat16 inputs)
    act: str = "float64"          # float64 | bfloat16: actor activations
    exact_dots: tuple = ()        # products a platform takes off the MXU

    def r(self, x):
        x = np.asarray(x, np.float32)
        return to_bf16(x) if self.env == "bfloat16" else x

    def a(self, x):
        if self.act == "bfloat16":
            return to_bf16(x).astype(np.float64)
        return np.asarray(x, np.float64)

    def dot(self, a, b, name: str = ""):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        if self.matmul == "exact" or name in self.exact_dots:
            return self.a(a @ b)
        return self.a(to_bf16(a).astype(np.float64)
                      @ to_bf16(b).astype(np.float64))


EXACT = Arith()


def arith(platform: str, control: bool = False, exact_dots=()) -> Arith:
    """The reference's arithmetic on `platform`: the environment in float32,
    the actor's matrix products as the platform's default precision takes
    them (on a TPU one bfloat16 pass: inputs rounded to bfloat16, products
    summed exactly; on a CPU exact), except the products named in
    `exact_dots`, which the TPU takes in float32 (a matrix-vector product is
    not sent to the MXU), and the rest of the actor in float64. The control
    steps down once: environment and actor activations in bfloat16."""
    mm = "one_pass" if platform == "tpu" else "exact"
    if control:
        return Arith(env="bfloat16", matmul=mm, act="bfloat16",
                     exact_dots=tuple(exact_dots))
    return Arith(matmul=mm, exact_dots=tuple(exact_dots))


@dataclass(frozen=True)
class Cluster:
    """The sizes of one configuration file's `cluster` group."""
    E: int
    K: int
    l: int
    s_min: int
    s_max: int
    time_limit: float
    max_steps: int
    alpha_q: float
    beta_t: float
    mu_t: float
    k_time: float
    lambda_q: float
    p_quality: float
    q_min: float
    time_scale: float
    q_max: float
    q_tau: float
    init_time: tuple          # seconds, for c = 1, 2, 4, 8
    step_time: tuple          # seconds per inference step, c = 1, 2, 4, 8
    max_carry: int

    @classmethod
    def from_config(cls, cfg: Dict) -> "Cluster":
        g = dict(cfg["cluster"])
        return cls(**{k: (tuple(v) if isinstance(v, list) else v)
                      for k, v in g.items() if k in cls.__dataclass_fields__})

    @property
    def A(self) -> int:
        return 2 + self.l


def _log2(c) -> int:
    return int(round(math.log2(max(int(c), 1))))


# ----------------------------------------------------------------- state
def reset_state(cl: Cluster) -> Dict[str, np.ndarray]:
    E, K = cl.E, cl.K
    return {"time": F32(0), "free": np.zeros(E, F32),
            "smodel": -np.ones(E, np.int32), "sgang": -np.ones(E, np.int32),
            "sgsize": np.zeros(E, np.int32),
            "status": np.zeros(K, np.int32), "start": np.zeros(K, F32),
            "finish": np.zeros(K, F32), "steps": np.zeros(K, np.int32),
            "quality": np.zeros(K, F32), "reload": np.zeros(K, np.int32),
            "taken": np.int32(0)}


def queue_view(cl: Cluster, trace, st):
    queued = (st["status"] == 0) & (trace["arr_time"] <= st["time"])
    prio = np.where(queued, trace["arr_time"], INF)
    idx = np.argsort(prio, kind="stable")[:cl.l].astype(np.int32)
    return idx, prio[idx] < INF, queued


def observe(cl: Cluster, ar: Arith, trace, st, view) -> np.ndarray:
    """Eq. 6: [available | wait], [remaining | c / 8], [model | 0]."""
    idx, valid, _ = view
    t = st["time"]
    inv = F32(1.0 / cl.time_scale)
    avail = (st["free"] <= t).astype(F32)
    remaining = ar.r(ar.r(np.maximum(ar.r(st["free"] - t), F32(0))) * inv)
    model = ar.r((st["smodel"].astype(F32) + F32(1)) * F32(1.0))
    wait = np.where(valid, ar.r(ar.r(t - trace["arr_time"][idx]) * inv), F32(0))
    c = np.where(valid, trace["c"][idx].astype(F32) / F32(8), F32(0))
    zeros = np.zeros(cl.l, F32)
    return np.stack([np.concatenate([avail, wait]),
                     np.concatenate([remaining, c]),
                     np.concatenate([model, zeros])]).astype(F32)


def _select(cl: Cluster, st, idle, m_k, c_k):
    """(selected servers, reuse): a complete idle gang of the same model and
    size is reused; otherwise the c_k idle servers that break the fewest
    intact gangs, lowest index first."""
    E = cl.E
    gang, size = st["sgang"], st["sgsize"]
    has = gang >= 0
    same = gang[:, None] == gang[None, :]
    ok = idle & has & (st["smodel"] == m_k) & (size == c_k)
    complete = ok & ((same & ok[None, :]).sum(axis=1) == c_k)
    if complete.any():
        g_star = gang[complete].min()
        return ok & (gang == g_star), True
    member = idle & has
    intact = member & ((same & member[None, :]).sum(axis=1) == size) & (size > 0)
    score = np.where(idle, intact.astype(F32) * (F32(100) + F32(10) * size)
                     + F32(0.001) * np.arange(E, dtype=F32), INF)
    rank = np.empty(E, np.int64)
    rank[np.argsort(score, kind="stable")] = np.arange(E)
    return idle & (rank < c_k), False


def decide(cl: Cluster, ar: Arith, trace, st, action, view):
    """One decision. Returns (state', reward, done, info)."""
    t = st["time"]
    st = dict(st)
    st["status"] = np.where((st["status"] == 1) & (st["finish"] <= t), 2,
                            st["status"]).astype(np.int32)
    idx, valid, queued = view
    a = np.asarray(action, F32)
    slot = int(np.argmax(np.where(valid, a[2:], -INF)))
    k = int(idx[slot])
    c_k, m_k = int(trace["c"][k]), int(trace["model"][k])
    idle = st["free"] <= t
    feasible = bool(a[0] <= F32(0.5)) and bool(valid[slot]) \
        and int(idle.sum()) >= c_k
    sel, reuse = _select(cl, st, idle, m_k, c_k)
    frac = ar.r(np.clip(a[1], F32(0), F32(1)) * F32(cl.s_max - cl.s_min))
    steps = int(np.round(ar.r(F32(cl.s_min) + frac)))
    t_exec = ar.r(ar.r(F32(cl.step_time[_log2(c_k)]) * F32(steps)) * F32(1))
    t_init = F32(0) if reuse else ar.r(F32(cl.init_time[_log2(c_k)]) * F32(1))
    finish = ar.r(ar.r(t + t_exec) + t_init)
    q = ar.r(ar.r(F32(cl.q_max) * ar.r(F32(1) - np.exp(
        ar.r(-F32(steps) * F32(1.0 / cl.q_tau)), dtype=F32)))
        + trace["noise"][k])
    pen = F32(cl.p_quality) if q < F32(cl.q_min) else F32(0)
    t_resp = ar.r(finish - trace["arr_time"][k])
    info = {"scheduled": feasible, "quality": q if feasible else F32(0),
            "task": k, "c": c_k, "steps": steps, "reuse": reuse}
    new = dict(st)
    if feasible:
        s = sel
        new["free"] = np.where(s, finish, st["free"]).astype(F32)
        new["smodel"] = np.where(s, m_k, st["smodel"]).astype(np.int32)
        new["sgang"] = np.where(s, k, st["sgang"]).astype(np.int32)
        new["sgsize"] = np.where(s, c_k, st["sgsize"]).astype(np.int32)
        for f, v in (("status", 1), ("start", t), ("finish", finish),
                     ("steps", steps), ("quality", q),
                     ("reload", 0 if reuse else 1)):
            new[f] = st[f].copy()
            new[f][k] = v
        still = queued & (np.arange(cl.K) != k)
        n_q = F32(max(int(still.sum()), 1))
        t_avg = ar.r(ar.r(np.sum(np.where(still, ar.r(t - trace["arr_time"]),
                                          F32(0)), dtype=F32)) / n_q)
        reward = ar.r(ar.r(ar.r(F32(cl.alpha_q) * q) - F32(cl.lambda_q) * pen)
                      + ar.r(F32(cl.k_time) / ar.r(
                          ar.r(ar.r(F32(cl.beta_t) * t_resp)
                               + ar.r(F32(cl.mu_t) * t_avg)) + F32(1e-3))))
        t_new = t
    else:
        reward = F32(0)
        arr = trace["arr_time"]
        nxt = min(np.min(np.where(arr > t, arr, INF)),
                  np.min(np.where(new["free"] > t, new["free"], INF)))
        t_new = nxt if nxt < INF else ar.r(t + F32(1))
    new["time"] = F32(t_new)
    new["taken"] = np.int32(st["taken"] + 1)
    resolved = (new["status"] == 2) | ((new["status"] == 1)
                                       & (new["finish"] <= new["time"]))
    done = bool(resolved.all()) or new["time"] >= F32(cl.time_limit) \
        or int(new["taken"]) >= cl.max_steps
    return new, F32(reward), done, info


# ------------------------------------------------------------------ actor
def _mish(x):
    return x * np.tanh(np.logaddexp(x, 0.0))


def _softmax(x):
    e = np.exp(x - x.max(axis=-1, keepdims=True))
    return e / e.sum(axis=-1, keepdims=True)


def vp_schedule(T: int, bmin: float, bmax: float):
    i = np.arange(1, T + 1, dtype=np.float64)
    betas = 1.0 - np.exp(-bmin / T - 0.5 * (bmax - bmin) * (2 * i - 1) / T ** 2)
    alphas = 1.0 - betas
    return betas, alphas, np.cumprod(alphas)


def actor_action(actor: Dict, params: Dict, ar: Arith, obs, noise) -> np.ndarray:
    """Env-space action of the EAT actor for one observation.

    Attention encoder (Eq. 9: columns of the state matrix are tokens),
    T-step DDPM reverse chain (Eqs. 10-12) over a Mish MLP with a
    sinusoidal timestep embedding, tanh bound, then the Gaussian head
    (Eq. 13): a = clip(x0 + exp(clip(x0 W + b)) eps, -1, 1), mapped to
    [0, 1]."""
    T = int(actor["T"])
    enc, den, head = params["enc"], params["denoiser"], params["sigma_head"]
    x = np.asarray(obs, np.float64).T
    q, k, v = (ar.dot(x, enc[n], "qkv") for n in ("wq", "wk", "wv"))
    att = ar.a(_softmax(ar.dot(q, k.T, "qk") / math.sqrt(q.shape[-1])))
    f_s = ar.dot(ar.dot(att, v, "av"), enc["wo"], "wo")
    betas, alphas, abars = vp_schedule(T, actor["beta_min"], actor["beta_max"])
    half = int(actor["t_dim"]) // 2
    freqs = np.exp(-math.log(1000.0) * np.arange(half) / half)
    xt = noise["x_T"].astype(np.float64)
    for step in range(T):
        i = T - 1 - step
        ang = (i + 1) * freqs
        h = np.concatenate([xt, np.sin(ang), np.cos(ang), f_s])
        layers = den["layers"]
        for j, lay in enumerate(layers):
            h = ar.a(ar.dot(h, lay["w"], "denoiser") + lay["b"])
            h = ar.a(_mish(h) if j < len(layers) - 1 else np.tanh(h))
        abar_prev = abars[i - 1] if i > 0 else 1.0
        mean = (xt - betas[i] / math.sqrt(1.0 - abars[i]) * h) / math.sqrt(alphas[i])
        var = betas[i] * (1.0 - abar_prev) / (1.0 - abars[i])
        nz = noise["chain"][step].astype(np.float64) if i > 0 else 0.0
        xt = ar.a(mean + math.sqrt(max(var, 1e-12)) * nz)
    x0 = ar.a(np.tanh(xt))
    log_sigma = np.clip(ar.dot(x0, head["w"], "head") + head["b"],
                        actor["log_sigma_min"], actor["log_sigma_max"])
    a = np.clip(ar.a(x0 + ar.a(np.exp(log_sigma)) * noise["eps"]), -1.0, 1.0)
    return (a + 1.0) * 0.5


def greedy_candidates(cl: Cluster, grid: int = 9) -> np.ndarray:
    """No-op, then every (visible slot, step fraction on a 9-point grid)."""
    out = [np.full(cl.A, F32(0.9))]
    for slot in range(cl.l):
        for s in np.linspace(0.0, 1.0, grid, dtype=F32):
            a = np.zeros(cl.A, F32)
            a[1], a[2 + slot] = s, F32(1)
            out.append(a)
    return np.stack(out)


def greedy_scores(cl: Cluster, ar: Arith, trace, st, cands) -> np.ndarray:
    """Quality first (alpha_q q - lambda_q I, when the task is scheduled),
    the step's reward breaking ties: 1e3 * quality term + reward."""
    view = queue_view(cl, trace, st)
    idx, valid, _ = view
    n_idle = int((st["free"] <= st["time"]).sum())
    out = np.zeros(len(cands), F32)
    for j, a in enumerate(cands):
        slot = int(np.argmax(np.where(valid, a[2:], -INF)))
        if a[0] > F32(0.5) or not valid[slot] \
                or int(trace["c"][idx[slot]]) > n_idle:
            continue                 # nothing scheduled: no quality, no reward
        _, r, _, info = decide(cl, ar, trace, st, a, view)
        q = info["quality"]
        pen = F32(cl.p_quality) if q < F32(cl.q_min) else F32(0)
        qual = ar.r(ar.r(F32(cl.alpha_q) * q) - ar.r(F32(cl.lambda_q) * pen)
                    + F32(1e-6)) if info["scheduled"] else F32(0)
        out[j] = ar.r(ar.r(F32(1e3) * qual) + r)
    return out


# ------------------------------------------------------------------- seam
def seam(cl: Cluster, trace, st):
    """The window seam of one stream: (stats, carried state, leftovers).
    Leftovers are the unscheduled tasks, oldest first, clocks rebased."""
    te = st["time"]
    sched = st["status"] >= 1
    resp = np.where(sched, st["finish"] - trace["arr_time"], F32(0))
    stats = {"n_sched": int(sched.sum()), "n_done": int((st["status"] == 2).sum()),
             "n_reload": int(np.where(sched, st["reload"], 0).sum()),
             "sum_resp": float(resp.astype(np.float64).sum())}
    gang = st["sgang"]
    has = gang >= 0
    same = (gang[:, None] == gang[None, :]) & has[None, :]
    leader = np.where(same, np.arange(cl.E)[None, :], cl.E).min(axis=1)
    carry = reset_state(cl)
    carry.update(free=np.maximum(st["free"] - te, F32(0)).astype(F32),
                 smodel=st["smodel"].copy(),
                 sgang=np.where(has, cl.K + leader, -1).astype(np.int32),
                 sgsize=st["sgsize"].copy())
    left = st["status"] == 0
    order = np.argsort(np.where(left, trace["arr_time"], INF), kind="stable")
    order = order[:int(left.sum())]
    lo = {c: trace[c][order] for c in ("arr_time", "c", "model", "noise")}
    lo["arr_time"] = (lo["arr_time"] - te).astype(F32)
    return stats, carry, lo


# ----------------------------------------------------------------- replay
@dataclass
class Readings:
    """The numbers the check compares, each the worst over what it saw."""
    actor_max_abs_diff: float = 0.0
    actor_abs_diff_sum: float = 0.0
    actor_values: int = 0
    greedy_score_gap: float = 0.0
    env_max_rel_err: float = 0.0
    int_mismatches: int = 0
    decisions: int = 0
    worst: str = ""

    def rel(self, got, want, what: str = "") -> None:
        got = np.asarray(got, np.float64)
        want = np.asarray(want, np.float64)
        if got.shape != want.shape:
            self.int_mismatches += 1
            return
        if got.size:
            err = np.abs(got - want) / (1.0 + np.abs(want))
            err = np.where(np.isfinite(err), err, np.inf)
            if float(err.max()) > self.env_max_rel_err:
                self.env_max_rel_err, self.worst = float(err.max()), what

    def same(self, got, want) -> None:
        got, want = np.asarray(got), np.asarray(want)
        if got.shape != want.shape:
            self.int_mismatches += 1
        else:
            self.int_mismatches += int(np.sum(got != want))


INT_FIELDS = ("smodel", "sgang", "sgsize", "status", "steps", "reload", "taken")
FLOAT_FIELDS = ("time", "free", "start", "finish", "quality")


def compare_state(rd: Readings, got: Dict, want: Dict) -> None:
    for f in INT_FIELDS:
        rd.same(got[f], want[f])
    for f in FLOAT_FIELDS:
        rd.rel(got[f], want[f], f)


def check_action(rd: Readings, cl: Cluster, ar: Arith, policy: str, trace,
                 st, obs, a_prog, cands=None, actor=None, params=None,
                 noise=None) -> None:
    """The action the policy owes the observation (eat: the reference actor
    on the same noise) or the state (greedy: no candidate scores better)."""
    if policy == "greedy":
        s = greedy_scores(cl, ar, trace, st, np.concatenate(
            [cands, a_prog[None]]))
        rd.greedy_score_gap = max(rd.greedy_score_gap,
                                  float(s[:-1].max() - s[-1]))
    else:
        a_ref = actor_action(actor, params, ar, obs, noise)
        d = np.abs(a_prog.astype(np.float64) - a_ref)
        rd.actor_max_abs_diff = max(rd.actor_max_abs_diff, float(d.max()))
        rd.actor_abs_diff_sum += float(d.sum())
        rd.actor_values += d.size


def replay(cl: Cluster, ar: Arith, policy: str, trace, carry, out,
           rd: Readings, actor=None, params=None, noise=None) -> Dict:
    """Check one stream's window against the reference, decision by
    decision. `out` holds what the program produced for the stream: per
    step `obs`, `action`, `reward`, `done`, `valid` and the final `state`.
    The reference state follows the program's own actions, so one
    disagreement does not derail the rest of the window; at every step it
    checks the program's observation and reward, recomputes the action the
    policy owes that observation (eat) or state (greedy), and at the end the
    state. Returns the reference's final state."""
    st = {k: (np.array(v) if np.ndim(v) else v) for k, v in carry.items()}
    cands = greedy_candidates(cl) if policy == "greedy" else None
    done = False
    for t in range(len(out["valid"])):
        rd.same(bool(out["valid"][t]), not done)
        if done:
            break
        view = queue_view(cl, trace, st)
        rd.rel(out["obs"][t], observe(cl, EXACT, trace, st, view), "obs")
        a_prog = np.asarray(out["action"][t], F32)
        check_action(rd, cl, ar, policy, trace, st, out["obs"][t], a_prog,
                     cands, actor, params,
                     None if noise is None
                     else {k: v[t] for k, v in noise.items()})
        st, r, d, _ = decide(cl, EXACT, trace, st, a_prog, view)
        rd.rel(out["reward"][t], r, "reward")
        rd.same(bool(out["done"][t]), d)
        rd.decisions += 1
        done = d
    compare_state(rd, out["state"], st)
    return st


def replay_decisions(cl: Cluster, ar: Arith, policy: str, trace, carry,
                     seen, final, T: int, rd: Readings, actor=None,
                     params=None, noise=None):
    """Check one window of a backend that decides one task at a time (the
    serving seam) from what its policy saw: `seen` holds, per decision, the
    `state` and `obs` handed to the policy and the `action` it returned;
    `final` is the state the window ended in. The reference state follows
    the program's actions; at every decision it checks the state and the
    observation, and the action the policy owes them. The window has to
    stop at the first decision that ends the episode, or after T. Returns
    (the reference's final state, its placements in order: dicts of task,
    c, steps and reuse)."""
    st = {k: (np.array(v) if np.ndim(v) else v) for k, v in carry.items()}
    cands = greedy_candidates(cl) if policy == "greedy" else None
    placed = []
    done = False
    for t in range(len(seen["action"])):
        if done:                    # a decision past the episode's end
            rd.int_mismatches += 1
            break
        compare_state(rd, seen["state"][t], st)
        view = queue_view(cl, trace, st)
        rd.rel(seen["obs"][t], observe(cl, EXACT, trace, st, view), "obs")
        a_prog = np.asarray(seen["action"][t], F32)
        check_action(rd, cl, ar, policy, trace, st, seen["obs"][t], a_prog,
                     cands, actor, params,
                     None if noise is None
                     else {k: v[t] for k, v in noise.items()})
        st, _, done, info = decide(cl, EXACT, trace, st, a_prog, view)
        if info["scheduled"]:
            placed.append({k: info[k] for k in ("task", "c", "steps",
                                                 "reuse")})
        rd.decisions += 1
    if not done and len(seen["action"]) < T:     # stopped early
        rd.int_mismatches += 1
    compare_state(rd, final, st)
    return st, placed


def run_free(cl: Cluster, ar: Arith, policy: str, trace, carry, T: int,
             actor=None, params=None, noise=None) -> Dict:
    """The reference in the program's place: one stream's window run freely
    at arithmetic `ar`, in the layout `replay` checks. With the control's
    arithmetic this is the control run."""
    st = {k: (np.array(v) if np.ndim(v) else v) for k, v in carry.items()}
    cands = greedy_candidates(cl) if policy == "greedy" else None
    rec = {"obs": [], "action": [], "reward": [], "done": [], "valid": []}
    done = False
    for t in range(T):
        view = queue_view(cl, trace, st)
        obs = observe(cl, ar, trace, st, view)
        if policy == "greedy":
            a = cands[int(np.argmax(greedy_scores(cl, ar, trace, st, cands)))]
        else:
            a = actor_action(actor, params, ar, obs,
                             {k: v[t] for k, v in noise.items()})
        a = np.asarray(a, F32)
        if done:                 # the frozen tail: the state stays put
            r, d = F32(0), True
        else:
            st, r, d, _ = decide(cl, ar, trace, st, a, view)
        for key, v in (("obs", obs), ("action", a), ("reward", r),
                       ("done", d), ("valid", not done)):
            rec[key].append(v)
        done = done or d
    out = {k: np.asarray(v) for k, v in rec.items()}
    out["state"] = st
    return out


def noise_for(keys, T_window: int, T_chain: int, A: int) -> Dict[str, np.ndarray]:
    """The actor's noise for a window of S streams, from their (S, 2) window
    keys: per decision `k, k_act = split(k)`; then `kd, ks = split(k_act)`,
    `kx, kn = split(kd)`; x_T ~ N(0, I) from kx, the chain's T noises from
    kn, the Gaussian head's eps from ks. Arrays are (S, T_window, ...)."""
    import jax

    def step(k, _):
        k, k_act = jax.random.split(k)
        kd, ks = jax.random.split(k_act)
        kx, kn = jax.random.split(kd)
        return k, (jax.random.normal(kx, (A,)),
                   jax.random.normal(kn, (T_chain, A)),
                   jax.random.normal(ks, (A,)))

    def one(key):
        return jax.lax.scan(step, key, None, length=T_window)[1]

    x_T, chain, eps = jax.jit(jax.vmap(one))(keys)
    return {"x_T": np.asarray(x_T), "chain": np.asarray(chain),
            "eps": np.asarray(eps)}
