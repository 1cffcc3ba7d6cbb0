"""Plain reference of one SAC update of the EAT learner (arXiv:2507.10026
Algorithm 2, Eqs. 13-22, Table VIII).

Written from the paper and the configuration file alone, in `jax.numpy`,
with gradients by `jax.grad` of a plain forward. It imports nothing of the
program. One update, on a batch of transitions (obs, agent-space action in
[-1, 1], reward, next obs, done):

* the target (Eq. 20): y = r + gamma (1 - done) min(Q1', Q2')(s', a'), a'
  the actor's sample at s', no gradient through y;
* each critic's loss, the mean squared gap to y, and its Adam step;
* the actor's loss (Eqs. 15-16), -mean(min(Q1, Q2)(s, a) + alpha H), a
  the actor's sample at s with the updated critics, H the Gaussian head's
  entropy 0.5 sum(log(2 pi e) + 2 log sigma), and its Adam step;
* Adam with weight decay added to the step: m, v moments with bias
  correction, the step -lr (m^ / (sqrt(v^) + eps) + wd p);
* the soft target update (Eq. 22): t <- (1 - tau) t + tau c, c the updated
  critic.

The actor is the one `reference/scheduler.py` `actor_action` computes for
one observation (attention encoder, T-step DDPM chain over a Mish MLP,
tanh bound, Gaussian head), batched. The critic is a Mish MLP over the
flattened observation and the action.

Arithmetic. Every value is float32. A matrix product takes the platform's
default precision as the configuration states it: on a TPU one bfloat16
pass, inputs rounded to bfloat16 and products summed in float32, forward
and backward alike, except the products named in `exact_dots`; elsewhere
float32 (`highest`). The control (`Hyper.dtype == "bfloat16"`) computes
every value, weight, moment and product in bfloat16.

Random numbers. The update's key is split as the paper's Algorithm 1 and
the update protocol split it (`sample`, `update`), with JAX's public PRNG.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Hyper:
    gamma: float
    tau: float
    actor_lr: float
    critic_lr: float
    weight_decay: float
    alpha: float                 # entropy weight
    T: int                       # diffusion steps
    beta_min: float
    beta_max: float
    t_dim: int
    log_sigma_min: float
    log_sigma_max: float
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    one_pass: bool = False       # products in one bfloat16 pass
    exact_dots: Tuple[str, ...] = ()
    dtype: str = "float32"       # float32 | bfloat16 (the control)


def hyper(cfg: Dict, train: Dict, platform: str,
          control: bool = False) -> Hyper:
    """The update's constants from the configuration's `actor` group and
    the traffic file's `train` group, and the arithmetic on `platform`."""
    a, s = cfg["actor"], train["sac"]
    return Hyper(gamma=s["gamma"], tau=s["tau"], actor_lr=s["actor_lr"],
                 critic_lr=s["critic_lr"], weight_decay=s["weight_decay"],
                 alpha=s["entropy_alpha"], T=int(a["T"]),
                 beta_min=a["beta_min"], beta_max=a["beta_max"],
                 t_dim=int(a["t_dim"]), log_sigma_min=a["log_sigma_min"],
                 log_sigma_max=a["log_sigma_max"],
                 one_pass=platform == "tpu",
                 exact_dots=tuple(a.get("exact_dots", ())),
                 dtype="bfloat16" if control else "float32")


# ------------------------------------------------------------- products
def _round(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _exact(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


@jax.custom_vjp
def _one_pass(a, b):
    return _exact(_round(a), _round(b))


def _one_pass_fwd(a, b):
    return _one_pass(a, b), (a, b)


def _one_pass_bwd(res, g):
    a, b = res
    _, vjp = jax.vjp(_exact, _round(a), _round(b))
    return vjp(_round(g))


_one_pass.defvjp(_one_pass_fwd, _one_pass_bwd)


def mm(a, b, name: str, hp: Hyper):
    if hp.dtype == "bfloat16":
        return jnp.matmul(a, b)
    if hp.one_pass and name not in hp.exact_dots:
        return _one_pass(a, b)
    return _exact(a, b)


# ------------------------------------------------------------- networks
def mish(x):
    return x * jnp.tanh(jnp.logaddexp(x, 0.0))


def softmax(x):
    e = jnp.exp(x - jax.lax.stop_gradient(x.max(axis=-1, keepdims=True)))
    return e / e.sum(axis=-1, keepdims=True)


def encode(enc: Dict, obs, hp: Hyper):
    """Eq. 9: the columns of each (3, E + l) observation are tokens."""
    x = jnp.swapaxes(obs, -1, -2)
    q, k, v = (mm(x, enc[n], "qkv", hp) for n in ("wq", "wk", "wv"))
    att = softmax(mm(q, jnp.swapaxes(k, -1, -2), "qk", hp)
                  / math.sqrt(q.shape[-1]))
    return mm(mm(att, v, "av", hp), enc["wo"], "wo", hp)


def schedule(hp: Hyper):
    i = np.arange(1, hp.T + 1, dtype=np.float64)
    betas = 1.0 - np.exp(-hp.beta_min / hp.T - 0.5 * (hp.beta_max
                                                      - hp.beta_min)
                         * (2 * i - 1) / hp.T ** 2)
    alphas = 1.0 - betas
    return betas, alphas, np.cumprod(alphas)


def sample(actor: Dict, obs, key, hp: Hyper):
    """(action in [-1, 1], entropy) of the actor at each observation, Eq.
    13. Keys: `kd, ks = split(key)`, `kx, kn = split(kd)`; x_T from kx, the
    chain's T noises from kn, the head's eps from ks."""
    dt = jnp.dtype(hp.dtype)
    kd, ks = jax.random.split(key)
    kx, kn = jax.random.split(kd)
    n = obs.shape[0]
    A = actor["sigma_head"]["b"].shape[-1]
    f_s = encode(actor["enc"], obs, hp)
    x = jax.random.normal(kx, (n, A)).astype(dt)
    noises = jax.random.normal(kn, (hp.T, n, A)).astype(dt)
    betas, alphas, abars = schedule(hp)
    half = hp.t_dim // 2
    freqs = np.exp(-math.log(1000.0) * np.arange(half) / half)
    layers = actor["denoiser"]["layers"]
    for step in range(hp.T):
        i = hp.T - 1 - step
        ang = (i + 1) * freqs
        temb = jnp.broadcast_to(jnp.asarray(np.concatenate(
            [np.sin(ang), np.cos(ang)]), dt), (n, 2 * half))
        h = jnp.concatenate([x, temb, f_s], axis=-1)
        for j, lay in enumerate(layers):
            h = mm(h, lay["w"], "denoiser", hp) + lay["b"]
            h = mish(h) if j < len(layers) - 1 else jnp.tanh(h)
        abar_prev = abars[i - 1] if i > 0 else 1.0
        mean = (x - (betas[i] / math.sqrt(1.0 - abars[i])) * h) \
            / math.sqrt(alphas[i])
        var = betas[i] * (1.0 - abar_prev) / (1.0 - abars[i])
        x = mean + math.sqrt(max(var, 1e-12)) * noises[step] if i > 0 \
            else mean
    x0 = jnp.tanh(x)
    head = actor["sigma_head"]
    log_sigma = jnp.clip(mm(x0, head["w"], "head", hp) + head["b"],
                         hp.log_sigma_min, hp.log_sigma_max)
    eps = jax.random.normal(ks, x0.shape).astype(dt)
    a = jnp.clip(x0 + jnp.exp(log_sigma) * eps, -1.0, 1.0)
    ent = 0.5 * jnp.sum(math.log(2 * math.pi * math.e) + 2 * log_sigma,
                        axis=-1)
    return a, ent


def critic(p: Dict, obs, action, hp: Hyper):
    x = jnp.concatenate([obs.reshape(obs.shape[0], -1), action], axis=-1)
    layers = p["layers"]
    for j, lay in enumerate(layers):
        x = mm(x, lay["w"], "critic", hp) + lay["b"]
        if j < len(layers) - 1:
            x = mish(x)
    return x[:, 0]


# ------------------------------------------------------------ optimizer
def adam(grads, opt: Dict, params, lr: float, hp: Hyper):
    """(new params, new moments) of one Adam step with weight decay."""
    step = opt["step"] + 1
    t = step.astype(jnp.float32)
    bc1 = 1.0 - jnp.power(jnp.float32(hp.b1), t)
    bc2 = 1.0 - jnp.power(jnp.float32(hp.b2), t)
    tm = jax.tree_util.tree_map
    mu = tm(lambda m, g: hp.b1 * m + (1 - hp.b1) * g, opt["mu"], grads)
    nu = tm(lambda v, g: hp.b2 * v + (1 - hp.b2) * g * g, opt["nu"], grads)
    new = tm(lambda p, m, v: p - lr * ((m / bc1.astype(m.dtype))
                                       / (jnp.sqrt(v / bc2.astype(v.dtype))
                                          + hp.eps)
                                       + hp.weight_decay * p),
             params, mu, nu)
    return new, {"step": step, "mu": mu, "nu": nu}


# --------------------------------------------------------------- update
def update(state: Dict, batch: Dict, key, hp: Hyper) -> Tuple[Dict, Dict]:
    """(state after one update, its losses). `state` holds `actor`,
    `critic1`, `critic2`, `target1`, `target2`, `opt_actor`, `opt_critic1`,
    `opt_critic2` (each `step`, `mu`, `nu`) and `step`. Keys: `k_next,
    k_actor, _ = split(key, 3)`."""
    dt = jnp.dtype(hp.dtype)
    cast = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, t)
    st, b = cast(state), cast(batch)
    obs, act, rew = b["obs"], b["action"], b["reward"]
    nobs, done = b["next_obs"], b["done"]
    k_next, k_actor, _ = jax.random.split(key, 3)

    a_next, _ = sample(st["actor"], nobs, k_next, hp)
    y = rew + hp.gamma * (1.0 - done) * jnp.minimum(
        critic(st["target1"], nobs, a_next, hp),
        critic(st["target2"], nobs, a_next, hp))
    y = jax.lax.stop_gradient(y)

    def critic_loss(p):
        return jnp.mean(jnp.square(y - critic(p, obs, act, hp)))

    l1, g1 = jax.value_and_grad(critic_loss)(st["critic1"])
    l2, g2 = jax.value_and_grad(critic_loss)(st["critic2"])
    c1, o1 = adam(g1, st["opt_critic1"], st["critic1"], hp.critic_lr, hp)
    c2, o2 = adam(g2, st["opt_critic2"], st["critic2"], hp.critic_lr, hp)

    def actor_loss(p):
        a, ent = sample(p, obs, k_actor, hp)
        q = jnp.minimum(critic(c1, obs, a, hp), critic(c2, obs, a, hp))
        return -jnp.mean(q + hp.alpha * ent)

    la, ga = jax.value_and_grad(actor_loss)(st["actor"])
    actor, oa = adam(ga, st["opt_actor"], st["actor"], hp.actor_lr, hp)
    soft = lambda t, c: jax.tree_util.tree_map(  # noqa: E731
        lambda x, y: (1 - hp.tau) * x + hp.tau * y, t, c)
    new = {"actor": actor, "critic1": c1, "critic2": c2,
           "target1": soft(st["target1"], c1),
           "target2": soft(st["target2"], c2),
           "opt_actor": oa, "opt_critic1": o1, "opt_critic2": o2,
           "step": st["step"] + 1}
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: x.astype(jnp.float32)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, t)
    losses = {"critic_loss": 0.5 * (l1 + l2), "actor_loss": la}
    grads = {"actor": ga, "critic1": g1, "critic2": g2}
    return f32(new), f32(dict(losses, grads=grads))


@functools.lru_cache(maxsize=None)
def jitted(hp: Hyper):
    """The update, compiled once per arithmetic. One update at the check's
    batch holds a few MB on the device, so it runs whole."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(functools.partial(update, hp=hp))


def run(state: Dict, batch: Dict, key, hp: Hyper):
    with jax.default_matmul_precision("highest"):
        return jitted(hp)(state, batch, key)


def zero_opt(params) -> Dict:
    z = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, jnp.float32),
                               params)
    return {"step": jnp.zeros((), jnp.int32), "mu": z,
            "nu": jax.tree_util.tree_map(jnp.copy, z)}


def initial_state(actor: Dict, critic1: Dict, critic2: Dict) -> Dict:
    """The state before the first update: the given weights, targets equal
    to the critics, zero moments."""
    return {"actor": actor, "critic1": critic1, "critic2": critic2,
            "target1": critic1, "target2": critic2,
            "opt_actor": zero_opt(actor), "opt_critic1": zero_opt(critic1),
            "opt_critic2": zero_opt(critic2),
            "step": jnp.zeros((), jnp.int32)}
