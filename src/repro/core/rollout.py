"""Device-resident batched rollout engine (fully jitted, vmapped episodes).

The env (`env.py`) is fixed-shape and jittable; this module exploits that to
run B episodes at once: `lax.scan` over decision steps inside, `vmap` over a
batch axis of (trace, PRNG key) pairs outside, one XLA program total. Every
consumer that previously stepped the env from a host Python loop (baseline
evaluation, SAC experience collection, PPO trajectory collection, scenario
sweeps) sits on top of `batch_rollout`.

Policy protocol
---------------
    policy(params, key, trace, state, obs) -> (env_action in [0,1]^A, extras)

`params` is an arbitrary pytree threaded through jit (NOT baked into the
compiled program — actor weights can change between calls without
recompiling); `extras` is a (possibly empty) dict of per-step auxiliary
outputs (e.g. raw agent-space actions, log-probs, values) that comes back
stacked in `Transitions.extras`. The policy callable itself is a static jit
argument: build it once (the factories here cache on `EnvConfig`) and reuse
it, or every call recompiles.

Parity with the host loop: the scan splits the carried key exactly like the
host-side evaluators (`key, k_act = split(key)` per decision step) and
freezes the state once `done`, so a batched episode reproduces the host-loop
episode bit-for-bit on the same (trace, policy, key).

Fused engine (`fused=True`, the default): instead of vmapping per-episode
scans, one `lax.scan` over decision steps advances all B envs per step
through the fused decision op (`kernels/env_step`): a single Pallas kernel
launch per decision on gpu/tpu, the op-minimized jnp reference on CPU.
Bitwise-identical to the unfused path — same key splits, same freeze
semantics, same float expressions — just one queue top-k per decision and
no `argsort`/scatter ops in the hot loop.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import env as EV
from repro.kernels.env_step import ops as EK

Policy = Callable[..., Any]


class Transitions(NamedTuple):
    """Stacked per-step records; leading axes (T,) or (B, T) when batched."""
    obs: jnp.ndarray        # (..., 3, E+l) observation before the action
    action: jnp.ndarray     # (..., A) env-space action in [0, 1]
    reward: jnp.ndarray     # (...,) f32, 0 after episode end
    next_obs: jnp.ndarray   # (..., 3, E+l)
    done: jnp.ndarray       # (...,) f32 done flag after this step
    valid: jnp.ndarray      # (...,) bool, step executed before episode end
    extras: Dict[str, jnp.ndarray]


class RolloutResult(NamedTuple):
    metrics: Dict[str, jnp.ndarray]   # episode_metrics + return + length
    final_state: EV.EnvState
    transitions: Optional[Transitions]


# ----------------------------------------------------------------------
def rollout_episode(ecfg: EV.EnvConfig, trace: Dict, policy: Policy, params,
                    key, *, num_steps: Optional[int] = None,
                    collect: bool = False,
                    init_state: Optional[EV.EnvState] = None) -> RolloutResult:
    """One episode as a lax.scan (traceable; jit/vmap at the call site).

    `init_state` lets a caller resume from carried environment state (the
    streaming engine threads server loads / clock between task windows);
    None means a fresh `EV.reset`, which reproduces the episodic behaviour.
    """
    T = int(num_steps) if num_steps else ecfg.max_steps
    state0 = EV.reset(ecfg) if init_state is None else init_state
    q0, obs0 = EV.reset_view(ecfg, trace, state0)

    def body(carry, _):
        state, q, obs, k, done, total, length = carry
        k, k_act = jax.random.split(k)
        action, extras = policy(params, k_act, trace, state, obs)
        # queue threading: the step consumes this decision's queue view and
        # hands back the next one, so one decision = one top-k (the legacy
        # step + observe pair did two)
        nstate, nq, nobs, r, d, _ = EV.step_with_queue(
            ecfg, trace, state, q, action)
        # freeze the episode once done so trailing scan steps are no-ops
        nstate = jax.tree_util.tree_map(
            lambda n, o: jnp.where(done, o, n), nstate, state)
        nq = jax.tree_util.tree_map(
            lambda n, o: jnp.where(done, o, n), nq, q)
        nobs = jnp.where(done, obs, nobs)
        r = jnp.where(done, 0.0, r)
        valid = ~done
        out = (Transitions(obs=obs, action=action, reward=r, next_obs=nobs,
                           done=d.astype(jnp.float32), valid=valid,
                           extras=extras)
               if collect else None)
        carry = (nstate, nq, nobs, k, done | d, total + r,
                 length + valid.astype(jnp.int32))
        return carry, out

    carry0 = (state0, q0, obs0, key, jnp.zeros((), bool),
              jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
    (state, _, _, _, _, total, length), traj = jax.lax.scan(
        body, carry0, None, length=T)
    metrics = dict(EV.episode_metrics(ecfg, trace, state))
    metrics["episode_return"] = total
    metrics["episode_len"] = length
    return RolloutResult(metrics=metrics, final_state=state,
                         transitions=traj if collect else None)


def _bcast(flag, like):
    """Broadcast a (B,) flag against a (B, ...) leaf."""
    return flag.reshape(flag.shape + (1,) * (like.ndim - flag.ndim))


def _batch_reset(ecfg: EV.EnvConfig, B: int) -> EV.EnvState:
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (B,) + x.shape), EV.reset(ecfg))


def _batch_rollout_fused(ecfg: EV.EnvConfig, traces: Dict, policy: Policy,
                         params, keys, *, num_steps, collect, init_state,
                         impl) -> RolloutResult:
    """Scan over decision steps; each step advances all B envs through one
    fused decision op (`kernels.env_step.ops.env_step_fused`). Bitwise-equal
    to `vmap(rollout_episode)` — the per-env op sequence is identical.
    The fused op also freezes the state and queue of an env whose episode
    is over."""
    T = int(num_steps) if num_steps else ecfg.max_steps
    B = keys.shape[0]
    state0 = _batch_reset(ecfg, B) if init_state is None else init_state
    statics = jax.vmap(lambda tr: EV.decision_statics(ecfg, tr))(traces)
    q0, obs0 = jax.vmap(
        lambda tr, st: EV.reset_view(ecfg, tr, st))(traces, state0)
    # the batch-axis policy view comes from the shared actor layer — one
    # cached vmap per (ecfg, policy) instead of a fresh closure per trace
    from repro.actors.program import actor_program
    vpolicy = actor_program(ecfg, policy).vmapped

    def body(carry, _):
        state, q, obs, ks, done, total, length = carry
        splits = jax.vmap(jax.random.split)(ks)          # (B, 2, 2)
        ks_next, k_act = splits[:, 0], splits[:, 1]
        action, extras = vpolicy(params, k_act, traces, state, obs)
        with jax.named_scope("env_step"):
            nstate, nq, nobs, r, d = EK.env_step_fused(
                ecfg, statics, state, action, q, frozen=done, impl=impl)
        nobs = jnp.where(_bcast(done, nobs), obs, nobs)
        r = jnp.where(done, 0.0, r)
        valid = ~done
        out = (Transitions(obs=obs, action=action, reward=r, next_obs=nobs,
                           done=d.astype(jnp.float32), valid=valid,
                           extras=extras)
               if collect else None)
        carry = (nstate, nq, nobs, ks_next, done | d, total + r,
                 length + valid.astype(jnp.int32))
        return carry, out

    carry0 = (state0, q0, obs0, keys, jnp.zeros((B,), bool),
              jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.int32))
    (state, _, _, _, _, total, length), traj = jax.lax.scan(
        body, carry0, None, length=T)
    metrics = dict(jax.vmap(
        lambda tr, st: EV.episode_metrics(ecfg, tr, st))(traces, state))
    metrics["episode_return"] = total
    metrics["episode_len"] = length
    if collect:   # scan stacks (T, B, ...) -> match the unfused (B, T, ...)
        traj = jax.tree_util.tree_map(lambda x: jnp.swapaxes(x, 0, 1), traj)
    return RolloutResult(metrics=metrics, final_state=state,
                         transitions=traj if collect else None)


@functools.partial(jax.jit,
                   static_argnames=("ecfg", "policy", "num_steps", "collect",
                                    "fused", "fused_impl"))
def batch_rollout(ecfg: EV.EnvConfig, traces: Dict, policy: Policy, params,
                  keys, *, num_steps: Optional[int] = None,
                  collect: bool = False,
                  init_state: Optional[EV.EnvState] = None,
                  fused: bool = True,
                  fused_impl: str = "auto") -> RolloutResult:
    """B episodes in one jitted program.

    `traces`: trace dict with a leading (B,) batch axis (see
    `workload.make_trace_batch` / `workload.stack_traces`); `keys`: (B, 2)
    PRNG keys. `params` is broadcast (shared policy weights). `init_state`,
    when given, is an `EnvState` whose leaves carry the same (B, ...) batch
    axis — each episode resumes from its own carried state. Returns a
    `RolloutResult` whose leaves all carry the (B, ...) batch axis.

    `fused=True` (default) advances all B envs per decision through the
    fused env-step op — one Pallas kernel launch per decision on gpu/tpu
    (`fused_impl="auto"`), the fused jnp reference on CPU. `fused=False` is
    the legacy vmap-of-scans engine on the compositional `env.step` path.
    Both produce bitwise-identical results on the same inputs.
    """
    if fused:
        return _batch_rollout_fused(ecfg, traces, policy, params, keys,
                                    num_steps=num_steps, collect=collect,
                                    init_state=init_state, impl=fused_impl)
    if init_state is None:
        def one(trace, key):
            return rollout_episode(ecfg, trace, policy, params, key,
                                   num_steps=num_steps, collect=collect)
        return jax.vmap(one)(traces, keys)

    def one_from(trace, key, st0):
        return rollout_episode(ecfg, trace, policy, params, key,
                               num_steps=num_steps, collect=collect,
                               init_state=st0)
    return jax.vmap(one_from)(traces, keys, init_state)


# ----------------------------------------------------------------------
# cached policy factories (the callable must stay identical across calls —
# it is a static jit argument of batch_rollout)
@functools.lru_cache(maxsize=None)
def uniform_policy(ecfg: EV.EnvConfig) -> Policy:
    """Random baseline: uniform env-space action (paper §VI.A.3 Random)."""
    def policy(params, key, trace, state, obs):
        return jax.random.uniform(key, (ecfg.action_dim,)), {}
    return policy


@functools.lru_cache(maxsize=None)
def greedy_policy(ecfg: EV.EnvConfig) -> Policy:
    """Greedy baseline: immediate quality-first candidate search."""
    from repro.core import baselines as BL
    def policy(params, key, trace, state, obs):
        return BL.greedy_act(ecfg, trace, state), {}
    return policy


@functools.lru_cache(maxsize=None)
def sequence_policy(ecfg: EV.EnvConfig) -> Policy:
    """Replay a precomputed action sequence (`params["seq"]`, (T, A) in
    env space) by decision index: step i plays seq[i] (clamped at the end).
    This is how the offline meta-heuristic schedules (genetic/harmony,
    which optimise a fixed sequence with no run-time feedback) run through
    the batched/streaming engines under the common policy protocol."""
    def policy(params, key, trace, state, obs):
        seq = params["seq"]
        idx = jnp.minimum(state.steps_taken, seq.shape[0] - 1)
        return seq[idx], {}
    return policy


@functools.lru_cache(maxsize=None)
def fifo_policy(ecfg: EV.EnvConfig, steps_frac: float = 0.5) -> Policy:
    """FIFO baseline: always try to schedule the earliest-arrived visible
    task (queue slot 0 — the visible queue is sorted by arrival) at a fixed
    inference-step fraction. When the head-of-line gang does not fit the
    idle servers, the env no-ops and time advances to the next event, so
    FIFO exhibits classic head-of-line blocking under bursts."""
    a = jnp.zeros((ecfg.action_dim,), jnp.float32)
    a = a.at[1].set(steps_frac).at[2].set(1.0)   # a_c=0 (execute), slot 0
    def policy(params, key, trace, state, obs):
        return a, {}
    return policy
