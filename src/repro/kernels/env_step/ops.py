"""Public wrapper: fused batched env decision step over (B,) parallel envs.

``env_step_fused`` advances every env in a batch by one scheduling decision
in a single fused op and returns the next queue view + observation along
with the state, so a rollout costs one queue pass per decision. Two
interchangeable implementations (bitwise-identical outputs):

* ``impl="ref"``   — the vmapped pure-jnp reference (`ref.env_step_ref`).
  This is the CPU fast path: XLA compiles the whole decision into one
  fused loop nest, with no `top_k`/`argsort`/scatter ops.
* ``impl="pallas"`` — the Pallas kernel (`kernel.env_step_pallas`), one
  kernel launch per decision across the batch. On CPU it runs with
  ``interpret=True`` (parity testing); on GPU/TPU it compiles.

``impl="auto"`` picks "pallas" on gpu/tpu backends and "ref" elsewhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import env as EV
from repro.kernels.env_step.kernel import env_step_pallas
from repro.kernels.env_step.ref import env_step_ref


def resolve_impl(impl: str = "auto") -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() in ("gpu", "tpu") else "ref"
    if impl not in ("ref", "pallas"):
        raise ValueError(f"impl must be auto|ref|pallas, got {impl!r}")
    return impl


def env_step_fused(ecfg: EV.EnvConfig, statics, state: EV.EnvState,
                   action, queue: EV.QueueView, *, impl: str = "auto",
                   block_b: int = 32, interpret=None):
    """One fused decision for B envs.

    All of `statics` (per-task constants from ``env.decision_statics``),
    `state`, `action` (B, A) and `queue` carry a leading (B,) batch axis.
    Returns (state', queue', obs', reward (B,), done (B,)) — bitwise equal
    to vmapping the legacy ``env.step`` and re-observing, minus the
    redundant second top-k.
    """
    impl = resolve_impl(impl)
    if impl == "ref":
        return jax.vmap(
            lambda st, a, qv, sx: env_step_ref(ecfg, sx, st, a, qv)
        )(state, action, queue, statics)

    if interpret is None:
        interpret = jax.default_backend() not in ("gpu", "tpu")
    as_i32 = lambda b: b.astype(jnp.int32)
    fault_kw = {}
    if EV.has_faults(statics):      # fault schedules ride as extra inputs
        fault_kw = dict(fds=statics["f_down_start"],
                        fde=statics["f_down_end"],
                        fslow=statics["f_slow"], fcold=statics["f_cold"])
    outs = env_step_pallas(
        ecfg,
        state.time[:, None], state.server_free_at, state.server_model,
        state.server_gang, state.server_gang_size,
        state.task_status, state.task_start, state.task_finish,
        state.task_steps, state.task_quality, state.task_reload,
        state.steps_taken[:, None],
        statics["arr_time"], statics["c"], statics["model"],
        statics["noise"], statics["step_base"], statics["init_base"],
        statics["scale"],
        action, queue.idx, as_i32(queue.valid), as_i32(queue.queued),
        **fault_kw, block_b=block_b, interpret=bool(interpret))
    (time, free, smodel, sgang, sgsize, tstatus, tstart, tfinish, tsteps,
     tqual, treload, staken, qidx, qvalid, qqueued, obs, reward, done) = outs
    new_state = EV.EnvState(
        time=time[:, 0], server_free_at=free, server_model=smodel,
        server_gang=sgang, server_gang_size=sgsize,
        task_status=tstatus, task_start=tstart, task_finish=tfinish,
        task_steps=tsteps, task_quality=tqual, task_reload=treload,
        steps_taken=staken[:, 0])
    new_queue = EV.QueueView(idx=qidx, valid=qvalid != 0,
                             queued=qqueued != 0)
    return new_state, new_queue, obs, reward[:, 0], done[:, 0] != 0
