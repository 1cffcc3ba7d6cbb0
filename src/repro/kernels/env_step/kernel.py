"""Pallas-fused environment decision step over a batch axis of envs.

One kernel launch advances a block of B parallel envs by one scheduling
decision: lazy retirement, visible-queue slot pick, reuse detection,
fragmentation-aware server selection, masked server/task state update,
reward terms, next-event time advance, and the *next* visible-queue top-k +
Eq.-6 observation — everything the per-decision hot path of ``env.step``
used to spend dozens of small XLA ops on.

Kernel-friendly restructurings (shared with ``ref.env_step_ref``, which is
the bitwise oracle):

* no `lax.top_k` / `argsort`: the queue top-k and the idle-server ranking
  are counting/rank passes (sum of pairwise strict comparisons), which the
  VPU handles as plain vectorized compares + reductions;
* no scatters/gathers: task updates are one-hot `where` masks, per-task
  attribute reads are one-hot masked reductions (exact — a single non-zero
  term per reduction);
* per-env scalars travel as (B, 1) lanes so every ref is at least 2-D
  (TPU-friendly); boolean masks cross the kernel boundary as int32.

The batch axis is tiled across the grid; E/K/queue-window dims stay whole.
``interpret=True`` is the CPU fallback used by the parity tests (the CPU
fast path in ``ops.env_step_fused`` is the vmapped jnp reference).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import env as EV
from repro.core import quality as Q

_I32 = jnp.int32
_F32 = jnp.float32


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(_I32, shape, axis)


def _env_step_kernel(cfg: EV.EnvConfig, faults: bool, *refs):
    (time_ref, free_ref, smodel_ref, sgang_ref, sgsize_ref,
     tstatus_ref, tstart_ref, tfinish_ref, tsteps_ref,
     tqual_ref, treload_ref, staken_ref,
     arr_ref, c_ref, model_ref, noise_ref,
     stepb_ref, initb_ref, scalem_ref,
     action_ref, qidx_ref, qvalid_ref, qqueued_ref) = refs[:23]
    n_in = 23
    if faults:                      # four extra fault-schedule inputs
        fds_ref, fde_ref, fslow_ref, fcold_ref = refs[23:27]
        n_in = 27
    (o_time, o_free, o_smodel, o_sgang, o_sgsize,
     o_tstatus, o_tstart, o_tfinish, o_tsteps,
     o_tqual, o_treload, o_staken,
     o_qidx, o_qvalid, o_qqueued, o_obs, o_reward, o_done) = refs[n_in:]
    E, K, l = cfg.num_servers, cfg.max_tasks, cfg.queue_window
    t = time_ref[...]                       # (bb, 1)
    free = free_ref[...]                    # (bb, E)
    smodel = smodel_ref[...]
    sgang = sgang_ref[...]
    sgsize = sgsize_ref[...]
    tstatus = tstatus_ref[...]              # (bb, K)
    tstart = tstart_ref[...]
    tfinish = tfinish_ref[...]
    tsteps = tsteps_ref[...]
    tqual = tqual_ref[...]
    treload = treload_ref[...]
    staken = staken_ref[...]                # (bb, 1)
    arr = arr_ref[...]                      # (bb, K)
    c = c_ref[...]
    model = model_ref[...]
    noise = noise_ref[...]
    step_base = stepb_ref[...]
    init_base = initb_ref[...]
    scale = scalem_ref[...]
    action = action_ref[...]                # (bb, 2 + l)
    qidx = qidx_ref[...]                    # (bb, l) i32
    qvalid = qvalid_ref[...] != 0           # (bb, l) bool
    queued = qqueued_ref[...] != 0          # (bb, K) bool

    bb = t.shape[0]
    iota_l = _iota((bb, l), 1)
    iota_K = _iota((bb, K), 1)

    # lazily retire finished tasks
    finished = (tstatus == 1) & (tfinish <= t)
    status = jnp.where(finished, 2, tstatus)

    if faults:
        # same fault semantics (and expressions) as env.decision_step /
        # ref.env_step_ref: down mask + cold-restart cache wipe
        ds = fds_ref[...]               # (bb, E, F)
        de = fde_ref[...]               # (bb, E, F)
        fslow = fslow_ref[...]          # (bb, E)
        fcold = fcold_ref[...]          # (bb, 1)
        t3 = t[:, :, None]              # (bb, 1, 1)
        down = jnp.any((ds <= t3) & (t3 < de), axis=2)            # (bb, E)
        wipe = jnp.any(ds <= t3, axis=2) & (fcold > 0)
        smodel = jnp.where(wipe, -1, smodel)
        sgang = jnp.where(wipe, -1, sgang)
        sgsize = jnp.where(wipe, 0, sgsize)

    # visible-queue slot pick (first-match argmax over preference scores)
    scores = jnp.where(qvalid, action[:, 2:], -1e30)
    smax = jnp.max(scores, axis=1, keepdims=True)
    slot = jnp.min(jnp.where(scores == smax, iota_l, l), axis=1, keepdims=True)
    at_slot = iota_l == slot
    k = jnp.sum(jnp.where(at_slot, qidx, 0), axis=1, keepdims=True)
    k_valid = jnp.sum(jnp.where(at_slot, qvalid.astype(_I32), 0),
                      axis=1, keepdims=True) > 0

    hotk = iota_K == k                                        # (bb, K)

    def pick(a, zero):
        return jnp.sum(jnp.where(hotk, a, zero), axis=1, keepdims=True)

    want_exec = action[:, 0:1] <= 0.5
    c_k = pick(c, 0)
    m_k = pick(model, 0)
    scale_k = pick(scale, 0.0)
    idle = free <= t
    if faults:                          # a down server cannot join a gang
        idle = idle & ~down
    n_idle = jnp.sum(idle.astype(_I32), axis=1, keepdims=True)
    feasible = want_exec & k_valid & (n_idle >= c_k)

    # --- server selection: reuse detection + counting-rank fresh pick -----
    has_gang = sgang >= 0
    same = sgang[:, :, None] == sgang[:, None, :]             # (bb, E, E)
    ok = idle & has_gang & (smodel == m_k) & (sgsize == c_k)
    counts = jnp.sum((same & ok[:, None, :]).astype(_I32), axis=2)
    complete = ok & (counts == c_k)
    reuse = jnp.any(complete, axis=1, keepdims=True)
    g_star = jnp.min(jnp.where(complete, sgang, 2 ** 30),
                     axis=1, keepdims=True)
    reuse_sel = ok & (sgang == g_star)

    member_ok = idle & has_gang
    counts_all = jnp.sum((same & member_ok[:, None, :]).astype(_I32), axis=2)
    intact = member_ok & (counts_all == sgsize) & (sgsize > 0)
    score = jnp.where(idle,
                      intact.astype(_F32) * (100.0 + 10.0 * sgsize)
                      + 0.001 * _iota((bb, E), 1),
                      1e30)
    rank = jnp.sum((score[:, None, :] < score[:, :, None]).astype(_I32),
                   axis=2)
    fresh_sel = idle & (rank < c_k)
    # a select between two bool vectors lowers to an i8->i1 truncation that
    # Mosaic rejects; the and/or form is the same mask
    sel = (reuse & reuse_sel) | (~reuse & fresh_sel)

    # --- timing / quality of the candidate decision -----------------------
    # env._pin blocks FMA contraction of product-then-add chains: the
    # kernel is code-generated in its own context where LLVM may fuse
    # mul+add (1 ulp off the jnp reference); an optimization_barrier alone
    # does not survive the fused loop body, the value-preserving min does.
    _pin = EV._pin
    steps = jnp.round(cfg.s_min + _pin(jnp.clip(action[:, 1:2], 0.0, 1.0)
                      * (cfg.s_max - cfg.s_min))).astype(_I32)
    steps_f = steps.astype(_F32)
    t_exec = _pin(pick(step_base, 0.0) * steps_f * scale_k)
    if faults:                          # gang speed = slowest member's speed
        slow_k = jnp.max(jnp.where(sel, fslow, 1.0), axis=1, keepdims=True)
        t_exec = _pin(t_exec * slow_k)
    t_init = _pin(jnp.where(reuse, 0.0, pick(init_base, 0.0) * scale_k))
    finish = t + t_exec + t_init
    q_k = Q.quality_of(steps, pick(noise, 0.0))
    pen = Q.quality_penalty(q_k, cfg.q_min, cfg.p_quality)
    t_resp = finish - pick(arr, 0.0)

    if faults:
        # in-flight failure: a selected server crashes before the gang
        # finishes (status 3, servers freed at the crash, no reward)
        # (the gang mask is applied after the F-reduction: Mosaic cannot
        # broadcast a bool vector to rank 3, and min is exact either way)
        fin3 = finish[:, :, None]       # (bb, 1, 1)
        crash_e = jnp.min(jnp.where((ds > t3) & (ds < fin3), ds, 1e30),
                          axis=2)                                 # (bb, E)
        crash_t = jnp.min(jnp.where(sel, crash_e, 1e30), axis=1,
                          keepdims=True)
        will_fail = crash_t < 1e30
        sched_status = jnp.where(will_fail, 3, 1)
        rec_finish = jnp.where(will_fail, crash_t, finish)
    else:
        sched_status, rec_finish = 1, finish

    # --- apply schedule (masked) ------------------------------------------
    f = feasible
    sel_f = sel & f
    new_free = jnp.where(sel_f, rec_finish, free)
    new_model = jnp.where(sel_f, m_k, smodel)
    new_gang = jnp.where(sel_f, k, sgang)
    new_gsize = jnp.where(sel_f, c_k, sgsize)

    hit = hotk & f
    status2 = jnp.where(hit, sched_status, status)
    start2 = jnp.where(hit, t, tstart)
    tfin2 = jnp.where(hit, rec_finish, tfinish)
    tsteps2 = jnp.where(hit, steps, tsteps)
    tq2 = jnp.where(hit, q_k, tqual)
    trl2 = jnp.where(hit, jnp.where(reuse, 0, 1).astype(_I32), treload)

    # reward (only on successful schedule)
    still_queued = queued & (iota_K != k)
    n_q = jnp.maximum(jnp.sum(still_queued.astype(_F32), axis=1,
                              keepdims=True), 1.0)
    t_avg = jnp.sum(jnp.where(still_queued, t - arr, 0.0), axis=1,
                    keepdims=True) / n_q
    r = _pin(cfg.alpha_q * q_k) - _pin(cfg.lambda_q * pen) \
        + cfg.k_time / (_pin(cfg.beta_t * t_resp) + _pin(cfg.mu_t * t_avg)
                        + 1e-3)
    reward = jnp.where(f, r, 0.0)
    if faults:                          # a gang that will crash earns nothing
        reward = jnp.where(will_fail, 0.0, reward)

    # --- advance time on no-op --------------------------------------------
    next_arrival = jnp.min(jnp.where(arr > t, arr, 1e30), axis=1,
                           keepdims=True)
    next_completion = jnp.min(jnp.where(new_free > t, new_free, 1e30),
                              axis=1, keepdims=True)
    next_event = jnp.minimum(next_arrival, next_completion)
    if faults:                          # recoveries are events too
        next_recovery = jnp.min(
            jnp.min(jnp.where((ds <= t3) & (de > t3), de, 1e30), axis=2),
            axis=1, keepdims=True)
        next_event = jnp.minimum(next_event, next_recovery)
    t_new = jnp.where(f, t, jnp.where(next_event < 1e30, next_event, t + 1.0))

    staken2 = staken + 1
    resolved = (status2 == 2) | ((status2 == 1) & (tfin2 <= t_new))
    if faults:                          # failed tasks resolve (host retries)
        resolved = resolved | (status2 == 3)
    all_done = jnp.all(resolved, axis=1, keepdims=True)
    done = all_done | (t_new >= cfg.time_limit) | (staken2 >= cfg.max_steps)

    # --- next visible queue: counting-rank top-k --------------------------
    queued2 = (status2 == 0) & (arr <= t_new)
    prio = jnp.where(queued2, arr, 1e30)
    earlier = (prio[:, None, :] < prio[:, :, None]) \
        | ((prio[:, None, :] == prio[:, :, None])
           & (iota_K[:, None, :] < iota_K[:, :, None]))
    rank_q = jnp.sum(earlier.astype(_I32), axis=2)            # (bb, K)
    slot_hit = rank_q[:, None, :] == iota_l[:, :, None]       # (bb, l, K)
    idx2 = jnp.sum(jnp.where(slot_hit, iota_K[:, None, :], 0), axis=2)
    valid2 = iota_l < jnp.sum(queued2.astype(_I32), axis=1, keepdims=True)

    # --- Eq.-6 observation of the new state -------------------------------
    up = new_free <= t_new
    if faults:                          # obs mirrors core.obs: down servers
        t_new3 = t_new[:, :, None]      # are unavailable to the policy too
        up = up & ~jnp.any((ds <= t_new3) & (t_new3 < de), axis=2)
    avail = up.astype(_F32)
    inv_ts = 1.0 / cfg.time_scale
    inv_nm = 1.0 / max(cfg.num_models, 1)
    remaining = jnp.maximum(new_free - t_new, 0.0) * inv_ts
    modelrow = (new_model.astype(_F32) + 1.0) * inv_nm
    arr_v = jnp.sum(jnp.where(slot_hit, arr[:, None, :], 0.0), axis=2)
    c_v = jnp.sum(jnp.where(slot_hit, c[:, None, :], 0), axis=2)
    wait = jnp.where(valid2, (t_new - arr_v) * inv_ts, 0.0)
    crow = jnp.where(valid2, c_v.astype(_F32) / 8.0, 0.0)
    if cfg.num_models > 1:
        m_v = jnp.sum(jnp.where(slot_hit, model[:, None, :], 0), axis=2)
        mrow = jnp.where(valid2, (m_v.astype(_F32) + 1.0) * inv_nm, 0.0)
    else:
        mrow = jnp.zeros_like(crow)
    obs = jnp.stack([jnp.concatenate([avail, wait], axis=1),
                     jnp.concatenate([remaining, crow], axis=1),
                     jnp.concatenate([modelrow, mrow], axis=1)], axis=1)

    o_time[...] = t_new
    o_free[...] = new_free
    o_smodel[...] = new_model
    o_sgang[...] = new_gang
    o_sgsize[...] = new_gsize
    o_tstatus[...] = status2
    o_tstart[...] = start2
    o_tfinish[...] = tfin2
    o_tsteps[...] = tsteps2
    o_tqual[...] = tq2
    o_treload[...] = trl2
    o_staken[...] = staken2
    o_qidx[...] = idx2
    o_qvalid[...] = valid2.astype(_I32)
    o_qqueued[...] = queued2.astype(_I32)
    o_obs[...] = obs
    o_reward[...] = reward
    o_done[...] = done.astype(_I32)


@functools.partial(jax.jit, static_argnames=("cfg", "block_b", "interpret"))
def env_step_pallas(cfg: EV.EnvConfig, time, free, smodel, sgang, sgsize,
                    tstatus, tstart, tfinish, tsteps, tqual, treload, staken,
                    arr, c, model, noise, step_base, init_base, scale,
                    action, qidx, qvalid, qqueued, *,
                    fds=None, fde=None, fslow=None, fcold=None,
                    block_b: int = 32, interpret: bool = True):
    """Raw batched kernel entry: (B, ...) arrays in, tuple of 18 arrays out.

    Per-env scalars are (B, 1); boolean masks are int32 0/1 on both sides.
    The optional fault-schedule quartet (`fds`/`fde` (B, E, F) down
    intervals, `fslow` (B, E) straggler multipliers, `fcold` (B, 1)
    cold-restart flag — see `repro.faults.schedule`) switches the kernel
    into fault mode; leaving them None traces the exact fault-free program.
    Use ``ops.env_step_fused`` for the EnvState/QueueView-level wrapper.

    `block_b` rows go to one grid step. Every (bb, E|K) block pads its
    lane axis to 128 and the pairwise ranks build (bb, E, E) and
    (bb, K, K) intermediates, so on a TPU a block of 128 rows with the
    fault columns already overruns scoped VMEM; 32 leaves room for
    E = K = 32.
    """
    faults = fds is not None
    B = time.shape[0]
    E, K, l = cfg.num_servers, cfg.max_tasks, cfg.queue_window
    A = cfg.action_dim
    bb = min(block_b, B)
    pad = (-B) % bb
    ins = [time, free, smodel, sgang, sgsize, tstatus, tstart, tfinish,
           tsteps, tqual, treload, staken, arr, c, model, noise,
           step_base, init_base, scale, action, qidx, qvalid, qqueued]
    if faults:
        F = fds.shape[2]
        ins += [fds, fde, fslow, fcold]
    if pad:
        ins = [jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) for x in ins]
    nb = (B + pad) // bb

    def spec(*dims):
        return pl.BlockSpec((bb,) + dims, lambda i: (i,) + (0,) * len(dims))

    in_specs = [spec(1), spec(E), spec(E), spec(E), spec(E),        # server
                spec(K), spec(K), spec(K), spec(K), spec(K), spec(K),
                spec(1),                                            # staken
                spec(K), spec(K), spec(K), spec(K), spec(K), spec(K),
                spec(K),                                            # statics
                spec(A), spec(l), spec(l), spec(K)]                 # act + q
    if faults:
        in_specs += [spec(E, F), spec(E, F), spec(E), spec(1)]      # faults
    out_specs = [spec(1), spec(E), spec(E), spec(E), spec(E),
                 spec(K), spec(K), spec(K), spec(K), spec(K), spec(K),
                 spec(1),
                 spec(l), spec(l), spec(K), spec(3, E + l), spec(1), spec(1)]

    def shp(dtype, *dims):
        return jax.ShapeDtypeStruct((B + pad,) + dims, dtype)

    out_shape = [shp(_F32, 1), shp(_F32, E), shp(_I32, E), shp(_I32, E),
                 shp(_I32, E),
                 shp(_I32, K), shp(_F32, K), shp(_F32, K), shp(_I32, K),
                 shp(_F32, K), shp(_I32, K),
                 shp(_I32, 1),
                 shp(_I32, l), shp(_I32, l), shp(_I32, K),
                 shp(_F32, 3, E + l), shp(_F32, 1), shp(_I32, 1)]

    outs = pl.pallas_call(
        functools.partial(_env_step_kernel, cfg, faults),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="env_step_pallas",
    )(*ins)
    if pad:
        outs = [o[:B] for o in outs]
    return tuple(outs)
