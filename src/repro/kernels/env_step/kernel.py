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
  are counting/rank passes (sum of pairwise strict comparisons);
* no scatters/gathers: task updates are one-hot `where` masks, per-task
  attribute reads are one-hot masked reductions (exact — a single non-zero
  term per reduction);
* boolean masks cross the kernel boundary as int32.

Layout: inside the kernel the envs (streams) sit on the 128-wide lane
axis. Every value is (X, B) — per-env scalars (1, B), servers (E, B), tasks
(K, B), the queue window (l, B), the action (A, B), the fault columns
(E, F, B) — so the per-env arithmetic is full-width elementwise VPU work
whatever E, K and l are. The pairwise passes, conceptually (K, K, B),
(E, E, B) and (l, K, B), accumulate over their leading axis one (X, B) slab
at a time (plain adds and compares); the first-match and one-hot picks
reduce over the sublane axis of one (K|l|E, B) array.

At the boundary the env side keeps the layout the rest of the program
holds it in: the state, the queue view and the per-task constants come in
batch-first, (B, X), and the kernel transposes them on the way in and the
next state and queue view on the way out, so no caller moves them — not a
rollout's carry, nor a policy that reads the state or the window's trace
(the constants are mostly trace columns; a lane-major copy of them would
lead XLA to lay the trace out batch-minor for every reader). The action
comes in lane-major, (A, B), and the observation leaves lane-major,
(3, E+l, B): the actor's side of a decision is lane-major too, XLA lays
the action and the observation out with the batch minor, and the actor's
batched passes run lane-dense. Fault columns come in lane-major. A
`frozen` flag per env (an episode already over) keeps that env's state and
queue view as they came in; the next state and queue view overwrite the
ones read.

The grid tiles the lane axis in blocks of `lane_block` envs, derived from
B and the shapes. ``interpret=True`` is the CPU fallback used by the parity
tests (the CPU fast path in ``ops.env_step_fused`` is the vmapped jnp
reference).
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

# Scoped VMEM one grid step may fill with its blocks (double-buffered in
# and out) and temporaries: half of the v5e's 16 MiB default scoped limit,
# which leaves the compiler its own scratch.
VMEM_BUDGET = 8 * 2 ** 20


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(_I32, shape, axis)


def _lead_sum(n, term):
    """sum_j term(j) for j < n, one (X, B) slab at a time: the leading-axis
    reduction of a pairwise (n, X, B) pass without building it."""
    acc = term(0)
    for j in range(1, n):
        acc = acc + term(j)
    return acc


def _env_step_kernel(cfg: EV.EnvConfig, faults: bool, *refs):
    (time_ref, free_ref, smodel_ref, sgang_ref, sgsize_ref,
     tstatus_ref, tstart_ref, tfinish_ref, tsteps_ref,
     tqual_ref, treload_ref, staken_ref,
     arr_ref, c_ref, model_ref, noise_ref,
     stepb_ref, initb_ref, scalem_ref,
     action_ref, qidx_ref, qvalid_ref, qqueued_ref, frozen_ref) = refs[:24]
    n_in = 24
    if faults:                      # four extra fault-schedule inputs
        fds_ref, fde_ref, fslow_ref, fcold_ref = refs[24:28]
        n_in = 28
    (o_time, o_free, o_smodel, o_sgang, o_sgsize,
     o_tstatus, o_tstart, o_tfinish, o_tsteps,
     o_tqual, o_treload, o_staken,
     o_qidx, o_qvalid, o_qqueued, o_obs, o_reward, o_done) = refs[n_in:]
    E, K, l = cfg.num_servers, cfg.max_tasks, cfg.queue_window
    lanes = lambda r: r[...].T              # noqa: E731 (bl, X) -> (X, bl)
    t = time_ref[...]                       # (1, bl)
    free = lanes(free_ref)                  # (E, bl)
    smodel_in = smodel = lanes(smodel_ref)
    sgang_in = sgang = lanes(sgang_ref)
    sgsize_in = sgsize = lanes(sgsize_ref)
    tstatus = lanes(tstatus_ref)            # (K, bl)
    tstart = lanes(tstart_ref)
    tfinish = lanes(tfinish_ref)
    tsteps = lanes(tsteps_ref)
    tqual = lanes(tqual_ref)
    treload = lanes(treload_ref)
    staken = staken_ref[...]                # (1, bl)
    arr = lanes(arr_ref)                    # (K, bl)
    c = lanes(c_ref)
    model = lanes(model_ref)
    noise = lanes(noise_ref)
    step_base = lanes(stepb_ref)
    init_base = lanes(initb_ref)
    scale = lanes(scalem_ref)
    a_exec = action_ref[0:1, :]             # (1, bl)
    a_steps = action_ref[1:2, :]
    prefs = action_ref[2:, :]               # (l, bl)
    qidx = lanes(qidx_ref)                  # (l, bl) i32
    qvalid_i = lanes(qvalid_ref)            # (l, bl) i32 0/1
    qqueued_i = lanes(qqueued_ref)          # (K, bl) i32 0/1
    qvalid = qvalid_i != 0
    queued = qqueued_i != 0
    frozen = frozen_ref[...] != 0           # (1, bl) bool

    bl = t.shape[1]
    iota_l = _iota((l, bl), 0)
    iota_K = _iota((K, bl), 0)

    # lazily retire finished tasks
    finished = (tstatus == 1) & (tfinish <= t)
    status = jnp.where(finished, 2, tstatus)

    if faults:
        # same fault semantics (and expressions) as env.decision_step /
        # ref.env_step_ref: down mask + cold-restart cache wipe
        ds = fds_ref[...]               # (E, F, bl)
        de = fde_ref[...]               # (E, F, bl)
        fslow = fslow_ref[...]          # (E, bl)
        fcold = fcold_ref[...]          # (1, bl)
        t3 = t[None]                    # (1, 1, bl)
        down = jnp.any((ds <= t3) & (t3 < de), axis=1)            # (E, bl)
        wipe = jnp.any(ds <= t3, axis=1) & (fcold > 0)
        smodel = jnp.where(wipe, -1, smodel)
        sgang = jnp.where(wipe, -1, sgang)
        sgsize = jnp.where(wipe, 0, sgsize)

    # visible-queue slot pick (first-match argmax over preference scores)
    scores = jnp.where(qvalid, prefs, -1e30)
    smax = jnp.max(scores, axis=0, keepdims=True)
    slot = jnp.min(jnp.where(scores == smax, iota_l, l), axis=0, keepdims=True)
    at_slot = iota_l == slot
    k = jnp.sum(jnp.where(at_slot, qidx, 0), axis=0, keepdims=True)
    k_valid = jnp.sum(jnp.where(at_slot, qvalid.astype(_I32), 0),
                      axis=0, keepdims=True) > 0

    hotk = iota_K == k                                        # (K, bl)

    def pick(a, zero):
        return jnp.sum(jnp.where(hotk, a, zero), axis=0, keepdims=True)

    want_exec = a_exec <= 0.5
    c_k = pick(c, 0)
    m_k = pick(model, 0)
    scale_k = pick(scale, 0.0)
    idle = free <= t
    if faults:                          # a down server cannot join a gang
        idle = idle & ~down
    n_idle = jnp.sum(idle.astype(_I32), axis=0, keepdims=True)
    feasible = want_exec & k_valid & (n_idle >= c_k)

    # --- server selection: reuse detection + counting-rank fresh pick -----
    # pairwise (E, E) passes over servers j, accumulated over j
    has_gang = sgang >= 0
    ok = idle & has_gang & (smodel == m_k) & (sgsize == c_k)
    counts = _lead_sum(E, lambda j: (
        (sgang[j:j + 1] == sgang) & ok[j:j + 1]).astype(_I32))
    complete = ok & (counts == c_k)
    reuse = jnp.any(complete, axis=0, keepdims=True)
    g_star = jnp.min(jnp.where(complete, sgang, 2 ** 30),
                     axis=0, keepdims=True)
    reuse_sel = ok & (sgang == g_star)

    member_ok = idle & has_gang
    counts_all = _lead_sum(E, lambda j: (
        (sgang[j:j + 1] == sgang) & member_ok[j:j + 1]).astype(_I32))
    intact = member_ok & (counts_all == sgsize) & (sgsize > 0)
    score = jnp.where(idle,
                      intact.astype(_F32) * (100.0 + 10.0 * sgsize)
                      + 0.001 * _iota((E, bl), 0),
                      1e30)
    rank = _lead_sum(E, lambda j: (score[j:j + 1] < score).astype(_I32))
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
    steps = jnp.round(cfg.s_min + _pin(jnp.clip(a_steps, 0.0, 1.0)
                      * (cfg.s_max - cfg.s_min))).astype(_I32)
    steps_f = steps.astype(_F32)
    t_exec = _pin(pick(step_base, 0.0) * steps_f * scale_k)
    if faults:                          # gang speed = slowest member's speed
        slow_k = jnp.max(jnp.where(sel, fslow, 1.0), axis=0, keepdims=True)
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
        fin3 = finish[None]             # (1, 1, bl)
        crash_e = jnp.min(jnp.where((ds > t3) & (ds < fin3), ds, 1e30),
                          axis=1)                                 # (E, bl)
        crash_t = jnp.min(jnp.where(sel, crash_e, 1e30), axis=0,
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
    n_q = jnp.maximum(jnp.sum(still_queued.astype(_F32), axis=0,
                              keepdims=True), 1.0)
    # summed task by task, the order XLA:CPU sums the reference's one-env
    # row in (up to 32 tasks): interpret mode stays bitwise, where a
    # sublane reduction's order would be the compiler's choice
    wait_q = jnp.where(still_queued, t - arr, 0.0)
    t_avg = _lead_sum(K, lambda j: wait_q[j:j + 1]) / n_q
    r = _pin(cfg.alpha_q * q_k) - _pin(cfg.lambda_q * pen) \
        + cfg.k_time / (_pin(cfg.beta_t * t_resp) + _pin(cfg.mu_t * t_avg)
                        + 1e-3)
    reward = jnp.where(f, r, 0.0)
    if faults:                          # a gang that will crash earns nothing
        reward = jnp.where(will_fail, 0.0, reward)

    # --- advance time on no-op --------------------------------------------
    next_arrival = jnp.min(jnp.where(arr > t, arr, 1e30), axis=0,
                           keepdims=True)
    next_completion = jnp.min(jnp.where(new_free > t, new_free, 1e30),
                              axis=0, keepdims=True)
    next_event = jnp.minimum(next_arrival, next_completion)
    if faults:                          # recoveries are events too
        next_recovery = jnp.min(
            jnp.min(jnp.where((ds <= t3) & (de > t3), de, 1e30), axis=1),
            axis=0, keepdims=True)
        next_event = jnp.minimum(next_event, next_recovery)
    t_new = jnp.where(f, t, jnp.where(next_event < 1e30, next_event, t + 1.0))

    staken2 = staken + 1
    resolved = (status2 == 2) | ((status2 == 1) & (tfin2 <= t_new))
    if faults:                          # failed tasks resolve (host retries)
        resolved = resolved | (status2 == 3)
    all_done = jnp.all(resolved, axis=0, keepdims=True)
    done = all_done | (t_new >= cfg.time_limit) | (staken2 >= cfg.max_steps)

    # --- next visible queue: counting-rank top-k --------------------------
    # rank of task i = tasks j before it in (arrival, index) order: the
    # pairwise (K, K) pass, accumulated over j
    queued2 = (status2 == 0) & (arr <= t_new)
    prio = jnp.where(queued2, arr, 1e30)
    rank_q = _lead_sum(K, lambda j: (
        (prio[j:j + 1] < prio)
        | ((prio[j:j + 1] == prio) & (j < iota_K))).astype(_I32))  # (K, bl)
    # slot s holds the task of rank s: the (l, K) one-hot pass, accumulated
    # over tasks k (exactly one k hits each slot, so the sums are exact)
    slot_hit = [rank_q[j:j + 1] == iota_l for j in range(K)]      # (l, bl)
    idx2 = _lead_sum(K, lambda j: jnp.where(slot_hit[j], j, 0))
    valid2 = iota_l < jnp.sum(queued2.astype(_I32), axis=0, keepdims=True)

    # --- Eq.-6 observation of the new state -------------------------------
    up = new_free <= t_new
    if faults:                          # obs mirrors core.obs: down servers
        t_new3 = t_new[None]            # are unavailable to the policy too
        up = up & ~jnp.any((ds <= t_new3) & (t_new3 < de), axis=1)
    avail = up.astype(_F32)
    inv_ts = 1.0 / cfg.time_scale
    inv_nm = 1.0 / max(cfg.num_models, 1)
    remaining = jnp.maximum(new_free - t_new, 0.0) * inv_ts
    modelrow = (new_model.astype(_F32) + 1.0) * inv_nm
    arr_v = _lead_sum(K, lambda j: jnp.where(slot_hit[j], arr[j:j + 1], 0.0))
    c_v = _lead_sum(K, lambda j: jnp.where(slot_hit[j], c[j:j + 1], 0))
    wait = jnp.where(valid2, (t_new - arr_v) * inv_ts, 0.0)
    crow = jnp.where(valid2, c_v.astype(_F32) / 8.0, 0.0)
    if cfg.num_models > 1:
        m_v = _lead_sum(K, lambda j: jnp.where(slot_hit[j], model[j:j + 1],
                                               0))
        mrow = jnp.where(valid2, (m_v.astype(_F32) + 1.0) * inv_nm, 0.0)
    else:
        mrow = jnp.zeros_like(crow)

    # a frozen env keeps the state and queue view it came in with (before
    # the fault wipe); its observation, reward and done are the caller's
    def out(o_ref, new, old, batch_first=True):
        kept = jnp.where(frozen, old, new)
        o_ref[...] = kept.T if batch_first else kept

    out(o_time, t_new, t, batch_first=False)
    out(o_free, new_free, free)
    out(o_smodel, new_model, smodel_in)
    out(o_sgang, new_gang, sgang_in)
    out(o_sgsize, new_gsize, sgsize_in)
    out(o_tstatus, status2, tstatus)
    out(o_tstart, start2, tstart)
    out(o_tfinish, tfin2, tfinish)
    out(o_tsteps, tsteps2, tsteps)
    out(o_tqual, tq2, tqual)
    out(o_treload, trl2, treload)
    out(o_staken, staken2, staken, batch_first=False)
    out(o_qidx, idx2, qidx)
    out(o_qvalid, valid2.astype(_I32), qvalid_i)
    out(o_qqueued, queued2.astype(_I32), qqueued_i)
    for row, (srv, que) in enumerate(((avail, wait), (remaining, crow),
                                      (modelrow, mrow))):
        o_obs[row, :E, :] = srv
        o_obs[row, E:, :] = que
    o_reward[...] = reward
    o_done[...] = done.astype(_I32)


def _tile(n: int, m: int) -> int:
    return -(-n // m) * m


def block_words(E: int, K: int, l: int, F: int = 0) -> int:
    """4-byte words one env's in and out blocks fill in VMEM at their tiled
    sizes: a batch-first (B, X) block a whole row of 128 lanes (X rounded
    up to 128), a lane-major (X, B) block X rounded up to 8 sublanes."""
    A = 2 + l
    batch_first = [E] * 4 + [K] * 13 + [l, l, K] + [E] * 4 + [K] * 6 \
        + [l, l, K]                         # state, constants, queue; in, out
    lane_major = [1] * 3 + [A] + [1] * 4 + [3 * _tile(E + l, 8)]
    if F:
        lane_major += [E * _tile(F, 8)] * 2 + [E, 1]
    return (sum(_tile(x, 128) for x in batch_first)
            + sum(_tile(x, 8) for x in lane_major))


def env_bytes(E: int, K: int, l: int, F: int = 0) -> int:
    """VMEM one env takes in a grid step: its in and out blocks twice (the
    pipeline's double buffers) and the kernel's temporaries — the
    transposed state, a few (K|E, B) arrays per pass and, with fault
    columns, four (E, F, B) masks."""
    temps = 2 * (4 * E + 7 * K + 3 * l) + 24 * (K + E) + 4 * E * F
    return 4 * (2 * block_words(E, K, l, F) + temps)


def lane_block(B: int, E: int, K: int, l: int, F: int = 0) -> int:
    """Envs per grid step. All of B where its `env_bytes` fit
    `VMEM_BUDGET`; else the widest multiple of 128 that fits, evened out
    over the steps B needs."""
    cap = max(128, VMEM_BUDGET // env_bytes(E, K, l, F) // 128 * 128)
    if B <= cap:
        return B
    steps = -(-B // cap)
    return -(-B // (steps * 128)) * 128


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def env_step_pallas(cfg: EV.EnvConfig, time, free, smodel, sgang, sgsize,
                    tstatus, tstart, tfinish, tsteps, tqual, treload, staken,
                    arr, c, model, noise, step_base, init_base, scale,
                    action, qidx, qvalid, qqueued, frozen, *,
                    fds=None, fde=None, fslow=None, fcold=None,
                    interpret: bool = True):
    """Raw batched kernel entry; returns a tuple of 18 arrays.

    Batch-first (B, X) in and out: the server (E) and task (K) state, the
    queue view (B, l), (B, l), (B, K); in only: the per-task constants
    (B, K). Per-env scalars are (1, B) both ways: time, steps taken,
    `frozen` in; reward and done out. Lane-major in: the action (A, B). The
    observation leaves lane-major, (3, E+l, B). Boolean masks are int32 0/1
    on both sides. The optional fault-schedule quartet (`fds`/`fde`
    (E, F, B) down intervals, `fslow` (E, B) straggler multipliers, `fcold`
    (1, B) cold-restart flag — see `repro.faults.schedule`), lane-major,
    switches the kernel into fault mode; leaving them
    None traces the exact fault-free program. Use ``ops.env_step_fused``
    for the EnvState/QueueView-level wrapper.

    The grid steps over `lane_block` envs at a time; B is padded up to a
    whole number of blocks only where it takes more than one.
    """
    faults = fds is not None
    B = time.shape[1]
    E, K, l = cfg.num_servers, cfg.max_tasks, cfg.queue_window
    A = cfg.action_dim
    F = fds.shape[1] if faults else 0
    bl = lane_block(B, E, K, l, F)
    pad = (-B) % bl
    nb = (B + pad) // bl
    ins = [time, free, smodel, sgang, sgsize, tstatus, tstart, tfinish,
           tsteps, tqual, treload, staken, arr, c, model, noise,
           step_base, init_base, scale, action, qidx, qvalid, qqueued,
           frozen]
    # (batch-first?, leading dims) of each input, then of each output
    ROW, LANE = True, False
    in_kind = ([(LANE, (1,))] + [(ROW, (E,))] * 4 + [(ROW, (K,))] * 6
               + [(LANE, (1,))] + [(ROW, (K,))] * 7
               + [(LANE, (A,)), (ROW, (l,)), (ROW, (l,)), (ROW, (K,)),
                  (LANE, (1,))])
    if faults:
        ins += [fds, fde, fslow, fcold]
        in_kind += [(LANE, (E, F)), (LANE, (E, F)), (LANE, (E,)),
                    (LANE, (1,))]
    out_kind = ([(LANE, (1,), _F32), (ROW, (E,), _F32)]
                + [(ROW, (E,), _I32)] * 3
                + [(ROW, (K,), d) for d in (_I32, _F32, _F32, _I32, _F32,
                                            _I32)]
                + [(LANE, (1,), _I32), (ROW, (l,), _I32), (ROW, (l,), _I32),
                   (ROW, (K,), _I32), (LANE, (3, E + l), _F32),
                   (LANE, (1,), _F32), (LANE, (1,), _I32)])

    def spec(batch_first, dims):
        if batch_first:             # (B, n) block
            return pl.BlockSpec((bl,) + dims, lambda i: (i, 0))
        return pl.BlockSpec(dims + (bl,), lambda i: (0,) * len(dims) + (i,))

    def shape(batch_first, dims, dtype):
        return jax.ShapeDtypeStruct(
            (B + pad,) + dims if batch_first else dims + (B + pad,), dtype)

    if pad:
        ins = [jnp.pad(x, ((0, pad), (0, 0)) if bf else
                       ((0, 0),) * (x.ndim - 1) + ((0, pad),))
               for x, (bf, _) in zip(ins, in_kind)]
    outs = pl.pallas_call(
        functools.partial(_env_step_kernel, cfg, faults),
        grid=(nb,),
        in_specs=[spec(*k) for k in in_kind],
        out_specs=[spec(bf, dims) for bf, dims, _ in out_kind],
        out_shape=[shape(*k) for k in out_kind],
        # the next state and queue view overwrite the ones read, so a scan
        # carries them in place (no copy of the carry a decision)
        input_output_aliases={**{i: i for i in range(12)},
                              20: 12, 21: 13, 22: 14},
        interpret=interpret,
        name="env_step_pallas",
    )(*ins)
    if pad:
        outs = [o[:B] if bf else o[..., :B]
                for o, (bf, _, _) in zip(outs, out_kind)]
    return tuple(outs)
