"""The serving execution backend: real model execution behind the
`batch_rollout` calling convention.

`ServingRollout` is a stateful callable with the unified backend signature

    fn(ecfg, traces, policy, params, keys, *,
       num_steps=None, collect=False, init_state=None) -> RolloutResult

so `Simulator(ExecSpec(backend="serving"))`, `StreamRunner(rollout_fn=...)`
and `train_stream_sac(exec_spec=...)` all drive a real serving cluster
through the exact seam the simulated engines use. One constraint: the batch
axis is 1 — there is one physical pool, not B parallel universes.

Design: the scheduler's view of the cluster is a *mirror* `EnvState`
advanced by the shared, parity-tested `env.step_with_queue` — gang
selection, reuse detection, reward shaping, and the Eq.-6 observation are
therefore byte-for-byte the simulator's. The pool (`serving.pool`) holds the
real per-server weights and the load/reuse ledger; the executor
(`serving.executor`) runs real patch-parallel prefill + decode for every
scheduled task. Two time modes:

* virtual (``serving_wall_clock=False``): latencies stay on the Table-VI
  model inside the decision step, so the whole rollout — final state,
  rewards, collected transitions — is bitwise-identical to the fused
  simulator on the same (trace, policy, key). This is the seam test: real
  execution rides along without perturbing the MDP.
* wall-clock (``serving_wall_clock=True``): each scheduled task's measured
  execution seconds are patched back into the mirror (`server_free_at`,
  `task_finish`), the reward is recomputed from the *measured* t_resp
  (Eq. 4a), and the next observation/queue derive from the patched state —
  the sim-to-real loop closes: `train_stream_sac` fine-tunes on measured
  latencies, and `StreamAggregator` rows report wall-clock QoS.

PRNG, freeze-after-done, and transition layout follow `rollout_episode`
exactly (one `split` per decision; post-done steps replay the frozen state),
so `sac.flatten_valid_transitions` consumes serving-collected windows
unchanged — asserted by tests/test_serving_backend.py.
"""
from __future__ import annotations

import functools
import time
import warnings
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.actors.program import actor_program
from repro.common.config import ASSIGNED_ARCHS
from repro.core import env as EV
from repro.core import obs as OBS
from repro.core import quality as Q
from repro.core.rollout import RolloutResult, Transitions
from repro.faults import ExecFaultInjector, ExecutorFault, FaultSpec
from repro.serving.executor import ModelExecutor
from repro.serving.pool import ServerPool
from repro.telemetry.profile import DecisionProfile
from repro.telemetry.trace import NULL_TRACER, tracer_for


def _policy_prog(ecfg: EV.EnvConfig, policy):
    """DEPRECATED door: the per-decision inference program now lives on the
    shared actor layer — use ``repro.actors.actor_program(ecfg,
    policy).act``. This wrapper returns exactly that program (same compiled
    executable, same (key split + actor forward) semantics, same bitwise
    guarantees vs the fused simulator) and will be removed once external
    callers migrate."""
    warnings.warn(
        "serving.backend._policy_prog is deprecated; use "
        "repro.actors.actor_program(ecfg, policy).act",
        DeprecationWarning, stacklevel=2)
    return actor_program(ecfg, policy).act


@functools.lru_cache(maxsize=None)
def _env_prog(ecfg: EV.EnvConfig):
    """The mirror env advance: `env.step_with_queue` on the pre-step queue
    view — the second half of one `rollout_episode` scan iteration."""
    @jax.jit
    def step(trace, state, q, action):
        return EV.step_with_queue(ecfg, trace, state, q, action)
    return step


@functools.lru_cache(maxsize=None)
def _wall_patch_prog(ecfg: EV.EnvConfig):
    """Patch a just-scheduled decision with its measured busy seconds:
    rewrite the gang's `server_free_at` and the task's finish time, recompute
    the reward from the measured t_resp (Eq. 4a; t_avg comes from the same
    pre-step queue view the virtual reward used), re-evaluate done, and
    rebuild the queue/observation from the patched state."""
    @jax.jit
    def patch(trace, q_pre, nstate, k, sel, busy):
        t = nstate.time                      # scheduling never moves time
        finish = t + busy
        st = nstate._replace(
            server_free_at=jnp.where(sel, finish, nstate.server_free_at),
            task_finish=nstate.task_finish.at[k].set(finish))
        q_k = st.task_quality[k]
        pen = Q.quality_penalty(q_k, ecfg.q_min, ecfg.p_quality)
        t_resp = finish - trace["arr_time"][k]
        still = q_pre.queued & (jnp.arange(ecfg.max_tasks) != k)
        n_q = jnp.maximum(jnp.sum(still.astype(jnp.float32)), 1.0)
        t_avg = jnp.sum(jnp.where(still, t - trace["arr_time"], 0.0)) / n_q
        r = ecfg.alpha_q * q_k - ecfg.lambda_q * pen \
            + ecfg.k_time / (ecfg.beta_t * t_resp + ecfg.mu_t * t_avg + 1e-3)
        all_done = jnp.all((st.task_status == 2) |
                           ((st.task_status == 1) & (st.task_finish <= t)))
        d = all_done | (t >= ecfg.time_limit) | \
            (st.steps_taken >= ecfg.max_steps)
        q2 = OBS.visible_queue(ecfg, trace, st)
        obs2 = OBS.observe_from(ecfg, trace, st, q2)
        return st, q2, obs2, r, d
    return patch


@functools.lru_cache(maxsize=None)
def _metrics_prog(ecfg: EV.EnvConfig):
    return jax.jit(lambda trace, st: EV.episode_metrics(ecfg, trace, st))


class ServingRollout:
    """Stateful serving backend under the `batch_rollout` convention.

    The pool (loaded weights, load/reuse counters) persists across calls —
    across stream windows and training rounds, exactly like a long-lived
    cluster. `reset()` drops every loaded model (the Simulator calls it at
    the start of each `run`, so sweep policies never inherit a warm pool).
    """

    backend = "serving"

    def __init__(self, num_servers: int, *, archs=(), reduced: bool = True,
                 wall_clock: bool = False, execute: bool = True,
                 prompt_len: int = 8, max_new_tokens: int = 16,
                 seed: int = 0, warmup: Optional[bool] = None, tracer=None,
                 faults: Optional[FaultSpec] = None):
        self.archs = tuple(archs) if archs else ASSIGNED_ARCHS
        self.reduced = reduced
        self.wall_clock = wall_clock
        self.execute = execute
        self.prompt_len = int(prompt_len)
        self.max_new_tokens = int(max_new_tokens)
        self.seed = int(seed)
        # warmup pre-compiles executor programs outside the timed region so
        # wall-clock latencies measure inference, not XLA compilation; it
        # defaults on exactly when measured seconds feed the MDP
        self.warmup = bool(wall_clock) if warmup is None else bool(warmup)
        self.tracer = NULL_TRACER if tracer is None else tracer
        self.pool = ServerPool(num_servers)
        self.executor = ModelExecutor(reduced=reduced, tracer=self.tracer)
        self.profile = DecisionProfile()
        self.faults = faults if (faults is not None and faults.active) \
            else None
        self.injector = ExecFaultInjector(self.faults)
        self.tasks_executed = 0
        self.measured_busy: list = []       # wall seconds per executed task
        self._load_key = jax.random.PRNGKey(seed)
        self._prompt_rng = np.random.default_rng(seed)
        # placement prefetch draws weights from its OWN key stream so the
        # on-demand `_load` sequence — and with it every scheduled task's
        # weights — is identical to a placement-free run
        self._prefetch_key = jax.random.PRNGKey(seed ^ 0x5EED)
        self.placement_prefetches = 0
        self.placement_evictions = 0

    # ------------------------------------------------------------------
    def reset(self) -> None:
        """Fresh cluster: unload every model, zero the ledgers. Compiled
        executor programs (and the warmed-shape memo) survive — compilation
        caches are process-level, not cluster state."""
        self.pool.reset()
        self.injector.reset()
        self.profile = DecisionProfile()
        self.tasks_executed = 0
        self.measured_busy = []
        self._load_key = jax.random.PRNGKey(self.seed)
        self._prompt_rng = np.random.default_rng(self.seed)
        self._prefetch_key = jax.random.PRNGKey(self.seed ^ 0x5EED)
        self.placement_prefetches = 0
        self.placement_evictions = 0

    def serving_stats(self) -> Dict[str, float]:
        out = dict(self.pool.counters())
        out["tasks_executed"] = self.tasks_executed
        if self.measured_busy:
            out["measured_busy_mean_s"] = float(np.mean(self.measured_busy))
        out.update(self.profile.summary())
        out.update(self.placement_counters())
        return out

    def placement_counters(self) -> Dict[str, int]:
        """Real-weight prefetch/evict ledger (zero in a placement-free
        run); kept off `pool.counters()`, whose key set is pinned."""
        return {"placement_weight_prefetches": self.placement_prefetches,
                "placement_weight_evictions": self.placement_evictions}

    def pool_counters(self) -> Dict[str, int]:
        """The pool's monotonic load/reuse/shed ledger alone (metrics
        registry counters; `serving_stats` adds derived scalars)."""
        return dict(self.pool.counters())

    def fault_counters(self) -> Dict[str, int]:
        """Fault-tolerance ledger: pool retry/degrade counts + injected
        errors (all zero in a fault-free run)."""
        out = dict(self.pool.fault_counters())
        out.update(self.injector.counters())
        return out

    # ------------------------------------------------------------------
    def _arch_of(self, m_k: int) -> str:
        return self.archs[m_k % len(self.archs)]

    def _run_task(self, m_k: int, c_k: int, steps: int, sel: np.ndarray,
                  reuse: bool) -> float:
        """Pool bookkeeping + real execution for one scheduled gang.
        Returns measured wall seconds of the load + generate work."""
        arch = self._arch_of(m_k)
        gang = [self.pool.servers[i] for i in np.flatnonzero(sel)]
        if self.execute and self.warmup:
            # compile prefill/decode for this shape bucket BEFORE the timer:
            # the first task of an (arch, shape) pair must not bill XLA
            # compilation as serving latency
            with self.tracer.span("executor_warmup", cat="serving",
                                  arch=arch, c=int(c_k)):
                self.executor.warm(arch, self.prompt_len, c_k, steps,
                                   self.max_new_tokens)
        t0 = time.perf_counter()
        if reuse:
            self.pool.reuse_count += 1
            leader = next((s for s in gang if s.params is not None), None)
            if leader is None:                # defensive: mirror said reuse
                leader = gang[0]              # but pool lost the weights
                self._load(leader, arch)
            for s in gang:
                s.params, s.model_name = leader.params, leader.model_name
        else:
            for s in gang:      # a cold gang drops what its members held
                s.params, s.model_name = None, None   # before it loads
            self._load(gang[0], arch)
            for s in gang[1:]:
                # each member materialises the weights in the real system;
                # the replicas are identical, so share the leader's array
                s.params, s.model_name = gang[0].params, arch
                self.pool.load_count += 1
        if self.execute:
            prompt = self._prompt_rng.integers(
                0, self.executor.model(arch).cfg.vocab_size,
                self.prompt_len, dtype=np.int64).astype(np.int32)
            self._generate_tolerant(arch, gang[0].params, prompt, c_k, steps)
        self.tasks_executed += 1
        return time.perf_counter() - t0

    def _generate_tolerant(self, arch: str, params, prompt, c_k: int,
                           steps: int) -> None:
        """Real generation under the fault-tolerance policy: each attempt is
        wall-clock-bounded (`exec_timeout_s`) and may draw an injected
        transient error; transient failures retry up to `exec_max_attempts`
        tries, with the LAST attempt degraded to `degrade_steps_frac` of the
        requested steps (graceful degradation: a reduced-quality result
        beats no result). Without an active FaultSpec this is exactly one
        plain `executor.generate` call."""
        spec = self.faults
        if spec is None:
            self.executor.generate(arch, params, prompt, c_k, steps,
                                   self.max_new_tokens)
            return
        attempts = max(int(spec.exec_max_attempts), 1)
        for attempt in range(1, attempts + 1):
            run_steps = steps
            if attempt == attempts and attempts > 1:
                run_steps = max(1, int(steps * spec.degrade_steps_frac))
            degraded = run_steps < steps
            try:
                if degraded:
                    with self.tracer.span("executor_degrade", cat="serving",
                                          arch=arch, steps=run_steps,
                                          requested=steps):
                        self.injector.maybe_fail("generate")
                        self.executor.generate(
                            arch, params, prompt, c_k, run_steps,
                            self.max_new_tokens,
                            deadline_s=spec.exec_timeout_s)
                    self.pool.exec_degraded += 1
                else:
                    self.injector.maybe_fail("generate")
                    self.executor.generate(
                        arch, params, prompt, c_k, run_steps,
                        self.max_new_tokens, deadline_s=spec.exec_timeout_s)
                return
            except ExecutorFault as err:
                self.pool.exec_failures += 1
                if attempt == attempts:
                    self.pool.exec_gave_up += 1
                    return          # every attempt failed: serve nothing
                self.pool.exec_retries += 1
                with self.tracer.span("executor_retry", cat="serving",
                                      arch=arch, attempt=attempt,
                                      error=type(err).__name__):
                    pass

    def _load(self, server, arch: str) -> None:
        with self.tracer.span("model_load", cat="serving", arch=arch):
            self._load_key, k = jax.random.split(self._load_key)
            server.params = self.executor.init_params(arch, k)
        server.model_name = arch
        self.pool.load_count += 1

    # ------------------------------------------------------------------
    def apply_placement(self, decision) -> None:
        """Materialise a seam placement in the real pool, OFF the timed
        path: evict weights the plan displaced, prefetch the planned
        models (own PRNG stream — the `_load` sequence stays identical to
        a placement-free run), and pre-compile each placed gang's
        executor programs via the warmup machinery. A subsequent matching
        gang hits `_run_task`'s reuse path with the weights already
        resident — the mirror and the pool agree the start is warm."""
        sp = decision.streams[0]            # serving is one physical cluster
        for i in np.flatnonzero(sp.evict):
            s = self.pool.servers[i]
            with self.tracer.span("evict", cat="placement", server=int(i),
                                  arch=s.model_name or ""):
                s.params, s.model_name = None, None
            self.placement_evictions += 1
        warmed = set()
        for i in np.flatnonzero(sp.prefetch):
            arch = self._arch_of(int(sp.model[i]))
            s = self.pool.servers[i]
            if s.model_name != arch or s.params is None:
                with self.tracer.span("prefetch", cat="placement",
                                      server=int(i), arch=arch):
                    self._prefetch_key, k = jax.random.split(
                        self._prefetch_key)
                    s.params = None     # release the old copy first
                    s.params = self.executor.init_params(arch, k)
                    s.model_name = arch
                self.placement_prefetches += 1
            # mirror the carry's synthetic gang into the pool bookkeeping,
            # so pool-level reuse queries see the placed gang as complete
            s.gang = int(sp.gang[i])
            s.gang_size = int(sp.gang_size[i])
            c = int(sp.gang_size[i])
            if self.execute and self.warmup and (arch, c) not in warmed:
                warmed.add((arch, c))
                with self.tracer.span("executor_warmup", cat="serving",
                                      arch=arch, c=c):
                    self.executor.warm(arch, self.prompt_len, c,
                                       self.max_new_tokens,
                                       self.max_new_tokens)

    # ------------------------------------------------------------------
    def __call__(self, ecfg: EV.EnvConfig, traces: Dict, policy, params,
                 keys, *, num_steps: Optional[int] = None,
                 collect: bool = False,
                 init_state: Optional[EV.EnvState] = None) -> RolloutResult:
        B = int(np.asarray(keys).shape[0])
        if B != 1:
            raise ValueError(
                f"serving backend runs ONE physical cluster; got batch {B} "
                "(build the workload with batch/streams=1)")
        if ecfg.num_servers != len(self.pool.servers):
            raise ValueError(
                f"serving pool has {len(self.pool.servers)} servers but "
                f"ecfg.num_servers={ecfg.num_servers}")
        T = int(num_steps) if num_steps else ecfg.max_steps
        trace = {k: v[0] for k, v in traces.items()}
        key = keys[0]
        state = (EV.reset(ecfg) if init_state is None
                 else jax.tree_util.tree_map(lambda x: x[0], init_state))
        q, obs = EV.reset_view(ecfg, trace, state)
        # the shared actor layer owns the per-decision inference program:
        # the jit boundary at the decision seam (key split + actor forward)
        # is the SAME compiled program the latency probe measures, and its
        # sampler label attributes every decision span
        prog = actor_program(ecfg, policy)
        act = prog.act
        sampler = prog.sampler
        env_step = _env_prog(ecfg)
        wall_patch = _wall_patch_prog(ecfg)
        tr = self.tracer

        done = False
        total = np.float32(0.0)
        length = 0
        rows = [] if collect else None
        # per-sampler self-time attribution in the span table
        # (scripts/trace_summary.py groups decision spans by this attr)
        dkw = {"sampler": sampler} if sampler else {}
        for t_i in range(T):
            t0 = time.perf_counter()
            with tr.span("decision", cat="serving", step=t_i, **dkw):
                key, action, extras = act(trace, state, obs, key, params)
                jax.block_until_ready(action)
            self.profile.observe("policy", time.perf_counter() - t0)
            t0 = time.perf_counter()
            with tr.span("env_advance", cat="serving", step=t_i):
                nstate, nq, nobs, r, d, info = env_step(
                    trace, state, q, action)
                jax.block_until_ready(r)
            self.profile.observe("env_advance", time.perf_counter() - t0)
            if (not done and bool(info["scheduled"])
                    and bool(np.asarray(info.get("failed", False)))):
                # the mirror says a selected server crashes mid-run: the
                # gang aborts, so no real execution happens for this task
                self.pool.crashed_tasks += 1
            elif not done and bool(info["scheduled"]):
                k_task = info["task"]
                sel = np.asarray(nstate.server_gang == k_task)
                with tr.span("execute_task", cat="serving", step=t_i,
                             task=int(k_task),
                             arch=self._arch_of(int(trace["model"][k_task])),
                             c=int(trace["c"][k_task]),
                             steps=int(info["steps"]),
                             reuse=bool(info["reuse"])):
                    busy = self._run_task(
                        int(trace["model"][k_task]), int(trace["c"][k_task]),
                        int(info["steps"]), sel, bool(info["reuse"]))
                self.profile.observe("executor", busy)
                if self.wall_clock:
                    self.measured_busy.append(busy)
                    with tr.span("wall_patch", cat="serving", step=t_i,
                                 busy_s=busy):
                        nstate, nq, nobs, r, d = wall_patch(
                            trace, q, nstate, k_task, jnp.asarray(sel),
                            jnp.float32(busy))
            if done:       # frozen episode: replay the carried state
                nstate, nq, nobs = state, q, obs
                r = jnp.float32(0.0)
            if collect:
                rows.append((obs, action, r, nobs, d, not done, extras))
            total = total + np.float32(r)
            length += 0 if done else 1
            state, q, obs = nstate, nq, nobs
            done = done or bool(d)
            if done and not collect:
                break

        metrics = {k: np.asarray(v)[None] for k, v in
                   _metrics_prog(ecfg)(trace, state).items()}
        metrics["episode_return"] = np.asarray([total], np.float32)
        metrics["episode_len"] = np.asarray([length], np.int32)
        final_state = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x)[None], state)
        transitions = self._stack(rows) if collect else None
        return RolloutResult(metrics=metrics, final_state=final_state,
                             transitions=transitions)

    @staticmethod
    def _stack(rows) -> Transitions:
        """Host rows -> the (B=1, T, ...) layout every simulated backend
        emits, so `sac.flatten_valid_transitions` consumes it unchanged."""
        stk = lambda xs: np.stack([np.asarray(x) for x in xs])[None]  # noqa: E731
        extras = {}
        if rows and rows[0][6]:
            extras = {k: stk([r[6][k] for r in rows]) for k in rows[0][6]}
        return Transitions(
            obs=stk([r[0] for r in rows]),
            action=stk([r[1] for r in rows]),
            reward=stk([r[2] for r in rows]),
            next_obs=stk([r[3] for r in rows]),
            done=stk([np.float32(r[4]) for r in rows]),
            valid=np.asarray([r[5] for r in rows], bool)[None],
            extras=extras)


def serving_rollout(spec) -> ServingRollout:
    """Build the serving backend for an `ExecSpec(backend="serving")`.

    Fresh state per call: each Simulator / StreamRunner / trainer gets its
    own pool, which then persists across that consumer's windows and rounds.
    Pool size is deferred to the first call's `ecfg.num_servers` (the spec
    does not know the workload) and fixed thereafter.
    """
    return _from_spec(spec)


def _from_spec(spec) -> "ServingRollout":
    class _Lazy:
        """Defers pool construction to the first call (the spec does not
        know num_servers; the workload's ecfg does)."""
        backend = "serving"

        def __init__(self):
            self.inner: Optional[ServingRollout] = None

        def _ensure(self, num_servers: int) -> ServingRollout:
            if self.inner is None:
                self.inner = ServingRollout(
                    num_servers, archs=spec.serving_archs,
                    reduced=spec.serving_reduced,
                    wall_clock=spec.serving_wall_clock,
                    execute=spec.serving_execute,
                    prompt_len=spec.serving_prompt_len,
                    max_new_tokens=spec.serving_max_new_tokens,
                    seed=spec.serving_seed,
                    warmup=getattr(spec, "serving_warmup", None),
                    tracer=tracer_for(getattr(spec, "trace", None)),
                    faults=getattr(spec, "faults", None))
            return self.inner

        def __call__(self, ecfg, traces, policy, params, keys, **kw):
            return self._ensure(ecfg.num_servers)(
                ecfg, traces, policy, params, keys, **kw)

        def reset(self):
            if self.inner is not None:
                self.inner.reset()

        def serving_stats(self):
            return self.inner.serving_stats() if self.inner else {}

        def pool_counters(self):
            return self.inner.pool_counters() if self.inner else {}

        def fault_counters(self):
            return self.inner.fault_counters() if self.inner else {}

        def apply_placement(self, decision):
            if self.inner is not None:      # placement fires after the
                self.inner.apply_placement(decision)   # first window ran

        def placement_counters(self):
            return self.inner.placement_counters() if self.inner else {}

    return _Lazy()
