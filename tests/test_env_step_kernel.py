"""Fused env-step parity: the Pallas kernel (interpret mode), the jnp
reference, and the pre-refactor compositional `env.step` must produce
bitwise-identical state / reward / done / queue / observation on randomized
EnvStates — including carried-gang labels in [K, K+E) (the streaming seam
relabeling), cold and warm servers, and multi-model configs — and the fused
rollout / streaming engines must reproduce the unfused ones exactly.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import env as EV
from repro.core import rollout as RO
from repro.core import scenarios as SC
from repro.core.workload import TraceConfig, make_trace, make_trace_batch
from repro.faults import FaultSpec
from repro.kernels.env_step import ops as EK
from repro.traffic import (PoissonArrivals, ProcessTaskSource, StreamConfig,
                           run_stream)


def _cfg(E, num_models=1):
    ms = tuple([1.0, 0.5, 2.0][:num_models]) if num_models > 1 else ()
    return EV.EnvConfig(num_servers=E, max_tasks=2 * E + 4, queue_window=4,
                        num_models=num_models, model_scale=ms)


def _tc(ecfg):
    return TraceConfig(num_tasks=ecfg.max_tasks, arrival_rate=0.2,
                       max_servers=ecfg.num_servers,
                       num_models=ecfg.num_models)


def _random_state(rng, ecfg, trace):
    """A semi-consistent EnvState: warm/cold servers, intact and broken
    gangs, labels from both the in-episode range [0, K) and the carried
    range [K, K+E), tasks in every status."""
    E, K = ecfg.num_servers, ecfg.max_tasks
    t = np.float32(rng.uniform(0.0, 60.0))
    free = np.where(rng.random(E) < 0.5, 0.0,
                    t + rng.uniform(-20.0, 40.0, E)).astype(np.float32)
    gang = -np.ones(E, np.int64)
    gsize = np.zeros(E, np.int64)
    model = -np.ones(E, np.int64)
    # place a few gangs; labels may come from the carried range [K, K+E)
    servers = rng.permutation(E)
    i = 0
    while i < E and rng.random() < 0.8:
        c = int(rng.choice([1, 2, 4, 8]))
        c = min(c, E - i)
        label = int(rng.integers(0, K + E))
        m = int(rng.integers(0, max(ecfg.num_models, 1)))
        members = servers[i:i + c]
        # sometimes break the gang: report a wrong size on purpose
        size = c if rng.random() < 0.8 else int(rng.integers(1, 9))
        gang[members] = label
        gsize[members] = size
        model[members] = m
        i += c
    status = rng.choice([0, 0, 1, 2], K)
    tstart = np.where(status >= 1, rng.uniform(0, t, K), 0).astype(np.float32)
    tfin = np.where(status >= 1, tstart + rng.uniform(1, 50, K),
                    0).astype(np.float32)
    return EV.EnvState(
        time=jnp.asarray(t),
        server_free_at=jnp.asarray(free),
        server_model=jnp.asarray(model, jnp.int32),
        server_gang=jnp.asarray(gang, jnp.int32),
        server_gang_size=jnp.asarray(gsize, jnp.int32),
        task_status=jnp.asarray(status, jnp.int32),
        task_start=jnp.asarray(tstart),
        task_finish=jnp.asarray(tfin),
        task_steps=jnp.asarray(rng.integers(0, 50, K), jnp.int32),
        task_quality=jnp.asarray(rng.uniform(0, 0.3, K), jnp.float32),
        task_reload=jnp.asarray(rng.integers(0, 2, K), jnp.int32),
        steps_taken=jnp.asarray(int(rng.integers(0, 100)), jnp.int32),
    )


def _b1(tree):
    return jax.tree_util.tree_map(lambda x: x[None], tree)


def _assert_tree_equal(a, b, ctx):
    fa = a._asdict() if hasattr(a, "_asdict") else a
    fb = b._asdict() if hasattr(b, "_asdict") else b
    for k in fa:
        np.testing.assert_array_equal(np.asarray(fa[k]), np.asarray(fb[k]),
                                      err_msg=f"{ctx}: field {k}")


# ---------------------------------------------------------------- per-step
@pytest.mark.parametrize("E,num_models", [(4, 1), (8, 1), (16, 1), (8, 3)])
def test_fused_step_matches_legacy_on_random_states(E, num_models):
    """Kernel (interpret) == jnp ref == pre-refactor step, bitwise, over
    randomized states and actions (schedule, no-op, infeasible)."""
    ecfg = _cfg(E, num_models)
    rng = np.random.default_rng(E * 100 + num_models)
    for trial in range(12):
        trace = make_trace(jax.random.PRNGKey(trial), _tc(ecfg))
        state = _random_state(rng, ecfg, trace)
        statics = EV.decision_statics(ecfg, trace)
        qv = EV.visible_queue(ecfg, trace, state)
        a = rng.uniform(size=ecfg.action_dim).astype(np.float32)
        if trial % 3 == 0:
            a[0] = 0.1          # force a schedule attempt
        a = jnp.asarray(a)
        ns_l, obs_l, r_l, d_l, _ = EV.step(ecfg, trace, state, a)
        q2_l = EV.visible_queue(ecfg, trace, ns_l)
        for impl in ("ref", "pallas"):
            ns_f, q_f, obs_f, r_f, d_f = EK.env_step_fused(
                ecfg, _b1(statics), _b1(state), a[None], _b1(qv), impl=impl)
            ctx = f"E={E} nm={num_models} trial={trial} impl={impl}"
            _assert_tree_equal(ns_l, jax.tree_util.tree_map(
                lambda x: x[0], ns_f), ctx)
            _assert_tree_equal(q2_l, jax.tree_util.tree_map(
                lambda x: x[0], q_f), ctx + " queue")
            np.testing.assert_array_equal(np.asarray(obs_l),
                                          np.asarray(obs_f[0]), ctx)
            assert float(r_l) == float(r_f[0]), ctx
            assert bool(d_l) == bool(d_f[0]), ctx


def test_fused_step_carried_gang_reuse():
    """A complete idle gang with a carried label in [K, K+E) must be reused
    identically by all three implementations (no reload)."""
    ecfg = _cfg(4)
    K = ecfg.max_tasks
    tc = TraceConfig(num_tasks=K, arrival_rate=100.0, max_servers=4,
                     c_support=(2,), c_probs=(1.0,))
    trace = make_trace(jax.random.PRNGKey(0), tc)
    state = EV.reset(ecfg)._replace(
        time=jnp.float32(1.0),
        server_gang=jnp.asarray([K + 1, K + 1, -1, -1], jnp.int32),
        server_gang_size=jnp.asarray([2, 2, 0, 0], jnp.int32),
        server_model=jnp.asarray([0, 0, -1, -1], jnp.int32),
    )
    a = jnp.asarray([0.0, 0.5, 1.0, 0.0, 0.0, 0.0], jnp.float32)
    ns_l, _, r_l, _, info = EV.step(ecfg, trace, state, a)
    assert bool(info["scheduled"]) and bool(info["reuse"])
    qv = EV.visible_queue(ecfg, trace, state)
    statics = EV.decision_statics(ecfg, trace)
    for impl in ("ref", "pallas"):
        ns_f, _, _, r_f, _ = EK.env_step_fused(
            ecfg, _b1(statics), _b1(state), a[None], _b1(qv), impl=impl)
        _assert_tree_equal(ns_l, jax.tree_util.tree_map(lambda x: x[0], ns_f),
                           impl)
        assert float(r_l) == float(r_f[0])
        # the reused servers kept the carried label and skipped the reload
        assert int(np.asarray(ns_f.task_reload[0]).sum()) == 0


# ---------------------------------------------------------------- rollouts
@pytest.mark.parametrize("policy_fn", [RO.uniform_policy, RO.greedy_policy,
                                       RO.fifo_policy],
                         ids=["random", "greedy", "fifo"])
def test_fused_rollout_matches_unfused(policy_fn):
    ecfg = EV.EnvConfig(num_servers=4, max_tasks=8, queue_window=4,
                        max_steps=96)
    tc = TraceConfig(num_tasks=8, arrival_rate=0.05, max_servers=4)
    traces = make_trace_batch(jax.random.PRNGKey(3), tc, 4)
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    pol = policy_fn(ecfg)
    a = RO.batch_rollout(ecfg, traces, pol, {}, keys, fused=False,
                         collect=True)
    for impl in ("ref", "pallas"):
        b = RO.batch_rollout(ecfg, traces, pol, {}, keys, fused=True,
                             collect=True, fused_impl=impl)
        for k in a.metrics:
            np.testing.assert_array_equal(np.asarray(a.metrics[k]),
                                          np.asarray(b.metrics[k]),
                                          err_msg=f"{impl} metric {k}")
        _assert_tree_equal(a.final_state, b.final_state, impl)
        for fld in ("obs", "action", "reward", "next_obs", "done", "valid"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a.transitions, fld)),
                np.asarray(getattr(b.transitions, fld)),
                err_msg=f"{impl} transitions {fld}")


def test_fused_scenario_grid_bitwise():
    """Representative scenario cells (distinct E / rates / gang mixes / a
    multi-model cell): fused and unfused episode metrics are bitwise equal,
    so every existing scenario result stays reproducible on the fused
    engine. The full default grid runs under -m slow."""
    cells = [SC._make("tiny-4srv", 4, 0.05, num_tasks=8),
             SC.cold_start_heavy(4),
             SC.multi_model_mix(num_servers=4, num_models=2,
                                model_scale=(1.0, 0.5))]
    for sc in cells:
        _assert_scenario_parity(sc, num_steps=128)


@pytest.mark.slow
def test_fused_full_default_grid_bitwise():
    """Acceptance: the full `scenarios.default_grid()` produces
    bitwise-identical episode metrics on fused vs unfused engines."""
    for sc in SC.default_grid():
        _assert_scenario_parity(sc, num_steps=256)


def _assert_scenario_parity(sc, num_steps):
    key = jax.random.PRNGKey(7)
    pol = RO.uniform_policy(sc.ecfg)
    a = SC.run_scenario(sc, pol, key, batch=2, num_steps=num_steps)
    # run_scenario goes through batch_rollout(fused default); force both
    from repro.core.workload import make_trace_batch as _mtb
    k_trace, k_run = jax.random.split(key)
    if sc.arrival is None:
        traces = _mtb(k_trace, sc.tcfg, 2)
    else:
        traces = SC.make_scenario_trace_batch(k_trace, sc, 2)
    keys = jax.random.split(k_run, 2)
    ra = RO.batch_rollout(sc.ecfg, traces, pol, {}, keys, fused=False,
                          num_steps=num_steps)
    rb = RO.batch_rollout(sc.ecfg, traces, pol, {}, keys, fused=True,
                          num_steps=num_steps)
    for k in ra.metrics:
        np.testing.assert_array_equal(np.asarray(ra.metrics[k]),
                                      np.asarray(rb.metrics[k]),
                                      err_msg=f"{sc.name}: {k}")
    _assert_tree_equal(ra.final_state, rb.final_state, sc.name)


# ---------------------------------------------------------------- streaming
@pytest.mark.parametrize("faults", [None, FaultSpec(
    seed=2, mtbf=60.0, mttr=15.0, straggler_prob=0.3, straggler_factor=3.0)],
    ids=["fault-free", "faults"])
@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_fused_stream_matches_unfused_across_seams(impl, faults):
    """Multi-window streaming (carried gangs relabelled into [K, K+E),
    backlog carry, clock rebase) is bitwise-identical on the fused engine:
    same summaries, same per-window ledgers, same final carry state. Also
    with the Pallas kernel (the envs on its lane axis, a finished env's
    state frozen inside it), over 3 streams (lanes that fill no tile) and
    with fault columns."""
    ecfg = EV.EnvConfig(num_servers=4, max_tasks=16, queue_window=4,
                        max_steps=64)
    tc = TraceConfig(num_tasks=16, arrival_rate=0.3, max_servers=4)

    def fused(ecfg, traces, policy, params, keys, **kw):
        return RO.batch_rollout(ecfg, traces, policy, params, keys,
                                fused=True, fused_impl=impl, **kw)

    def run(is_fused):
        src = ProcessTaskSource(PoissonArrivals(0.3), tc,
                                jax.random.PRNGKey(0), num_streams=3)
        return run_stream(ecfg, RO.fifo_policy(ecfg), {}, src,
                          jax.random.PRNGKey(1),
                          StreamConfig(num_windows=5, num_streams=3,
                                       fused=is_fused, faults=faults),
                          rollout_fn=fused if is_fused else None)

    a, b = run(False), run(True)
    assert a.summary == b.summary
    assert a.per_window == b.per_window
    _assert_tree_equal(a.final_carry, b.final_carry, "final_carry")


def test_stream_config_fused_default_on():
    assert StreamConfig().fused is True
    assert dataclasses.replace(StreamConfig(), fused=False).fused is False
