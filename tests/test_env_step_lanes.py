"""The lane-major env-step kernel against the jnp reference, bitwise.

The Pallas kernel runs in interpret mode with the envs on the lane axis;
`ref.env_step_ref`, stepping one env at a time, is the oracle. Batches of one
env, of 130 (lanes that fill no whole 128-lane tile: two padded blocks) and
of 256 (the benchmark's), with E of 8 and 32, without and with fault
columns; random states (warm, cold and carried gangs, tasks in every status)
and three decisions along the reference's own trajectory from each, some
envs frozen.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import env as EV
from repro.core.workload import TraceConfig, make_trace_batch
from repro.faults import FaultSpec, FaultTimeline, fault_horizon
from repro.kernels.env_step import kernel as KN
from repro.kernels.env_step import ops as EK
from repro.kernels.env_step.ref import env_step_ref


def _random_states(rng, ecfg, B):
    """(B, ...) EnvStates: idle and busy servers, intact and broken gangs
    with labels from the episode's range [0, K) and the carried [K, K+E),
    tasks unscheduled, running and done."""
    E, K = ecfg.num_servers, ecfg.max_tasks
    t = rng.uniform(0.0, 60.0, B).astype(np.float32)
    free = np.where(rng.random((B, E)) < 0.5, 0.0,
                    t[:, None] + rng.uniform(-20.0, 40.0, (B, E)))
    gang = -np.ones((B, E), np.int32)
    gsize = np.zeros((B, E), np.int32)
    model = -np.ones((B, E), np.int32)
    for b in range(B):
        servers, i = rng.permutation(E), 0
        while i < E and rng.random() < 0.8:
            c = min(int(rng.choice([1, 2, 4, 8])), E - i)
            members = servers[i:i + c]
            gang[b, members] = rng.integers(0, K + E)
            gsize[b, members] = c if rng.random() < 0.8 else rng.integers(1, 9)
            model[b, members] = rng.integers(0, ecfg.num_models)
            i += c
    status = rng.choice([0, 0, 1, 2], (B, K))
    tstart = np.where(status >= 1, rng.uniform(0, 1, (B, K)) * t[:, None], 0)
    tfin = np.where(status >= 1, tstart + rng.uniform(1, 50, (B, K)), 0)
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    return EV.EnvState(
        time=f32(t), server_free_at=f32(free), server_model=i32(model),
        server_gang=i32(gang), server_gang_size=i32(gsize),
        task_status=i32(status), task_start=f32(tstart),
        task_finish=f32(tfin),
        task_steps=i32(rng.integers(0, 50, (B, K))),
        task_quality=f32(rng.uniform(0, 0.3, (B, K))),
        task_reload=i32(rng.integers(0, 2, (B, K))),
        steps_taken=i32(rng.integers(0, 100, B)))


def _assert_equal(got, want, ctx):
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w),
            err_msg=f"{ctx} {jax.tree_util.keystr(path)}")


@pytest.mark.parametrize("faults", [False, True], ids=["fault-free", "faults"])
@pytest.mark.parametrize("E", [8, 32])
@pytest.mark.parametrize("B", [1, 130, 256])
def test_lane_major_kernel_matches_reference(B, E, faults):
    ecfg = EV.EnvConfig(num_servers=E)
    rng = np.random.default_rng(1000 * B + E + faults)
    traces = dict(make_trace_batch(
        jax.random.PRNGKey(B + E), TraceConfig(
            num_tasks=ecfg.max_tasks, arrival_rate=0.2, max_servers=E), B))
    if faults:
        spec = FaultSpec(seed=E, mtbf=60.0, mttr=15.0, straggler_prob=0.3,
                         straggler_factor=3.0)
        traces.update(FaultTimeline(spec, E, B).window_arrays(
            0, np.zeros(B), fault_horizon(ecfg.time_limit, spec)))
    statics = jax.vmap(lambda tr: EV.decision_statics(ecfg, tr))(traces)
    state = _random_states(rng, ecfg, B)
    q = jax.vmap(lambda tr, st: EV.visible_queue(ecfg, tr, st))(traces, state)
    # the reference steps one env at a time, as `env.step` does: a vmapped
    # reference is another XLA:CPU program, whose rewards can round apart
    # from the one-env program's by an ulp at these batch sizes
    ref = jax.jit(lambda *xs: jax.lax.map(
        lambda x: env_step_ref(ecfg, *x), xs))
    kern = jax.jit(lambda sx, st, a, qv, fz: EK.env_step_fused(
        ecfg, sx, st, a, qv, frozen=fz, impl="pallas", interpret=True))
    for i in range(3):
        a = rng.uniform(size=(B, ecfg.action_dim)).astype(np.float32)
        a[i::3, 0] = 0.1                    # a third try to schedule
        a = jnp.asarray(a)
        frozen = jnp.asarray(rng.random(B) < 0.25)
        want = ref(statics, state, a, q)
        got = kern(statics, state, a, q, frozen)
        _assert_equal(got[2:], want[2:], f"B={B} E={E} faults={faults} "
                      f"step {i}")
        keep = lambda n, o: np.where(  # noqa: E731
            np.asarray(frozen).reshape((B,) + (1,) * (n.ndim - 1)), o, n)
        _assert_equal(got[:2], jax.tree_util.tree_map(
            keep, want[:2], (state, q)), f"B={B} E={E} faults={faults} "
            f"step {i} (frozen: {int(frozen.sum())})")
        state, q = want[0], want[1]


@pytest.mark.parametrize("B", [1, 130, 256])
def test_frozen_envs_keep_state_and_queue(B):
    """Every env frozen: the kernel hands back the state and queue view it
    was given, bit for bit, and so does the jnp reference path."""
    ecfg = EV.EnvConfig(num_servers=8)
    rng = np.random.default_rng(B)
    traces = make_trace_batch(jax.random.PRNGKey(B), TraceConfig(
        num_tasks=ecfg.max_tasks, arrival_rate=0.2, max_servers=8), B)
    statics = jax.vmap(lambda tr: EV.decision_statics(ecfg, tr))(traces)
    state = _random_states(rng, ecfg, B)
    q = jax.vmap(lambda tr, st: EV.visible_queue(ecfg, tr, st))(traces, state)
    a = jnp.asarray(rng.uniform(size=(B, ecfg.action_dim)), jnp.float32)
    for impl in ("pallas", "ref"):
        ns, nq, *_ = EK.env_step_fused(ecfg, statics, state, a, q,
                                       frozen=jnp.ones((B,), bool),
                                       impl=impl, interpret=True)
        _assert_equal((ns, nq), (state, q), f"B={B} impl={impl}")


@pytest.mark.parametrize("B,E,F,want", [
    (1, 8, 0, 1), (128, 8, 0, 128), (130, 8, 0, 128), (256, 8, 0, 128),
    (256, 32, 16, 128), (1024, 8, 0, 128), (1000, 32, 16, 128),
    (4096, 32, 16, 128)])
def test_lane_block_follows_the_shapes(B, E, F, want):
    """All of B in one grid step where it fits the VMEM budget; else the
    widest multiple of 128 that does, evened out over the steps B needs.
    A batch-first block fills a whole row of 128 lanes per env whatever
    its width, so at K=32 one step holds 128 envs."""
    assert KN.lane_block(B, E, 32, 8, F) == want


def test_lane_block_evens_out_the_steps(monkeypatch):
    """Where a step holds more than 128 envs, B splits into equal steps:
    with a budget for 768 envs, 1024 envs take two steps of 512, not 768
    and a padded 768."""
    monkeypatch.setattr(KN, "VMEM_BUDGET", 768 * KN.env_bytes(8, 32, 8))
    assert KN.lane_block(768, 8, 32, 8) == 768
    assert KN.lane_block(1024, 8, 32, 8) == 512
    assert KN.lane_block(4096, 8, 32, 8) == 768
