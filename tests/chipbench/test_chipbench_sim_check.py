"""`correct` for the simulated cells: true for the program, false for the
control and for each fault a sim cell can have, planted in the program."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import testing


@pytest.fixture(autouse=True)
def fresh_programs():
    jax.clear_caches()         # planted faults must be traced anew
    yield
    jax.clear_caches()


def run(workload, **kw):
    return testing.tiny_run(testing.tiny_cell(workload), **kw)[1]


@pytest.mark.parametrize("workload", ["sim-paper8-eat", "sim-paper8-greedy"])
def test_program_is_correct_and_control_is_not(workload):
    out = run(workload)
    assert testing.correct(out), out.checks
    ctl = run(workload, control=True)
    assert not testing.correct(ctl), ctl.checks


def test_step_returning_its_state_unchanged(monkeypatch):
    from repro.kernels.env_step import ops
    real = ops.env_step_fused

    def stuck(ecfg, statics, state, action, queue, **kw):
        _, _, obs, r, d = real(ecfg, statics, state, action, queue, **kw)
        return state, queue, obs, r, d
    monkeypatch.setattr(ops, "env_step_fused", stuck)
    out = run("sim-paper8-eat")
    assert not testing.correct(out), out.checks


def test_half_the_streams_left_out(monkeypatch):
    from repro.core import rollout
    real = rollout.batch_rollout

    def half(ecfg, traces, policy, params, keys, **kw):
        h = keys.shape[0] // 2
        cut = lambda t: jax.tree_util.tree_map(lambda x: x[:h], t)  # noqa: E731
        if kw.get("init_state") is not None:
            kw["init_state"] = cut(kw["init_state"])
        res = real(ecfg, cut(traces), policy, params, keys[:h], **kw)
        return jax.tree_util.tree_map(
            lambda x: jnp.concatenate([x, x]), res)
    monkeypatch.setattr(rollout, "batch_rollout", half)
    out = run("sim-paper8-eat")
    assert not testing.correct(out), out.checks


def test_action_altered_where_the_actor_makes_it(monkeypatch):
    from repro.core import agent
    monkeypatch.setattr(agent, "to_env_action",
                        lambda a: jnp.clip((a + 1.0) * 0.5 + 0.1, 0.0, 1.0))
    out = run("sim-paper8-eat")
    assert not testing.correct(out), out.checks


def test_greedy_choice_altered(monkeypatch):
    from repro.core import baselines
    real = baselines.greedy_act

    def fewer_steps(ecfg, trace, state):
        a = real(ecfg, trace, state)
        return a.at[1].set(jnp.maximum(a[1] - 0.25, 0.0))
    monkeypatch.setattr(baselines, "greedy_act", fewer_steps)
    out = run("sim-paper8-greedy")
    assert not testing.correct(out), out.checks
