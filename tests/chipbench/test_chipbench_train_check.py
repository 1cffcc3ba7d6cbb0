"""`correct` for the stream-training cell: true for the program, false for
the control and for each fault the cell can have, each on its own number;
and the plain SAC reference against the program's update step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import testing
from chipbench.reference import sac as R


@pytest.fixture(autouse=True)
def fresh_programs():
    jax.clear_caches()         # planted faults must be traced anew
    yield
    jax.clear_caches()


def run(**kw):
    return testing.tiny_run(testing.tiny_cell("train-paper8-sac"), **kw)[1]


def failed(out):
    return {c.name for c in out.checks if not c.ok}


def test_program_is_correct_and_control_is_not():
    out = run()
    assert testing.correct(out), out.checks
    assert out.e2e["train_transitions_per_s"] > 0
    assert out.counts["updates"] == 4 * out.counts["rounds"]
    ctl = run(control=True)
    assert "update_max_rel_err" in failed(ctl), ctl.checks


def test_reference_agrees_with_the_update_step():
    """Three updates of the program's `update_step` from its own initial
    state, each recomputed by the reference from the state before it."""
    from repro.core import agent as AG
    from repro.core import env as EV
    from repro.core import sac as SAC
    from chipbench.drivers.train import as_dict
    ecfg = EV.EnvConfig(num_servers=8, queue_window=8, max_tasks=32)
    acfg = AG.AgentConfig()
    scfg = SAC.SACConfig(batch_size=32)
    cell = testing.tiny_cell("train-paper8-sac")
    hp = R.hyper(cell.config, cell.traffic["train"], "cpu")
    ts = SAC.init_train_state(jax.random.PRNGKey(0), ecfg, acfg)
    rng = np.random.default_rng(0)
    for i in range(3):
        batch = {"obs": rng.random((32, 3, 16), np.float32),
                 "action": rng.uniform(-1, 1, (32, 10)).astype(np.float32),
                 "reward": rng.normal(size=32).astype(np.float32),
                 "next_obs": rng.random((32, 3, 16), np.float32),
                 "done": (rng.random(32) < 0.2).astype(np.float32)}
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        key = jax.random.PRNGKey(10 + i)
        new, losses = R.run(as_dict(ts), batch, key, hp)
        ts, met = SAC.update_step(ts, batch, key, ecfg=ecfg, acfg=acfg,
                                  scfg=scfg)
        got = as_dict(ts)
        for k in ("critic_loss", "actor_loss"):
            assert float(met[k]) == pytest.approx(float(losses[k]),
                                                  rel=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves({k: got[k] for k in new}),
                        jax.tree_util.tree_leaves(new)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


def test_critic_gradient_scaled(monkeypatch):
    from repro.core import sac
    real = sac.adam_update
    calls = []

    def scaled(grads, state, params, lr, **kw):
        calls.append(1)
        if len(calls) % 3 != 0:          # critic1, critic2, then the actor
            grads = jax.tree_util.tree_map(lambda g: 1.5 * g, grads)
        return real(grads, state, params, lr, **kw)
    monkeypatch.setattr(sac, "adam_update", scaled)
    out = run()
    assert "update_max_rel_err" in failed(out), out.checks


def test_transition_dropped_from_the_push(monkeypatch):
    from repro.core import sac
    real = sac.flatten_valid_transitions
    monkeypatch.setattr(sac, "flatten_valid_transitions",
                        lambda tr: tuple(x[:-1] for x in real(tr)))
    out = run()
    assert "replay_mismatches" in failed(out), out.checks


def test_action_altered_at_the_seam(monkeypatch):
    from repro.core import agent
    monkeypatch.setattr(agent, "to_env_action",
                        lambda a: jnp.clip((a + 1.0) * 0.5 + 0.1, 0.0, 1.0))
    out = run()
    assert "actor_mean_abs_diff" in failed(out), out.checks


def test_step_returning_its_state_unchanged(monkeypatch):
    from repro.core import sac
    real = sac.update_step

    def stuck(ts, batch, key, **kw):
        return ts, real(ts, batch, key, **kw)[1]
    monkeypatch.setattr(sac, "update_step", stuck)
    out = run()
    assert "change_norm_gap" in failed(out), out.checks
    assert next(c.value for c in out.checks
                if c.name == "change_norm_gap") == pytest.approx(1.0)


def test_half_the_batch_left_out(monkeypatch):
    from repro.core import sac
    real = sac.update_step

    def half(ts, batch, key, **kw):
        return real(ts, {k: v[:len(v) // 2] for k, v in batch.items()},
                    key, **kw)
    monkeypatch.setattr(sac, "update_step", half)
    out = run()
    assert {"loss_rel_err", "grad_norm_gap"} & failed(out), out.checks
