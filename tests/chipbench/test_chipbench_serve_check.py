"""`correct` for the served cell, at the program's reduced qwen2 preset:
true for the program, false for the float8 control and for each fault the
cell can have: a served token altered, a decode step that leaves its cache
unchanged, half of a task's decode steps left out, fewer steps executed
than were placed, and an action altered at the decision seam."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import testing


@pytest.fixture(autouse=True)
def fresh_programs():
    jax.clear_caches()
    yield
    jax.clear_caches()


def run(**kw):
    return testing.tiny_run(testing.tiny_cell("serve-paper4-qwen2"), **kw)[1]


def failed(out):
    return {c.name for c in out.checks if not c.ok}


def test_program_is_correct_and_control_is_not():
    out = run()
    assert testing.correct(out), out.checks
    assert out.e2e["serve_tasks_per_s"] > 0
    assert 0 < out.e2e["serve_task_p90_s"] < float("inf")
    ctl = run(control=True)
    assert not testing.correct(ctl), ctl.checks


def test_token_altered_where_it_is_produced(monkeypatch):
    from repro.serving.executor import ModelExecutor
    real = ModelExecutor.generate

    def altered(self, arch, params, prompt, c, steps, *a, **kw):
        out = np.array(real(self, arch, params, prompt, c, steps, *a, **kw))
        out[len(out) // 2] = (out[len(out) // 2] + 1) % 1000
        return out
    monkeypatch.setattr(ModelExecutor, "generate", altered)
    out = run()
    assert not testing.correct(out), out.checks


def test_decode_step_leaving_its_cache_unchanged(monkeypatch):
    from repro.models import lm
    real = lm.lm_decode

    def stale(params, cfg, cache, token, *a, **kw):
        logits, _ = real(params, cfg, cache, token, *a, **kw)
        return logits, cache
    monkeypatch.setattr(lm, "lm_decode", stale)
    out = run()
    assert not testing.correct(out), out.checks


def test_decode_halved(monkeypatch):
    from repro.serving.executor import ModelExecutor
    real = ModelExecutor.generate

    def halved(self, arch, params, prompt, c, steps, *a, **kw):
        return real(self, arch, params, prompt, c, max(1, steps // 2),
                    *a, **kw)
    monkeypatch.setattr(ModelExecutor, "generate", halved)
    out = run()
    assert "decode_steps_short" in failed(out), out.checks


def test_fewer_steps_executed_than_placed(monkeypatch):
    from repro.serving.backend import ServingRollout
    real = ServingRollout._run_task

    def fewer(self, m_k, c_k, steps, sel, reuse):
        return real(self, m_k, c_k, steps - 1, sel, reuse)
    monkeypatch.setattr(ServingRollout, "_run_task", fewer)
    out = run()
    assert "placements_mismatched" in failed(out), out.checks


def test_action_altered_at_the_decision_seam(monkeypatch):
    from repro.core import agent
    monkeypatch.setattr(agent, "to_env_action",
                        lambda a: jnp.clip((a + 1.0) * 0.5 + 0.1, 0.0, 1.0))
    out = run()
    assert "actor_mean_abs_diff" in failed(out), out.checks
