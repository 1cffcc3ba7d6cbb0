#!/usr/bin/env python
"""CI actor smoke: the compiled-inference layer's load-bearing promises.

    PYTHONPATH=src python scripts/actor_smoke.py

Gates three contracts on tiny runs (`make actor-smoke`):

1. **No regression.** The default actor (``sampler="ddpm"``) resolved
   through the registry/ActorProgram layer is BITWISE-identical to the
   pre-refactor door (`core.sac.actor_policy` fed straight into
   `batch_rollout`) on the fused backend, and the fused / sharded /
   serving backends agree on the same run.
2. **The chain kernel is exact.** The Pallas whole-chain denoiser kernel
   (interpret mode on CPU) matches the jnp chain oracle bitwise.
3. **Fast samplers hold deterministic parity.** ``ddim:K`` and
   ``distilled`` produce identical deterministic decision processes on
   the fused and serving backends (virtual time, mirror mode) — the
   contract that lets serving swap samplers without a parity suite rerun.
"""
from __future__ import annotations

import re
import sys
import warnings

_MEASURED = re.compile(
    r"(_latency_(p\d+|mean)_s$|_decisions$|^decision_latency_n$"
    r"|measured_busy|^wall_s$|^wall_clock$"
    r"|^model_loads$|^model_reuses$|^tasks_executed$)")


def _det(summary):
    return {k: v for k, v in summary.items()
            if isinstance(v, (int, float, bool)) and not _MEASURED.search(k)}


def _assert_same(da, db, what):
    """Every deterministic key of `da` matches `db` (the serving backend
    adds ledger-only extras — weight prefetch/evict counters — on top)."""
    diff = {k: (v, db.get(k)) for k, v in da.items() if db.get(k) != v}
    assert not diff, f"{what} diverged: {diff}"


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import actors as ACT
    from repro.api import (ExecSpec, PolicySpec, Simulator, WorkloadSpec,
                           UntrainedPolicyWarning)
    from repro.core import agent as AG
    from repro.core import diffusion as DF
    from repro.core import rollout as RO
    from repro.core import sac as SAC
    from repro.core.env import EnvConfig
    from repro.core.scenarios import Scenario
    from repro.core.workload import TraceConfig, make_trace
    from repro.kernels.denoiser import ops as KOPS

    warnings.simplefilter("ignore", UntrainedPolicyWarning)

    ecfg = EnvConfig(num_servers=4, max_tasks=8, queue_window=4,
                     max_steps=24)
    tcfg = TraceConfig(num_tasks=8, arrival_rate=0.05, max_servers=4)
    cell = Scenario(name="actor-smoke-cell", ecfg=ecfg, tcfg=tcfg)
    acfg = AG.AgentConfig(variant="eat-a", T=4, hidden=32)
    params = AG.init_actor(jax.random.PRNGKey(0), ecfg, acfg)
    key = jax.random.PRNGKey(42)

    # 1a. registry door == pre-refactor door, bitwise, on shared traces ---
    print("[actor-smoke] ddpm no-regression: registry vs core.sac door")
    B = 4
    traces = jax.vmap(lambda k: make_trace(k, tcfg))(
        jax.random.split(jax.random.PRNGKey(7), B))
    keys = jax.random.split(jax.random.PRNGKey(8), B)
    old = RO.batch_rollout(ecfg, traces,
                           SAC.actor_policy(ecfg, acfg, deterministic=True),
                           params, keys)
    spec = PolicySpec("eat", params=params,
                      options={"acfg": acfg, "deterministic": True})
    rp = Simulator(WorkloadSpec.episodic(cell, batch=B), ExecSpec()) \
        .resolve(spec)
    assert rp.meta["sampler"] == "ddpm"
    assert rp.program.sampler == "ddpm"
    new = RO.batch_rollout(ecfg, traces, rp.policy, rp.params, keys)
    for name in old.metrics:
        a, b = np.asarray(old.metrics[name]), np.asarray(new.metrics[name])
        np.testing.assert_array_equal(a, b, err_msg=name)
    print(f"  {len(old.metrics)} metric arrays bitwise-identical")

    # 1b. fused == sharded == serving on the registry path ----------------
    def run(backend, spec, **es_kw):
        wl = WorkloadSpec.streaming(
            cell, streams=1, num_windows=2, window_tasks=8,
            max_steps_per_window=16)
        return Simulator(wl, ExecSpec(backend=backend, **es_kw)) \
            .run(spec, key)

    base = run("fused", spec)
    for backend, kw in (("sharded", {}),
                        ("serving", {"serving_execute": False})):
        print(f"[actor-smoke] ddpm parity: fused vs {backend}")
        other = run(backend, spec, **kw)
        _assert_same(_det(base.summary), _det(other.summary),
                     f"{backend} vs fused")
        print("  bitwise-identical summaries")

    # 2. whole-chain kernel vs oracle, bitwise ----------------------------
    print("[actor-smoke] chain kernel (interpret) vs jnp oracle")
    A, F, K = 3, ecfg.obs_shape[1], 5
    ks = jax.random.split(jax.random.PRNGKey(3), 7)
    p = DF.init_denoiser(ks[0], A, F, hidden=24)
    x = jax.random.normal(ks[1], (9, A))
    noises = jax.random.normal(ks[2], (K, 9, A))
    f_s = jax.random.normal(ks[3], (9, F))
    tembs = DF.timestep_embedding(jnp.arange(K) + 1, 16)
    cx = 1.0 + 0.1 * jax.random.normal(ks[4], (K,))
    ce = 0.1 * jax.random.normal(ks[5], (K,))
    cn = 0.1 * jax.random.uniform(ks[6], (K,))
    ref = KOPS.denoise_chain(p, x, noises, f_s, tembs, cx, ce, cn,
                             impl="ref")
    ker = KOPS.denoise_chain(p, x, noises, f_s, tembs, cx, ce, cn,
                             impl="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))
    print("  bitwise")

    # 3. fast samplers: deterministic fused == serving --------------------
    for sampler in ("ddim:2", "distilled"):
        print(f"[actor-smoke] {sampler} deterministic parity: "
              "fused vs serving")
        fspec = PolicySpec("eat", sampler=sampler,
                           options={"acfg": acfg, "deterministic": True})
        rf = run("fused", fspec)
        rs = run("serving", fspec, serving_execute=False)
        assert rf.summary["sampler"] == rs.summary["sampler"] == sampler
        _assert_same(_det(rf.summary), _det(rs.summary),
                     f"{sampler} serving vs fused")
        print("  bitwise-identical summaries")

    print("[actor-smoke] OK")
    return 0


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
