"""Table XII — per-decision inference latency of each scheduler.

Wall-clocks one scheduling decision (state -> action) per registered policy
on this host via the unified probe (`telemetry.profile.profile_policy`):
every policy resolves through `api.registry` to the rollout protocol, so
the measured program is exactly the inference the serving backend pays at
its `ActorProgram.act` jit boundary per arriving task. Reports p50/p95/p99
and mean seconds per decision and writes `BENCH_decision_latency.json`.

The paper's ordering (Greedy > EAT > EAT-A > EAT-DA ~ PPO > Random ~
meta-heuristics) comes from: Greedy enumerates candidate futures, the
diffusion policies run the T=10 denoise chain, the attention encoder adds a
little on top of the MLP encoder, and the precomputed-sequence methods only
index a replay buffer.

The diffusion actor additionally gets per-sampler rows on the SAME weights
(`eat-ddpm` — the full T-step chain, `eat-ddim:5` — strided deterministic
DDIM, `eat-distilled` — the one-call consistency student trained here
in-benchmark against the frozen ddpm teacher), each measured both per
single decision and per batched decision step (`GATE_BATCH` envs through
`ActorProgram.vmapped` — single-decision timings on small nets are floored
by host dispatch; batch scale is where a cheaper sampler's compute saving
shows). Two gates guard the fast path and fail the benchmark loudly:

* latency — the distilled sampler must cut the eat batched decision p99 by
  >= 3x vs ddpm;
* quality — mean eval return of the distilled actor on a fixed seeded
  trace batch must stay within max(15% of |ddpm return|, 1.0).
"""
from __future__ import annotations

import warnings
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

try:        # `python benchmarks/bench_decision_latency.py` (script dir)
    from common import make_env_cfg, make_trace_cfg, write_bench_json
except ImportError:     # `python -m benchmarks...` (package)
    from benchmarks.common import (make_env_cfg, make_trace_cfg,
                                   write_bench_json)
from repro.api import PolicySpec
from repro.api import registry as REG
from repro.core import rollout as RO
from repro.core.workload import make_trace
from repro.telemetry.profile import profile_policy

#: eat ablation variants ride along with the registered names — same
#: builder, different AgentConfig.variant
EAT_VARIANTS = ("eat", "eat-a", "eat-d", "eat-da")
#: sampler rows on the full eat variant — same denoiser weights, three
#: inference programs
EAT_SAMPLERS = ("ddpm", "ddim:5", "distilled")
#: envs per batched decision step in the sampler-gate probe
GATE_BATCH = 64
#: latency gate: distilled must cut the eat batched decision p99 this much
GATE_SPEEDUP = 3.0
#: quality gate: |R_distilled - R_ddpm| <= max(REL * |R_ddpm|, ABS)
GATE_REWARD_REL, GATE_REWARD_ABS = 0.15, 1.0


def _specs(policies: Optional[Sequence] = None) -> List:
    if policies is not None:
        return list(policies)
    # offline meta-heuristics: tiny resolve-time optimisation budget — the
    # measured program (sequence_policy indexing) is identical regardless
    small = {"genetic": {"seq_len": 64, "generations": 2, "population": 8},
             "harmony": {"seq_len": 64, "improvisations": 4,
                         "memory_size": 8}}
    specs = []
    for name in REG.available_policies():
        if name == "eat":
            specs.extend(PolicySpec("eat", options={"variant": v})
                         for v in EAT_VARIANTS)
        else:
            specs.append(PolicySpec(name, options=small.get(name, {})))
    return specs


def _distill(ecfg, teacher_params, verbose: bool):
    """Consistency-distil the fresh ddpm teacher into a student head —
    in-benchmark, so the eat-distilled row measures a REAL student and the
    quality gate compares a trained one."""
    from repro.core import agent as AG
    from repro.training.distill import DistillConfig, distill_actor
    acfg = AG.AgentConfig()
    dcfg = DistillConfig(steps=600, batch=256, dataset=2048,
                         noise_per_obs=8, collect_episodes=4,
                         collect_steps=64,
                         log_every=200 if verbose else 0)
    params, hist = distill_actor(jax.random.PRNGKey(7), teacher_params,
                                 ecfg, acfg, dcfg)
    if verbose:
        print(f"[distill] {dcfg.steps} steps, final loss "
              f"{hist[-1]['loss']:.4f}")
    return params


def _eval_return(ecfg, tcfg, policy, params, batch: int = 16) -> float:
    """Mean episode return on a FIXED seeded trace batch (deterministic
    actor) — the quality side of the sampler gate."""
    traces = jax.vmap(lambda k: make_trace(k, tcfg))(
        jax.random.split(jax.random.PRNGKey(123), batch))
    keys = jax.random.split(jax.random.PRNGKey(321), batch)
    res = RO.batch_rollout(ecfg, traces, policy, params, keys,
                           num_steps=64)
    return float(np.mean(np.asarray(res.metrics["episode_return"])))


def _sampler_rows_and_gate(ecfg, tcfg, trace, iters: int,
                           verbose: bool) -> Dict:
    """Per-sampler rows (single + batched probes) and the latency/quality
    gate record."""
    from repro.core import agent as AG
    out: Dict[str, Dict] = {}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", REG.UntrainedPolicyWarning)
        teacher = REG.resolve(PolicySpec("eat", sampler="ddpm"), ecfg)
    params = _distill(ecfg, teacher.params, verbose)

    rewards: Dict[str, float] = {}
    for sampler in EAT_SAMPLERS:
        label = f"eat-{sampler}"
        rp = REG.resolve(PolicySpec("eat", params=params, sampler=sampler),
                         ecfg)
        row = profile_policy(ecfg, rp.policy, rp.params,
                             jax.random.PRNGKey(1), trace=trace,
                             iters=iters)
        batched = profile_policy(ecfg, rp.policy, rp.params,
                                 jax.random.PRNGKey(1), trace=trace,
                                 iters=iters, batch=GATE_BATCH)
        for k, v in batched.items():
            if k.startswith("decision_latency"):
                row["batched_" + k] = v
        row["decision_batch"] = float(GATE_BATCH)
        row["kind"] = rp.kind
        out[label] = row
        det = REG.resolve(
            PolicySpec("eat", params=params, sampler=sampler,
                       options={"deterministic": True}), ecfg)
        rewards[sampler] = _eval_return(ecfg, tcfg, det.policy, det.params)

    p99_ddpm = out["eat-ddpm"]["batched_decision_latency_p99_s"]
    p99_dist = out["eat-distilled"]["batched_decision_latency_p99_s"]
    speedup = p99_ddpm / max(p99_dist, 1e-12)
    tol = max(GATE_REWARD_REL * abs(rewards["ddpm"]), GATE_REWARD_ABS)
    drift = abs(rewards["distilled"] - rewards["ddpm"])
    gate = {
        "batch": GATE_BATCH,
        "ddpm_batched_p99_s": p99_ddpm,
        "distilled_batched_p99_s": p99_dist,
        "p99_speedup": speedup,
        "latency_ok": bool(speedup >= GATE_SPEEDUP),
        "reward_ddpm": rewards["ddpm"],
        "reward_ddim": rewards["ddim:5"],
        "reward_distilled": rewards["distilled"],
        "reward_tolerance": tol,
        "reward_ok": bool(drift <= tol),
    }
    gate["ok"] = gate["latency_ok"] and gate["reward_ok"]
    if verbose:
        print(f"[gate] batched p99: ddpm {p99_ddpm:.2e}s, distilled "
              f"{p99_dist:.2e}s -> {speedup:.1f}x "
              f"({'OK' if gate['latency_ok'] else 'FAIL'}, need >= "
              f"{GATE_SPEEDUP}x)")
        print(f"[gate] eval return: ddpm {rewards['ddpm']:.3f}, ddim:5 "
              f"{rewards['ddim:5']:.3f}, distilled "
              f"{rewards['distilled']:.3f} (tol {tol:.3f}, "
              f"{'OK' if gate['reward_ok'] else 'FAIL'})")
    return out, gate


def run(verbose: bool = True, num_servers: int = 4, iters: int = 50,
        policies: Optional[Sequence] = None, samplers: bool = True):
    ecfg = make_env_cfg(num_servers)
    tcfg = make_trace_cfg(num_servers, 0.75)
    trace = make_trace(jax.random.PRNGKey(0), tcfg)
    trace_fn = lambda key: make_trace(key, tcfg)  # noqa: E731

    out: Dict[str, Dict[str, float]] = {}
    for spec in _specs(policies):
        label = spec if isinstance(spec, str) else (
            spec.options.get("variant", spec.name))
        with warnings.catch_warnings():
            # untrained weights are fine: latency depends on architecture,
            # not on weight values
            warnings.simplefilter("ignore", REG.UntrainedPolicyWarning)
            rp = REG.resolve(spec, ecfg, trace_fn=trace_fn)
        out[label] = profile_policy(ecfg, rp.policy, rp.params,
                                    jax.random.PRNGKey(1), trace=trace,
                                    iters=iters)
        out[label]["kind"] = rp.kind

    gate = None
    if samplers and (policies is None):
        rows, gate = _sampler_rows_and_gate(ecfg, tcfg, trace, iters,
                                            verbose)
        out.update(rows)

    if verbose:
        print("Table XII — scheduler decision latency (s/decision)")
        print("| policy        |     mean |      p50 |      p99 |")
        print("|---------------|----------|----------|----------|")
        for k, m in sorted(out.items(),
                           key=lambda kv: -kv[1]["decision_latency_mean_s"]):
            print(f"| {k:13s} | {m['decision_latency_mean_s']:.2e} "
                  f"| {m['decision_latency_p50_s']:.2e} "
                  f"| {m['decision_latency_p99_s']:.2e} |")
    return out, gate


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    res, gate = run()
    payload = {"policies": res, "iters": 50, "num_servers": 4}
    if gate is not None:
        payload["sampler_gate"] = gate
    write_bench_json("decision_latency", payload)
    if gate is not None and not gate["ok"]:
        raise SystemExit(
            f"sampler gate FAILED: {gate}")
