"""Fig. 5 — training curves of the DRL schedulers (EAT vs ablations vs PPO).

    PYTHONPATH=src python examples/compare_agents.py --episodes 15 \
        --servers 8 --variants eat,eat-da,ppo

Trains each variant on the 8-server simulated cluster at the paper's
arrival rate (experience collected from ``--num-envs`` parallel envs via the
batched rollout engine), dumps reward / episode-length curves to
``artifacts/training_curves.json`` (paper Fig. 5a/5c: EAT trends above the
ablations; Fig. 5b: diffusion-policy variants converge to shorter episodes),
then evaluates every trained policy — plus Random/Greedy — on ``--eval-batch``
held-out traces in one jitted program per policy.
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import numpy as np

from repro.api import BACKENDS, ExecSpec, PolicySpec, evaluate_batch
from repro.core import agent as AG
from repro.core import ppo as PPO
from repro.core import sac as SAC
from repro.core.env import EnvConfig
from repro.core.workload import (TraceConfig, make_trace, make_trace_batch,
                                 paper_rate_for)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--episodes", type=int, default=15)
    ap.add_argument("--servers", type=int, default=8)
    ap.add_argument("--variants", default="eat,eat-a,eat-d,eat-da,ppo")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--num-envs", type=int, default=4)
    ap.add_argument("--eval-batch", type=int, default=16)
    ap.add_argument("--curriculum", action="store_true",
                    help="sample training traces from the scenario grid "
                         "(rate sweep, cold-start, bursty/flash arrivals) "
                         "instead of one fixed TraceConfig")
    ap.add_argument("--backend", default="fused", choices=BACKENDS,
                    help="repro.api execution backend for collection and "
                         "evaluation (sharded = device-mesh batch split)")
    ap.add_argument("--out", default="artifacts/training_curves.json")
    args = ap.parse_args()
    exec_spec = ExecSpec(backend=args.backend)

    ecfg = EnvConfig(num_servers=args.servers)
    rate = paper_rate_for(args.servers)
    tc = TraceConfig(arrival_rate=rate, max_servers=args.servers)
    trace_fn = lambda key: make_trace(key, tc)  # noqa: E731
    curriculum = None
    if args.curriculum:
        from repro.core.scenarios import training_curriculum
        curriculum = training_curriculum(ecfg)
        print("curriculum cells:", [sc.name for sc in curriculum])

    curves = {}
    # PolicySpec per evaluated policy: trained weights pass through params=
    eval_specs = {"random": PolicySpec("random"),
                  "greedy": PolicySpec("greedy")}
    for variant in args.variants.split(","):
        print(f"=== training {variant} ({args.episodes} episodes, "
              f"{args.servers} servers, rate {rate}, "
              f"{args.num_envs} parallel envs) ===")
        if variant == "ppo":
            st, hist = PPO.train_ppo(ecfg, PPO.PPOConfig(), trace_fn,
                                     args.episodes, seed=args.seed,
                                     log_every=5, num_envs=args.num_envs,
                                     curriculum=curriculum,
                                     exec_spec=exec_spec)
            eval_specs[variant] = PolicySpec("ppo", params=st.params)
        else:
            acfg = AG.AgentConfig(variant=variant)
            scfg = SAC.SACConfig(batch_size=128, warmup_steps=192,
                                 update_every=2)
            ts, hist = SAC.train(ecfg, acfg, scfg, trace_fn, args.episodes,
                                 seed=args.seed, log_every=5,
                                 num_envs=args.num_envs,
                                 curriculum=curriculum, exec_spec=exec_spec)
            eval_specs[variant] = PolicySpec("eat", params=ts.actor,
                                             options={"acfg": acfg})
        curves[variant] = hist

    # -- held-out evaluation: one batched program per policy, any backend --
    print(f"\n=== batched evaluation ({args.eval_batch} held-out traces) ===")
    eval_traces = make_trace_batch(jax.random.PRNGKey(10_000), tc,
                                   args.eval_batch)
    eval_keys = jax.random.split(jax.random.PRNGKey(777), args.eval_batch)
    evaluation = {}
    for name, spec in eval_specs.items():
        m = evaluate_batch(ecfg, eval_traces, spec, eval_keys,
                           exec_spec=exec_spec)
        evaluation[name] = {k: float(np.mean(v)) for k, v in m.items()}
    print(f"{'policy':8s} {'return':>8s} {'quality':>8s} {'resp':>8s} "
          f"{'reload':>7s}")
    for name, m in evaluation.items():
        print(f"{name:8s} {m['episode_return']:8.1f} {m['avg_quality']:8.3f} "
              f"{m['avg_response']:8.1f} {m['reload_rate']:7.2f}")
    curves = {"curves": curves, "evaluation": evaluation}

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(curves, f, indent=1)

    print(f"\ncurves -> {args.out}")
    print(f"{'variant':8s} {'first-3 R':>10s} {'last-3 R':>10s} "
          f"{'last-3 len':>10s} {'resp':>8s}")
    for v, hist in curves["curves"].items():
        f3 = sum(h["episode_return"] for h in hist[:3]) / min(3, len(hist))
        l3 = sum(h["episode_return"] for h in hist[-3:]) / min(3, len(hist))
        ln = sum(h["episode_len"] for h in hist[-3:]) / min(3, len(hist))
        rs = sum(h["avg_response"] for h in hist[-3:]) / min(3, len(hist))
        print(f"{v:8s} {f3:10.1f} {l3:10.1f} {ln:10.0f} {rs:8.1f}")


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
