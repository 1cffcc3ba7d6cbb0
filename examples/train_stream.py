"""Train in the stream: SAC/PPO from windowed streaming rollouts.

    PYTHONPATH=src python examples/train_stream.py                 # SAC
    PYTHONPATH=src python examples/train_stream.py --algo ppo
    PYTHONPATH=src python examples/train_stream.py \
        --curriculum --rate-scale 2.0 --backend sharded \
        --rounds 64 --streams 8

Each round advances one (or more) windows of an open-loop arrival stream —
backlog, clock, and server occupancy carried across the seam — collects the
transitions, and runs gradient updates, so the agent trains on the backlog
distribution it induces rather than on fresh episode resets
(`repro.training.stream_train`). `--rate-scale > 1` trains under sustained
overload; `--curriculum` cycles the arrival-process cells (rate sweep,
cold-start-heavy, MMPP bursts, flash crowds) through one continuous stream
clock. `--backend sharded` splits the stream axis over the local device
mesh (bitwise-identical collection; on CPU force devices with
XLA_FLAGS=--xla_force_host_platform_device_count=8).
"""
from __future__ import annotations

import argparse
import json

from repro.api import BACKENDS, ExecSpec
from repro.core import agent as AG
from repro.core import ppo as PPO
from repro.core import sac as SAC
from repro.core.env import EnvConfig
from repro.core.scenarios import training_curriculum
from repro.training import stream_train as ST


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--algo", default="sac", choices=("sac", "ppo"))
    ap.add_argument("--servers", type=int, default=8)
    ap.add_argument("--window-tasks", type=int, default=32,
                    help="tasks per window per stream (= env max_tasks)")
    ap.add_argument("--streams", type=int, default=4,
                    help="parallel streams (the sharded batch axis)")
    ap.add_argument("--rounds", type=int, default=32)
    ap.add_argument("--windows-per-round", type=int, default=1)
    ap.add_argument("--rate-scale", type=float, default=1.5,
                    help="arrival-intensity multiplier (>1 = sustained "
                         "overload, the streaming regime)")
    ap.add_argument("--curriculum", action="store_true",
                    help="cycle arrival cells (rates/coldstart/bursty/"
                         "flashcrowd) instead of one Poisson cell")
    ap.add_argument("--variant", default="eat",
                    help="SAC actor variant: eat|eat-a|eat-d|eat-da")
    ap.add_argument("--diffusion-steps", type=int, default=10)
    ap.add_argument("--warmup-steps", type=int, default=256)
    ap.add_argument("--max-updates-per-round", type=int, default=-1,
                    help="cap gradient updates per round (0 = collect-only, "
                         "-1 = no cap; matches StreamTrainConfig semantics)")
    ap.add_argument("--backend", default="fused", choices=BACKENDS)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="write the per-round history rows as JSON")
    args = ap.parse_args()

    ecfg = EnvConfig(num_servers=args.servers, max_tasks=args.window_tasks)
    stcfg = ST.StreamTrainConfig(
        rounds=args.rounds, windows_per_round=args.windows_per_round,
        streams=args.streams, rate_scale=args.rate_scale,
        max_updates_per_round=(None if args.max_updates_per_round < 0
                               else args.max_updates_per_round),
        log_every=1)
    curriculum = training_curriculum(ecfg) if args.curriculum else None
    exec_spec = ExecSpec(backend=args.backend)

    if args.algo == "sac":
        acfg = AG.AgentConfig(variant=args.variant, T=args.diffusion_steps)
        scfg = SAC.SACConfig(warmup_steps=args.warmup_steps)
        res = ST.train_stream_sac(ecfg, acfg, scfg, stcfg,
                                  curriculum=curriculum, seed=args.seed,
                                  exec_spec=exec_spec)
    else:
        res = ST.train_stream_ppo(ecfg, PPO.PPOConfig(), stcfg,
                                  curriculum=curriculum, seed=args.seed,
                                  exec_spec=exec_spec)

    s = res.stream.summary
    print(f"\n=== run summary ({args.algo}, backend={args.backend}) ===")
    for k in ("tasks_injected", "tasks_scheduled", "tasks_dropped",
              "latency_p95", "latency_p99", "qos_violation_rate",
              "drop_rate", "goodput_per_s", "utilization"):
        print(f"  {k:24s} {s[k]}")
    if res.history:
        first, last = res.history[0], res.history[-1]
        print(f"  return round0 -> final   {first['episode_return_mean']:.2f} "
              f"-> {last['episode_return_mean']:.2f}")
        print(f"  violation round0 -> final {first['qos_violation_rate']:.3f} "
              f"-> {last['qos_violation_rate']:.3f}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"history": res.history, "summary": s}, f, indent=1)
        print(f"history -> {args.out}")


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
