"""End-to-end serving driver — the paper's Fig. 1 system, executable.

    PYTHONPATH=src python examples/serve_cluster.py [--policy eat|greedy|fifo]
        [--servers 4] [--tasks 12] [--archs qwen2-1.5b,tinyllama-1.1b]

Submits a batch of AIGC requests (prompts against real reduced models from
the assigned-architecture zoo), lets the chosen scheduler gang-allocate
logical edge servers, REALLY executes patch-parallel prefill + decode on the
loaded weights, and reports the Table-IX/X/XI metrics. Model loads and
reuses are real (weight materialisation vs pointer sharing), so the
cold-start economics the paper schedules around are visible in the metrics.

Virtual time (time_dilation=1) accounts busy-time with the calibrated
Table-VI latency model so the run is deterministic and completes in
seconds on CPU.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.core import agent as AG
from repro.core import env as EV
from repro.core import sac as SAC
from repro.serving.engine import Request, ServingEngine


def make_policy(name: str, num_servers: int, queue_window: int):
    if name == "fifo":
        # always execute the oldest task with mid steps
        def fifo(obs, key):
            a = np.zeros(2 + queue_window, np.float32)
            a[1] = 0.5
            a[2] = 1.0
            return a
        return fifo
    if name == "greedy":
        # prefer the task whose patch count matches an idle loaded gang
        def greedy(obs, key):
            a = np.zeros(2 + queue_window, np.float32)
            a[1] = 1.0                       # max steps (paper's Greedy)
            a[2:] = obs[0, -queue_window:]   # prefer longest-waiting
            return a
        return greedy
    # eat: the full attention+diffusion actor (untrained here: quickstart
    # scale; examples/compare_agents.py trains it properly)
    ecfg = EV.EnvConfig(num_servers=num_servers, queue_window=queue_window)
    acfg = AG.AgentConfig(variant="eat")
    actor = AG.init_actor(jax.random.PRNGKey(7), ecfg, acfg)

    def eat(obs, key):
        a = SAC.policy_act(actor, jax.numpy.asarray(obs), key,
                           ecfg=ecfg, acfg=acfg)
        return np.asarray(AG.to_env_action(a))
    return eat


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="eat",
                    choices=["eat", "greedy", "fifo"])
    ap.add_argument("--servers", type=int, default=4)
    ap.add_argument("--tasks", type=int, default=12)
    ap.add_argument("--archs", default="qwen2-1.5b,tinyllama-1.1b")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    archs = args.archs.split(",")
    eng = ServingEngine(args.servers, archs, queue_window=8,
                        reduced=True, time_dilation=1.0, seed=args.seed)
    rng = np.random.default_rng(args.seed)

    # batched request arrivals (D_g exponential, D_c over {1,2,4})
    t = 0.0
    reqs = []
    for rid in range(args.tasks):
        t += rng.exponential(1.0 / 0.05)
        c = int(rng.choice([1, 2, 4], p=[0.4, 0.4, 0.2]))
        c = min(c, args.servers)
        reqs.append(Request(
            rid=rid, arch=archs[rid % len(archs)],
            prompt=rng.integers(1, 100, size=24).astype(np.int32),
            patches=c, arrive_t=t, max_new_tokens=8))

    policy = make_policy(args.policy, args.servers, eng.l)
    key = jax.random.PRNGKey(args.seed)
    pending = sorted(reqs, key=lambda r: r.arrive_t)
    decisions = 0
    while (pending or eng.queue) and decisions < 10 * args.tasks:
        now = eng.now()
        while pending and pending[0].arrive_t <= now:
            eng.submit(pending.pop(0))
        if not eng.queue:
            eng._advance(max(0.5, pending[0].arrive_t - now) if pending else 1.0)
            continue
        key, k = jax.random.split(key)
        done = eng.try_schedule(policy(eng.observe(), k))
        decisions += 1
        if done is not None:
            print(f"[{done.finish_t:8.1f}s] req {done.rid:2d} "
                  f"({done.arch}, c={done.patches}) steps={done.steps} "
                  f"reused={done.reused} resp={done.finish_t - done.arrive_t:7.1f}s "
                  f"tokens={done.tokens[:4]}...")

    m = eng.qos_summary()
    print(f"\npolicy={args.policy} servers={args.servers}: "
          f"scheduled {m['tasks_scheduled']}/{args.tasks}, "
          f"latency p50/p95 {m['latency_p50']:.1f}/{m['latency_p95']:.1f}s, "
          f"quality {m['avg_quality']:.3f}, "
          f"cold-start rate {m['cold_start_rate']:.2f} "
          f"({m['model_loads']} loads, {m['model_reuses']} reuses)")


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
