"""Stream-training benchmark -> BENCH_stream_train.json.

    PYTHONPATH=src python benchmarks/bench_stream_train.py
    PYTHONPATH=src python benchmarks/bench_stream_train.py --devices 8

Runs the same streamed SAC training (>= 8 windows, sustained-overload
arrival rate — `StreamTrainConfig.rate_scale` > 1) on the "fused" and
"sharded" execution backends and records, per backend: windows/s,
transitions/s (collection + gradient updates included — this is end-to-end
training throughput), and the round-0 -> final episode return and
drop-inclusive QoS-violation rate. Every window's collected replay batch is
SHA-256 digested through the trainer's `transition_hook`; the bench asserts
the fused and sharded digests are bitwise-identical before writing the
record. `--devices N` forces N host CPU devices (XLA_FLAGS, set before jax
starts its backend) so the sharded backend runs a multi-device mesh on a
CPU container; on an accelerator the mesh is the chips.
"""
from __future__ import annotations

import argparse
import hashlib
import os
import sys
import time


def eval_policy_stream(ecfg, acfg, actor_params, backend, args,
                       windows: int = 8, seed: int = 12345):
    """Evaluate an actor on a fresh overload stream (empty cluster, same
    arrival seed for every policy) — the fair round-0 vs trained comparison:
    inside the *training* stream, later windows inherit the saturated
    backlog, so raw per-round telemetry confounds policy quality with
    backlog age."""
    import jax
    import numpy as np

    from repro.api import ExecSpec
    from repro.api.backends import rollout_fn_for
    from repro.core import sac as SAC
    from repro.training import stream_train as ST
    from repro.traffic.stream import (CurriculumTaskSource, StreamConfig,
                                      StreamRunner)

    cells = ST.resolve_cells(ecfg, None, None, args.rate_scale)
    k_src, k_stream = jax.random.split(jax.random.PRNGKey(seed))
    source = CurriculumTaskSource([(p, t) for _, p, t in cells], k_src,
                                  num_streams=args.streams)
    runner = StreamRunner(ecfg, SAC.actor_policy(ecfg, acfg), actor_params,
                          source, k_stream,
                          StreamConfig(num_windows=windows,
                                       num_streams=args.streams),
                          rollout_fn=rollout_fn_for(ExecSpec(backend=backend)))
    rets = [runner.run_window().record["episode_return_mean"]
            for _ in range(windows)]
    s = runner.result().summary
    return {"return_mean": float(np.mean(rets)),
            "violation_rate": s["qos_violation_rate"],
            "drop_rate": s["drop_rate"],
            "goodput_per_s": s["goodput_per_s"]}


def run_backend(backend: str, args):
    import jax

    from repro.api import ExecSpec
    from repro.core import agent as AG
    from repro.core import sac as SAC
    from repro.core.env import EnvConfig
    from repro.training import stream_train as ST

    ecfg = EnvConfig(num_servers=args.servers, max_tasks=args.window_tasks,
                     max_steps=4 * args.window_tasks)
    acfg = AG.AgentConfig(variant=args.variant, T=args.diffusion_steps)
    scfg = SAC.SACConfig(warmup_steps=args.warmup_steps,
                         batch_size=args.batch_size)
    stcfg = ST.StreamTrainConfig(
        rounds=args.rounds, streams=args.streams,
        rate_scale=args.rate_scale,
        max_updates_per_round=args.max_updates_per_round)

    digest = hashlib.sha256()
    counts = {"n": 0}

    def hook(r, flat):
        for a in flat:
            digest.update(a.tobytes())
        counts["n"] += len(flat[2])

    # warm the compiled programs (warmup + actor collection, update step) on
    # a throwaway short run so the timed run measures steady-state
    # windows/s, not compilation. Same scfg: SACConfig is a static jit arg.
    warm = ST.StreamTrainConfig(rounds=3, streams=args.streams,
                                rate_scale=args.rate_scale,
                                max_updates_per_round=1)
    ST.train_stream_sac(ecfg, acfg, scfg, warm, seed=args.seed,
                        exec_spec=ExecSpec(backend=backend))

    # the true round-0 policy: a zero-round run reproduces the trainer's
    # seed derivation exactly and returns the untouched initial actor
    # (capturing inside a callback would see round 0's post-update weights)
    round0_actor = ST.train_stream_sac(
        ecfg, acfg, scfg, ST.StreamTrainConfig(rounds=0, streams=args.streams),
        seed=args.seed, exec_spec=ExecSpec(backend=backend)).state.actor

    t0 = time.perf_counter()
    res = ST.train_stream_sac(ecfg, acfg, scfg, stcfg, seed=args.seed,
                              exec_spec=ExecSpec(backend=backend),
                              transition_hook=hook)
    wall = time.perf_counter() - t0
    first, last = res.history[0], res.history[-1]
    ev0 = eval_policy_stream(ecfg, acfg, round0_actor, backend, args)
    evT = eval_policy_stream(ecfg, acfg, res.state.actor, backend, args)
    rec = {
        "exec_backend": backend,
        "device_count": jax.local_device_count(),
        "wall_s": round(wall, 3),
        "windows": args.rounds,
        "windows_per_s": round(args.rounds / wall, 3),
        "transitions": counts["n"],
        "transitions_per_s": round(counts["n"] / wall, 1),
        "digest_sha256": digest.hexdigest(),
        "round0_return": first["episode_return_mean"],
        "final_return": last["episode_return_mean"],
        "round0_violation_rate": first["qos_violation_rate"],
        "final_violation_rate": last["qos_violation_rate"],
        "tasks_injected": res.stream.summary["tasks_injected"],
        "drop_rate": res.stream.summary["drop_rate"],
        # fresh-stream eval: round-0 actor vs trained actor on the SAME
        # arrival sequence from an empty cluster
        "eval_round0": ev0,
        "eval_trained": evT,
        "improved": bool(evT["return_mean"] > ev0["return_mean"]
                         or evT["violation_rate"] < ev0["violation_rate"]),
    }
    print(f"[{backend:8s}] {rec['windows_per_s']:6.2f} windows/s  "
          f"{rec['transitions_per_s']:8.1f} transitions/s")
    print(f"  eval (fresh stream): R {ev0['return_mean']:.2f} -> "
          f"{evT['return_mean']:.2f}  viol {ev0['violation_rate']:.3f} -> "
          f"{evT['violation_rate']:.3f}  improved={rec['improved']}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=4,
                    help="forced host CPU devices for the sharded mesh")
    ap.add_argument("--servers", type=int, default=4)
    ap.add_argument("--window-tasks", type=int, default=32)
    ap.add_argument("--streams", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=16,
                    help=">= 8 windows per the acceptance criterion")
    ap.add_argument("--rate-scale", type=float, default=2.0,
                    help="sustained overload: offered load / paper rate")
    ap.add_argument("--variant", default="eat-da")
    ap.add_argument("--diffusion-steps", type=int, default=2)
    ap.add_argument("--warmup-steps", type=int, default=256)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--max-updates-per-round", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()
    if args.devices > 1:
        from repro.launch.mesh import force_host_devices
        force_host_devices(args.devices)
    sys.path.insert(0, os.path.dirname(__file__))
    from common import write_bench_json

    recs = {b: run_backend(b, args) for b in ("fused", "sharded")}
    assert recs["fused"]["digest_sha256"] == recs["sharded"]["digest_sha256"], \
        "fused and sharded backends collected different transitions"
    assert recs["fused"]["transitions"] == recs["sharded"]["transitions"]
    print(f"collection bitwise-identical across backends "
          f"({recs['fused']['transitions']} transitions, sha256 "
          f"{recs['fused']['digest_sha256'][:16]}...)")
    payload = {
        "config": {k: v for k, v in vars(args).items() if k != "json_out"},
        "backends": recs,
        "sharded_speedup": round(recs["sharded"]["windows_per_s"]
                                 / recs["fused"]["windows_per_s"], 3),
        "collection_bitwise_identical": True,
        "improved_on_both_backends": bool(recs["fused"]["improved"]
                                          and recs["sharded"]["improved"]),
    }
    write_bench_json("stream_train", payload, out=args.json_out,
                     exec_backend="sharded")


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
