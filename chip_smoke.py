#!/usr/bin/env python3
"""Chip smoke: the EAT scheduling path, end to end, on a TPU.

    python3 chip_smoke.py              # one chip: device, simulate, serve
    python3 chip_smoke.py --chips 4    # only the sharded phase, four chips

Run it from the root of a checkout: it imports `src/repro`. One process
holds the chip(s) and starts no children. The phases, in order:

1. device   - JAX must report a TPU; there is no CPU fallback.
2. simulate - `repro.api.Simulator` on the fused backend over the paper's
   default cluster (E=8 servers, K=32 tasks per window, l=8 visible slots)
   with B=256 streams, so the compiled Pallas env-step and denoiser
   kernels run. On the chip it checks the env-step kernel against its jnp
   reference on the same randomized batch states (with and without fault
   columns), the chain and one-step denoiser kernels against their
   references at the highest and at the ambient matmul precision, and the
   stream ledger of every run; it prints each
   policy's summary with the Pallas env step and with the jnp reference
   side by side.
3. serve    - `ExecSpec(backend="serving")` at full width: qwen2-1.5b in
   bfloat16 on E=4 servers with wall-clock latencies, greedy then eat.
   Every scheduled task must be executed. Before the pool fills, the
   serving executor's bfloat16 greedy decode is compared with float32 at
   the highest matmul precision from the same parameters, and E copies of
   the weights are held at once, the pool's worst case. Peak HBM is printed
   against the device limit.

`--chips 4` runs only the sharded phase: `ExecSpec(backend="sharded",
mesh_devices=4)` against a one-chip fused run on the same traces and keys,
with B divisible by 4 so the mesh cannot shrink.

A failed check raises and the exit code is not 0. Only when every phase
passed is the last line of stdout one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
The compile cache follows `repro.common.compile_cache`.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import warnings
from importlib import metadata
from pathlib import Path

SEED = 0
STREAMS = 256                # simulate: batch of streams (divisible by 4)
SIM_WINDOWS = 3
SERVE_ARCH = "qwen2-1.5b"
SERVE_WINDOWS, SERVE_WINDOW_TASKS = 2, 8        # 16 tasks injected
# env step, Pallas vs jnp reference, same inputs: integer and mask outputs
# must be equal; float outputs within |d| <= ENV_ATOL + ENV_RTOL * |ref|
ENV_RTOL, ENV_ATOL = 1e-4, 1e-4
# denoiser kernels vs their references, same inputs, both sides at the same
# matmul precision. At "highest" (float32 products) the kernels' arithmetic
# is checked: outputs are tanh-bounded, |d| <= DENOISER_ATOL. At the ambient
# precision, the one the actor runs at (a TPU's default multiplies float32
# in one bfloat16 pass), the chain amplifies rounding: a CPU emulation of
# one-pass bfloat16 against float32 moves the chain's output by up to 0.108
# (3 seeds, B=256), so |d| <= DENOISER_AMBIENT_ATOL
DENOISER_ATOL = 1e-4
DENOISER_AMBIENT_ATOL = 0.25
# served greedy decode: GEN_STEPS tokens from the serving executor at
# bfloat16. The model is then run teacher-forced along the served tokens in
# float32 at the highest precision and in bfloat16, same parameters. Each
# served token's float32 logit lies within TOKEN_GAP times that step's rms
# bfloat16-vs-float32 logit error of the float32 maximum: only a swap of
# near-tied logits is bfloat16 rounding. The logits themselves agree to
# ||bf16 - f32|| / ||f32|| <= LOGITS_REL_L2 at every step
GEN_STEPS = 4
TOKEN_GAP = 8.0
LOGITS_REL_L2 = 0.08


class SmokeFailure(RuntimeError):
    pass


def check(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def say(*a) -> None:
    print(*a, flush=True)


def _import_repo():
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        raise SmokeFailure(f"no repro package under {src}: run chip_smoke.py "
                           "from the root of a checkout")
    sys.path.insert(0, str(src))
    from repro.common.compile_cache import enable_compile_cache
    say(f"[setup] compile cache: {enable_compile_cache()}")


# ----------------------------------------------------------------- device
def device_phase(want_count: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    say(f"[device] platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)} jax={jax.__version__} libtpu={libtpu}")
    check(d.platform == "tpu",
          f"JAX found no TPU (platform {d.platform!r}); this smoke has no "
          "CPU fallback")
    check(len(devs) >= want_count,
          f"need {want_count} chips, JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# --------------------------------------------------------------- simulate
def _compare(name, got, want):
    """Integer/bool leaves equal; float leaves within the env tolerance.
    Returns the largest float difference."""
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape, f"{name}: shape {got.shape} vs "
          f"{want.shape}")
    if not np.issubdtype(want.dtype, np.floating):
        bad = int(np.sum(got != want))
        check(bad == 0, f"{name}: {bad} of {want.size} entries differ")
        return 0.0
    d = np.abs(got.astype(np.float64) - want.astype(np.float64))
    lim = ENV_ATOL + ENV_RTOL * np.abs(want.astype(np.float64))
    check(bool(np.all(d <= lim)),
          f"{name}: |pallas - ref| up to {d.max()!r} exceeds "
          f"{ENV_ATOL} + {ENV_RTOL}*|ref|")
    return float(d.max())


def env_step_check(sc, B: int, steps: int = 8) -> None:
    """Pallas vs jnp env step on the same randomized batch states: a random
    action from every state along a ref-driven trajectory."""
    import jax
    import numpy as np
    from repro.core import env as EV
    from repro.core import scenarios as SC
    from repro.faults import FaultSpec, FaultTimeline, fault_horizon
    from repro.kernels.env_step import ops as EK

    check(EK.resolve_impl("auto") == "pallas",
          "the fused env step does not select its Pallas kernel here")
    ecfg = sc.ecfg
    key = jax.random.PRNGKey(SEED + 1)
    traces = SC.make_scenario_trace_batch(key, sc, B)
    spec = FaultSpec(seed=SEED, mtbf=60.0, mttr=15.0, straggler_prob=0.3,
                     straggler_factor=3.0)
    faulty = dict(traces)
    faulty.update(FaultTimeline(spec, ecfg.num_servers, B).window_arrays(
        0, np.zeros(B), fault_horizon(ecfg.time_limit, spec)))
    step = jax.jit(lambda st, sx, a, q, impl: EK.env_step_fused(
        ecfg, sx, st, a, q, impl=impl), static_argnames="impl")
    names = (tuple(f"state.{f}" for f in EV.EnvState._fields)
             + ("queue.idx", "queue.valid", "queue.queued", "obs", "reward",
                "done"))
    for label, tr in (("fault-free", traces), ("faults", faulty)):
        statics = jax.vmap(lambda t: EV.decision_statics(ecfg, t))(tr)
        state = jax.tree_util.tree_map(
            lambda x: jax.numpy.broadcast_to(x, (B,) + x.shape),
            EV.reset(ecfg))
        q, _ = jax.vmap(lambda t, s: EV.reset_view(ecfg, t, s))(tr, state)
        worst = 0.0
        for i in range(steps):
            a = jax.random.uniform(jax.random.fold_in(key, i),
                                   (B, ecfg.action_dim))
            ref = step(state, statics, a, q, impl="ref")
            got = step(state, statics, a, q, impl="pallas")
            leaves_r = jax.tree_util.tree_leaves(ref)
            leaves_p = jax.tree_util.tree_leaves(got)
            check(len(leaves_r) == len(names) == len(leaves_p),
                  "env step output structure changed")
            for n, g, w in zip(names, leaves_p, leaves_r):
                worst = max(worst, _compare(f"env_step[{label}] {n}", g, w))
            state, q = ref[0], ref[1]
        say(f"[simulate] env_step pallas vs ref ({label}, B={B}, {steps} "
            f"decisions): ints equal, max float |d|={worst!r} "
            f"(limit {ENV_ATOL} + {ENV_RTOL}*|ref|)")


def denoiser_check(ecfg, B: int) -> None:
    """Chain kernel (ddpm, ddim:5) and one-step kernel vs their references
    on the same inputs, at the highest and at the ambient matmul
    precision."""
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.actors import samplers as SMP
    from repro.core import agent as AG
    from repro.core import diffusion as DF
    from repro.kernels.denoiser import ops as KOPS

    check(KOPS.resolve_impl("auto") == "pallas",
          "the denoiser chain does not select its Pallas kernel here")
    acfg = AG.AgentConfig()
    params = AG.init_actor(jax.random.PRNGKey(SEED), ecfg, acfg)["denoiser"]
    sched = DF.vp_schedule(acfg.T)
    A, Fd = ecfg.action_dim, ecfg.obs_shape[1]
    ks = jax.random.split(jax.random.PRNGKey(SEED + 2), 4)
    f_s = jax.random.normal(ks[0], (B, Fd))
    x = jax.random.normal(ks[1], (B, A))
    i = jnp.full((B,), acfg.T)
    cases = {"denoiser_step": (lambda: jax.jit(KOPS.denoise_eps_fused)(
        params, x, i, f_s), lambda: jax.jit(DF.denoise_eps)(
        params, x, i, f_s))}
    for name, coefs in (("ddpm", SMP.ddpm_coeffs(sched)),
                        ("ddim:5", SMP.ddim_coeffs(sched, 5))):
        cx, ce, cn, t_in = coefs
        noises = jax.random.normal(ks[2], (int(t_in.shape[0]), B, A))
        tembs = DF.timestep_embedding(t_in, 16)
        args = (params, x, noises, f_s, tembs, cx, ce, cn)
        cases[f"denoiser_chain {name} (K={int(t_in.shape[0])})"] = tuple(
            (lambda impl=impl, args=args: jax.jit(
                lambda *a: KOPS.denoise_chain(*a, impl=impl))(*args))
            for impl in ("pallas", "ref"))
    for prec, limit in (("highest", DENOISER_ATOL),
                        ("ambient", DENOISER_AMBIENT_ATOL)):
        for name, (kernel, ref) in cases.items():
            with (jax.default_matmul_precision("highest")
                  if prec == "highest" else contextlib.nullcontext()):
                got, want = np.asarray(kernel()), np.asarray(ref())
            d = float(np.max(np.abs(got - want)))
            check(np.all(np.isfinite(got)), f"{name}: non-finite output")
            check(d <= limit, f"{name} at {prec} precision: |pallas - ref| "
                  f"= {d!r} > {limit}")
            say(f"[simulate] {name} B={B} pallas vs ref at {prec} matmul "
                f"precision: max |d|={d!r} (limit {limit})")


def _ledger(label: str, s) -> None:
    lhs = s["tasks_injected"]
    rhs = (s["tasks_scheduled"] + s["tasks_dropped"] + s["tasks_leftover"]
           + s["tasks_failed_pending_retry"])
    check(lhs == rhs, f"{label}: ledger broken, injected {lhs} != scheduled "
          f"+ dropped + leftover + pending retry = {rhs}")


SIDE_BY_SIDE = ("tasks_injected", "tasks_scheduled", "tasks_dropped",
                "tasks_leftover", "avg_steps", "reuse_rate", "latency_p50",
                "latency_p99", "qos_violation_rate", "utilization")


def simulate_phase(streams: int = STREAMS, windows: int = SIM_WINDOWS):
    import jax
    from repro import api
    from repro.core import scenarios as SC

    sc = SC.paper_scenarios()[1]
    e = sc.ecfg
    check((e.num_servers, e.max_tasks, e.queue_window) == (8, 32, 8),
          f"paper default cluster changed: {e}")
    env_step_check(sc, streams)
    denoiser_check(e, streams)
    wl = api.WorkloadSpec.streaming(sc, streams=streams, num_windows=windows)
    key = jax.random.PRNGKey(SEED)
    specs = {"pallas": api.ExecSpec(backend="fused"),
             "ref": api.ExecSpec(backend="fused", fused_impl="ref")}
    policies = [api.PolicySpec("eat", sampler=s)
                for s in ("ddpm", "ddim:5", "distilled")]
    policies.append(api.PolicySpec("greedy"))
    for p in policies:
        label = p.name + (f"[{p.sampler}]" if p.sampler else "")
        runs = {}
        for impl, spec in specs.items():
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", api.UntrainedPolicyWarning)
                t0 = time.perf_counter()
                runs[impl] = api.Simulator(wl, spec).run(p, key)
                first = time.perf_counter() - t0
            _ledger(f"{label} env_step={impl}", runs[impl].summary)
            say(f"[simulate] {label} env_step={impl}: {windows} windows x "
                f"{streams} streams in {first:.2f}s (compile included), "
                "ledger conserved")
        say(f"[simulate] {label}: {'key':28s} {'pallas':>14s} {'ref':>14s}")
        for k in SIDE_BY_SIDE:
            a, b = runs["pallas"].summary[k], runs["ref"].summary[k]
            say(f"[simulate] {label}:   {k:26s} {a!r:>14} {b!r:>14}")


# ------------------------------------------------------------------ serve
def _hbm(label: str) -> None:
    import jax
    st = jax.devices()[0].memory_stats() or {}
    peak, limit = st.get("peak_bytes_in_use"), st.get("bytes_limit")
    check(peak is not None and limit, "the device reports no memory stats")
    say(f"[serve] HBM {label}: peak_bytes_in_use={peak} "
        f"({peak / 2**30:.2f} GiB) of bytes_limit={limit} "
        f"({limit / 2**30:.2f} GiB), in use now {st.get('bytes_in_use')}")
    check(peak < limit, "peak HBM reached the device limit")


def generate_check(ex, arch: str) -> None:
    """The serving executor's greedy decode (its jitted bfloat16 prefill,
    chunk-cache merge, argmax and decode programs) against float32 at the
    highest matmul precision from the same parameters, before the serving
    pool exists."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    model = ex.model(arch)
    params = ex.init_params(arch, jax.random.PRNGKey(SEED))
    dtypes = {x.dtype for x in jax.tree_util.tree_leaves(params)}
    check(dtypes == {jnp.dtype(jnp.bfloat16)},
          f"full-width weights are {dtypes}, not bfloat16")
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    V = model.cfg.vocab_size
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(SEED + 3),
                                           (8,), 0, V), np.int32)
    served = ex.generate(arch, params, prompt, c=1, steps=GEN_STEPS,
                         max_new_tokens=GEN_STEPS)
    check(served.shape == (GEN_STEPS,), f"served {served.shape} tokens")

    cap = len(prompt) + GEN_STEPS

    def forced(p, dtype):
        """(GEN_STEPS, V) next-token logits along the served tokens."""
        prefill = jax.jit(lambda p, t: model.prefill(
            p, {"tokens": t}, model.make_cache(1, cap, dtype=dtype),
            compute_dtype=dtype))
        decode = jax.jit(lambda p, c, t: model.decode(p, c, t,
                                                      compute_dtype=dtype))
        logits, cache = prefill(p, jnp.asarray(prompt[None]))
        out = [logits[0, -1, :V]]
        for tok in served[:-1]:
            logits, cache = decode(p, cache, jnp.full((1, 1), tok, jnp.int32))
            out.append(logits[0, -1, :V])
        return np.asarray(jnp.stack(out), np.float64)

    low = forced(params, jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        p32 = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), params)
        ref = forced(p32, jnp.float32)
    del p32
    check(np.all(np.isfinite(low)) and np.all(np.isfinite(ref)),
          "teacher-forced logits are not finite")
    err = np.sqrt(np.mean((low - ref) ** 2, axis=1))
    rel = np.linalg.norm(low - ref, axis=1) / np.linalg.norm(ref, axis=1)
    gaps = ref.max(axis=1) - ref[np.arange(GEN_STEPS), served]
    same = int(np.sum(ref.argmax(axis=1) == served))
    say(f"[serve] {arch}: {n} parameters in bfloat16; served greedy tokens "
        f"{served.tolist()}, {same}/{GEN_STEPS} equal to the float32 argmax")
    say(f"[serve] {arch}: per step, f32 max - f32[served] {gaps.tolist()} "
        f"vs {TOKEN_GAP} x rms bf16-f32 logit error {err.tolist()}; "
        f"bf16 vs f32 logits rel L2 {rel.tolist()} (limit {LOGITS_REL_L2})")
    check(np.all(gaps <= TOKEN_GAP * err), "a served token is not a float32 "
          "greedy choice up to bfloat16 rounding")
    check(np.all(rel <= LOGITS_REL_L2), "bfloat16 logits stray from float32")


def serve_phase(arch: str = SERVE_ARCH):
    import jax
    from repro import api
    from repro.core import scenarios as SC
    from repro.serving.executor import ModelExecutor

    sc = SC.paper_scenarios()[0]
    E = sc.ecfg.num_servers
    check(E == 4, f"expected E=4, got {sc.ecfg}")
    ex = ModelExecutor(reduced=False)
    generate_check(ex, arch)
    _hbm("after the generate check")
    # the pool's worst case: every server holds its own copy of the weights
    pool = [ex.init_params(arch, jax.random.PRNGKey(i)) for i in range(E)]
    jax.block_until_ready(pool)
    _hbm(f"holding {E} copies of {arch}, the pool's worst case")
    del pool
    gc.collect()
    wl = api.WorkloadSpec.streaming(sc, streams=1, num_windows=SERVE_WINDOWS,
                                    window_tasks=SERVE_WINDOW_TASKS)
    spec = api.ExecSpec(backend="serving", serving_reduced=False,
                        serving_archs=(arch,), serving_wall_clock=True)
    for name in ("greedy", "eat"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", api.UntrainedPolicyWarning)
            res = api.Simulator(wl, spec).run(name, jax.random.PRNGKey(SEED))
        s = res.summary
        gc.collect()        # the run's pool and its weights are gone now
        _ledger(f"serve {name}", s)
        check(s["tasks_scheduled"] > 0, f"serve {name}: nothing scheduled")
        check(s["tasks_executed"] == s["tasks_scheduled"],
              f"serve {name}: executed {s['tasks_executed']} of "
              f"{s['tasks_scheduled']} scheduled tasks")
        say(f"[serve] {name}: injected={s['tasks_injected']} "
            f"scheduled={s['tasks_scheduled']} executed="
            f"{s['tasks_executed']} loads={s['model_loads']} "
            f"reuses={s['model_reuses']} measured_busy_mean_s="
            f"{s.get('measured_busy_mean_s')!r} latency_p50="
            f"{s['latency_p50']!r} wall_s={res.wall_s!r}")
        _hbm(f"after {name}")


# ---------------------------------------------------------------- sharded
def sharded_phase(streams: int = STREAMS, windows: int = 2):
    import jax
    import numpy as np
    from repro import api
    from repro.core import scenarios as SC

    check(streams % 4 == 0, "B must divide over 4 chips")
    sc = SC.paper_scenarios()[1]
    ecfg = sc.ecfg
    key = jax.random.PRNGKey(SEED)
    traces = SC.make_scenario_trace_batch(key, sc, streams)
    keys = jax.random.split(jax.random.fold_in(key, 1), streams)
    fused_fn = api.rollout_fn_for(api.ExecSpec(backend="fused"))
    sharded_fn = api.rollout_fn_for(api.ExecSpec(backend="sharded",
                                                 mesh_devices=4))
    check(api.resolve_shards(streams, api.ExecSpec(
        backend="sharded", mesh_devices=4)) == 4, "the mesh would shrink")
    for name in ("greedy", "eat"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", api.UntrainedPolicyWarning)
            rp = api.resolve(api.PolicySpec(name), ecfg)
        one = fused_fn(ecfg, traces, rp.policy, rp.params, keys)
        four = sharded_fn(ecfg, traces, rp.policy, rp.params, keys)
        ret = four.metrics["episode_return"]
        shards = sorted((s.device.id, s.data.shape)
                        for s in ret.addressable_shards)
        check(len(ret.sharding.device_set) == 4,
              f"sharded output spans {len(ret.sharding.device_set)} devices")
        say(f"[sharded] {name}: episodic B={streams}, output shards "
            f"(device, shape): {shards}")
        for k in sorted(one.metrics):
            a, b = np.asarray(one.metrics[k]), np.asarray(four.metrics[k])
            check(np.array_equal(a, b), f"[sharded] {name} {k}: fused and "
                  f"sharded differ (max |d| {np.max(np.abs(a - b))!r})")
        say(f"[sharded] {name}: all {len(one.metrics)} episode metrics "
            "bitwise equal, fused (1 chip) vs sharded (4 chips)")
    wl = api.WorkloadSpec.streaming(sc, streams=streams, num_windows=windows)
    for name in ("greedy", "eat"):
        out = {}
        for backend, spec in (("fused", api.ExecSpec(backend="fused")),
                              ("sharded", api.ExecSpec(backend="sharded",
                                                       mesh_devices=4))):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", api.UntrainedPolicyWarning)
                out[backend] = api.Simulator(wl, spec).run(name, key).summary
            _ledger(f"sharded {name} {backend}", out[backend])
        diff = {k for k, v in out["fused"].items()
                if v != out["sharded"][k] and v == v}     # NaN == NaN here
        check(not diff, f"[sharded] {name} stream summaries differ in "
              f"{sorted(diff)}")
        say(f"[sharded] {name}: stream summary ({windows} windows x "
            f"{streams} streams) equal on every key, e.g. scheduled="
            f"{out['sharded']['tasks_scheduled']} latency_p99="
            f"{out['sharded']['latency_p99']!r}")


# ------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase on a 4-chip mesh")
    args = ap.parse_args(argv)
    _import_repo()
    device = device_phase(args.chips)
    phases = ([("sharded", sharded_phase)] if args.chips == 4
              else [("simulate", simulate_phase), ("serve", serve_phase)])
    for name, fn in phases:
        t0 = time.perf_counter()
        fn()
        say(f"[{name}] passed in {time.perf_counter() - t0:.1f}s")
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
