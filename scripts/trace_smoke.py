#!/usr/bin/env python
"""CI trace smoke: traced streaming windows on the fused AND serving
backends must (a) emit schema-valid traces and (b) leave every result
number bitwise-identical to an untraced run.

    PYTHONPATH=src python scripts/trace_smoke.py [--outdir DIR]

Runs a short streaming workload per backend twice — tracing off, then on
(with a metrics snapshot) — and fails loudly on any schema violation or
any summary difference. This is the observability contract `make
trace-smoke` gates in CI.
"""
from __future__ import annotations

import argparse
import re
import sys
import tempfile
import warnings

# host wall-clock measurements (decision-profile percentiles, measured
# executor seconds) differ between ANY two runs, traced or not — the
# bitwise contract covers the *result* numbers (QoS, rewards, ledgers)
_MEASURED = re.compile(
    r"(_latency_(p\d+|mean)_s$|_decisions$|^decision_latency_n$"
    r"|measured_busy|^wall_s$)")


def run_backend(backend: str, outdir: str) -> str:
    import jax
    import numpy as np

    from repro import api
    from repro.core import env as EV
    from repro.core.scenarios import Scenario
    from repro.core.workload import TraceConfig as WTraceConfig
    from repro.telemetry import (TraceConfig, default_registry,
                                 reset_tracers, validate_trace)

    ecfg = EV.EnvConfig(num_servers=4, max_tasks=8)
    cell = Scenario(name="trace-smoke", ecfg=ecfg,
                    tcfg=WTraceConfig(num_tasks=8, arrival_rate=2.0,
                                      max_servers=4))
    streams = 1 if backend == "serving" else 2
    wl = api.WorkloadSpec.streaming(cell, streams=streams, num_windows=2,
                                    window_tasks=8, max_steps_per_window=16)
    extra = ({"serving_archs": ("tinyllama-1.1b",),
              "serving_prompt_len": 8, "serving_max_new_tokens": 8}
             if backend == "serving" else {})

    def run(trace_cfg):
        reset_tracers()
        default_registry().clear()
        sim = api.Simulator(wl, api.ExecSpec(backend=backend,
                                             trace=trace_cfg, **extra))
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", api.UntrainedPolicyWarning)
            return sim.run("fifo", jax.random.PRNGKey(0))

    r_off = run(TraceConfig())
    path = f"{outdir}/trace_{backend}.json"
    r_on = run(TraceConfig(enabled=True, path=path,
                           metrics_path=f"{outdir}/metrics_{backend}.prom"))

    errors = validate_trace(path, strict_names=True)
    errors += validate_trace(path + ".jsonl", strict_names=True)
    if errors:
        return f"[{backend}] schema violations:\n  " + "\n  ".join(errors)

    if set(r_off.summary) != set(r_on.summary):
        return (f"[{backend}] summary keys differ: "
                f"{set(r_off.summary) ^ set(r_on.summary)}")
    n_cmp = 0
    for k, v in r_off.summary.items():
        if _MEASURED.search(k):
            continue
        w = r_on.summary[k]
        same = (v == w) or (isinstance(v, float) and isinstance(w, float)
                            and np.isnan(v) and np.isnan(w))
        if not same:
            return (f"[{backend}] summary[{k!r}] differs with tracing on: "
                    f"{v!r} vs {w!r}")
        n_cmp += 1
    n_spans = sum(1 for line in open(path + ".jsonl"))
    print(f"[{backend}] OK: {n_spans} events, summaries bitwise-identical "
          f"on vs off ({n_cmp} result keys compared)")
    return ""


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--outdir", default=None,
                    help="where trace/metrics files land (default: tmp)")
    args = ap.parse_args(argv)
    outdir = args.outdir or tempfile.mkdtemp(prefix="trace_smoke_")

    failures = [msg for backend in ("fused", "serving")
                for msg in [run_backend(backend, outdir)] if msg]
    for msg in failures:
        print(msg, file=sys.stderr)
    if not failures:
        print(f"trace smoke PASSED (files in {outdir})")
    return 1 if failures else 0


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
