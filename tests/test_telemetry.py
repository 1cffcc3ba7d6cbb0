"""Telemetry subsystem: tracing, metrics registry, decision profiling.

The contract under test: (1) emitted traces validate against the
machine-readable schema and nest deterministically for a fixed seed;
(2) tracing is observability, not physics — every number a run produces is
bitwise-identical with tracing on vs off; (3) the Prometheus exposition
round-trips; (4) `LatencyHistogram.percentile` boundary semantics
(underflow slot, q=0, overflow clamp); (5) executor warmup moves XLA
compilation out of the serving backend's timed region.
"""
import json
import time

import jax
import numpy as np
import pytest

from repro import api
from repro.core import env as EV
from repro.core.scenarios import Scenario
from repro.core.workload import TraceConfig as WorkloadTraceConfig
from repro.telemetry import (DECISION_EDGES, NULL_TRACER, DecisionProfile,
                             LatencyHistogram, MetricsRegistry, TraceConfig,
                             default_registry, parse_prometheus,
                             profile_policy, reset_tracers, span_durations,
                             tracer_for, validate_trace)
from repro.telemetry.schema import KNOWN_SPANS, validate_events

ECFG = EV.EnvConfig(num_servers=4, max_tasks=8)
TCFG = WorkloadTraceConfig(num_tasks=8, arrival_rate=2.0, max_servers=4)
CELL = Scenario(name="telemetry-cell", ecfg=ECFG, tcfg=TCFG)


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    reset_tracers()
    default_registry().clear()
    yield
    reset_tracers()
    default_registry().clear()


def _wl(streams=2, windows=2):
    return api.WorkloadSpec.streaming(CELL, streams=streams,
                                      num_windows=windows, window_tasks=8,
                                      max_steps_per_window=16)


def _run(spec, policy="fifo", key=0):
    sim = api.Simulator(_wl(), spec)
    return sim.run(policy, jax.random.PRNGKey(key))


# ------------------------------------------------------------ tracing
def test_trace_validates_against_schema(tmp_path):
    path = str(tmp_path / "trace.json")
    spec = api.ExecSpec(trace=TraceConfig(enabled=True, path=path))
    _run(spec)
    assert validate_trace(path, strict_names=True) == []
    assert validate_trace(path + ".jsonl", strict_names=True) == []
    doc = json.load(open(path))
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"run", "window", "build_window", "window_rollout",
            "window_seam"} <= names
    assert names - {"backlog"} <= set(KNOWN_SPANS)


def test_span_nesting_deterministic_for_fixed_seed(tmp_path):
    seqs = []
    for tag in ("a", "b"):
        reset_tracers()
        path = str(tmp_path / f"trace_{tag}.json")
        spec = api.ExecSpec(trace=TraceConfig(enabled=True, path=path))
        _run(spec, key=7)
        doc = json.load(open(path))
        seqs.append([(e["name"], e["args"].get("depth"))
                     for e in doc["traceEvents"] if e["ph"] == "X"])
    assert seqs[0] == seqs[1]
    # spans nest: every window-phase span sits under its window span
    depths = {n: d for n, d in seqs[0]}
    assert depths["window"] > depths["run"]
    assert depths["build_window"] > depths["window"]


def test_tracing_is_bitwise_invisible(tmp_path):
    """Summaries (and therefore every downstream number) are identical
    with tracing enabled vs disabled — observability cannot perturb."""
    r_off = _run(api.ExecSpec(), key=3)
    reset_tracers()
    default_registry().clear()
    path = str(tmp_path / "trace.json")
    r_on = _run(api.ExecSpec(trace=TraceConfig(enabled=True, path=path)),
                key=3)
    assert set(r_off.summary) == set(r_on.summary)
    for k, v in r_off.summary.items():
        if isinstance(v, float):
            np.testing.assert_equal(v, r_on.summary[k], err_msg=k)
        else:
            assert v == r_on.summary[k], k


def test_one_tracer_per_config(tmp_path):
    cfg = TraceConfig(enabled=True, path=str(tmp_path / "t.json"))
    assert tracer_for(cfg) is tracer_for(cfg)
    assert tracer_for(TraceConfig()) is NULL_TRACER
    assert tracer_for(None) is NULL_TRACER


def test_span_durations_and_counters(tmp_path):
    cfg = TraceConfig(enabled=True, path=str(tmp_path / "t.json"))
    tr = tracer_for(cfg)
    with tr.span("outer", cat="phase"):
        with tr.span("inner", cat="phase"):
            time.sleep(0.002)
        tr.counter("backlog", 3.0)
    tr.write()
    assert validate_events(json.load(open(cfg.path))) == []
    d = span_durations(json.load(open(cfg.path))["traceEvents"])
    assert d["outer"]["count"] == d["inner"]["count"] == 1
    assert d["outer"]["total_s"] >= d["inner"]["total_s"]
    # self time excludes the contained child span
    assert d["outer"]["self_total_s"] <= d["outer"]["total_s"]


# ------------------------------------------------------------ metrics
def test_metrics_registry_prometheus_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("eat_test_events_total").inc(3, labels={"cell": "a"})
    reg.gauge("eat_test_backlog").set(7.5)
    h = reg.histogram("eat_test_latency_seconds", edges=DECISION_EDGES)
    for v in (1e-5, 3e-4, 0.02, 0.02, 5.0, 1e3):
        h.observe(v)
    text = reg.to_prometheus()
    parsed = parse_prometheus(text)
    flat = {}
    for rec in reg.snapshot().values():
        flat.update(rec["samples"])
    assert parsed == flat
    # bucket convention: cumulative, +Inf equals count
    assert parsed['eat_test_latency_seconds_bucket{le="+Inf"}'] == 6.0
    assert parsed["eat_test_latency_seconds_count"] == 6.0
    assert parsed["eat_test_latency_seconds_sum"] == pytest.approx(1005.04031)


def test_metrics_registry_kind_conflict():
    reg = MetricsRegistry()
    reg.counter("eat_x")
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("eat_x")


def test_run_publishes_into_default_registry():
    _run(api.ExecSpec())
    snap = default_registry().snapshot()
    assert "eat_stream_latency_p99" in snap
    assert "eat_stream_latency_seconds" in snap
    sample = next(iter(snap["eat_stream_latency_p99"]["samples"]))
    assert 'policy="fifo"' in sample and 'backend="fused"' in sample


def test_metrics_identical_tracing_on_vs_off(tmp_path):
    _run(api.ExecSpec(), key=5)
    off = default_registry().snapshot()
    reset_tracers()
    default_registry().clear()
    spec = api.ExecSpec(trace=TraceConfig(
        enabled=True, path=str(tmp_path / "t.json"),
        metrics_path=str(tmp_path / "metrics.prom")))
    _run(spec, key=5)
    on = default_registry().snapshot()
    assert off == on
    # and the exported file parses back to the same samples
    parsed = parse_prometheus(open(str(tmp_path / "metrics.prom")).read())
    flat = {}
    for rec in on.values():
        flat.update(rec["samples"])
    assert parsed == flat


# ------------------------------------------------------------ percentiles
def test_percentile_underflow_slot_interpolates_from_zero():
    h = LatencyHistogram(np.asarray([1.0, 2.0, 4.0]))
    h.add_values([0.5, 0.5])          # both in the underflow slot (-inf, 1]
    assert 0.0 < h.percentile(0.5) <= 1.0
    assert h.percentile(1.0) == 1.0   # upper edge of the underflow slot


def test_percentile_q0_resolves_first_occupied_slot():
    h = LatencyHistogram(np.asarray([1.0, 2.0, 4.0]))
    h.add_values([3.0, 3.5])          # slot (2, 4] only
    assert h.percentile(0.0) == 2.0   # lower edge of the occupied slot
    h2 = LatencyHistogram(np.asarray([1.0, 2.0, 4.0]))
    h2.add_values([0.2])
    assert h2.percentile(0.0) == 0.0  # underflow slot: lower bound 0


def test_percentile_boundary_values_land_in_closed_upper_slot():
    h = LatencyHistogram(np.asarray([1.0, 2.0, 4.0]))
    h.add_values([1.0, 2.0, 4.0])     # exactly on the edges: slots 0,1,2
    assert np.array_equal(h.counts, [1, 1, 1, 0])
    assert h.percentile(1.0) == 4.0


def test_percentile_overflow_clamps_to_top_edge():
    h = LatencyHistogram(np.asarray([1.0, 2.0, 4.0]))
    h.add_values([100.0, 200.0])
    assert h.percentile(0.5) == 4.0
    assert h.percentile(1.0) == 4.0


def test_percentile_empty_is_nan():
    assert np.isnan(LatencyHistogram().percentile(0.5))


# ------------------------------------------------------------ profiling
def test_profile_policy_reports_percentiles():
    out = profile_policy(ECFG, *_fifo(), jax.random.PRNGKey(0), iters=5)
    assert out["decision_latency_n"] == 5.0
    assert 0 < out["decision_latency_p50_s"] <= out["decision_latency_p99_s"]


def _fifo():
    rp = api.registry.resolve("fifo", ECFG)
    return rp.policy, rp.params


def test_decision_profile_summary_keys():
    p = DecisionProfile()
    for _ in range(4):
        p.observe("policy", 1e-3)
        p.observe("env_advance", 2e-3)
    s = p.summary()
    assert s["policy_decisions"] == 4.0
    assert s["decision_latency_p50_s"] == s["policy_latency_p50_s"]
    assert "executor_latency_p50_s" not in s   # no executor observations


def test_simulator_profile_decisions_knob(tmp_path):
    spec = api.ExecSpec(trace=TraceConfig(
        enabled=True, path=str(tmp_path / "t.json"),
        profile_decisions=True, profile_iters=4))
    res = _run(spec)
    assert res.summary["decision_latency_n"] == 4.0
    assert "decision_latency_p99_s" in res.row()


# ------------------------------------------------------------ warmup
def test_executor_warmup_memoizes_shape_buckets():
    from repro.serving.executor import ModelExecutor
    ex = ModelExecutor(reduced=True)
    assert ex.warm("tinyllama-1.1b", 8, 1, 4, 8) is True
    assert ex.warm("tinyllama-1.1b", 8, 1, 4, 8) is False
    # same capacity bucket (steps/max_new_tokens round to the same cache)
    assert ex.shape_key("tinyllama-1.1b", 8, 1, 4, 8) == \
        ex.shape_key("tinyllama-1.1b", 8, 1, 6, 8)
    assert ex.warm("tinyllama-1.1b", 8, 1, 6, 8) is False


@pytest.mark.parametrize("arch,c", [("tinyllama-1.1b", 1),
                                    ("tinyllama-1.1b", 3),
                                    ("xlstm-125m", 2)])
def test_executor_warmup_compiles_from_shapes(arch, c):
    """`warm` compiles every program the matching `generate` runs (chunked
    and unchunked prefill, decode, weight init) from shapes alone: it
    leaves no array on the device, and the first generate compiles
    nothing."""
    from repro.serving.executor import ModelExecutor
    ex = ModelExecutor(reduced=True)
    ex.model(arch)
    live = {id(a) for a in jax.live_arrays()}
    assert ex.warm(arch, 8, c, 6, 8) is True
    assert not [a for a in jax.live_arrays() if id(a) not in live]
    compiles = []

    def listen(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        params = ex.init_params(arch, jax.random.PRNGKey(0))
        toks = ex.generate(arch, params, np.arange(8, dtype=np.int32), c, 6,
                           8)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert len(toks) == 6
    assert compiles == []


def test_executor_warmup_removes_first_task_compile_cost():
    """After `warm`, the first timed generate is steady-state work, not an
    XLA compile: it must be far cheaper than a cold executor's first call
    and comparable to its own steady state."""
    from repro.serving.executor import ModelExecutor
    arch, prompt = "tinyllama-1.1b", np.arange(8, dtype=np.int32)

    cold = ModelExecutor(reduced=True)
    params = cold.init_params(arch, jax.random.PRNGKey(0))
    t0 = time.perf_counter()
    cold.generate(arch, params, prompt, 1, 4, 8)
    t_cold = time.perf_counter() - t0

    warm = ModelExecutor(reduced=True)
    warm.warm(arch, 8, 1, 4, 8)
    t0 = time.perf_counter()
    warm.generate(arch, params, prompt, 1, 4, 8)
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm.generate(arch, params, prompt, 1, 4, 8)
    t_steady = time.perf_counter() - t0

    assert t_first < t_cold / 5, (t_first, t_cold)
    assert t_first < max(20 * t_steady, 0.05), (t_first, t_steady)


def test_serving_warmup_defaults_follow_wall_clock():
    from repro.serving.backend import serving_rollout
    on = serving_rollout(api.ExecSpec(backend="serving",
                                      serving_wall_clock=True))
    off = serving_rollout(api.ExecSpec(backend="serving"))
    forced = serving_rollout(api.ExecSpec(backend="serving",
                                          serving_warmup=True))
    assert on._ensure(4).warmup is True
    assert off._ensure(4).warmup is False
    assert forced._ensure(4).warmup is True


def test_serving_mirror_run_reports_decision_profile():
    wl = api.WorkloadSpec.streaming(CELL, streams=1, num_windows=1,
                                    window_tasks=8, max_steps_per_window=12)
    sim = api.Simulator(wl, api.ExecSpec(backend="serving",
                                         serving_execute=False))
    res = sim.run("fifo", jax.random.PRNGKey(0))
    assert res.summary["policy_decisions"] > 0
    assert res.summary["decision_latency_p50_s"] > 0
    snap = default_registry().snapshot()
    assert "eat_serving_model_loads_total" in snap
