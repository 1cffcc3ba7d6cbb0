"""Idle time attributed to the program's spans, op time to named scopes,
and the readers of the metrics that need them."""
import json
import shutil
from pathlib import Path

import pytest

from chipbench import devtrace, harness, spantrace
from chipbench.devtrace import Event
from chipbench.harness import Outcome
from chipbench.spantrace import Span

DATA = Path(__file__).parent / "data"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def op(name, s, e, plane=DEV):
    return Event(plane, devtrace.OPS_LINE, name, s * 1e6, e * 1e6)


def mark(name, s, e):
    return Event(HOST, "python", devtrace.MARK + name, s * 1e6, e * 1e6)


def span(name, s, e, **args):
    return Span(spantrace.PROGRAM + name, s * 1e6, e * 1e6, args)


def window_trace():
    """100 ms: a benchmark `window` mark around the program's window (1-92)
    with its build (1-10), rollout (10-60, ops 12-40 and 45-58) and seam
    (60-90), then its record (92-94); device work outside the spans at
    95-98."""
    events = [mark("profiled", 0, 100), mark("window", 0, 100),
              op("while.1", 12, 40), op("fusion.1", 12, 20),
              op("env_step_pallas.7", 20, 40), op("fusion.1", 45, 58),
              op("copy.1", 95, 98)]
    spans = [span("window", 1, 92, window=4),
             span("build_window", 1, 10, window=4),
             span("window_rollout", 10, 60, window=4, steps=128),
             span("window_seam", 60, 90, window=4),
             span("window_record", 92, 94, window=4)]
    scopes = {(DEV, 12e6, "fusion.1"): "jit(r)/while/body/vmap(policy)/dot:",
              (DEV, 20e6, "env_step_pallas.7"):
                  "jit(r)/while/body/env_step/env_step_pallas/pallas_call:",
              (DEV, 45e6, "fusion.1"): "jit(r)/while/body/vmap(policy)/add:"}
    return events, spans, scopes


def test_program_spans_name_and_split_the_gaps():
    d = spantrace.reduce(*window_trace())
    base = devtrace.reduce(window_trace()[0])
    assert (d.window_s, d.busy_s, d.op_seconds, d.op_calls) == (
        base.window_s, base.busy_s, base.op_seconds, base.op_calls)
    assert [g for _, g in d.idle_gaps] == [g for _, g in base.idle_gaps]
    # whole gaps, each named at its midpoint by the innermost span
    assert [n for n, _ in d.idle_gaps] == [
        "build_window", "window_rollout", "window_seam", "bench:window"]
    assert [n for n, _ in base.idle_gaps] == ["window"] * 4
    # the gap 58-95 is cut at the spans' edges: 58-60 rollout, 60-90
    # seam, 90-92 the program's window, 92-94 record, 94-95 only the
    # benchmark's mark
    idle = d.idle_by_span
    assert idle["build_window"] == pytest.approx(0.009)     # 1-10
    assert idle["window_rollout"] == pytest.approx(0.002 + 0.005 + 0.002)
    assert idle["window_seam"] == pytest.approx(0.030)
    assert idle["window"] == pytest.approx(0.002)           # 90-92
    assert idle["window_record"] == pytest.approx(0.002)
    assert idle["bench:window"] == pytest.approx(0.001 + 0.001 + 0.002)
    assert sum(idle.values()) == pytest.approx(d.window_s - d.busy_s)
    assert d.span_calls["window_rollout"] == {"calls": 1, "window": 4,
                                              "steps": 128}
    assert d.span_calls["window_seam"] == {"calls": 1, "window": 4}


def test_op_time_by_named_scope():
    d = spantrace.reduce(*window_trace())
    assert d.scope_seconds("policy") == (pytest.approx(0.021), 2)
    assert d.scope_seconds("env_step") == (pytest.approx(0.020), 1)
    assert d.scope_seconds("while") == (pytest.approx(0.041), 3)
    assert d.scope_seconds("pol") == (0, 0)
    assert spantrace.in_scope("a/vmap(policy)/b:", "policy")
    assert spantrace.in_scope("a/broadcast;vmap(policy)/b", "policy")
    assert not spantrace.in_scope("a/policy_head/b", "policy")


def test_spans_move_onto_the_device_clock():
    """A launch seen 3 ms later on the host than on the device moves every
    span 3 ms earlier before it names the gaps; devtrace's numbers stay."""
    events, spans, scopes = window_trace()
    late = [sp._replace(start=sp.start + 3e6, end=sp.end + 3e6)
            for sp in spans]
    launches = [spantrace.Launch(DEV, 15e6, 12e6),     # queued: 3 ms
                spantrace.Launch(DEV, 46e6, 45e6),     # 1 ms, device busy
                spantrace.Launch("/device:TPU:1", 9e6, 0.0)]
    d = spantrace.reduce(events, late, scopes, launches)
    assert d.clock_offset_s == pytest.approx(0.003)
    on_time = spantrace.reduce(events, spans, scopes).idle_by_span
    for k in ("build_window", "window_rollout", "window_seam", "window",
              "window_record"):
        assert d.idle_by_span[k] == pytest.approx(on_time[k])
    # the benchmark's marks move too: its window mark now ends at 97 ms
    assert d.idle_by_span["bench:window"] == pytest.approx(0.002)
    assert d.idle_by_span[spantrace.NO_SPAN] == pytest.approx(0.002)
    assert d.busy_s == devtrace.reduce(events).busy_s
    # left on the host's clock, the build would lose 1 ms to the rollout
    askew = spantrace.reduce(events, late, scopes).idle_by_span
    assert askew["build_window"] == pytest.approx(0.008)    # 4-12
    assert askew["window_rollout"] == pytest.approx(0.010)  # 40-45, 58-63


def test_no_span_and_no_window():
    events, _, _ = window_trace()
    d = spantrace.reduce(events, [], {})
    assert [n for n, _ in d.idle_gaps] == ["bench:window"] * 4
    assert d.span_calls == {} and d.scope_seconds("policy") == (0, 0)
    assert spantrace.reduce([op("x", 0, 1)], [], {}) is None
    bare = [e for e in events if e.name != devtrace.MARK + "window"]
    d = spantrace.reduce(bare, [span("decode", 50, 150, steps=3)], {})
    assert d.idle_by_span["between benchmark calls"] == pytest.approx(0.017)
    assert d.idle_by_span["decode"] == pytest.approx(0.039)
    assert d.span_calls == {"decode": {"calls": 1, "steps": 3}}


def test_recorded_excerpt_keeps_devtrace_numbers():
    """On the recorded excerpt without program spans, every number
    `devtrace` computes is the same, and each gap is still labelled."""
    ev = [Event(*e) for e in json.loads((DATA / "trace_excerpt.json")
                                        .read_text())]
    base, d = devtrace.reduce(ev), spantrace.reduce(ev, [], {})
    assert (d.window_s, d.busy_s, d.devices) == (base.window_s, base.busy_s,
                                                 base.devices)
    assert (d.op_seconds, d.op_calls) == (base.op_seconds, base.op_calls)
    assert [g for _, g in d.idle_gaps] == [g for _, g in base.idle_gaps]
    assert sum(d.idle_by_span.values()) == pytest.approx(
        d.window_s - d.busy_s, rel=1e-9)


def load_excerpt(name):
    d = json.loads((DATA / name).read_text())
    return ([Event(*e) for e in d["events"]],
            [Span(*sp) for sp in d["spans"]],
            {(p, s, n): d["paths"][i] for p, s, n, i in d["scopes"]},
            [spantrace.Launch(*la) for la in d["launches"]])


def test_recorded_excerpt_with_program_spans():
    """32 ms of a profiled sim-paper8-eat window on one v5e, from 2 ms
    before a `window_seam` span into the next rollout, with the program's
    spans, the ops' scope paths and the launches seen on both clocks."""
    events, spans, scopes, launches = load_excerpt("span_excerpt.json")
    d = spantrace.reduce(events, spans, scopes, launches)
    base = devtrace.reduce(events)
    assert (d.window_s, d.busy_s, d.op_seconds, d.op_calls) == (
        base.window_s, base.busy_s, base.op_seconds, base.op_calls)
    assert [g for _, g in d.idle_gaps] == [g for _, g in base.idle_gaps]
    assert d.clock_offset_s == pytest.approx(3.08354e-4)
    assert d.idle_by_span == pytest.approx({
        "bench:rollout": 2.69197e-3, "bench:window": 7.3062e-4,
        "between benchmark calls": 1.6235e-4, "build_window": 9.145873e-3,
        "window": 1.18111e-4, "window_record": 7.57093e-3,
        "window_rollout": 1.053547e-3, "window_seam": 7.171858e-3},
        rel=1e-6)
    assert sum(d.idle_by_span.values()) == pytest.approx(
        d.window_s - d.busy_s, rel=1e-9)
    assert max(d.idle_gaps, key=lambda g: g[1]) == (
        "window_record", pytest.approx(0.021906595))
    assert d.span_calls["window_rollout"] == {
        "calls": 1, "window": 554, "streams": 256, "steps": 128}
    assert d.scope_seconds("policy") == (pytest.approx(6.96754e-4), 1584)
    assert d.scope_seconds("env_step") == (pytest.approx(1.528282e-3), 162)
    assert d.ops_matching("env_step_pallas") == (pytest.approx(1.50167e-3),
                                                 18)


def test_scopes_and_spans_from_a_chip_profile(tmp_path):
    """`scopes.xplane.pb`: one v5e profile of a jitted 4-step scan whose
    body runs a matmul under `jax.named_scope("policy")` and a
    `pallas_call(name="probe_kernel")` under `jax.named_scope("env_step")`,
    three calls each inside `TraceAnnotation("eat:decode", arch="qwen",
    steps=12, flag=True)`, all inside `bench:profiled`. The op scopes come
    from the `tf_op` stat of the device plane's event metadata."""
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    shutil.copy(DATA / "scopes.xplane.pb", d / "host.xplane.pb")
    tf = spantrace._tf_ops(str(d / "host.xplane.pb"))[DEV]
    assert sorted(v for v in tf.values()) == [
        "jit(f)/while/body/closed_call/add:",
        "jit(f)/while/body/closed_call/env_step/probe_kernel/pallas_call:",
        "jit(f)/while/body/closed_call/policy/dot_general:"]
    events, spans, scopes, launches = spantrace.load(str(tmp_path))
    assert events == devtrace.load(str(tmp_path))
    assert [(s.name, s.args) for s in spans] == [
        ("eat:decode", {"arch": "qwen", "steps": 12, "flag": 1})] * 3
    # each call's device events read 1.28-1.34 ms before its launch
    assert [la.host - la.device for la in launches] == [
        1281160.0, 1325064.0, 1339765.0]
    assert spantrace.clock_offset(launches, DEV) == 1339765.0
    t = spantrace.reduce(events, spans, scopes, launches)
    assert t.clock_offset_s == pytest.approx(1.339765e-3)
    # `devtrace`'s window, on the host's clock, holds the device work of
    # the last call only (4 scan steps); the host made all three in it
    assert t.span_calls == {"decode": {"calls": 3, "steps": 36, "flag": 3}}
    assert t.ops_matching("probe_kernel")[1] == 4
    assert t.scope_seconds("env_step")[1] == 4
    assert t.scope_seconds("policy")[1] == 4
    assert set(t.idle_by_span) == {"decode", "between benchmark calls"}
    assert sum(t.idle_by_span.values()) == pytest.approx(
        t.window_s - t.busy_s, rel=1e-9)
    # moved onto the device's clock, the spans end 1.34 ms before the
    # window does: that idle time is under no span
    assert t.idle_by_span["between benchmark calls"] == pytest.approx(
        t.clock_offset_s, rel=0.05)


# ---- the readers -------------------------------------------------------
class Ctx:
    def __init__(self, device):
        self.device = device


def read(metric, device, out=None):
    out = out or Outcome(e2e={}, attempted=0, failed=0, checks=[])
    return harness.reader(metric).read(Ctx(device), out)


def serve_trace():
    """A decision (0-4: device 1-2), its env step (4-6), a task's prefill
    and decode (6-30: device 7-27 with gaps at 12-13 and 20-22), under
    the benchmark's window mark."""
    events = [mark("profiled", 0, 30), mark("window", 0, 30),
              op("decide", 1, 2), op("env", 4.5, 5), op("prefill", 7, 12),
              op("decode", 13, 20), op("decode", 22, 27)]
    spans = [span("decision", 0, 4, step=0), span("env_advance", 4, 6, step=0),
             span("execute_task", 6, 30, steps=4),
             span("prefill", 6, 12.5), span("decode", 12.5, 28, steps=4)]
    return spantrace.reduce(events, spans, {})


def test_seam_idle_and_policy_device_readers():
    d = spantrace.reduce(*window_trace())
    # (build_window 9 + window 2 + window_seam 30 + record 2 ms) over one
    # window
    assert read("seam_idle_ms", d) == pytest.approx(43.0)
    out = Outcome(e2e={}, attempted=0, failed=0, checks=[], counts={"T": 128})
    assert read("policy_device_us", d, out) == pytest.approx(21e3 / 128)
    assert read("policy_device_us", d) is None                 # no T
    events, spans, _ = window_trace()
    plain = spantrace.reduce(events, spans, {})
    assert read("policy_device_us", plain, out) is None        # no scope
    bare = spantrace.reduce(events, [], {})
    for m in ("seam_idle_ms", "policy_device_us"):
        assert read(m, bare, out) is None
        assert read(m, devtrace.reduce(events), out) is None
        assert read(m, None, out) is None


def test_decision_and_decode_idle_readers():
    d = serve_trace()
    # decision: 0-1, 2-4; env_advance: 4-4.5, 5-6
    assert read("decision_idle_ms", d) == pytest.approx(4.5)
    # decode: 12.5-13 and 20-22 and 27-28 over 4 tokens
    assert read("decode_idle_ms_per_token", d) == pytest.approx(3.5 / 4)
    assert d.idle_by_span["prefill"] == pytest.approx(0.0015)   # 6-7, 12-12.5
    for m in ("decision_idle_ms", "decode_idle_ms_per_token"):
        assert read(m, spantrace.reduce(*window_trace())) is None
        assert read(m, None) is None


def test_model_load_reader():
    loads = [{"name": "model_load", "dur": 40e3}, {"name": "model_load",
                                                    "dur": 60e3},
             {"name": "decode", "dur": 1e3}]
    out = Outcome(e2e={}, attempted=0, failed=0, checks=[], spans=loads)
    assert read("model_load_ms", None, out) == pytest.approx(50.0)
    out.spans = loads[2:]
    assert read("model_load_ms", None, out) is None


def test_traced_serve_run_reads_its_cold_loads():
    """A traced serve run at CPU size: the program's `model_load` span
    holds each cold load of the measured window, synced, and the
    reader averages them; the spans nest under their own parents."""
    from chipbench import testing
    cell = testing.tiny_cell("serve-paper4-qwen2")
    ctx, out = testing.tiny_run(cell, seconds=0.3, trace=True)
    assert testing.correct(out)
    loads = [e for e in out.spans if e["name"] == "model_load"]
    assert loads and all(e["dur"] > 0 for e in loads)
    ids = {e["args"]["id"]: e for e in out.spans if e["ph"] == "X"}
    assert {ids[e["args"]["parent"]]["name"] for e in loads} == {
        "execute_task"}
    ms = harness.reader("model_load_ms").read(ctx, out)
    assert ms == pytest.approx(sum(e["dur"] for e in loads)
                               / len(loads) / 1e3)


# ---- one reduction for every traced cell --------------------------------
DEVICE_READERS = ("env_step_kernel_us", "env_step_roofline",
                  "device_idle_share.sim", "device_idle_share.serve")


class CellCtx(Ctx):
    def __init__(self, device):
        super().__init__(device)
        self.config = json.loads((Path(harness.ROOT) / "chipbench/configs"
                                  / "paper-8srv.json").read_text())
        self.peaks = harness.peaks_for("TPU v5 lite")


@pytest.mark.parametrize("source", ["span_excerpt", "scopes.xplane.pb"])
def test_device_readers_read_the_same_under_both_reductions(source,
                                                            tmp_path):
    """The readers the benchmark had before the profile was reduced by
    `spantrace` read the same under `devtrace` and `spantrace`, on a
    recorded sim window and on a chip profile; the gaps keep their
    lengths (their labels may change)."""
    if source == "span_excerpt":
        events, spans, scopes, launches = load_excerpt("span_excerpt.json")
    else:
        d = tmp_path / "plugins" / "profile" / "1"
        d.mkdir(parents=True)
        shutil.copy(DATA / source, d / "host.xplane.pb")
        events, spans, scopes, launches = spantrace.load(str(tmp_path))
    old = devtrace.reduce(events)
    new = spantrace.reduce(events, spans, scopes, launches)
    out = Outcome(e2e={}, attempted=0, failed=0, checks=[],
                  counts={"B": 256, "T": 128})
    read_any = 0
    for m in DEVICE_READERS:
        a = harness.reader(m).read(CellCtx(old), out)
        b = harness.reader(m).read(CellCtx(new), out)
        assert a == b, m
        read_any += a is not None
    assert read_any >= 2
    assert sorted(g for _, g in old.idle_gaps) == sorted(
        g for _, g in new.idle_gaps)
    assert old.breakdown()["device_ops"] == new.breakdown()["device_ops"]


def train_trace():
    """A profiled round: collection (0-10 ms, device 2-8) with its push
    (10-12), then the update schedule (12-40) of 4 updates, device busy
    14-18, 22-26, 30-34 and 36-39."""
    events = [mark("profiled", 0, 40), op("rollout", 2, 8),
              op("update", 14, 18), op("update", 22, 26),
              op("update", 30, 34), op("update", 36, 39)]
    spans = [span("train_round", 0, 40, round=3),
             span("window", 0, 10, window=3),
             span("replay_push", 10, 12, n=4),
             span("gradient_update", 12, 40, new_transitions=4)]
    return spantrace.reduce(events, spans, {})


def test_busy_and_idle_by_span_and_the_learner_readers():
    d = train_trace()
    assert d.busy_by_span == pytest.approx({"window": 0.006,
                                            "gradient_update": 0.015})
    assert sum(d.busy_by_span.values()) == pytest.approx(d.busy_s)
    # gradient_update: 12-14, 18-22, 26-30, 34-36, 39-40 = 13 ms
    assert d.idle_by_span["gradient_update"] == pytest.approx(0.013)
    out = Outcome(e2e={}, attempted=0, failed=0, checks=[],
                  counts={"profiled_updates": 4})
    assert read("update_idle_ms", d, out) == pytest.approx(13.0 / 4)
    assert read("update_device_us", d, out) == pytest.approx(15e3 / 4)
    assert read("device_idle_share.train", d, out) == pytest.approx(
        100.0 * 19 / 40)
    for m in ("update_idle_ms", "update_device_us"):
        assert read(m, d) is None                   # no updates counted
        assert read(m, spantrace.reduce(*window_trace()), out) is None
        assert read(m, None, out) is None


def test_learner_span_readers():
    spans = [{"name": "gradient_update", "dur": 30e3},
             {"name": "gradient_update", "dur": 50e3},
             {"name": "replay_push", "dur": 2e3},
             {"name": "replay_push", "dur": 4e3}]
    out = Outcome(e2e={}, attempted=0, failed=0, checks=[], spans=spans,
                  counts={"updates": 40, "rounds": 2})
    assert read("gradient_update_ms", None, out) == pytest.approx(2.0)
    assert read("replay_push_ms", None, out) == pytest.approx(3.0)
    out.counts = {}
    assert read("gradient_update_ms", None, out) is None
    assert read("replay_push_ms", None, out) is None
