"""Two-timescale model placement (`repro.placement`, ISSUE 9).

The contracts under test:

* **Off means off.** ``placement=None`` and ``PlacementSpec.none()`` produce
  bitwise-identical summaries AND final carries on the fused and serving
  backends — placement applies on the host carry between windows, so an
  inactive spec changes no compiled program and no result.
* **Same arrivals.** An active placement policy sees the exact arrival
  stream the placement-free run sees (`tasks_injected` parity): the slow
  timescale rewrites idle-server caches, never demand.
* **Ledger conservation.** The streaming seam ledger balances with
  placement active, with and without fault injection.
* **Fault interaction.** Placement under an aggressive `FaultSpec` stays
  deterministic and conserves both ledgers; a cold restart wipes placed
  caches through the same decision-step wipe that covers carried ones.
* **Planner semantics.** Whole synthetic gangs only (the env's reuse test
  needs complete idle gangs), keep-before-bind, cheapest-first binding,
  busy servers untouched, seam-convention gang labels.
* **Pool tie-break.** `ServerPool.pick_fresh` prefers arch-matching idle
  servers among equally fragmented candidates — and reproduces the
  historical order exactly when no arch is given.
"""
import dataclasses
import re

import jax
import numpy as np
import pytest

from repro import api
from repro.core import env as EV
from repro.core.scenarios import Scenario, zipf_probs
from repro.core.workload import TraceConfig
from repro.faults import FaultSpec
from repro.placement import (DemandStats, PlacementManager, PlacementSpec,
                             known_policies, placement_active, plan_gangs,
                             plan_stream, prior_weights)
from repro.serving.pool import LogicalServer, ServerPool

ECFG = EV.EnvConfig(num_servers=4, max_tasks=8, num_models=3)
TCFG = TraceConfig(num_tasks=8, arrival_rate=2.0, max_servers=4,
                   num_models=3, model_probs=zipf_probs(3))
CELL = Scenario(name="placement-test-cell", ecfg=ECFG, tcfg=TCFG)

SERVE_ECFG = EV.EnvConfig(num_servers=4, max_tasks=8)
SERVE_CELL = Scenario(name="placement-serve-cell", ecfg=SERVE_ECFG,
                      tcfg=TraceConfig(num_tasks=8, arrival_rate=2.0,
                                       max_servers=4))
MIRROR = api.ExecSpec(backend="serving", serving_execute=False)

_MEASURED = re.compile(
    r"(_latency_(p\d+|mean)_s$|_decisions$|^decision_latency_n$"
    r"|measured_busy|^wall_s$)")


def _det(summary):
    """The deterministic slice of a summary (drop wall-clock noise)."""
    return {k: v for k, v in summary.items()
            if isinstance(v, (int, float, bool)) and not _MEASURED.search(k)}


def _wl(cell=CELL, **kw):
    kw.setdefault("streams", 2)
    kw.setdefault("num_windows", 3)
    kw.setdefault("window_tasks", 8)
    return api.WorkloadSpec.streaming(cell, **kw)


def _run(wl, spec, key=None):
    sim = api.Simulator(wl, spec)
    return sim.run(api.PolicySpec("greedy"), key or jax.random.PRNGKey(0))


# ------------------------------------------------------------ spec
def test_spec_validation():
    assert set(known_policies()) >= {"none", "static", "lfu", "forecast"}
    assert PlacementSpec.none().active is False
    assert PlacementSpec(policy="lfu").active is True
    assert placement_active(None) is False
    assert placement_active(PlacementSpec.none()) is False
    assert placement_active(PlacementSpec(policy="forecast")) is True
    with pytest.raises(ValueError, match="policy"):
        PlacementSpec(policy="nope")
    with pytest.raises(ValueError, match="interval"):
        PlacementSpec(policy="lfu", interval=0)
    with pytest.raises(ValueError, match="ewma_alpha"):
        PlacementSpec(policy="forecast", ewma_alpha=0.0)
    with pytest.raises(ValueError, match="model_probs"):
        PlacementSpec(policy="static", model_probs=(-1.0, 2.0))
    # frozen + hashable: the ExecSpec contract
    hash(PlacementSpec(policy="lfu", model_probs=(0.5, 0.5)))


def test_manager_rejects_inactive_spec():
    with pytest.raises(ValueError, match="active spec"):
        PlacementManager(PlacementSpec.none(), ECFG)


def test_simulator_rejects_episodic_placement():
    wl = api.WorkloadSpec.episodic(CELL, batch=2)
    with pytest.raises(ValueError, match="streaming-only"):
        api.Simulator(wl, api.ExecSpec(placement=PlacementSpec(policy="lfu")))


# ------------------------------------------------------------ stats
def test_demand_stats_binning_and_accessors():
    st = DemandStats(1, 2, (1, 2, 4, 8))
    model = np.array([[0, 0, 1, 0, -1]])
    c = np.array([[1, 3, 4, 8, 2]])      # c=3 bins DOWN to the 2-slot
    st.observe(model, c)
    last = st.last(0)
    assert last[0, 0] == 1 and last[0, 1] == 1 and last[0, 3] == 1
    assert last[1, 2] == 1
    assert last.sum() == 4               # model=-1 ignored
    # single window: EWMA == last; seasonal(period<=1) == last
    np.testing.assert_array_equal(st.ewma(0, 0.5), last)
    np.testing.assert_array_equal(st.seasonal(0, 1, 0), last)
    st.observe(np.zeros((1, 5), int), np.ones((1, 5), int))
    ew = st.ewma(0, 0.5)
    assert ew[0, 0] == 0.5 * 5 + 0.5 * 1  # alpha*new + (1-alpha)*old
    # phase 0 of period 2 picks only the first window
    np.testing.assert_array_equal(st.seasonal(0, 2, 0), last)


def test_policies_return_demand_weights():
    st = DemandStats(1, 3, (1, 2, 4, 8))
    spec = PlacementSpec(policy="forecast", model_probs=(0.7, 0.2, 0.1))
    from repro.placement.policies import get_placement_policy
    # before any observation: every policy falls back to the static prior
    prior = prior_weights(spec, 3, st.c_support)
    for name in ("static", "lfu", "forecast"):
        w = get_placement_policy(name)(spec, st, 0)
        assert w.shape == (3, 4) and (w >= 0).all()
        np.testing.assert_allclose(w, prior)
    # flash crowd on model 2: the trend boost outranks the EWMA baseline
    st.observe(np.zeros((1, 4), int), np.full((1, 4), 2))
    st.observe(np.full((1, 8), 2), np.full((1, 8), 2))
    w = get_placement_policy("forecast")(spec, st, 0)
    assert w[2, 1] > w[0, 1]


# ------------------------------------------------------------ planner
def test_plan_gangs_tracks_demand_and_capacity():
    w = np.array([[4.0, 0.0], [1.0, 0.0]])
    gangs = plan_gangs(w, capacity=6, c_support=(1, 2))
    assert sum(c for _, c in gangs) <= 6
    n0 = sum(1 for m, _ in gangs if m == 0)
    n1 = sum(1 for m, _ in gangs if m == 1)
    assert n0 > n1 >= 1                  # credit-halving shares capacity
    capped = plan_gangs(w, 6, (1, 2), max_gangs_per_cell=1)
    assert sum(1 for m, _ in capped if m == 0) == 1


def test_plan_stream_binds_cheapest_first():
    # 4 idle broken servers: s1 already holds model 0 (hit), s0/s3 empty,
    # s2 holds model 1 (evict) -> a (0, 2)-gang binds to {s1, s0}
    idle = np.ones(4, bool)
    model = np.array([-1, 0, 1, -1], np.int32)
    gang = np.full(4, -1, np.int32)
    size = np.zeros(4, np.int32)
    w = np.zeros((2, 2))
    w[0, 1] = 1.0                        # demand: one gang of (m=0, c=2)
    sp = plan_stream(w, idle, model, gang, size, (1, 2), K=8,
                     max_gangs_per_cell=1)
    placed = np.flatnonzero(sp.gang_size == 2)
    assert set(placed) == {0, 1}
    assert sp.counters["evictions"] == 0
    assert sp.counters["prefetches"] == 1        # only s0 changed model
    assert not sp.prefetch[1]                    # s1 was already warm
    # seam-convention label: K + min(member index)
    assert sp.gang[0] == sp.gang[1] == 8 + 0


def test_plan_stream_keeps_existing_gangs_and_skips_busy():
    # s0+s1: a complete idle gang already matching (m=1, c=2); s2 busy
    idle = np.array([True, True, False, True])
    model = np.array([1, 1, 0, -1], np.int32)
    gang = np.array([5, 5, 7, -1], np.int32)
    size = np.array([2, 2, 1, 0], np.int32)
    w = np.zeros((2, 2))
    w[1, 1] = 1.0
    sp = plan_stream(w, idle, model, gang, size, (1, 2), K=8,
                     max_gangs_per_cell=1)
    assert sp.counters["gangs_kept"] == 1
    assert sp.counters["prefetches"] == sp.counters["evictions"] == 0
    np.testing.assert_array_equal(sp.model, model)    # zero churn
    np.testing.assert_array_equal(sp.gang, gang)
    # busy server untouched even under heavy demand
    w[0, 0] = 10.0
    sp2 = plan_stream(w, idle, model, gang, size, (1, 2), K=8)
    assert sp2.model[2] == 0 and sp2.gang[2] == 7 and sp2.gang_size[2] == 1


# ------------------------------------------------------------ identity
def test_placement_none_bitwise_identical_fused():
    """None vs PlacementSpec.none() vs the pre-placement default: same
    summary, same final carry, byte for byte (fused backend)."""
    base = _run(_wl(), api.ExecSpec(backend="fused"))
    off = _run(_wl(), api.ExecSpec(backend="fused",
                                   placement=PlacementSpec.none()))
    assert _det(base.summary) == _det(off.summary)
    a = jax.tree_util.tree_map(np.asarray, base.raw.final_carry)
    b = jax.tree_util.tree_map(np.asarray, off.raw.final_carry)
    jax.tree_util.tree_map(np.testing.assert_array_equal, a, b)
    assert base.raw.placement_counters == off.raw.placement_counters == {}


def test_placement_none_bitwise_identical_serving():
    wl = _wl(SERVE_CELL, streams=1, num_windows=2)
    base = _run(wl, MIRROR)
    off = _run(wl, dataclasses.replace(MIRROR,
                                       placement=PlacementSpec.none()))
    assert _det(base.summary) == _det(off.summary)
    a = jax.tree_util.tree_map(np.asarray, base.raw.final_carry)
    b = jax.tree_util.tree_map(np.asarray, off.raw.final_carry)
    jax.tree_util.tree_map(np.testing.assert_array_equal, a, b)


# ------------------------------------------------------------ active runs
@pytest.mark.parametrize("policy", ["static", "lfu", "forecast"])
def test_active_placement_same_arrivals_and_ledger(policy):
    """An active policy sees the placement-free arrival stream (injection
    parity), balances the seam ledger, and reports its decision ledger."""
    base = _run(_wl(), api.ExecSpec(backend="fused"))
    res = _run(_wl(), api.ExecSpec(
        backend="fused", placement=PlacementSpec(policy=policy)))
    s = res.summary
    assert s["tasks_injected"] == base.summary["tasks_injected"]
    assert s["tasks_injected"] == (
        s["tasks_scheduled"] + s["tasks_dropped"]
        + s["tasks_failed_pending_retry"] + s["tasks_leftover"])
    pc = res.raw.placement_counters
    assert pc["placement_decisions"] == 3       # one decision per seam
    assert pc["placement_gangs_planned"] > 0
    assert set(pc["per_model"]) == {0, 1, 2}
    for row in pc["per_model"].values():
        assert 0.0 <= row["cold_start_rate"] <= 1.0


def test_placement_interval_skips_seams():
    res = _run(_wl(num_windows=4), api.ExecSpec(
        backend="fused", placement=PlacementSpec(policy="lfu", interval=2)))
    # seams after windows 0..3; only (w+1) % 2 == 0 decides -> w=1, w=3
    assert res.raw.placement_counters["placement_decisions"] == 2


def test_placement_deterministic():
    spec = api.ExecSpec(backend="fused",
                        placement=PlacementSpec(policy="forecast"))
    r1, r2 = _run(_wl(), spec), _run(_wl(), spec)
    assert _det(r1.summary) == _det(r2.summary)
    assert r1.raw.placement_counters == r2.raw.placement_counters


def test_serving_prefetch_and_warm_hits():
    """Real-weight pre-warm: `apply_placement` evicts displaced weights,
    prefetches the planned models off the timed path, and the resulting
    gang satisfies the pool's reuse test — a warm hit, not a cold load."""
    from repro.placement import PlacementDecision, StreamPlacement
    from repro.serving.backend import ServingRollout
    ro = ServingRollout(4, execute=False)
    ro.pool.servers[2].model_name = "stale-arch"   # displaced by the plan
    ro.pool.servers[2].params = object()
    arch = ro._arch_of(0)
    sp = StreamPlacement(
        model=np.array([0, 0, 0, -1], np.int32),
        gang=np.array([8, 8, 8, -1], np.int32),
        gang_size=np.array([3, 3, 3, 0], np.int32),
        prefetch=np.array([True, True, True, False]),
        evict=np.array([False, False, True, False]),
        counters={})
    ro.apply_placement(PlacementDecision(0, [sp], {}))
    assert ro.placement_counters() == {"placement_weight_prefetches": 3,
                                       "placement_weight_evictions": 1}
    for i in range(3):
        s = ro.pool.servers[i]
        assert s.model_name == arch and s.params is not None
    # the placed gang is a complete idle gang: the reuse test finds it
    gang = ro.pool.find_reusable_gang(arch, 3, now=0.0)
    assert gang is not None and {s.sid for s in gang} == {0, 1, 2}
    # already-warm servers are skipped: re-planning the same layout (the
    # planner emits no evictions against an unchanged state) loads nothing
    again = sp._replace(evict=np.zeros(4, bool))
    ro.apply_placement(PlacementDecision(1, [again], {}))
    assert ro.placement_prefetches == 3
    # pinned key set: placement counters must NOT leak into pool.counters()
    assert set(ro.pool.counters()) == {"model_loads", "model_reuses"}


# ------------------------------------------------------------ faults
CHAOS = FaultSpec(seed=2, mtbf=60.0, mttr=15.0, straggler_prob=0.3,
                  straggler_factor=3.0, max_retries=3, backoff_base=2.0,
                  backoff_cap=20.0, retry_deadline=600.0)


def test_placement_under_faults_conserves_both_ledgers():
    """Cold restarts wipe placed caches through the decision-step wipe:
    chaos + placement stays deterministic, conserves the stream and the
    failure ledgers, reports the same fault outcome on the fused and the
    reference engine, and keeps fault arrivals identical to the
    placement-free chaos run."""
    spec = api.ExecSpec(backend="fused", faults=CHAOS,
                        placement=PlacementSpec(policy="lfu"))
    res = _run(_wl(num_windows=4), spec)
    s = res.summary
    assert s["tasks_failed"] > 0                  # chaos actually fired
    assert res.raw.placement_counters["placement_decisions"] > 0
    assert s["tasks_injected"] == (
        s["tasks_scheduled"] + s["tasks_dropped"]
        + s["tasks_failed_pending_retry"] + s["tasks_leftover"])
    assert s["tasks_dropped"] == (s["tasks_dropped_shed"]
                                  + s["tasks_dropped_retry_exhausted"])
    # every crashed task is either requeued or out of retries
    assert s["tasks_failed"] == (s["tasks_retried"]
                                 + s["tasks_dropped_retry_exhausted"])
    # the reference engine, fed the same placed carries and crash columns,
    # fails exactly the same tasks
    ref = _run(_wl(num_windows=4), dataclasses.replace(spec,
                                                       backend="reference"))
    assert _det(ref.summary) == _det(s)
    assert ref.raw.fault_counters == res.raw.fault_counters
    assert ref.raw.placement_counters == res.raw.placement_counters
    base = _run(_wl(num_windows=4),
                api.ExecSpec(backend="fused", faults=CHAOS))
    assert s["tasks_injected"] == base.summary["tasks_injected"]
    # the crash schedule is placement-independent ...
    for k in ("down_events", "down_events_truncated"):
        assert res.raw.fault_counters[k] == base.raw.fault_counters[k], k
    # ... and so is the fault outcome wherever placement is inert. Under
    # the default PRNG this cell's arrivals let LFU pre-warm a gang that is
    # then reused (reuse_rate 2/11), which legitimately saves one task from
    # exhausting its retries (53 vs 54 failures); under the legacy
    # threefry stream no placed gang is ever reused, and there placement
    # must leave the failures exactly as the placement-free run has them.
    with jax.threefry_partitionable(False):
        inert = _run(_wl(num_windows=4), spec).summary
        inert_base = _run(_wl(num_windows=4),
                          api.ExecSpec(backend="fused", faults=CHAOS)).summary
    assert inert["reuse_rate"] == inert_base["reuse_rate"] == 0.0
    assert inert["tasks_failed"] > 0
    for k in ("tasks_failed", "tasks_retried",
              "tasks_dropped_retry_exhausted", "tasks_scheduled"):
        assert inert[k] == inert_base[k], k
    rep = _run(_wl(num_windows=4), spec)
    assert _det(res.summary) == _det(rep.summary)
    assert res.raw.placement_counters == rep.raw.placement_counters


def test_cold_restart_wipes_stale_placement():
    """A placed cache on a crashed server must not survive the restart:
    under chaos the placement run's reuse economics can differ from the
    fault-free placement run (wiped caches reload), while the placement
    DECISION ledger — which only sees demand — stays identical."""
    place = PlacementSpec(policy="lfu")
    faulty = _run(_wl(num_windows=4),
                  api.ExecSpec(backend="fused", faults=CHAOS,
                               placement=place))
    clean = _run(_wl(num_windows=4),
                 api.ExecSpec(backend="fused", placement=place))
    pf, pc = (faulty.raw.placement_counters, clean.raw.placement_counters)
    assert pf["placement_decisions"] == pc["placement_decisions"]
    # chaos cost tasks: the wipe forces reloads the clean run never pays
    assert faulty.summary["tasks_scheduled"] <= clean.summary["tasks_scheduled"]


# ------------------------------------------------------------ pool
def _pool(rows):
    """rows: (model_name, gang, gang_size, busy_until) per server."""
    p = ServerPool(len(rows))
    for s, (m, g, gs, b) in zip(p.servers, rows):
        s.model_name, s.gang, s.gang_size, s.busy_until = m, g, gs, b
    return p


def test_pick_fresh_prefers_arch_matches():
    p = _pool([("a", -1, 0, 0.0), ("b", -1, 0, 0.0),
               (None, -1, 0, 0.0), ("b", -1, 0, 0.0)])
    gang = p.pick_fresh(2, 0.0, arch="b")
    assert [s.sid for s in gang] == [1, 3]
    # no arch: the exact historical sid order
    assert [s.sid for s in p.pick_fresh(2, 0.0)] == [0, 1]


def test_pick_fresh_arch_never_outranks_fragmentation():
    # s0+s1: intact idle gang holding "b"; s2 empty; s3 holds "b" but its
    # gang partner s4 is busy (broken gang). Even hunting for "b", intact
    # gangs are still broken LAST: the warm broken server then the empty
    # one win, and the intact pair survives.
    p = _pool([("b", 9, 2, 0.0), ("b", 9, 2, 0.0),
               (None, -1, 0, 0.0), ("b", 3, 2, 0.0), ("b", 3, 2, 99.0)])
    gang = p.pick_fresh(2, 0.0, arch="b")
    assert [s.sid for s in gang] == [3, 2]
