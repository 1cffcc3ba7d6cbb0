"""Property test: the streaming seam ledger is conserved for *every*
window/carry/fault configuration, not just the hand-picked ones.

    injected == scheduled + dropped + failed_pending_retry + leftover

with dropped = shed + retry-exhausted. Two drivers share one core check:

* a Hypothesis property (`hypothesis` ships in requirements-dev.txt but not
  in the minimal container, so it is `importorskip`'d), and
* a seeded-RNG fallback sweep that always runs, drawing the same parameter
  space from `np.random.default_rng` so tier-1 keeps randomized coverage
  even without Hypothesis installed.
"""
import jax
import numpy as np
import pytest

from repro.core import env as EV
from repro.core import rollout as RO
from repro.core.workload import TraceConfig
from repro.faults import FaultSpec
from repro.traffic.arrivals import PoissonArrivals
from repro.traffic.stream import ProcessTaskSource, StreamConfig, run_stream


def check_ledger(*, windows: int, streams: int, K: int, max_carry,
                 fault_seed: int, mtbf: float, max_retries: int,
                 rate: float, key_seed: int) -> None:
    ecfg = EV.EnvConfig(num_servers=4, queue_window=4, max_tasks=K,
                        time_limit=600.0, max_steps=8 * K)
    faults = None
    if mtbf > 0.0:
        faults = FaultSpec(seed=fault_seed, mtbf=mtbf, mttr=30.0,
                           straggler_prob=0.2, max_retries=max_retries,
                           backoff_base=2.0, backoff_cap=20.0,
                           retry_deadline=300.0)
    key = jax.random.PRNGKey(key_seed)
    src = ProcessTaskSource(PoissonArrivals(rate=rate),
                            TraceConfig(num_tasks=K), key,
                            num_streams=streams)
    scfg = StreamConfig(num_windows=windows, num_streams=streams,
                        max_carry=max_carry, resp_sla=120.0, faults=faults)
    res = run_stream(ecfg, RO.greedy_policy(ecfg), None, src, key, scfg)
    s = res.summary
    assert s["tasks_injected"] == (
        s["tasks_scheduled"] + s["tasks_dropped"]
        + s["tasks_failed_pending_retry"] + s["tasks_leftover"]), s
    assert s["tasks_dropped"] == (s["tasks_dropped_shed"]
                                  + s["tasks_dropped_retry_exhausted"]), s
    for k in ("tasks_scheduled", "tasks_dropped", "tasks_leftover",
              "tasks_failed_pending_retry", "tasks_failed", "tasks_retried"):
        assert s.get(k, 0) >= 0, (k, s)


def _draw(rng):
    mtbf = float(rng.choice([0.0, 40.0, 120.0, 300.0]))
    return dict(
        windows=int(rng.integers(1, 5)),
        streams=int(rng.integers(1, 4)),
        K=int(rng.choice([8, 12, 16])),
        max_carry=(None if rng.random() < 0.5
                   else int(rng.integers(0, 9))),
        fault_seed=int(rng.integers(0, 1000)),
        mtbf=mtbf,
        max_retries=int(rng.integers(0, 4)),
        rate=float(rng.choice([0.05, 0.2, 1.0])),
        key_seed=int(rng.integers(0, 1000)),
    )


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_ledger_conserved_seeded_sweep(seed):
    """Fallback sweep (no external deps): 6 random configs per tier-1 run."""
    check_ledger(**_draw(np.random.default_rng(seed)))


def test_ledger_conserved_hypothesis():
    """The same invariant under Hypothesis' adversarial shrinking search."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=12, deadline=None,
                  suppress_health_check=list(hyp.HealthCheck))
    @hyp.given(
        windows=st.integers(1, 4),
        streams=st.integers(1, 3),
        K=st.sampled_from([8, 12, 16]),
        max_carry=st.one_of(st.none(), st.integers(0, 8)),
        fault_seed=st.integers(0, 999),
        mtbf=st.sampled_from([0.0, 40.0, 120.0, 300.0]),
        max_retries=st.integers(0, 3),
        rate=st.sampled_from([0.05, 0.2, 1.0]),
        key_seed=st.integers(0, 999),
    )
    def prop(**kw):
        # StreamRunner rejects max_carry >= K (a window must admit new
        # arrivals); such a draw is not a stream configuration
        hyp.assume(kw["max_carry"] is None or kw["max_carry"] < kw["K"])
        check_ledger(**kw)

    prop()
