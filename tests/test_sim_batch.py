"""Batched-engine equivalence suite (ISSUE 8 tentpole a).

``SimEngine(engine_mode="batched")`` must be *bit-identical* to the
per-event oracle (``engine_mode="event"``) — same summary dict, same
ledger array, same recovery journal, same slot count — on every trace:
clean and chaos (``FaultPlan`` machine incidents + job failures +
re-fail cascades), all four policies, both metrics modes, and both array
backends. The randomized soups below lean on same-slot collisions (high
arrival rates pile many events into one slot, which is exactly what the
batched drain groups).

Also covers the streaming-metrics memory fix that rides along: censored
closures (rejections / departures / evictions) now fold into running
counters instead of retaining per-job rows, so ``outcomes`` stays
bounded by the in-flight job count on arbitrarily long streams.
"""
from __future__ import annotations

import pytest
from hypothesis import given, settings

from repro.sim import RollingWindow, SimEngine, TraceConfig, make_policy
from repro.core import make_cluster
from repro.sim.metrics import MetricsCollector

from strategies import (
    SLOT_POLICIES,
    assert_equivalent as _assert_equivalent,
    policies,
    run_sim as _run,
    seeds,
)


# ------------------------------------------------------------ property
@settings(max_examples=8)
@given(seeds(), policies(SLOT_POLICIES))
def test_batched_equiv_clean_event_soup(seed, policy):
    """Randomized clean streams: batched == oracle bit-for-bit."""
    _assert_equivalent(policy, seed)


@settings(max_examples=6)
@given(seeds(), policies(SLOT_POLICIES))
def test_batched_equiv_chaos_event_soup(seed, policy):
    """Chaos soups (machine incidents + failures + re-fail cascades)
    force same-slot collisions across every event kind."""
    _assert_equivalent(policy, seed, faults=True)


@settings(max_examples=4)
@given(seeds())
def test_batched_equiv_same_slot_collisions(seed):
    """Very high arrival rate: most slots carry multi-event groups."""
    _assert_equivalent("fifo", seed, rate=8.0, num_jobs=80)


# ------------------------------------------------------------ explicit
@pytest.mark.parametrize("faults", [False, True])
@pytest.mark.parametrize("metrics_mode", ["exact", "streaming"])
def test_batched_equiv_pdors(faults, metrics_mode):
    _assert_equivalent("pdors", 3, num_jobs=40, faults=faults,
                       metrics_mode=metrics_mode)


@pytest.mark.parametrize("rng_mode", ["derived", "compat"])
def test_batched_equiv_pdors_reoffer_heavy(rng_mode):
    """Heavy job-failure and re-fail churn on a small cluster: preempted
    jobs are re-offered over and over, each re-offer building a fresh
    plan against a ledger that only partly changed since the last one."""
    tcfg = TraceConfig(num_jobs=60, seed=4, arrival_rate=5.0,
                       failure_rate=0.4)
    r1, _ = _assert_equivalent("pdors", 4, trace_cfg=tcfg, refail=0.4,
                               max_slots=2000,
                               policy_kwargs={"rng_mode": rng_mode})
    assert r1.summary["preemptions"] > 0


@pytest.mark.parametrize("policy", ["fifo", "dorm"])
def test_batched_equiv_streaming_metrics(policy):
    _assert_equivalent(policy, 11, metrics_mode="streaming", faults=True)


def test_batched_equiv_with_checkpoints():
    """Checkpointing disables the journal trim and snapshots batched-mode
    state; recovery bookkeeping must not perturb parity."""
    _assert_equivalent("fifo", 7, faults=True, checkpoint_every=16)


def test_batched_equiv_jax_backend():
    pytest.importorskip("jax")
    _assert_equivalent("fifo", 2, num_jobs=30, backend="jax")


def test_engine_mode_validated():
    cl = make_cluster(4, 8)
    with pytest.raises(ValueError):
        SimEngine(RollingWindow(cl), make_policy("fifo"),
                  engine_mode="vectorized")


def test_batched_reports_admission_latency():
    rep, eng = _run("fifo", "batched", 0)
    lat = eng.admission_latency()
    assert lat["count"] > 0
    assert lat["p99_ms"] >= lat["p50_ms"] >= 0.0
    assert lat["mean_ms"] > 0.0


# ------------------------------------------------- streaming memory fix
def test_streaming_outcomes_bounded_by_in_flight():
    """A long stream with rejections and departures must not retain one
    outcome row per offered job in streaming mode (the O(n) leak): rows
    for closed jobs fold into counters and drop."""
    rep, eng = _run("pdors", "batched", 5, num_jobs=120, rate=6.0,
                    metrics_mode="streaming")
    s = rep.summary
    closed = (s["jobs_completed"] + s["jobs_rejected"]
              + s["jobs_departed"] + s["jobs_evicted"])
    assert closed > 0
    # every closed job's row is gone; only still-in-flight rows remain
    assert len(eng.metrics.outcomes) <= s["jobs_offered"] - closed

    # streaming summary still matches the exact-mode counts
    rex, _ = _run("pdors", "batched", 5, num_jobs=120, rate=6.0,
                  metrics_mode="exact")
    for k in ("jobs_offered", "jobs_completed", "jobs_rejected",
              "jobs_departed", "jobs_evicted", "preemptions"):
        assert s[k] == rex.summary[k], k


def test_collector_level_closed_rows_drop():
    """Direct collector check: 100k offered-then-closed jobs hold O(1)
    rows, and the folded counters stay exact."""
    mc = MetricsCollector(["gpu"], num_machines=4, mode="streaming")
    for jid in range(100_000):
        oc = mc.outcome(jid, arrival=jid)
        if jid % 3 == 0:
            oc.admitted = False
            mc.count("rejection")
        elif jid % 3 == 1:
            oc.departed_at = jid + 5
            mc.count("departure")
        else:
            oc.admitted = True
            oc.evicted_at = jid + 2
            oc.preemptions = 1
            mc.count("eviction")
        mc.job_closed(oc)
    assert len(mc.outcomes) == 0
    mc.record_slot(0, {"gpu": 0.0}, 0, 0)
    s = mc.summary()
    assert s["jobs_offered"] == 100_000
    assert s["jobs_rejected"] == 33_334
    assert s["jobs_departed"] == 33_333
    assert s["jobs_evicted"] == 33_333
    assert s["preemptions"] == 33_333
