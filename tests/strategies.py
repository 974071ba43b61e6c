"""Shared property-test generators and runners for the sim suites.

Promoted from the ad-hoc fuzz loops in ``tests/test_sim_batch.py`` so the
batched-equivalence suite and the elastic/reshape suite (ISSUE 10) draw
their traces, fault plans, and reshape storms from ONE place. The
per-example deadline is off for every test (the ``tests/conftest.py``
Hypothesis profile): one example here is a whole engine run.

Building blocks
---------------
* ``seeds()`` / ``policies()``           — strategies for @given
* ``make_trace`` / ``reshape_storm``    — TraceConfig builders
* ``chaos_plan``                         — the standard FaultPlan soup
* ``run_sim``                            — one engine run (any policy,
  engine mode, metrics mode, backend, trace overrides, fault injection,
  checkpoint/kill knobs)
* ``assert_equivalent``                  — batched-vs-event bit-identity
  (summary, slots, ledger, journal, exact-mode outcome rows)
"""
from __future__ import annotations

import numpy as np
from hypothesis import strategies as st

from repro.core import make_cluster
from repro.sim import (
    FaultPlan,
    RollingWindow,
    SimEngine,
    TraceConfig,
    calibrate_prices,
    make_policy,
    merge_event_streams,
    stream,
)

ALL_POLICIES = ("pdors", "fifo", "drf", "dorm")
SLOT_POLICIES = ("fifo", "drf", "dorm")

# summary keys that describe job *quality metadata* rather than scheduling
# decisions: the one block allowed to differ between a run over elastic-
# annotated jobs and the identical run with the annotations stripped
QUALITY_KEYS = frozenset({
    "reshapes", "deadline_jobs", "deadline_hits", "deadline_attainment",
    "slo_jobs", "slo_hits", "slo_attainment", "final_loss_mean",
})


# ------------------------------------------------------------ strategies
def seeds(lo: int = 0, hi: int = 10**6):
    return st.integers(lo, hi)


def policies(names=SLOT_POLICIES):
    return st.sampled_from(list(names))


# ------------------------------------------------------------- builders
def make_trace(seed: int, *, num_jobs: int = 60, rate: float = 3.0,
               failure_rate: float = 0.1, **overrides) -> TraceConfig:
    """The suite's standard short google stream (failures on)."""
    return TraceConfig(num_jobs=num_jobs, seed=seed, arrival_rate=rate,
                       failure_rate=failure_rate, **overrides)


def reshape_storm(seed: int, *, num_jobs: int = 60, rate: float = 3.0,
                  **overrides) -> TraceConfig:
    """An elastic trace tuned so reshapes actually fire: most jobs carry
    profiles, the SLAQ floor is high enough that mid-level jobs shrink
    within a few epochs, and the adadamp damper is loose enough that
    early-loss jobs grow — with deadlines and loss SLOs riding along so
    the quality columns are exercised too."""
    kw = dict(
        elastic_frac=0.7,
        elastic_levels=(0.5, 1.0, 1.5),
        marginal_floor=0.15,
        damper_loss=0.6,
        deadline_frac=0.5,
        slo_frac=0.5,
    )
    kw.update(overrides)
    return make_trace(seed, num_jobs=num_jobs, rate=rate, **kw)


def chaos_plan(seed: int, H: int) -> FaultPlan:
    """The standard machine-incident soup (crashes + stragglers over
    correlated fault domains)."""
    return FaultPlan(
        seed=seed, until=200, crash_rate=0.02, straggler_rate=0.02,
        downtime=(2, 6),
        domains=[(h, h + 1) for h in range(0, H - 1, 2)],
        domain_correlation=0.5,
    )


# -------------------------------------------------------------- runners
def run_sim(policy_name: str, mode: str, seed: int, *, num_jobs: int = 60,
            rate: float = 3.0, faults: bool = False, metrics_mode="exact",
            backend=None, refail: float = 0.1, H: int = 6, W: int = 12,
            checkpoint_every=None, kill_at=None, max_slots: int = 2500,
            trace_cfg: TraceConfig = None, policy_kwargs=None,
            engine_kwargs=None, events=None):
    """One full engine run; returns (report, engine). ``trace_cfg``
    overrides the default ``make_trace`` stream (elastic suites pass a
    ``reshape_storm``); pdors runs calibrate prices off the same trace.
    ``events`` replaces the trace stream entirely (the elastic suite
    feeds a transformed copy of the same stream through it)."""
    tcfg = trace_cfg if trace_cfg is not None else make_trace(
        seed, num_jobs=num_jobs, rate=rate)
    cl = make_cluster(H, W, backend=backend)
    win = RollingWindow(cl)
    pkw = dict(policy_kwargs or {})
    if policy_name == "pdors":
        params = calibrate_prices(tcfg, cl, n=16)
        pol = make_policy("pdors", price_params=params, quanta=8, **pkw)
    else:
        pol = make_policy(policy_name, **pkw)
    eng = SimEngine(win, pol, seed=seed, max_slots=max_slots,
                    patience=tcfg.patience, metrics_mode=metrics_mode,
                    engine_mode=mode, refail_rate=refail,
                    checkpoint_every=checkpoint_every, kill_at=kill_at,
                    **(engine_kwargs or {}))
    ev = stream(tcfg) if events is None else events
    if faults:
        ev = merge_event_streams(ev, chaos_plan(seed, H).events(H))
    rep = eng.run(ev)
    return rep, eng


def strip_elastic(events):
    """Yield the same event stream with every job's elastic annotations
    removed — the 'static twin' of an elastic trace."""
    from dataclasses import replace
    for ev in events:
        if ev.job is not None and ev.job.elastic is not None:
            ev = replace(ev, job=replace(ev.job, elastic=None))
        yield ev


def assert_reports_identical(r1, e1, r2, e2, *, exact_outcomes=True):
    """Bit-identity across two finished runs: summary dict, slot count,
    dense ledger array, recovery journal, and (exact mode) every per-job
    outcome row."""
    assert r1.summary == r2.summary
    assert r1.slots_run == r2.slots_run
    assert np.array_equal(np.asarray(e1.window.cluster._used),
                          np.asarray(e2.window.cluster._used))
    assert e1.journal == e2.journal
    if exact_outcomes:
        assert e1.metrics.outcomes == e2.metrics.outcomes


def assert_equivalent(policy: str, seed: int, **kw):
    """Batched engine == per-event oracle, bit-for-bit."""
    r1, e1 = run_sim(policy, "event", seed, **kw)
    r2, e2 = run_sim(policy, "batched", seed, **kw)
    assert_reports_identical(
        r1, e1, r2, e2,
        exact_outcomes=kw.get("metrics_mode", "exact") == "exact",
    )
    return r1, r2
