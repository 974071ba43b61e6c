"""Elastic quality-driven jobs: conservation and parity invariants for
the reshape/re-offer path (ISSUE 10 tentpole).

The invariants, asserted across policies and both engine modes:

* a reshape-free elastic trace schedules EXACTLY like its static twin —
  same ledger, same slot count, same journal (modulo the annotation
  field), same summary outside the quality-column block;
* under reshape storms the ledger is never oversubscribed
  (``check_ledger`` is always on — a violation raises), and batched vs
  per-event engines stay bit-identical;
* ``SimEngine.recover()`` replays in-flight reshapes bit-identically.
"""
from __future__ import annotations

from dataclasses import replace

import numpy as np
import pytest
from hypothesis import given, settings

from repro.core import make_cluster
from repro.core.job import ElasticProfile, JobSpec, QualityCurve
from repro.sim import (
    RollingWindow,
    SimEngine,
    SimKilled,
    calibrate_prices,
    make_policy,
    sample_jobs,
    stream,
)

from strategies import (
    ALL_POLICIES,
    QUALITY_KEYS,
    assert_equivalent,
    make_trace,
    policies,
    reshape_storm,
    run_sim,
    seeds,
    strip_elastic,
)


# ------------------------------------------------------------ job model
def test_quality_curve_fit_recovers_truth_and_is_deterministic():
    truth = QualityCurve(a=0.8, b=1.2, c=0.1)
    pts = [(float(e), truth.loss(float(e))) for e in range(1, 9)]
    fit1 = QualityCurve.fit(pts)
    fit2 = QualityCurve.fit(list(pts))
    assert fit1 is not None and fit1 == fit2  # rng-free, input-determined
    # the fit predicts the same marginal-improvement decay the truth does
    for e in (1.0, 3.0, 6.0):
        assert fit1.marginal(e) == pytest.approx(truth.marginal(e), rel=0.35)
    assert fit1.marginal(1.0) > fit1.marginal(6.0)


def test_quality_curve_fit_degenerate_inputs():
    assert QualityCurve.fit([]) is None
    assert QualityCurve.fit([(1.0, 0.5), (2.0, 0.4)]) is None  # < 3 points
    # no epoch spread
    assert QualityCurve.fit([(2.0, 0.5), (2.0, 0.5), (2.0, 0.5)]) is None
    # non-improving losses fit a <= 0 -> rejected
    assert QualityCurve.fit([(1.0, 0.3), (2.0, 0.4), (3.0, 0.5)]) is None


def _elastic_job(levels=(0.5, 1.0, 1.5), level=1, **prof_kw) -> JobSpec:
    job = sample_jobs(make_trace(3), 1)[0]
    return replace(job, elastic=ElasticProfile(
        levels=levels, level=level,
        curve=QualityCurve(a=0.8, b=1.0, c=0.1), **prof_kw))


def test_at_level_scales_demands_ratio_based():
    job = _elastic_job()
    up = job.at_level(2)
    assert up.elastic.level == 2
    for r, v in job.worker_demand.items():
        assert up.worker_demand[r] == pytest.approx(v * 1.5)
    assert up.batch_size == max(1, int(round(job.batch_size * 1.5)))
    assert up.ps_demand == job.ps_demand and up.gamma == job.gamma
    down = job.at_level(0)
    for r, v in job.worker_demand.items():
        assert down.worker_demand[r] == pytest.approx(v * 0.5)
    with pytest.raises(ValueError):
        job.at_level(3)
    with pytest.raises(ValueError):
        replace(job, elastic=None).at_level(1)


def test_elastic_defaults_leave_stream_untouched():
    """elastic_frac=0 (default) must not consume ANY extra randomness:
    the stream is byte-identical to a config that never heard of
    elasticity — plus the knobs themselves change nothing until a
    fraction is turned on."""
    base = sample_jobs(make_trace(11), 40)
    knobbed = sample_jobs(make_trace(11, marginal_floor=0.5, damper_loss=0.9,
                                     deadline_frac=1.0, slo_frac=1.0), 40)
    assert base == knobbed
    assert all(j.elastic is None for j in base)
    annotated = sample_jobs(reshape_storm(11), 40)
    stripped = [replace(j, elastic=None) for j in annotated]
    assert stripped == base  # base draws untouched by the elastic stream
    assert any(j.elastic is not None for j in annotated)


# -------------------------------------------- reshape-free bit-identity
def _strip_quality(summary):
    return {k: v for k, v in summary.items() if k not in QUALITY_KEYS}


def _strip_journal(journal):
    return [
        replace(ev, job=replace(ev.job, elastic=None))
        if ev.job is not None and ev.job.elastic is not None else ev
        for ev in journal
    ]


@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_reshape_free_elastic_matches_static_run(policy):
    """Profiles attached but triggers disarmed: scheduling must be
    bit-identical to the same trace with the annotations stripped —
    ledger, slots, journal (modulo the annotation field), and every
    summary column outside the quality block. The quality block itself
    must show metadata flowing (deadlines/SLOs tracked, zero reshapes)."""
    cfg = reshape_storm(17, marginal_floor=0.0, damper_loss=0.0)
    r1, e1 = run_sim(policy, "batched", 17, trace_cfg=cfg)
    r2, e2 = run_sim(policy, "batched", 17, trace_cfg=cfg,
                     events=strip_elastic(stream(cfg)))
    assert _strip_quality(r1.summary) == _strip_quality(r2.summary)
    assert r1.slots_run == r2.slots_run
    assert np.array_equal(np.asarray(e1.window.cluster._used),
                          np.asarray(e2.window.cluster._used))
    assert _strip_journal(e1.journal) == e2.journal
    assert r1.summary["reshapes"] == 0
    assert r1.summary["deadline_jobs"] > 0 and r1.summary["slo_jobs"] > 0
    assert r2.summary["deadline_jobs"] == 0 and r2.summary["slo_jobs"] == 0
    assert r2.summary["reshapes"] == 0


# ---------------------------------------- reshape storms: conservation
@settings(max_examples=6)
@given(seeds(), policies(ALL_POLICIES))
def test_storm_ledger_conserved_and_engines_agree(seed, policy):
    """Property: on reshape-heavy traces the batched and per-event
    engines agree bit-for-bit, and the ledger invariant holds throughout
    (``check_ledger`` is on — an oversubscription raises
    LedgerInvariantError and fails the test)."""
    assert_equivalent(policy, seed, trace_cfg=reshape_storm(seed))


def test_storm_actually_reshapes():
    """The storm config is not vacuous: reshapes fire for the re-offer
    path (pdors) and the in-place path (fifo) alike, and the summary's
    event counter agrees with the per-outcome tally."""
    for policy in ("pdors", "fifo"):
        rep, eng = run_sim(policy, "batched", 23,
                           trace_cfg=reshape_storm(23))
        s = rep.summary
        assert s["reshapes"] > 0, policy
        assert s["events"].get("reshape", 0) == s["reshapes"]
        assert sum(oc.reshapes for oc in eng.metrics.outcomes.values()) \
            == s["reshapes"]


def test_storm_chaos_engines_agree():
    """Reshapes + machine incidents + refail cascades in one soup."""
    assert_equivalent("fifo", 29, trace_cfg=reshape_storm(29), faults=True)


def test_storm_quality_exact_vs_streaming():
    """Quality count columns are exact in streaming mode (fold-and-drop
    must not lose reshape/SLO accounting); the float mean matches to
    summation-order rounding."""
    r1, _ = run_sim("pdors", "batched", 23, trace_cfg=reshape_storm(23),
                    metrics_mode="exact")
    r2, _ = run_sim("pdors", "batched", 23, trace_cfg=reshape_storm(23),
                    metrics_mode="streaming")
    for k in ("reshapes", "deadline_jobs", "deadline_hits", "slo_jobs",
              "slo_hits", "deadline_attainment", "slo_attainment"):
        assert r1.summary[k] == r2.summary[k], k
    assert r1.summary["final_loss_mean"] == pytest.approx(
        r2.summary["final_loss_mean"])


def test_elastic_jax_backend():
    pytest.importorskip("jax")
    assert_equivalent("fifo", 2, trace_cfg=reshape_storm(2, num_jobs=30),
                      num_jobs=30, backend="jax")


# --------------------------------------------------- recovery parity
@pytest.mark.parametrize("mode", ["event", "batched"])
def test_recover_replays_inflight_reshapes_bit_identically(mode):
    """Kill the engine mid-storm (reshapes in flight: elastic state,
    requeued re-offers, cooldowns) and recover from the checkpoint: the
    finished report must equal the uninterrupted run's bit-for-bit."""
    storm = reshape_storm(37)
    ref, ref_eng = run_sim("pdors", mode, 37, trace_cfg=storm)
    assert ref.summary["reshapes"] > 0
    kill = ref.slots_run // 2
    with pytest.raises(SimKilled):
        run_sim("pdors", mode, 37, trace_cfg=storm,
                checkpoint_every=8, kill_at=kill)
    # run_sim constructed a fresh engine inside the raising call; rebuild
    # the same killed engine to recover from it
    cfg = storm
    cl = make_cluster(6, 12)
    win = RollingWindow(cl)
    pol = make_policy("pdors", price_params=calibrate_prices(cfg, cl, n=16),
                      quanta=8)
    eng = SimEngine(win, pol, seed=37, max_slots=2500, patience=cfg.patience,
                    engine_mode=mode, refail_rate=0.1,
                    checkpoint_every=8, kill_at=kill)
    with pytest.raises(SimKilled):
        eng.run(stream(cfg))
    rec = eng.recover(stream(cfg))
    assert rec.summary == ref.summary
    assert rec.slots_run == ref.slots_run
    assert np.array_equal(np.asarray(eng.window.cluster._used),
                          np.asarray(ref_eng.window.cluster._used))
    assert eng.metrics.outcomes == ref_eng.metrics.outcomes
