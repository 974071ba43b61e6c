"""Tests for the repro.obs observability layer: tracer bit-parity with
instrumentation on vs off, span-tree well-formedness under exceptions,
registry semantics + Prometheus rendering, recover()-determinism of the
published gauges, primal-dual gap telemetry, P-squared streaming
quantiles, the device launch/sync spans of the jax path, and the spans'
mirror in the profiler trace."""
from collections import Counter
from contextlib import nullcontext

import numpy as np
import pytest

from repro.core import (
    PDORS,
    Allocation,
    JobSpec,
    SigmoidUtility,
    SubproblemConfig,
    WorkloadConfig,
    estimate_price_params,
    make_cluster,
    synthetic_jobs,
)
from repro.core.subproblem import SolverFault
from repro.obs import PDGapTracker, Tracer, get_registry
from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsRegistry, warn_once_event
from repro.sim import (
    Event,
    EventKind,
    LedgerInvariantError,
    RollingWindow,
    SimEngine,
    SimKilled,
    SolverFaultInjector,
    TraceConfig,
    calibrate_prices,
    make_policy,
    stream,
)
from repro.sim.metrics import MetricsCollector, P2Quantile
from repro.sim.policy import Decision, SchedulingPolicy


def small_job(job_id=0, arrival=0, V=2000, F=16, gamma=2.0, **kw):
    defaults = dict(
        epochs=1, num_samples=V, batch_size=F, tau=1e-3, grad_size=100.0,
        gamma=gamma, bw_internal=1e6, bw_external=2e5,
        worker_demand={"gpu": 1.0, "cpu": 2.0, "mem": 4.0, "storage": 1.0},
        ps_demand={"gpu": 0.0, "cpu": 2.0, "mem": 4.0, "storage": 1.0},
        utility=SigmoidUtility(theta1=50.0, theta2=0.5, theta3=5.0),
    )
    defaults.update(kw)
    return JobSpec(job_id=job_id, arrival=arrival, **defaults)


def _fingerprint(records):
    """Full decision fingerprint: admission, utility, and the exact
    committed slot allocations."""
    out = []
    for r in records:
        slots = None
        if r.schedule is not None:
            slots = tuple(
                (t, tuple(sorted(a.workers.items())),
                 tuple(sorted(a.ps.items())))
                for t, a in sorted(r.schedule.slots.items())
            )
        out.append((r.job.job_id, r.admitted, r.utility, slots))
    return out


def _run_offers(H, T, N, scale, rng_mode, seed=0, tracer=None, cfg_kw=None,
                backend="numpy"):
    wcfg = WorkloadConfig(num_jobs=N, horizon=T, seed=seed,
                          workload_scale=scale)
    jobs = sorted(synthetic_jobs(wcfg), key=lambda j: (j.arrival, j.job_id))
    cluster = make_cluster(H, T, backend=backend)
    params = estimate_price_params(jobs, cluster, cluster.horizon)
    sched = PDORS(cluster, params,
                  cfg=SubproblemConfig(rng_mode=rng_mode, **(cfg_kw or {})),
                  quanta=16, seed=seed)
    ctx = (obs_trace.activate(tracer) if tracer is not None
           else nullcontext())
    with ctx:
        for job in jobs:
            sched.offer(job)
    return _fingerprint(sched.records)


# --------------------------------------------------------- bit parity
# four workload regimes: online many-small-jobs, heavy LP-bound
# contention, a mid mix, and an oversized mix where most thetas are
# external — crossed with both rounding-rng disciplines
REGIMES = [(5, 8, 8, 0.003), (5, 8, 8, 0.3), (8, 10, 10, 0.05),
           (6, 12, 9, 0.5)]


#: the jax backend on the CPU, with the Pallas min-plus kernel (interpret
#: mode) so that every device span of the path is on
JAX_PALLAS = dict(backend="jax", cfg_kw=dict(minplus_backend="pallas"))


@pytest.mark.parametrize("rng_mode", ["compat", "derived"])
@pytest.mark.parametrize("H,T,N,scale,path", [
    *[(*regime, "numpy") for regime in REGIMES],
    (5, 8, 6, 0.003, "jax"), (8, 10, 8, 0.05, "jax"),
])
def test_tracing_never_changes_decisions(H, T, N, scale, path, rng_mode):
    kw = JAX_PALLAS if path == "jax" else {}
    base = _run_offers(H, T, N, scale, rng_mode, **kw)
    tracer = Tracer()
    traced = _run_offers(H, T, N, scale, rng_mode, tracer=tracer, **kw)
    assert traced == base               # bit-identical, slot-for-slot
    assert tracer.spans, "tracing enabled but no spans recorded"
    assert tracer.well_formed()
    if path == "jax":
        names = {sp.name for sp in tracer.spans}
        assert {"device.launch", "device.sync"} <= names


def test_offer_span_tree_shape():
    tracer = Tracer()
    _run_offers(5, 8, 8, 0.3, "compat", tracer=tracer)
    names = {sp.name for sp in tracer.spans}
    assert "offer" in names and "offer.schedule" in names
    # every root is an offer; offer.schedule nests strictly inside it
    for sp in tracer.spans:
        if sp.parent < 0:
            assert sp.name == "offer"
        if sp.name == "offer.schedule":
            assert tracer.spans[sp.parent].name == "offer"
    # self-times partition wall: sum over the table == root durations
    table = tracer.phase_table()
    assert sum(row["self_s"] for row in table.values()) == pytest.approx(
        tracer.total_self_s())


def test_plan_finish_span_counts_headroom_work():
    """Each plan.finish span says how much head-room work its finish did:
    at most two zero-load rows a slot (one a pass) and two loaded cells a
    candidate machine. On a light cluster the candidates load few of the
    machines, so the loaded cells are far below candidates x H. The
    fills read only a prefix of each slot's price order, so the
    zero-load columns computed are far below the dense 2 x slots x H, and
    at most every candidate widens its first prefix."""
    H, T = 64, 10
    tracer = Tracer()
    _run_offers(H, T, 8, 0.05, "compat", tracer=tracer)
    finishes = [sp.attrs for sp in tracer.spans if sp.name == "plan.finish"]
    assert finishes
    for at in finishes:
        cands = at["candidates"]
        assert 0 <= at["head_rows"] <= 2 * min(cands, T)
        assert 0 <= at["head_cells"] <= 2 * cands * H
        assert 0 <= at["head_cols"] <= 2 * cands * H
        assert 0 <= at["head_widened"] <= cands
    cands = sum(at["candidates"] for at in finishes)
    cells = sum(at["head_cells"] for at in finishes)
    assert cands > 0 and sum(at["head_rows"] for at in finishes) > 0
    assert 0 < cells < cands * H / 4
    cols = sum(at["head_cols"] for at in finishes)
    dense = sum(2 * min(at["candidates"], T) * H for at in finishes)
    assert 0 < cols < dense / 8


# ------------------------------------------- exception well-formedness
def test_span_tree_well_formed_under_solver_fault():
    tracer = Tracer()
    with pytest.raises(SolverFault):
        _run_offers(
            5, 8, 8, 0.3, "compat", tracer=tracer,
            cfg_kw=dict(lp_fault_hook=SolverFaultInjector(rate=1.0, seed=0)),
        )
    assert tracer.well_formed()
    assert any(sp.attrs.get("error") == "SolverFault"
               for sp in tracer.spans)


def test_span_tree_well_formed_under_ledger_invariant_error():
    class Rogue(SchedulingPolicy):
        reoffers_on_preempt = True

        def on_arrivals(self, event, view):
            dec = Decision()
            for job in event.jobs:
                view.commit(view.now, job,
                            Allocation(workers={0: 1000}, ps={0: 1}))
                dec.admitted[job.job_id] = True
            return dec

    tracer = Tracer()
    eng = SimEngine(RollingWindow(make_cluster(2, 6)), Rogue(),
                    max_slots=10, trace=tracer)
    with pytest.raises(LedgerInvariantError):
        eng.run([Event(time=0, kind=EventKind.ARRIVAL, job=small_job())])
    # the invariant check fires inside the slot's sim.slot span, which
    # records the error — the contract is that the unwind leaves the
    # tree closed
    assert tracer.spans
    assert tracer.well_formed()


# ------------------------------------------------- recover determinism
def _sim_engine(tcfg, params, **eng_kw):
    cl = make_cluster(4, 12)
    return SimEngine(
        RollingWindow(cl),
        make_policy("pdors", price_params=params, quanta=8),
        seed=3, max_slots=600, patience=tcfg.patience, **eng_kw)


def test_registry_and_pd_gap_deterministic_under_recover():
    tcfg = TraceConfig(num_jobs=12, seed=3, arrival_rate=0.6,
                       failure_rate=0.2)
    params = calibrate_prices(tcfg, make_cluster(4, 12), n=16)

    def pd_gauges():
        return {k: v for k, v in get_registry().snapshot().items()
                if k.startswith("repro_pd_")}

    get_registry().reset()
    base = _sim_engine(tcfg, params).run(stream(tcfg))
    base_gauges = pd_gauges()

    get_registry().reset()
    tracer = Tracer()
    eng = _sim_engine(tcfg, params, checkpoint_every=4, kill_at=10,
                      trace=tracer)
    with pytest.raises(SimKilled):
        eng.run(stream(tcfg))
    assert tracer.well_formed()         # SimKilled unwound cleanly
    rep = eng.recover(stream(tcfg))
    assert tracer.well_formed()
    assert rep.summary == base.summary
    assert rep.pd_gap == base.pd_gap
    assert pd_gauges() == base_gauges   # gauges set from recovered state


# ------------------------------------------------------------ registry
def test_registry_instruments_and_render():
    reg = MetricsRegistry()
    reg.counter("repro_x_total", "events").inc()
    reg.counter("repro_x_total").inc(2)
    reg.gauge("repro_g").set(2.5)
    h = reg.histogram("repro_h", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["repro_x_total"] == 3
    assert snap["repro_g"] == 2.5
    assert snap["repro_h_count"] == 3
    assert snap["repro_h_sum"] == pytest.approx(5.55)
    text = reg.render()
    assert "# TYPE repro_x_total counter" in text
    assert "# HELP repro_x_total events" in text
    assert "# TYPE repro_g gauge" in text
    assert 'repro_h_bucket{le="0.1"} 1' in text
    assert 'repro_h_bucket{le="1"} 2' in text
    assert 'repro_h_bucket{le="+Inf"} 3' in text
    assert reg.value("repro_g") == 2.5
    assert reg.value("missing", default=-1.0) == -1.0
    with pytest.raises(TypeError):
        reg.gauge("repro_x_total")      # kind mismatch at the same name


def test_warn_once_event_counts_every_hit_logs_once(caplog):
    reg = get_registry()
    before = reg.value("repro_test_fallback_total")
    with caplog.at_level("WARNING", logger="repro.obs"):
        warn_once_event("repro_test_fallback_total", "test:unique-key-a",
                        "fallback engaged", kernel="unit")
        warn_once_event("repro_test_fallback_total", "test:unique-key-a",
                        "fallback engaged", kernel="unit")
    assert reg.value("repro_test_fallback_total") == before + 2
    hits = [r for r in caplog.records if "fallback engaged" in r.message]
    assert len(hits) == 1               # one structured record per key


# ------------------------------------------------------------- pd gap
def test_pd_gap_tracker_math_and_publish():
    gap = PDGapTracker()                # unbound: price term is zero
    gap.record_offer(True, payoff=3.0, utility=5.0)
    gap.record_offer(False, payoff=9.0, utility=9.0)   # rejected: ignored
    gap.record_offer(True, payoff=-1.0, utility=2.0)   # payoff clamps at 0
    snap = gap.snapshot()
    assert snap["pd_offers"] == 3 and snap["pd_admits"] == 2
    assert snap["pd_primal"] == 7.0
    assert snap["pd_dual"] == 3.0
    assert snap["duality_gap"] == -4.0
    assert snap["empirical_ratio"] == pytest.approx(3.0 / 7.0)
    reg = MetricsRegistry()
    gap.publish(reg)
    assert reg.value("repro_pd_primal") == 7.0

    empty = PDGapTracker().snapshot()
    assert empty["empirical_ratio"] is None   # no admitted primal yet


def test_pd_gap_dual_bounds_primal_on_real_run():
    """Weak duality end-to-end: D >= P on a real offer stream, and the
    empirical ratio is a tighter certificate than the worst-case bound."""
    wcfg = WorkloadConfig(num_jobs=10, horizon=10, seed=1,
                          workload_scale=0.08)
    jobs = sorted(synthetic_jobs(wcfg), key=lambda j: (j.arrival, j.job_id))
    cluster = make_cluster(6, 10)
    params = estimate_price_params(jobs, cluster, cluster.horizon)
    sched = PDORS(cluster, params, quanta=16, seed=1)
    for job in jobs:
        sched.offer(job)
    snap = sched.pd_gap.snapshot()
    assert snap["pd_offers"] == len(jobs)
    assert snap["pd_dual"] >= snap["pd_primal"]
    assert snap["duality_gap"] >= 0.0
    if snap["empirical_ratio"] is not None:
        assert snap["empirical_ratio"] >= 1.0
        assert snap["ratio_bound"] > 0.0


# ------------------------------------------------------------ P-squared
@pytest.mark.parametrize("draw", [
    lambda rng, n: rng.exponential(10.0, n),
    lambda rng, n: rng.uniform(0.0, 100.0, n),
    lambda rng, n: np.abs(rng.normal(50.0, 15.0, n)),
])
@pytest.mark.parametrize("p", [0.5, 0.95])
def test_p2_quantile_tracks_exact_percentile(draw, p):
    xs = draw(np.random.default_rng(7), 4000)
    est = P2Quantile(p)
    for x in xs:
        est.observe(x)
    exact = float(np.percentile(xs, p * 100.0))
    assert abs(est.value() - exact) <= 0.05 * exact + 0.5


def test_p2_quantile_exact_below_five_observations():
    est = P2Quantile(0.5)
    assert est.value() == 0.0
    for x in (5.0, 1.0, 3.0):
        est.observe(x)
    assert est.value() == pytest.approx(np.percentile([5.0, 1.0, 3.0], 50))
    with pytest.raises(ValueError):
        P2Quantile(1.5)


def test_streaming_collector_matches_exact_summary_schema():
    def run(mode):
        tcfg = TraceConfig(num_jobs=40, seed=2, arrival_rate=1.5,
                           failure_rate=0.1)
        cl = make_cluster(4, 12)
        params = calibrate_prices(tcfg, cl, n=16)
        eng = SimEngine(
            RollingWindow(cl),
            make_policy("pdors", price_params=params, quanta=8),
            seed=2, max_slots=600, patience=tcfg.patience,
            metrics_mode=mode)
        return eng.run(stream(tcfg))

    exact = run("exact")
    stream_rep = run("streaming")
    es, ss = exact.summary, stream_rep.summary
    assert set(es) == set(ss)
    approx_keys = {"jct_p50", "jct_p95", "queue_delay_p50",
                   "queue_delay_p95", "utilization_mean",
                   "utilization_busy_mean", "goodput_samples",
                   "wasted_samples", "goodput_fraction", "total_utility",
                   "jct_mean"}
    for k in set(es) - approx_keys:
        assert ss[k] == es[k], k        # censoring/count columns exact
    for k in ("total_utility", "jct_mean", "goodput_samples",
              "goodput_fraction"):
        assert ss[k] == pytest.approx(es[k], rel=1e-9)
    for k in ("jct_p50", "jct_p95"):    # P-squared estimates
        assert abs(ss[k] - es[k]) <= 0.35 * es[k] + 2.5
    # streaming mode actually dropped the completed outcome rows
    assert len(stream_rep.metrics.outcomes) < len(exact.metrics.outcomes)
    assert exact.metrics.jct_cdf()[0]   # exact CDF still available
    assert stream_rep.metrics.jct_cdf()[0]   # reservoir-backed CDF

    with pytest.raises(ValueError):
        MetricsCollector(["gpu"], mode="bogus")


# --------------------------------------------------- device boundary
def _enclosing(tracer, sp, name):
    """The nearest enclosing span called ``name``, or None."""
    while sp.parent >= 0:
        sp = tracer.spans[sp.parent]
        if sp.name == name:
            return sp
    return None


def test_minplus_syncs_match_each_dp_sweeps_steps():
    """The min-plus kernel's launches and syncs under each dp.sweep match
    its device calls: one of each for the whole sweep, however many steps
    it holds, each a leaf with the site minplus_sweep."""
    tracer = Tracer()
    _run_offers(5, 8, 6, 0.05, "compat", tracer=tracer, **JAX_PALLAS)
    sweeps = [sp for sp in tracer.spans if sp.name == "dp.sweep"]
    assert sweeps
    assert any(sw.attrs["slots"] > 1 for sw in sweeps)
    per_sweep = {sp.index: Counter() for sp in sweeps}
    for sp in tracer.spans:
        if sp.name in ("device.launch", "device.sync"):
            assert "site" in sp.attrs
            assert all(c.parent != sp.index for c in tracer.spans)  # leaf
            assert sp.attrs["site"] != "minplus"    # no per-step round trip
            if sp.attrs["site"] == "minplus_sweep":
                owner = _enclosing(tracer, sp, "dp.sweep")
                assert owner is not None
                per_sweep[owner.index][sp.name] += 1
    for sw in sweeps:
        assert sw.attrs["device_calls"] == 1
        assert per_sweep[sw.index]["device.sync"] == sw.attrs["device_calls"]
        assert per_sweep[sw.index]["device.launch"] == sw.attrs["device_calls"]


@pytest.mark.parametrize("backend,device_calls", [("pallas", 1),
                                                  ("numpy", 0)])
def test_dp_sweep_device_spans_by_backend(backend, device_calls):
    """One traced dp.sweep: on the pallas backend it holds exactly one
    device.launch and one device.sync, both of site minplus_sweep, and
    says device_calls=1; on the numpy backend it holds no device span."""
    from repro.core.dp import WorkloadDP
    from repro.core.pricing import PriceTable

    T = 8
    jobs = synthetic_jobs(WorkloadConfig(num_jobs=2, horizon=T, seed=1,
                                         batch=(20, 100),
                                         workload_scale=0.05))
    cluster = make_cluster(5, T)
    prices = PriceTable(estimate_price_params(jobs, cluster, T), cluster)
    dp = WorkloadDP(jobs[0], cluster, prices, quanta=8,
                    cfg=SubproblemConfig(minplus_backend=backend))
    tracer = Tracer()
    with obs_trace.activate(tracer):
        dp.solve_prefix(T - 1)
    assert tracer.well_formed()
    (sweep,) = [sp for sp in tracer.spans if sp.name == "dp.sweep"]
    assert sweep.attrs["slots"] == T - jobs[0].arrival > 1
    assert sweep.attrs["device_calls"] == device_calls
    inside = Counter((sp.name, sp.attrs.get("site"))
                     for sp in tracer.spans
                     if sp.name.startswith("device.")
                     and _enclosing(tracer, sp, "dp.sweep") is sweep)
    want = {("device.launch", "minplus_sweep"): 1,
            ("device.sync", "minplus_sweep"): 1} if device_calls else {}
    assert inside == want


def test_device_spans_nest_under_their_layer_span():
    """The engine's ledger advance launches under sim.advance and its
    oversubscription check under sim.slot; every span of the slot loop
    hangs under one sim.slot per iteration."""
    tcfg = TraceConfig(num_jobs=8, seed=3, arrival_rate=0.6)
    cl = make_cluster(4, 12, backend="jax")
    params = calibrate_prices(tcfg, make_cluster(4, 12), n=16)
    tracer = Tracer()
    eng = SimEngine(RollingWindow(cl),
                    make_policy("pdors", price_params=params, quanta=8),
                    seed=3, max_slots=200, patience=tcfg.patience,
                    engine_mode="batched", trace=tracer)
    eng.run(stream(tcfg))
    assert tracer.well_formed()
    # every root until the loop ends is a slot (the run's summary reads
    # prices after it)
    roots = [sp.name for sp in tracer.spans if sp.parent < 0]
    last = len(roots) - roots[::-1].index("sim.slot")
    assert set(roots[:last]) == {"sim.slot"}
    sites = Counter((tracer.spans[sp.parent].name, sp.name, sp.attrs["site"])
                    for sp in tracer.spans if sp.name.startswith("device."))
    assert sites[("sim.advance", "device.launch", "ledger_advance")] > 0
    assert sites[("sim.slot", "device.sync", "oversubscribed")] > 0
    assert any(sp.name == "offer.batch"
               and tracer.spans[sp.parent].name == "sim.arrivals"
               for sp in tracer.spans)


def test_profiler_host_plane_holds_every_span(tmp_path):
    """With a tracer installed, each span opens a TraceAnnotation of its
    name: the captured .xplane.pb's host plane holds one event per span."""
    import glob

    import jax
    from jax.profiler import ProfileData

    tracer = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _run_offers(5, 8, 4, 0.05, "compat", tracer=tracer, **JAX_PALLAS)
    finally:
        jax.profiler.stop_trace()
    want = Counter(sp.name for sp in tracer.spans)
    assert want["device.sync"] and want["dp.sweep"]
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    got = Counter()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                got.update(ev.name for ev in line.events if ev.name in want)
    assert got == want


def test_no_tracer_device_sites_are_the_shared_noop(monkeypatch):
    """Off: the launch/sync helpers return the shared no-op span, a host
    array passes device_get untouched, and no annotation is created."""
    made = []

    class Annotation:
        def __init__(self, name):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    import jax  # noqa: F401  (the annotation is only looked up with jax)
    monkeypatch.setattr(obs_trace, "_annotation_cls", Annotation)
    prev = obs_trace.get_tracer()
    obs_trace.install(None)
    try:
        assert obs_trace.launch("minplus") is obs_trace._NULL_SPAN
        assert obs_trace.sync("to_host:price") is obs_trace._NULL_SPAN
        host = np.arange(3.0)
        assert obs_trace.device_get(host, "x") is host
        _run_offers(5, 8, 4, 0.05, "compat", **JAX_PALLAS)
        assert made == []
        tracer = Tracer()
        _run_offers(5, 8, 4, 0.05, "compat", tracer=tracer, **JAX_PALLAS)
        assert made == [sp.name for sp in tracer.spans]
    finally:
        obs_trace.install(prev)


def test_traced_numpy_run_never_imports_jax():
    """repro.obs.trace imports without jax, and a traced numpy-backend run
    mirrors nothing into a profiler: jax stays unimported."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    code = (
        "import sys\n"
        "from repro.obs import Tracer, trace\n"
        "from repro.core import PDORS, make_cluster, synthetic_jobs, "
        "WorkloadConfig, estimate_price_params\n"
        "jobs = synthetic_jobs(WorkloadConfig(num_jobs=4, horizon=8, seed=0,"
        " workload_scale=0.05))\n"
        "cl = make_cluster(5, 8)\n"
        "sched = PDORS(cl, estimate_price_params(jobs, cl, 8), quanta=8)\n"
        "tracer = Tracer()\n"
        "with trace.activate(tracer):\n"
        "    for job in jobs:\n"
        "        sched.offer(job)\n"
        "assert tracer.spans and trace._annotation_cls is None\n"
        "print('jax' in sys.modules)\n")
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, PYTHONPATH=src, REPRO_TRACE="")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


# ------------------------------------------------------- off-mode API
def test_disabled_mode_is_a_shared_noop_singleton():
    prev = obs_trace.get_tracer()
    obs_trace.install(None)
    try:
        assert not obs_trace.enabled()
        s1 = obs_trace.span("offer")
        s2 = obs_trace.span("lp.solve", k=1)
        assert s1 is s2                 # one shared null span, no alloc
        with s1 as sp:
            sp.set(a=1).add("b", 2.0)   # all no-ops, chainable
        obs_trace.annotate(x=1)
        obs_trace.add("y", 1.0)
    finally:
        obs_trace.install(prev)
