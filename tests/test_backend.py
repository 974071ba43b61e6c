"""Array-backend parity suite: numpy (bit-parity reference) vs jax
(device-resident ledger, tolerance parity).

Covers the ISSUE-3 backend contract:
  * backend selection (default, env var, explicit);
  * ledger op parity — commit / clamped release / advance produce equal
    ledgers on both backends;
  * repricing parity — the jitted device price tensor matches the numpy
    ``PriceTable.prewarm`` expression to float64 tolerance;
  * snapshot-bundle kernel agreement — numpy reference vs jitted jnp vs
    the Pallas masked-reduction kernel (interpret mode off-TPU);
  * golden-seed admission equivalence numpy-vs-jax across the four
    workload regimes of the vectorization golden tests;
  * ``RollingWindow.advance`` / ``release_from`` clamp invariants on both
    backends;
  * the no-host-copy regression — jit-compiled repricing stays on device
    and does not silently fall back to (re-traced or eager) host numpy;
  * full sim-trace equivalence through ``SimEngine``.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.backend import get_backend
from repro.core import (
    WorkloadConfig,
    make_cluster,
    run_pdors,
    synthetic_jobs,
)
from repro.core.job import Allocation
from repro.core.pricing import PriceTable, estimate_price_params

jax = pytest.importorskip("jax")


def small_jobs(scale=0.1, seed=3, n=8, horizon=10):
    cfg = WorkloadConfig(num_jobs=n, horizon=horizon, seed=seed,
                         batch=(30, 150), workload_scale=scale)
    return synthetic_jobs(cfg)


def decision_trace(res):
    out = []
    for r in res.records:
        slots = None
        if r.schedule is not None:
            slots = {
                t: (sorted(a.workers.items()), sorted(a.ps.items()))
                for t, a in r.schedule.slots.items()
            }
        out.append((r.job.job_id, r.admitted, slots))
    return out


# ---------------------------------------------------------------- selection
def test_default_backend_is_numpy(monkeypatch):
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    assert get_backend(None).name == "numpy"
    assert make_cluster(2, 3).backend.name == "numpy"


def test_env_var_selects_backend(monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "jax")
    assert get_backend(None).name == "jax"
    cl = make_cluster(2, 3)
    assert cl.backend.name == "jax"
    assert isinstance(cl._used, jax.Array)


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown"):
        get_backend("tpu9000")


def test_instance_passthrough():
    be = get_backend("jax")
    assert get_backend(be) is be
    assert make_cluster(2, 3, backend=be).backend is be


# ---------------------------------------------------------------- ledger ops
def test_ledger_ops_parity():
    """commit / clamped release / advance leave equal ledgers behind."""
    jobs = small_jobs()
    cln = make_cluster(4, 6, backend="numpy")
    clj = make_cluster(4, 6, backend="jax")
    a0 = Allocation(workers={0: 2, 2: 1}, ps={1: 1})
    a1 = Allocation(workers={3: 4}, ps={3: 1})
    for cl in (cln, clj):
        cl.commit(0, jobs[0], a0)
        cl.commit(2, jobs[1], a1)
        cl.commit(5, jobs[2], a0)
        cl.release(2, jobs[1], a1)         # exact inverse
        cl.advance(2)                      # rows 0-1 roll off
    un = cln.backend.to_host(cln._used)
    uj = clj.backend.to_host(clj._used)
    assert un.shape == uj.shape
    np.testing.assert_allclose(uj, un, rtol=0, atol=1e-12)
    assert (un >= 0).all() and (uj >= 0).all()
    # ledger dtype stays float64 on device (jax.enable_x64-scoped ops)
    assert clj._used.dtype == np.float64


def test_release_clamps_on_device():
    """A jax release never drives the ledger negative (clamp preserved
    even though the debug assert is numpy-only)."""
    jobs = small_jobs()
    clj = make_cluster(2, 3, backend="jax")
    alloc = Allocation(workers={0: 1}, ps={0: 1})
    clj.commit(1, jobs[0], alloc)
    clj.release(1, jobs[0], alloc)
    clj.release(1, jobs[0], alloc)         # double release: clamped, no raise
    u = clj.backend.to_host(clj._used)
    assert (u >= 0).all() and u.sum() == 0.0
    assert not clj.oversubscribed()


def test_advance_clears_whole_window():
    clj = make_cluster(2, 3, backend="jax")
    jobs = small_jobs()
    clj.commit(0, jobs[0], Allocation(workers={0: 1}, ps={1: 1}))
    clj.advance(10)                        # steps > horizon zeroes all rows
    assert clj.backend.to_host(clj._used).sum() == 0.0


# ----------------------------------------------------------------- pricing
def test_price_tensor_parity():
    jobs = small_jobs()
    cln = make_cluster(4, 6, backend="numpy")
    clj = make_cluster(4, 6, backend="jax")
    alloc = Allocation(workers={0: 3, 1: 1}, ps={2: 2})
    for cl in (cln, clj):
        cl.commit(1, jobs[0], alloc)
        cl.commit(4, jobs[1], alloc)
    params = estimate_price_params(jobs, cln, cln.horizon)
    ptn = PriceTable(params, cln)
    ptj = PriceTable(params, clj)
    ptn.prewarm()
    ptj.prewarm()
    for t in range(cln.horizon):
        np.testing.assert_allclose(
            ptj.price_matrix(t), ptn.price_matrix(t), rtol=1e-12
        )
    # the device tensor itself matches the host cache slices
    dev = clj.backend.to_host(ptj.device_tensor())
    np.testing.assert_allclose(dev[2], ptj.price_matrix(2), rtol=0)


def test_free_matrix_parity_after_mutations():
    jobs = small_jobs()
    cln = make_cluster(3, 5, backend="numpy")
    clj = make_cluster(3, 5, backend="jax")
    alloc = Allocation(workers={1: 2}, ps={2: 1})
    for cl in (cln, clj):
        cl.commit(2, jobs[0], alloc)
    for t in range(5):
        np.testing.assert_allclose(
            clj.free_matrix(t), cln.free_matrix(t), rtol=0, atol=1e-12
        )


# ---------------------------------------------------------- bundle kernels
def test_price_bundle_kernels_agree():
    from repro.kernels.pricing import (
        price_bundle_jnp,
        price_bundle_numpy,
        price_bundle_pallas,
    )

    rng = np.random.default_rng(7)
    for H, R in ((5, 4), (40, 4), (130, 6)):
        price = rng.uniform(0.1, 8.0, (H, R))
        free = rng.uniform(0.0, 30.0, (H, R))
        wdem = rng.uniform(0.0, 3.0, R) * (rng.random(R) > 0.3)
        sdem = rng.uniform(0.0, 3.0, R) * (rng.random(R) > 0.3)
        gamma = 4.0
        ref = price_bundle_numpy(price, free, wdem, sdem, gamma)
        with jax.enable_x64(True):
            jn = price_bundle_jnp(price, free, wdem, sdem, gamma)
        pl = price_bundle_pallas(price, free, wdem, sdem, gamma)
        for a, b in zip(ref, jn):
            np.testing.assert_allclose(b, a, rtol=1e-9)
        for a, b in zip(ref[:3], pl[:3]):
            np.testing.assert_allclose(b, a, rtol=2e-4, atol=2e-4)
        for a, b in zip(ref[3:], pl[3:]):
            # head-room counts are integer decisions: exact, never f32
            np.testing.assert_array_equal(b, a)
    # a float32 ratio would overestimate this head-room by a whole unit
    # (free=8.9999999 rounds to 9.0f; 3 workers need 9.0 > free): the
    # pallas path must keep the float64 answer
    price1 = np.ones((1, 1))
    free_edge = np.array([[8.9999999]])
    dem3 = np.array([3.0])
    ref_mw = price_bundle_numpy(price1, free_edge, dem3, dem3, 1.0)[3]
    pal_mw = price_bundle_pallas(price1, free_edge, dem3, dem3, 1.0)[3]
    assert ref_mw[0] == 2.0 and pal_mw[0] == 2.0
    # all-zero demand: head-room is +inf on every path
    z = np.zeros(4)
    for fn in (price_bundle_numpy, price_bundle_pallas):
        out = fn(np.ones((3, 4)), np.ones((3, 4)), z, z, 2.0)
        assert np.isinf(out[3]).all() and np.isinf(out[4]).all()


# ------------------------------------------------------ golden equivalence
@pytest.mark.parametrize("scale,seed", [
    (0.1, 3), (0.05, 11), (0.3, 7), (0.003, 0),
])
def test_golden_admission_equivalence_numpy_vs_jax(scale, seed):
    """The four golden workload regimes of the vectorization parity tests:
    the jax backend must reproduce the numpy backend's admissions,
    per-slot allocations, and (to tolerance) total utility."""
    jobs = small_jobs(scale=scale, seed=seed, n=8, horizon=10)
    vec = run_pdors(jobs, make_cluster(6, 10, backend="numpy"),
                    quanta=8, seed=0)
    dev = run_pdors(jobs, make_cluster(6, 10, backend="jax"),
                    quanta=8, seed=0)
    assert decision_trace(vec) == decision_trace(dev)
    assert dev.total_utility == pytest.approx(vec.total_utility, rel=1e-9)


# ------------------------------------------------------------ rolling window
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_rolling_window_release_clamp_invariants(backend):
    """Commit a forward schedule, slide the window, release the tail:
    the ledger never goes negative, never oversubscribes, and fully
    releasing a job restores the free capacity of its remaining rows."""
    from repro.sim import RollingWindow

    jobs = small_jobs()
    cl = make_cluster(3, 6, backend=backend)
    win = RollingWindow(cl)
    job = jobs[0]
    alloc = Allocation(workers={0: 2, 1: 1}, ps={2: 1})
    win.commit_schedule(job, {0: alloc, 2: alloc, 4: alloc})
    assert not win.oversubscribed()
    win.advance_to(1)                       # row 0 rolls off for free
    assert win.alloc_at(job.job_id, 0) is None
    assert win.alloc_at(job.job_id, 2) is not None
    free_before = cl.free_matrix(win.rel(2)).copy()
    released = win.release_from(job.job_id, 2)
    assert released == 2                    # abs slots 2 and 4
    u = cl.backend.to_host(cl._used)
    assert (u >= -1e-9).all()
    assert u.sum() == pytest.approx(0.0, abs=1e-9)
    assert not win.oversubscribed()
    free_after = cl.free_matrix(win.rel(2))
    assert (free_after >= free_before - 1e-9).all()
    # releasing again is a no-op, not a negative ledger
    assert win.release_from(job.job_id, 0) == 0
    assert cl.backend.to_host(cl._used).sum() == pytest.approx(0.0, abs=1e-9)


# ----------------------------------------------------------- no host copy
def test_jit_repricing_stays_on_device():
    """The no-host-copy regression: repeated repricings at a fixed shape
    must neither leave the device nor re-trace the jitted functions —
    a silent numpy fallback (or a retrace storm) fails here."""
    be = get_backend("jax")
    jobs = small_jobs()
    cl = make_cluster(4, 6, backend="jax")
    params = estimate_price_params(jobs, cl, cl.horizon)
    pt = PriceTable(params, cl)
    alloc = Allocation(workers={0: 1}, ps={1: 1})

    dev = pt.device_tensor()                # may compile once
    assert isinstance(dev, jax.Array)
    assert isinstance(cl.device_free_tensor(), jax.Array)
    traces_price = be.trace_counts["price_tensor"]
    traces_free = be.trace_counts["free_tensor"]
    for t in range(3):                      # reprice after each admission
        cl.commit(t, jobs[t], alloc)
        dev = pt.device_tensor()
        assert isinstance(dev, jax.Array)
        assert isinstance(cl.device_free_tensor(), jax.Array)
        pt.prewarm()                        # the one host sync per version
    assert be.trace_counts["price_tensor"] == traces_price
    assert be.trace_counts["free_tensor"] == traces_free
    # version-cached: no recompute without a ledger mutation
    assert pt.device_tensor() is dev


# ------------------------------------------------------------- sim parity
def test_sim_trace_equivalence_numpy_vs_jax():
    """A full event-driven trace (completions + failures/preemption)
    produces the same engine-level outcome on both backends."""
    from repro.core import make_cluster as mk
    from repro.sim import (
        RollingWindow,
        SimEngine,
        TraceConfig,
        calibrate_prices,
        make_policy,
        stream,
    )

    summaries = {}
    for backend in ("numpy", "jax"):
        tcfg = TraceConfig(preset="google", num_jobs=15, failure_rate=0.1,
                           seed=1)
        cluster = mk(4, 8, backend=backend)
        window = RollingWindow(cluster)
        policy = make_policy(
            "pdors", price_params=calibrate_prices(tcfg, cluster), quanta=8
        )
        rep = SimEngine(window, policy, patience=tcfg.patience).run(
            stream(tcfg)
        )
        summaries[backend] = rep.summary
    a, b = summaries["numpy"], summaries["jax"]
    for k in ("jobs_admitted", "jobs_completed", "admission_rate",
              "completion_rate", "jct_p50", "jct_p95"):
        assert a[k] == b[k], k
    assert b["total_utility"] == pytest.approx(a["total_utility"], rel=1e-9)
