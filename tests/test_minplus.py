"""Property tests for the min-plus (tropical) DP step kernels: the NumPy
and Pallas implementations must agree with the scalar reference on random
instances, including +inf (unreachable-state) patterns; the fused Pallas
sweep must equal its single step repeated, bit for bit."""
import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.minplus import (
    MAX_P,
    default_backend,
    minplus_numpy,
    minplus_pallas,
    minplus_scalar,
    minplus_step,
    minplus_sweep_pallas,
)


def _random_instance(rng, n, inf_frac=0.2):
    prev = rng.uniform(0.0, 100.0, n)
    tcost = rng.uniform(0.0, 100.0, n)
    prev[rng.random(n) < inf_frac] = np.inf
    tcost[rng.random(n) < inf_frac] = np.inf
    prev[0] = 0.0 if rng.random() < 0.5 else prev[0]
    tcost[0] = 0.0  # v=0 always costs nothing in the DP
    return prev, tcost


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10_000), st.integers(2, 48))
def test_property_numpy_matches_scalar(seed, n):
    """NumPy step must be BIT-identical to the scalar reference — values
    and backtracking choices — since the DP cost table feeds exact-equality
    admission parity."""
    rng = np.random.default_rng(seed)
    prev, tcost = _random_instance(rng, n)
    cs, chs = minplus_scalar(prev, tcost)
    cn, chn = minplus_numpy(prev, tcost)
    np.testing.assert_array_equal(cn, cs)
    np.testing.assert_array_equal(chn, chs)


def test_numpy_replays_scalar_hysteresis_in_near_ties():
    """The scalar loop's 1e-12 acceptance hysteresis keeps the FIRST
    candidate when a later one is less than 1e-12 better; the vectorized
    path must reproduce that value, not the true minimum."""
    prev = np.array([0.0, 0.3, 0.6000000000000001])
    tcost = np.array([0.0, 0.30000000000000004, 0.6])
    cs, chs = minplus_scalar(prev, tcost)
    cn, chn = minplus_numpy(prev, tcost)
    np.testing.assert_array_equal(cn, cs)
    np.testing.assert_array_equal(chn, chs)
    assert cs[2] == 0.6000000000000001  # hysteresis keeps v=0, not 0.6


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_property_pallas_matches_scalar(seed):
    rng = np.random.default_rng(seed)
    prev, tcost = _random_instance(rng, 33)
    cs, chs = minplus_scalar(prev, tcost)
    cp, chp = minplus_pallas(prev, tcost, interpret=True)
    # float32 kernel accumulation
    finite = np.isfinite(cs)
    assert (np.isfinite(cp) == finite).all()
    np.testing.assert_allclose(cp[finite], cs[finite], rtol=2e-6, atol=2e-4)
    assert ((chp < 0) == (chs < 0)).all()
    for u in np.flatnonzero(chp >= 0):
        v = int(chp[u])
        assert prev[u - v] + tcost[v] == pytest.approx(cs[u], rel=2e-6, abs=2e-4)


def test_pallas_rejects_width_above_max():
    """Above MAX_P padded states the kernel would overflow VMEM: the
    wrapper refuses with a clear error instead of a compiler failure."""
    n = MAX_P + 1
    with pytest.raises(ValueError, match="MAX_P"):
        minplus_pallas(np.zeros(n), np.zeros(n), interpret=True)


def _random_sweep(rng, k, n, inf_frac=0.2, start="random"):
    """A start row and k theta-cost rows as the DP feeds them."""
    prev = np.full(n, np.inf)
    if start == "random":
        prev, _ = _random_instance(rng, n, inf_frac)
    elif start == "dp":
        prev[0] = 0.0
    tcosts = rng.uniform(0.0, 1e6, (k, n))
    tcosts[rng.random((k, n)) < inf_frac] = np.inf
    tcosts[:, 0] = 0.0
    return prev, tcosts


@pytest.mark.parametrize("k,n,inf_frac,start", [
    (1, 17, 0.2, "random"),
    (2, 17, 0.5, "dp"),
    (5, 17, 0.2, "random"),         # 5 steps in a bucket of 8
    (17, 33, 0.3, "dp"),            # 17 steps in a bucket of 32
    (64, 17, 0.2, "dp"),            # the benchmark's W = 64, Q = 16
    (3, 130, 0.4, "random"),        # two row tiles
    (9, 17, 0.2, "unreachable"),    # no state reachable at the start
    (4, 9, 1.0, "dp"),              # every level but v = 0 infeasible
])
def test_sweep_matches_repeated_single_steps(k, n, inf_frac, start):
    """The fused sweep equals k calls of one step, each fed the previous
    step's output: ``best`` and ``choice`` bit for bit."""
    rng = np.random.default_rng(1000 * k + n)
    prev, tcosts = _random_sweep(rng, k, n, inf_frac, start)
    best, choice = minplus_sweep_pallas(prev, tcosts, interpret=True)
    assert best.shape == choice.shape == (k, n)
    row = prev
    for i in range(k):
        b, ch = minplus_pallas(row, tcosts[i], interpret=True)
        np.testing.assert_array_equal(best[i], b)
        np.testing.assert_array_equal(choice[i], ch)
        row = b
    if start == "unreachable":
        assert np.isinf(best).all() and (choice == -1).all()


def test_sweep_choice_in_blocks_matches_one_block(monkeypatch):
    """The host rebuilds ``choice`` in blocks of steps (bounded memory at
    large Q): blocks of 3 over 17 steps, a ragged last block included,
    give the table of one block."""
    from repro.kernels import minplus

    rng = np.random.default_rng(11)
    prev, tcosts = _random_sweep(rng, 17, 17, 0.3, "dp")
    best, choice = minplus_sweep_pallas(prev, tcosts, interpret=True)
    monkeypatch.setattr(minplus, "_CHOICE_BLOCK", 3 * 17 * 17)
    best3, choice3 = minplus_sweep_pallas(prev, tcosts, interpret=True)
    np.testing.assert_array_equal(best3, best)
    np.testing.assert_array_equal(choice3, choice)


def test_sweep_matches_numpy_steps_within_float32():
    """Against the float64 NumPy step chained k times: the same reachable
    states and values within float32 accumulation."""
    rng = np.random.default_rng(7)
    prev, tcosts = _random_sweep(rng, 12, 17, 0.2, "dp")
    tcosts[:, 1:] /= 1e4
    best, choice = minplus_sweep_pallas(prev, tcosts, interpret=True)
    row = prev
    for i in range(len(tcosts)):
        row, ch = minplus_numpy(row, tcosts[i])
        finite = np.isfinite(row)
        assert (np.isfinite(best[i]) == finite).all()
        np.testing.assert_allclose(best[i][finite], row[finite],
                                   rtol=2e-6, atol=2e-4)
        assert ((choice[i] < 0) == (ch < 0)).all()


def test_pallas_solve_prefix_matches_per_step_loop():
    """``WorkloadDP.solve_prefix`` on the pallas backend (one fused sweep)
    gives the C and choice tables of a per-step loop of single Pallas
    steps over the same memoized theta costs."""
    from repro.core import (
        SubproblemConfig, WorkloadConfig, estimate_price_params,
        make_cluster, synthetic_jobs,
    )
    from repro.core.dp import WorkloadDP
    from repro.core.pricing import PriceTable

    H, T = 6, 10
    jobs = synthetic_jobs(WorkloadConfig(num_jobs=4, horizon=T, seed=3,
                                         batch=(20, 100),
                                         workload_scale=0.05))
    cluster = make_cluster(H, T)
    prices = PriceTable(estimate_price_params(jobs, cluster, T), cluster)
    checked = 0
    for job in jobs:
        dp = WorkloadDP(job, cluster, prices, quanta=12,
                        cfg=SubproblemConfig(minplus_backend="pallas"))
        C = dp.solve_prefix(T - 1)
        a, k = job.arrival, T - job.arrival
        C_loop = np.full_like(C, np.inf)
        C_loop[0, 0] = 0.0
        choice_loop = np.full_like(dp._choice, -1)
        for i in range(k):
            C_loop[i + 1], choice_loop[i + 1] = minplus_pallas(
                C_loop[i], dp._theta_costs(a + i), interpret=True)
        np.testing.assert_array_equal(C, C_loop)
        np.testing.assert_array_equal(dp._choice, choice_loop)
        checked += np.isfinite(C[1:]).sum()
    assert checked, "fixture regression: no reachable DP state"


def test_sweep_rejects_width_above_max():
    n = MAX_P + 1
    with pytest.raises(ValueError, match="MAX_P"):
        minplus_sweep_pallas(np.zeros(n), np.zeros((2, n)), interpret=True)


def test_all_unreachable():
    prev = np.full(5, np.inf)
    tcost = np.zeros(5)
    for fn in (minplus_scalar, minplus_numpy):
        cur, ch = fn(prev, tcost)
        assert np.isinf(cur).all()
        assert (ch == -1).all()


def test_identity_step():
    """tcost = [0, inf, ...] keeps prev unchanged with choice 0."""
    prev = np.array([0.0, 3.0, np.inf, 7.0])
    tcost = np.array([0.0, np.inf, np.inf, np.inf])
    cur, ch = minplus_numpy(prev, tcost)
    np.testing.assert_array_equal(cur, prev)
    assert (ch[np.isfinite(prev)] == 0).all()
    assert ch[2] == -1


def test_dispatch_and_fallback():
    assert default_backend() in ("numpy", "pallas")
    prev = np.array([0.0, 1.0, 2.0])
    tcost = np.array([0.0, 5.0, 50.0])
    for backend in (None, "numpy", "scalar"):
        cur, ch = minplus_step(prev, tcost, backend=backend)
        np.testing.assert_allclose(cur, [0.0, 1.0, 2.0])
    # the pallas path runs the kernel in interpret mode off-TPU
    cur, ch = minplus_step(prev, tcost, backend="pallas")
    np.testing.assert_allclose(cur, [0.0, 1.0, 2.0], rtol=1e-6)


def test_dp_backends_agree_end_to_end():
    """A full run_pdors with the scalar and numpy min-plus backends must
    produce identical admission records (kernel swap is decision-neutral)."""
    from repro.core import (
        SubproblemConfig, WorkloadConfig, make_cluster, run_pdors,
        synthetic_jobs,
    )

    jobs = synthetic_jobs(WorkloadConfig(num_jobs=8, horizon=10, seed=5,
                                         batch=(20, 100), workload_scale=0.05))
    outs = []
    for backend in ("scalar", "numpy"):
        cfg = SubproblemConfig(minplus_backend=backend)
        res = run_pdors(jobs, make_cluster(6, 10), cfg=cfg, quanta=10, seed=0)
        outs.append([
            (r.job.job_id, r.admitted, r.utility,
             sorted((t, tuple(sorted(a.workers.items())))
                    for t, a in r.schedule.slots.items())
             if r.schedule else None)
            for r in res.records
        ])
    assert outs[0] == outs[1]
