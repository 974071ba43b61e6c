"""Parity + behavior tests for the plan-then-solve pipeline
(core/solve_plan.py): the batched path must be bit-identical to the lazy
per-(t, v) loop in BOTH rng modes, across regimes, and through the
batched offer front-ends."""
import numpy as np
import pytest

from repro.core import (
    PDORS,
    WorkloadConfig,
    estimate_price_params,
    make_cluster,
    run_pdors,
    synthetic_jobs,
)
from repro.core.dp import WorkloadDP
from repro.core.pricing import PriceTable
from repro.core.solve_plan import SolvePlan, infeasible_levels
from repro.core.subproblem import SubproblemConfig


def _decisions(records):
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


def _run(jobs, cluster, cfg, seed, quanta=32, batched=False):
    params = estimate_price_params(jobs, cluster, cluster.horizon)
    sched = PDORS(cluster, params, cfg=cfg, quanta=quanta, seed=seed)
    ordered = sorted(jobs, key=lambda j: (j.arrival, j.job_id))
    if batched:
        sched.run(jobs)
    else:
        for job in ordered:
            sched.offer(job)
    return _decisions(sched.records)


REGIMES = [
    # (H, T, num_jobs, workload_scale, seed)
    (6, 8, 10, 0.003, 0),      # online many-small-jobs mix
    (8, 8, 12, 0.08, 1),       # mixed
    (12, 10, 18, 0.3, 2),      # heavy contention (LP-bound)
]


@pytest.mark.parametrize("H,T,N,scale,seed", REGIMES)
@pytest.mark.parametrize("rng_mode", ["compat", "derived"])
def test_plan_bit_identical_to_lazy_loop(H, T, N, scale, seed, rng_mode):
    """cfg.use_plan=True vs False: identical admissions, utilities, and
    per-slot allocations — the plan hoists rng-free work only."""
    cfgw = WorkloadConfig(num_jobs=N, horizon=T, seed=seed,
                          batch=(50, 200), workload_scale=scale)
    jobs = synthetic_jobs(cfgw)
    d_plan = _run(jobs, make_cluster(H, T),
                  SubproblemConfig(rng_mode=rng_mode), seed)
    d_lazy = _run(jobs, make_cluster(H, T),
                  SubproblemConfig(rng_mode=rng_mode, use_plan=False), seed)
    assert d_plan == d_lazy


@pytest.mark.parametrize("H,T,N,scale,seed", REGIMES)
def test_offer_batch_matches_sequential_offers(H, T, N, scale, seed):
    """The cross-job batched offer path (stacked LPs, plan rebuild after
    each admission) must reproduce one-at-a-time offers exactly."""
    cfgw = WorkloadConfig(num_jobs=N, horizon=T, seed=seed,
                          batch=(50, 200), workload_scale=scale)
    jobs = synthetic_jobs(cfgw)
    d_seq = _run(jobs, make_cluster(H, T), SubproblemConfig(), seed)
    d_bat = _run(jobs, make_cluster(H, T), SubproblemConfig(), seed,
                 batched=True)
    assert d_seq == d_bat


def test_plan_against_frozen_reference_heavy():
    """Golden-seed check straight against the frozen scalar core at a
    small heavy-contention point."""
    from repro.core._reference import (
        make_cluster_reference, run_pdors_reference,
    )

    H, T, N = 10, 8, 14
    cfgw = WorkloadConfig(num_jobs=N, horizon=T, seed=5,
                          batch=(50, 200), workload_scale=0.3)
    jobs = synthetic_jobs(cfgw)
    res_v = run_pdors(jobs, make_cluster(H, T), quanta=32, seed=5)
    res_r = run_pdors_reference(jobs, make_cluster_reference(H, T),
                                quanta=32, seed=5)
    assert _decisions(res_v.records) == _decisions(res_r.records)
    assert res_v.total_utility == res_r.total_utility


def test_stale_plan_is_rebuilt_not_consumed():
    """A plan built before a ledger mutation must be detected as stale
    (fresh() False) and silently replaced — decisions unchanged."""
    H, T, N = 8, 8, 10
    cfgw = WorkloadConfig(num_jobs=N, horizon=T, seed=3,
                          batch=(50, 200), workload_scale=0.08)
    jobs = sorted(synthetic_jobs(cfgw), key=lambda j: (j.arrival, j.job_id))
    cluster = make_cluster(H, T)
    params = estimate_price_params(jobs, cluster, T)
    sched = PDORS(cluster, params, quanta=32, seed=3)
    # build a plan for job[1] against the pristine ledger, then admit
    # job[0] (repricing), then offer job[1] WITH the stale plan injected
    stale = sched._build_plan(jobs[1])
    assert stale is not None and stale.fresh()
    rec0 = sched.offer(jobs[0])
    if rec0.admitted:
        assert not stale.fresh()
    rec1 = sched.offer(jobs[1], plan=stale)

    # replay without the stale injection: identical outcome
    cluster2 = make_cluster(H, T)
    sched2 = PDORS(cluster2, params, quanta=32, seed=3)
    sched2.offer(jobs[0])
    rec1b = sched2.offer(jobs[1])
    assert _decisions([rec1]) == _decisions([rec1b])


def test_infeasible_levels_memoized_without_solving():
    """Satellite: levels whose workload caps fail on both theta paths are
    memoized as None up front — no snapshot build, no rng drift."""
    H, T = 6, 6
    cfgw = WorkloadConfig(num_jobs=4, horizon=T, seed=0,
                          batch=(4, 8), workload_scale=0.5)
    jobs = synthetic_jobs(cfgw)
    job = jobs[0]
    cluster = make_cluster(H, T)
    params = estimate_price_params(jobs, cluster, T)
    prices = PriceTable(params, cluster)
    dp = WorkloadDP(job, cluster, prices, quanta=32)
    inf = infeasible_levels(job, dp.quanta, dp.unit)
    # big batch-relative workload at scale 0.5 guarantees some dead levels
    assert inf, "fixture regression: expected infeasible levels"
    for v in sorted(inf)[:3]:
        assert dp.theta(0, v) is None
        assert (0, v) in dp._theta
    # no snapshot was built for those memoized levels
    assert 0 not in dp._snaps


def test_headroom_all_matches_scalar_oracle():
    """The vectorized (and stacked) head-room must equal the lazy
    per-machine ``_headroom_one`` for every machine and load."""
    from repro.core.subproblem import _headroom_all, _headroom_one

    H, T = 7, 6
    cfgw = WorkloadConfig(num_jobs=6, horizon=T, seed=2,
                          batch=(50, 200), workload_scale=0.2)
    jobs = synthetic_jobs(cfgw)
    cluster = make_cluster(H, T)
    params = estimate_price_params(jobs, cluster, T)
    prices = PriceTable(params, cluster)
    rng = np.random.default_rng(0)
    from repro.core.subproblem import PriceSnapshot
    snap = PriceSnapshot(jobs[0], cluster, prices, 0)
    for kind in ("w", "s"):
        W2d = rng.integers(0, 5, size=(3, H))
        S2d = rng.integers(0, 3, size=(3, H))
        got = _headroom_all(snap, kind, W2d, S2d)
        assert got.shape == (3, H)
        for c in range(3):
            row = _headroom_all(snap, kind, W2d[c], S2d[c])
            for h in range(H):
                ref = _headroom_one(snap, kind, h,
                                    int(W2d[c, h]), int(S2d[c, h]))
                assert got[c, h] == row[h] == ref


def _dense_heads(aux, kind, F, slots, W, S):
    """The dense head-room oracle: one ``_headroom_from_aux`` call over
    the (C, H, R) stack of each candidate's slot free matrix."""
    from repro.core.subproblem import _headroom_from_aux

    pos, dpos, _fp, wdp, sdp, wdn, sdn, _fn = aux
    Fr = F[slots]
    fnon = (Fr[:, :, ~pos] + 1e-9) if (~pos).any() else None
    return _headroom_from_aux(
        (pos, dpos, Fr[:, :, pos] + 1e-9, wdp, sdp, wdn, sdn, fnon),
        kind, W, S,
    )


@pytest.mark.parametrize("kind", ["w", "s"])
@pytest.mark.parametrize("case", ["shared_slots", "some_slots", "boundary",
                                  "guard", "unloaded"])
def test_headroom_rows_match_dense_stack(kind, case):
    """Per-slot zero-load rows with the loaded cells recomputed equal the
    dense (C, H, R) head-room bit for bit: candidates sharing a slot, a
    pass that uses only some slots, loads at exact capacity boundaries
    (the one-ulp fix-up), a zero-demand column with negative free cells
    (the guard) and candidates with no loaded cell."""
    from repro.core.solve_plan import _headroom_rows
    from repro.core.subproblem import PriceSnapshot

    H, T, U, C = 9, 6, 4, 12
    jobs = synthetic_jobs(WorkloadConfig(num_jobs=6, horizon=T, seed=2,
                                         batch=(50, 200),
                                         workload_scale=0.2))
    # gpu is a zero-demand column of both kinds for job 0; for job 1 only
    # the servers' gpu demand is zero, so the "s" guard reads the workers'
    # gpu load against the free gpu
    job = jobs[1] if case == "guard" and kind == "s" else jobs[0]
    cluster = make_cluster(H, T)
    prices = PriceTable(estimate_price_params(jobs, cluster, T), cluster)
    snap = PriceSnapshot(job, cluster, prices, 0)
    aux = snap.head_aux(kind)
    rng = np.random.default_rng(["shared_slots", "some_slots", "boundary",
                                 "guard", "unloaded"].index(case))
    R = snap.wdem.size
    F = rng.uniform(0.0, 1.0, (U, H, R)) * snap.free_mat
    slots = rng.integers(0, U, C)
    if case == "shared_slots":
        slots[: C // 2] = 2
    if case == "some_slots":
        slots = rng.choice([0, 3], C)
    loaded = rng.random((C, H)) < (0.0 if case == "unloaded" else 0.3)
    W = np.where(loaded, rng.integers(0, 5, (C, H)), 0)
    S = np.where(loaded, rng.integers(0, 3, (C, H)), 0)
    if case == "boundary":
        # free = the load grown by k units exactly, less the tolerance
        k = rng.integers(0, 4, (C, H))
        dem = snap.wdem if kind == "w" else snap.sdem
        full = (W[:, :, None] * snap.wdem + S[:, :, None] * snap.sdem
                + k[:, :, None] * dem) - 1e-9
        F[slots] = full          # the last candidate of a slot wins
    if case == "guard":
        gpu = cluster.resources.index("gpu")
        assert not aux[0][gpu]   # gpu is a zero-demand column of the kind
        F[:, ::2, gpu] = -1.0    # negative free gpu on every other machine
        F[:, 1::2, gpu] = 1.5    # fits one worker's gpu, not two
    got, rows, cells = _headroom_rows(aux, kind, F, slots, W, S)
    want = _dense_heads(aux, kind, F, slots, W, S)
    assert got.dtype == want.dtype and np.array_equal(got, want)
    assert rows == np.unique(slots).size
    assert cells == int(((W != 0) | (S != 0)).sum())
    if case == "unloaded":
        assert cells == 0
        for c in range(C):
            first = np.flatnonzero(slots == slots[c])[0]
            assert np.array_equal(got[c], got[first])
    if case == "guard":
        assert (got[:, ::2] == 0).all()
        if kind == "s":
            assert (got[:, 1::2][W[:, 1::2] >= 2] == 0).all()


FILL_CASES = ["covered", "full_prefix", "uncoverable", "loaded_in_out",
              "guard"]


@pytest.mark.parametrize("kind", ["w", "s"])
@pytest.mark.parametrize("case", FILL_CASES)
def test_prefix_fill_matches_dense_fill(kind, case):
    """The fill over a widening prefix of each slot's price order gives
    exactly the dense (C, H) fill: the same takes, the same ``fail`` and
    the same final loads, when the first prefix covers the need, when it
    is all full machines (widening), when no column can cover the need (K
    reaches H), with loaded cells inside and outside the prefix, and with
    the zero-demand guard column."""
    from repro.core.solve_plan import _FILL_K0, _prefix_fill
    from repro.core.subproblem import PriceSnapshot

    H, T, U, C = 40, 6, 3, 10
    jobs = synthetic_jobs(WorkloadConfig(num_jobs=6, horizon=T, seed=2,
                                         batch=(50, 200),
                                         workload_scale=0.2))
    job = jobs[1] if case == "guard" and kind == "s" else jobs[0]
    cluster = make_cluster(H, T)
    prices = PriceTable(estimate_price_params(jobs, cluster, T), cluster)
    snap = PriceSnapshot(job, cluster, prices, 0)
    aux = snap.head_aux(kind)
    rng = np.random.default_rng(FILL_CASES.index(case))
    R = snap.wdem.size
    F = np.broadcast_to(snap.free_mat, (U, H, R)).copy()
    order = np.stack([rng.permutation(H) for _ in range(U)])
    slots = rng.integers(0, U, C)
    slots[:U] = np.arange(U)                 # every slot is used
    W = np.zeros((C, H), dtype=np.int64)
    S = np.zeros((C, H), dtype=np.int64)
    X = rng.integers(1, 4, C)
    if case == "full_prefix":
        for u in range(U):                   # the cheapest 2 K0 are full
            F[u, order[u, :2 * _FILL_K0]] = 0.0
    if case == "uncoverable":
        X = np.full(C, 10 ** 6)
        X[0] = -2                            # a met need places nothing
    if case == "loaded_in_out":
        for c in range(C):
            o = order[slots[c]]
            cells = np.r_[o[:3], o[-3:]]     # inside and past the prefix
            W[c, cells] = rng.integers(0, 3, cells.size)
            S[c, cells] = rng.integers(0, 2, cells.size)
        X = rng.integers(1, 40, C)
    if case == "guard":
        gpu = cluster.resources.index("gpu")
        assert not aux[0][gpu]   # gpu is a zero-demand column of the kind
        F[:, ::2, gpu] = -1.0    # negative free gpu on every other machine
        F[:, 1::2, gpu] = 1.5    # fits one worker's gpu, not two
        W[:, ::3] = rng.integers(0, 3, (C, W[:, ::3].shape[1]))
        X = rng.integers(1, 30, C)

    # the dense oracle: head-room over all H columns, filled in price order
    heads = _dense_heads(aux, kind, F, slots, W, S)
    o = order[slots]
    hv = np.minimum(np.take_along_axis(heads, o, 1),
                    np.maximum(X, 0)[:, None])
    takes = np.clip(X[:, None] - (np.cumsum(hv, axis=1) - hv), 0, hv)
    Wd, Sd = W.copy(), S.copy()
    (Wd if kind == "w" else Sd)[np.arange(C)[:, None], o] += takes
    want_fill = takes.sum(axis=1)

    Wp, Sp = W.copy(), S.copy()
    filled, widened, (rows, cols, cells) = _prefix_fill(
        aux, kind, F, order, slots, Wp, Sp, X)
    assert np.array_equal(filled, want_fill)
    assert np.array_equal(X - filled > 0, X - want_fill > 0)   # fail
    assert np.array_equal(Wp, Wd) and np.array_equal(Sp, Sd)
    assert rows == U and _FILL_K0 * U <= cols <= H * U
    if case == "covered":
        assert not widened.any() and cols == _FILL_K0 * U
    if case == "full_prefix":
        assert widened.all() and (filled == X).all()
    if case == "uncoverable":
        assert (widened == (X > 0)).all() and cols == H * U
        assert (X - filled > 0)[1:].all() and filled[0] == 0
    if case == "loaded_in_out":
        # only the loaded cells of the columns a candidate's fill read
        assert 0 < cells < int(((W != 0) | (S != 0)).sum())


def test_fused_bundle_batch_matches_per_slot_numpy():
    """The fused (W, H) bundle pass must be bit-identical to W per-slot
    reductions on the numpy backend."""
    from repro.kernels.pricing import price_bundle_batch_numpy, price_bundle_numpy

    rng = np.random.default_rng(0)
    W, H, R = 5, 7, 4
    price = rng.uniform(0.1, 3.0, (W, H, R))
    free = rng.uniform(0.0, 10.0, (W, H, R))
    wdem = np.array([1.0, 0.0, 2.0, 0.5])
    sdem = np.array([0.0, 1.0, 0.0, 0.25])
    fused = price_bundle_batch_numpy(price, free, wdem, sdem, 4.0)
    for t in range(W):
        per = price_bundle_numpy(price[t], free[t], wdem, sdem, 4.0)
        for a, b in zip(fused, per):
            assert np.array_equal(a[t], b)


def test_plan_lp_results_stackable_across_jobs():
    """solve_plans on several jobs' plans installs each plan's own slice;
    resolution then matches per-plan solving."""
    from repro.core.solve_plan import solve_plans

    H, T, N = 10, 8, 8
    cfgw = WorkloadConfig(num_jobs=N, horizon=T, seed=7,
                          batch=(50, 200), workload_scale=0.3)
    jobs = sorted(synthetic_jobs(cfgw), key=lambda j: (j.arrival, j.job_id))
    cluster = make_cluster(H, T)
    params = estimate_price_params(jobs, cluster, T)
    prices = PriceTable(params, cluster)
    cfg = SubproblemConfig()
    plans = [SolvePlan(j, cluster, prices, cfg, j.arrival, T - 1, quanta=32)
             for j in jobs[:3]]
    solo = [SolvePlan(j, cluster, prices, cfg, j.arrival, T - 1, quanta=32)
            for j in jobs[:3]]
    solve_plans(plans)
    for p, s in zip(plans, solo):
        s.solve()
        assert len(p.lp_results) == len(s.lp_results)
        for a, b in zip(p.lp_results, s.lp_results):
            assert a.status == b.status
            if a.x is not None:
                assert np.array_equal(a.x, b.x)


# ======================================================================
# ISSUE 8 tentpole b: warm-started re-offers — SolvePlan.patch parity
# ======================================================================
def _theta_equal(a, b):
    if a is None or b is None:
        return a is b
    return (a.cost == b.cost and a.mode == b.mode
            and a.alloc.workers == b.alloc.workers
            and a.alloc.ps == b.alloc.ps)


def _plan_fixture(seed=3, H=8, T=8, N=10, scale=0.08):
    cfgw = WorkloadConfig(num_jobs=N, horizon=T, seed=seed,
                          batch=(50, 200), workload_scale=scale)
    jobs = sorted(synthetic_jobs(cfgw), key=lambda j: (j.arrival, j.job_id))
    cluster = make_cluster(H, T)
    params = estimate_price_params(jobs, cluster, T)
    prices = PriceTable(params, cluster)
    return jobs, cluster, prices


def test_finish_clip_check_matches_scalar_repair(monkeypatch):
    """The fused finish's clip check reads only loaded cells: a rounding
    that overloads one of its machines takes the scalar ``_repair``
    fallback, one that fits (a cover shortfall alone) does not, and every
    candidate's external result equals the scalar repair and ratio
    guarantee on the same rounding."""
    import repro.core.solve_plan as sp
    from repro.core.subproblem import _ensure_ratio, _repair
    from repro.core.job import Allocation
    from repro.core.subproblem import ThetaResult, _alloc_cost

    jobs, cluster, prices = _plan_fixture(seed=6, scale=0.3, H=10, N=12)
    job = jobs[1]
    cfg = SubproblemConfig()
    plan = SolvePlan(job, cluster, prices, cfg, job.arrival,
                     cluster.horizon - 1, quanta=32).solve()
    pend = [p for p in plan.pending if p.action == sp._A_LP
            and plan.lp_results[p.lp_index].status == "optimal"
            and p.cand.W1 > 2]
    assert len(pend) >= 4, "fixture regression: too few LP candidates"
    S, H = cfg.rounding_rounds, cluster.num_machines
    work, keys, want, clipped = [], [], {}, 0
    for n, p in enumerate(pend):
        M = len(p.cand.machines)
        # one worker and one server on the first machine: they fit, and
        # the cover falls short ...
        X0 = np.zeros(2 * M, dtype=np.int64)
        X0[0] = X0[M] = 1
        if n % 3 == 1:                           # ... or nothing loaded
            X0[:] = 0
        if n % 3 == 2:                           # ... or an overload too
            X0[0] = 10 * job.batch_size
            clipped += 1
        q = sp._Pending(p.t, p.v, sp._A_LP, None, cand=p.cand,
                        lp_index=p.lp_index, w2=p.w2)
        work.append((q, np.tile(X0, (S, 1))))
        keys.append((p.t, p.v))
        # the scalar tail of _external_finish on the same rounding
        snap = plan.snaps[p.t]
        w = np.zeros(H, dtype=np.int64)
        s = np.zeros(H, dtype=np.int64)
        w[p.cand.machines], s[p.cand.machines] = X0[:M], X0[M:]
        w, s = _repair(job, snap, w, s, p.cand.W1)
        if w is not None:
            s = _ensure_ratio(job, snap, w, s)
        ext = None
        if w is not None and s is not None and int(w.sum()) != 0:
            alloc = Allocation(
                workers={int(h): int(w[h]) for h in np.flatnonzero(w > 0)},
                ps={int(h): int(s[h]) for h in np.flatnonzero(s > 0)})
            ext = ThetaResult(cost=_alloc_cost(snap, alloc), alloc=alloc,
                              mode="external")
        want[(p.t, p.v)] = ext
    calls = []

    def counted(*a, **kw):
        calls.append(1)
        return _repair(*a, **kw)

    monkeypatch.setattr(sp, "_repair", counted)
    memo = {}
    plan._finish_batched(work, keys, memo)
    assert len(calls) == clipped
    assert any(v is not None for v in want.values())
    for k in keys:
        assert _theta_equal(memo[k], want[k]), k


@pytest.mark.parametrize("rng_mode", ["compat", "derived"])
@pytest.mark.parametrize("solve_first", [False, True])
def test_patched_plan_matches_cold_rebuild(rng_mode, solve_first):
    """Build a plan, mutate a couple of ledger slots underneath it, patch
    it — then compare the resolved theta memo against a cold rebuild at
    the mutated ledger: bit-identical in BOTH rng modes, whether the LP
    batch was solved before or after going stale (a pre-solved plan keeps
    its clean slots' LP results)."""
    from repro.core.job import Allocation

    jobs, cluster, prices = _plan_fixture()
    T = cluster.horizon
    job = jobs[1]
    cfg = SubproblemConfig(rng_mode=rng_mode, seed=11)
    plan = SolvePlan(job, cluster, prices, cfg, job.arrival, T - 1,
                     quanta=32)
    if solve_first:
        plan.solve()
    # dirty two slots (an admission-shaped mutation), leave the rest
    other = jobs[0]
    cluster.commit(1, other, Allocation(workers={0: 2}, ps={1: 1}))
    cluster.commit(3, other, Allocation(workers={2: 1}, ps={2: 1}))
    assert not plan.fresh()
    assert plan.patch(skip=set())
    assert plan.fresh()

    cold = SolvePlan(job, cluster, prices, cfg, job.arrival, T - 1,
                     quanta=32)
    plan.solve()
    cold.solve()
    assert len(plan.lp_results) == len(cold.lp_results)

    memo_p, memo_c = {}, {}
    rng_p = np.random.default_rng(99)
    rng_c = np.random.default_rng(99)
    plan.resolve_into(memo_p, lambda t, v: rng_p)
    cold.resolve_into(memo_c, lambda t, v: rng_c)
    assert set(memo_p) == set(memo_c)
    for k in memo_p:
        assert _theta_equal(memo_p[k], memo_c[k]), k
    if rng_mode == "compat":
        # the shared stream positions must match exactly too
        assert rng_p.integers(1 << 30) == rng_c.integers(1 << 30)


def test_patch_noop_when_fresh_and_refuses_after_slide():
    """Staleness drill: a fresh plan patches trivially; a window slide
    (Cluster.advance) shifts what relative indices mean, so patch must
    refuse and force the rebuild path."""
    jobs, cluster, prices = _plan_fixture()
    T = cluster.horizon
    job = jobs[1]
    plan = SolvePlan(job, cluster, prices, SubproblemConfig(),
                     job.arrival, T - 1, quanta=32)
    assert plan.patch() is True          # fresh: nothing to do
    cluster.advance(1)
    assert not plan.fresh()
    assert plan.patch() is False         # slid: caller must rebuild


@pytest.mark.parametrize("rng_mode", ["compat", "derived"])
def test_offer_with_stale_plan_patches_decision_identical(rng_mode):
    """End-to-end through the DP drop site (_ensure_plan): offering with
    a stale injected plan now patches it in place — decisions must equal
    a replay that never saw the stale plan. The patch really runs (the
    registry counter moves)."""
    from repro.obs.metrics import get_registry

    jobs, cluster, prices = _plan_fixture(seed=6, scale=0.3, H=10, N=12)
    params = estimate_price_params(jobs, cluster, cluster.horizon)
    cfg = SubproblemConfig(rng_mode=rng_mode)
    sched = PDORS(cluster, params, cfg=cfg, quanta=32, seed=6)
    stale = sched._build_plan(jobs[1])
    assert stale is not None
    before = get_registry().value("repro_plan_patches_total")
    rec0 = sched.offer(jobs[0])
    rec1 = sched.offer(jobs[1], plan=stale)
    if rec0.admitted:
        assert get_registry().value("repro_plan_patches_total") > before

    cluster2 = make_cluster(10, cluster.horizon)
    sched2 = PDORS(cluster2, params, cfg=cfg, quanta=32, seed=6)
    sched2.offer(jobs[0])
    rec1b = sched2.offer(jobs[1])
    assert _decisions([rec1]) == _decisions([rec1b])
