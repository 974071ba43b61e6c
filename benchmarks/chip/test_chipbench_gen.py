"""The benchmark's frozen traffic still yields what the program's
generators yield, at every traffic mix the program's generator can
express and a few seeds; and each job-mix key a traffic file may state
draws as it says (CPU only)."""
import json
import math
import sys
from dataclasses import fields, replace
from pathlib import Path

import numpy as np
import pytest

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parents[1] / "src"))

from gen.jobmath import PlainJob  # noqa: E402
from gen.traffic import (Traffic, _draw_batch, _draw_demand,  # noqa: E402
                         _draw_utility, _is_range, backlog, burst_factor, calibrate,
                         job_stream, load_traffic, size_multiplier)
from harness.cells import check_demands, load_config  # noqa: E402

TRAFFIC = sorted(p.stem for p in (BENCH_DIR / "traffic").glob("*.json"))
SEEDS = [0, 7, 2**31 + 11]
#: the keys passed to the program's TraceConfig, and those that draw nothing
PASSED = {"preset", "arrival_rate", "failure_rate", "failure_delay",
          "patience", "workload_scale", "batch"}
NOT_DRAWN = {"calib_jobs", "warm_slots", "base_seed", "sources", "assumed",
             "notes"}


def _expressible(tr) -> bool:
    """The program's generator draws this mix: every other key at its
    preset's value, F one range, no size tail."""
    plain = Traffic(preset=tr.preset)
    return _is_range(tr.batch) and all(
        getattr(tr, f.name) == getattr(plain, f.name)
        for f in fields(Traffic) if f.name not in PASSED | NOT_DRAWN)


PROGRAM_TRAFFIC = [t for t in TRAFFIC if _expressible(
    load_traffic(BENCH_DIR / "traffic" / f"{t}.json"))]


def _program_config(tr, seed, n):
    from repro.sim.traces import TraceConfig
    assert _expressible(tr)
    return TraceConfig(
        preset=tr.preset, num_jobs=n, seed=seed, arrival_rate=tr.arrival_rate,
        failure_rate=tr.failure_rate, failure_delay=tuple(tr.failure_delay),
        patience=tr.patience, workload_scale=tr.workload_scale,
        batch=tuple(tr.batch))


def test_the_cell_traffic_is_compared_with_the_program():
    assert "google_light" in PROGRAM_TRAFFIC


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("traffic", PROGRAM_TRAFFIC)
def test_stream_matches_program(traffic, seed):
    from repro.sim.traces import job_stream as program_stream
    tr = load_traffic(BENCH_DIR / "traffic" / f"{traffic}.json")
    n = 120
    ours = list(job_stream(tr, seed, n))
    theirs = list(program_stream(_program_config(tr, seed, n)))
    assert len(ours) == len(theirs) == n
    for (a, fa), (b, fb) in zip(ours, theirs):
        assert a == b
        assert fa == fb
    assert any(f is not None for _, f in ours)      # failures are drawn


@pytest.mark.parametrize("traffic", PROGRAM_TRAFFIC)
def test_calibration_matches_program(traffic):
    from repro.core.cluster import make_cluster
    from repro.sim.traces import calibrate_prices
    tr = load_traffic(BENCH_DIR / "traffic" / f"{traffic}.json")
    cluster = make_cluster(32, 64)
    want = calibrate_prices(_program_config(tr, 5, tr.calib_jobs), cluster,
                            n=tr.calib_jobs)
    jobs = [replace(PlainJob.of(j), arrival=0)
            for j, _ in job_stream(tr, 5, tr.calib_jobs)]
    got = calibrate(jobs, [dict(m.capacity) for m in cluster.machines], 64)
    assert got.L == want.L and got.mu == want.mu
    assert got.U == want.U


def test_unbounded_stream_keeps_going():
    tr = load_traffic(BENCH_DIR / "traffic" / f"{TRAFFIC[0]}.json")
    stream = job_stream(tr, 3)
    last = None
    for _ in range(2000):
        last = next(stream)
    assert last[0].job_id == 1999


def test_traffic_files_parse_and_name_their_keys():
    for name in TRAFFIC:
        raw = json.loads((BENCH_DIR / "traffic" / f"{name}.json").read_text())
        tr = load_traffic(BENCH_DIR / "traffic" / f"{name}.json")
        assert tr.warm_slots > 0 and tr.arrival_rate > 0
        assert set(raw) <= set(tr.__dataclass_fields__)


@pytest.mark.parametrize("traffic", TRAFFIC)
def test_backlog_reorders_the_same_work(traffic):
    """Every seed replays the base stream's arrival slots and, slot by
    slot, the base stream's jobs (with their failure slots), only in
    another order within the slot."""
    tr = load_traffic(BENCH_DIR / "traffic" / f"{traffic}.json")
    n = 200
    base = list(job_stream(tr, tr.base_seed, n + 40))
    last_slot = base[n][0].arrival       # compare whole slots only

    def key(job, fail_at):
        return repr((PlainJob.of(replace(job, job_id=0)), fail_at))

    def by_slot(pairs):
        out = {}
        for job, fail_at in pairs:
            if job.arrival < last_slot:
                out.setdefault(job.arrival, []).append(key(job, fail_at))
        return out

    want = by_slot(base)
    assert max(len(v) for v in want.values()) > 1
    runs = []
    for seed in (1, 2, 2**31 + 3):
        it = backlog(tr, seed)
        got = [next(it) for _ in range(n + 40)]
        assert [j.job_id for j, _ in got] == list(range(n + 40))
        assert [j.arrival for j, _ in got] == [j.arrival for j, _ in base]
        slots = by_slot(got)
        assert {t: sorted(v) for t, v in slots.items()} == \
            {t: sorted(v) for t, v in want.items()}
        runs.append(slots)
    assert runs[0] != runs[1]                      # the order does change


# ------------------------------------------------------ the job-mix keys
N_DRAWS = 20_000


def _within(share, want, n=N_DRAWS):
    """Five binomial standard deviations."""
    return abs(share - want) <= 5 * math.sqrt(max(want * (1 - want), 1e-4) / n)


def test_mix_shares_over_many_draws():
    tr = Traffic(mix=(0.6, 0.35, 0.05))
    rng = np.random.default_rng(11)
    t2 = np.array([_draw_utility(rng, tr).theta2 for _ in range(N_DRAWS)])
    shares = [(t2 == 0).mean(), ((t2 >= 0.01) & (t2 <= 1.0)).mean(),
              (t2 >= 4.0).mean()]
    assert sum(shares) == pytest.approx(1.0)
    assert all(_within(s, w) for s, w in zip(shares, tr.mix)), shares


def test_batch_bucket_shares_over_many_draws():
    tr = Traffic(batch=((0.5, 1, 1), (0.3, 2, 8), (0.2, 16, 64)))
    rng = np.random.default_rng(12)
    F = np.array([_draw_batch(rng, tr.batch) for _ in range(N_DRAWS)])
    shares = [(F == 1).mean(), ((F >= 2) & (F <= 8)).mean(),
              ((F >= 16) & (F <= 64)).mean()]
    assert sum(shares) == pytest.approx(1.0)
    assert all(_within(s, w) for s, w in zip(shares, (0.5, 0.3, 0.2))), shares
    assert set(F[F >= 16]) == set(range(16, 65))        # uniform in a bucket
    assert tr.max_batch == 64


def test_burst_shapes_by_hand():
    g = burst_factor("google", 0.0)
    assert g == pytest.approx((1.0 + 2.0 * math.exp(-0.09 / 0.02)
                               + 1.5 * math.exp(-0.49 / 0.03)) / 1.9)
    assert burst_factor("google", 48.0 + 14.4) == pytest.approx(
        (1.0 + 2.0 + 1.5 * math.exp(-0.16 / 0.03)) / 1.9)
    assert [burst_factor("none", t) for t in (0.0, 7.3, 1e6)] == [1.0] * 3
    sine = {"sine": 0.3, "period": 64.0}
    assert burst_factor(sine, 0.0) == 1.0
    assert burst_factor(sine, 16.0) == pytest.approx(1.3)
    assert burst_factor(sine, 48.0) == pytest.approx(0.7)
    assert burst_factor(sine, 64.0 + 16.0) == pytest.approx(1.3)
    assert Traffic().burst == "google"
    assert Traffic(burst={"sine": 0.3, "period": 64}).burst == sine


def test_sine_burst_is_the_programs_philly_burst():
    from repro.sim.traces import _burst_factor
    sine = Traffic(burst={"sine": 0.3, "period": 64}).burst
    ts = [0.0, 0.5, 1.0, 13.37, 16.0, 31.9, 47.25, 63.999, 64.0, 100.1,
          1e4 + 0.3] + list(np.random.default_rng(3).uniform(0, 5000, 200))
    for t in ts:
        assert burst_factor(sine, float(t)) == _burst_factor("philly", float(t))


def test_a_fixed_demand_consumes_no_draw():
    a, b = np.random.default_rng(5), np.random.default_rng(5)
    got = _draw_demand(a, {"gpu": 1.0, "cpu": (1, 10), "mem": 7.0,
                           "storage": (5, 10)})
    want = _draw_demand(b, {"cpu": (1, 10), "storage": (5, 10)})
    assert got == {"gpu": 1.0, "cpu": want["cpu"], "mem": 7.0,
                   "storage": want["storage"]}
    assert list(got) == ["gpu", "cpu", "mem", "storage"]   # the file's order
    assert a.random() == b.random()
    # whole jobs: a fixed server GPU of 0 or of 2 leaves every draw alike
    s0 = list(job_stream(Traffic(), 9, 50))
    s2 = list(job_stream(Traffic(ps_demand={"gpu": 2, "cpu": [1, 10],
                                            "mem": [2, 32],
                                            "storage": [5, 10]}), 9, 50))
    for (j0, f0), (j2, f2) in zip(s0, s2):
        assert j2.ps_demand == {**j0.ps_demand, "gpu": 2.0}
        assert replace(j2, ps_demand=j0.ps_demand) == j0 and f0 == f2


def test_size_tail_cap_holds_and_null_is_unclipped():
    rng = np.random.default_rng(8)
    capped = [size_multiplier(rng, {"sigma": 1.2, "cap": 2.0})
              for _ in range(N_DRAWS)]
    assert max(capped) == 2.0 and min(capped) > 0
    assert sum(m == 2.0 for m in capped) > N_DRAWS // 20
    rng = np.random.default_rng(8)
    free = [size_multiplier(rng, {"sigma": 1.2, "cap": None})
            for _ in range(N_DRAWS)]
    assert [min(m, 2.0) for m in free] == capped          # same draws
    assert max(free) > 40.0
    assert np.mean(free) == pytest.approx(1.0, abs=0.06)  # mean 1


def test_size_tail_is_the_programs_philly_tail():
    from repro.sim.traces import TraceConfig, _philly_tail
    cfg = TraceConfig(preset="philly")
    assert (cfg.tail_sigma, cfg.tail_cap) == (1.2, 40.0)
    tail = Traffic(size_tail={"sigma": 1.2, "cap": 40}).size_tail
    job = next(job_stream(Traffic(), 1, 1))[0]
    big = replace(job, num_samples=10**15)
    for seed in range(200):
        a, b = np.random.default_rng(seed), np.random.default_rng(seed)
        mult = size_multiplier(a, tail)
        assert _philly_tail(big, b, cfg).num_samples == max(1, int(10**15 * mult))
        assert a.random() == b.random()


def test_size_tail_is_drawn_after_the_utility_and_before_the_failure():
    """With a tail, each job's other parameters and failure slot are as
    without one, except K and the failure, which take the draw after."""
    plain = Traffic(failure_rate=1.0)
    tailed = replace(plain, size_tail={"sigma": 1.2, "cap": None})
    for (a, fa), (b, fb) in zip(job_stream(plain, 4, 40),
                                job_stream(tailed, 4, 40)):
        assert replace(b, num_samples=a.num_samples) == a
        assert fa is not None and fb is not None
    rng = np.random.default_rng(np.random.SeedSequence((4, 7, 0)))
    rng.exponential(1.0 / plain.arrival_rate)
    from gen.traffic import draw_job
    draw_job(rng, plain, 0, 0)
    mult = size_multiplier(rng, tailed.size_tail)
    first = next(job_stream(tailed, 4, 1))[0]
    K0 = next(job_stream(plain, 4, 1))[0].num_samples
    assert first.num_samples == max(1, int(K0 * mult))


def test_preset_values_stated_in_a_file_draw_the_same_stream(tmp_path):
    """Stating the google preset's job mix key by key changes nothing."""
    raw = json.loads((BENCH_DIR / "traffic" / "google_light.json").read_text())
    raw.update({"mix": [0.30, 0.69, 0.01], "burst": "google",
                "worker_demand": {"gpu": [0, 4], "cpu": [1, 10],
                                  "mem": [2, 32], "storage": [5, 10]},
                "ps_demand": {"gpu": 0, "cpu": [1, 10], "mem": [2, 32],
                              "storage": [5, 10]}})
    (tmp_path / "t.json").write_text(json.dumps(raw))
    stated = load_traffic(tmp_path / "t.json")
    light = load_traffic(BENCH_DIR / "traffic" / "google_light.json")
    assert stated == light and _expressible(stated)
    assert list(job_stream(stated, 3, 60)) == list(job_stream(light, 3, 60))


@pytest.mark.parametrize("key, value, says", [
    ("mix", [0.5, 0.4], "mix"),
    ("mix", [0.5, 0.4, 0.2], "mix"),
    ("burst", "diurnal", "burst"),
    ("burst", {"sine": 1.5, "period": 64}, "burst"),
    ("burst", {"sine": 0.3}, "burst"),
    ("worker_demand", {"gpu": [4, 1]}, "worker_demand"),
    ("ps_demand", {"gpu": "one"}, "ps_demand"),
    ("worker_demand", {}, "worker_demand"),
    ("batch", [[0.5, 1, 1], [0.4, 2, 8]], "batch"),
    ("batch", [[0.5, 0, 1], [0.5, 2, 8]], "batch"),
    ("batch", [64, 8], "batch"),
    ("size_tail", {"sigma": 1.2}, "size_tail"),
    ("size_tail", {"sigma": -1, "cap": None}, "size_tail"),
    ("size_tail", {"sigma": 1.2, "cap": 0}, "size_tail"),
    ("preset", "philly", "preset"),
    ("tail_sigma", 1.2, "unknown traffic keys"),
])
def test_malformed_traffic_fails_at_load_naming_the_key(tmp_path, key, value,
                                                        says):
    raw = json.loads((BENCH_DIR / "traffic" / "google_light.json").read_text())
    raw[key] = value
    path = tmp_path / "bad.json"
    path.write_text(json.dumps(raw))
    with pytest.raises(ValueError, match=says) as e:
        load_traffic(path)
    assert str(path) in str(e.value)


def _config(tmp_path, **change):
    raw = json.loads((BENCH_DIR / "testdata" / "mixed_fleet.config.json").read_text())
    raw.update(change)
    path = tmp_path / "cfg.json"
    path.write_text(json.dumps(raw))
    return path


FULL = {"cpu": 180.0, "gpu": 72.0, "mem": 576.0, "storage": 180.0}


@pytest.mark.parametrize("change, says", [
    ({"machines": 25}, "counts sum to 24, not machines 25"),
    ({"machine_classes": [{"name": "a", "count": 16, "capacity": FULL},
                          {"name": "b", "count": 8,
                           "capacity": {"cpu": 90.0, "gpu": 36.0,
                                        "mem": 288.0}}]},
     "machine_classes: class 'b' has resources"),
    ({"machine_classes": [{"name": "a", "count": 0, "capacity": FULL},
                          {"name": "b", "count": 24, "capacity": FULL}]},
     r"machine_classes\[0\]: count"),
    ({"machine_classes": [{"name": "a", "count": 24, "capacity": FULL,
                           "gpu_model": "P100"}]},
     r"machine_classes\[0\]"),
    ({"machine_classes": [{"name": "a", "count": 24,
                           "capacity": {**FULL, "gpu": 0}}]},
     r"machine_classes\[0\].capacity"),
    ({"capacity": FULL}, "capacity or machine_classes, not both"),
])
def test_malformed_config_fails_at_load_naming_the_key(tmp_path, change, says):
    path = _config(tmp_path, **change)
    with pytest.raises(ValueError, match=says) as e:
        load_config(path)
    assert str(path) in str(e.value)


def test_a_demand_the_config_lacks_fails_at_load(tmp_path):
    cfg = load_config(_config(tmp_path))
    tr = Traffic(worker_demand={"gpu": 1, "cpu": [1, 10], "ib": 1})
    with pytest.raises(ValueError, match=r"worker_demand/ps_demand name \['ib'\]"):
        check_demands(cfg, tr, "traffic 'x'")
    check_demands(cfg, Traffic(), "traffic 'google'")


def test_one_capacity_is_one_class():
    cfg = load_config(BENCH_DIR / "configs" / "google-1024.json")
    assert len(cfg.classes) == 1 and cfg.classes[0].count == cfg.machines
    assert cfg.capacity_rows() == [FULL] * 1024


def test_mixed_fleet_builds_the_programs_cluster_in_class_order(tmp_path):
    from harness.engine import make_cluster_of
    cfg = load_config(_config(tmp_path))
    cluster = make_cluster_of(cfg, "numpy")
    np.testing.assert_array_equal(cluster.capacity_matrix, cfg.capacity_array())
    assert cluster.capacity_matrix[15].tolist() == [180.0, 72.0, 576.0, 180.0]
    assert cluster.capacity_matrix[16].tolist() == [90.0, 36.0, 288.0, 90.0]
    with pytest.raises(ValueError, match="preset 'ethernet' capacities"):
        make_cluster_of(replace(cfg, preset="ethernet"), "numpy")


def test_calibration_sums_the_fleet_in_machine_order():
    from repro.core.cluster import Cluster, Machine
    from repro.core.pricing import estimate_price_params
    from repro.sim.traces import TraceConfig, sample_jobs
    half = {k: v / 2 for k, v in FULL.items()}
    rows = [FULL] * 5 + [half] * 3
    cluster = Cluster([Machine(h, dict(r)) for h, r in enumerate(rows)], 64)
    jobs = sample_jobs(TraceConfig(seed=5), 64)
    want = estimate_price_params([replace(j, arrival=0) for j in jobs],
                                 cluster, 64)
    got = calibrate([replace(PlainJob.of(j), arrival=0) for j in jobs], rows, 64)
    assert (got.L, got.mu, got.U) == (want.L, want.mu, want.U)
