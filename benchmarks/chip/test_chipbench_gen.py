"""The benchmark's frozen traffic still yields what the program's
generators yield, at every traffic mix and a few seeds (CPU only)."""
import json
import sys
from dataclasses import replace
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parents[1] / "src"))

from gen.jobmath import PlainJob  # noqa: E402
from gen.traffic import backlog, calibrate, job_stream, load_traffic  # noqa: E402

TRAFFIC = sorted(p.stem for p in (BENCH_DIR / "traffic").glob("*.json"))
SEEDS = [0, 7, 2**31 + 11]


def _program_config(tr, seed, n):
    from repro.sim.traces import TraceConfig
    return TraceConfig(
        preset=tr.preset, num_jobs=n, seed=seed, arrival_rate=tr.arrival_rate,
        failure_rate=tr.failure_rate, failure_delay=tuple(tr.failure_delay),
        patience=tr.patience, workload_scale=tr.workload_scale,
        batch=tuple(tr.batch))


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("traffic", TRAFFIC)
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


@pytest.mark.parametrize("traffic", TRAFFIC)
def test_calibration_matches_program(traffic):
    from repro.core.cluster import make_cluster
    from repro.sim.traces import calibrate_prices
    tr = load_traffic(BENCH_DIR / "traffic" / f"{traffic}.json")
    cluster = make_cluster(32, 64)
    want = calibrate_prices(_program_config(tr, 5, tr.calib_jobs), cluster,
                            n=tr.calib_jobs)
    jobs = [replace(PlainJob.of(j), arrival=0)
            for j, _ in job_stream(tr, 5, tr.calib_jobs)]
    cap = dict(cluster.machines[0].capacity)
    got = calibrate(jobs, cap, 32, 64)
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
