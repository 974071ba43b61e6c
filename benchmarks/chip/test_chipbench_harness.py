"""CPU tests of the chip harness: no TPU means no result, the window's
arithmetic on a fake clock, pieces found by name, the kernel work counts
and the trace reduction."""
import json
import math
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parent
REPO = BENCH_DIR.parents[1]
sys.path.insert(0, str(BENCH_DIR))

from harness import cells, trace, work  # noqa: E402
from harness.window import Recorder, WindowClosed, percentile  # noqa: E402

BENCH = cells.load_benchmark(REPO)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _run(cwd: Path, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = BENCH["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", cell,
         "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    out = _run(REPO)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    (tmp_path / "benchmarks").mkdir()
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


# ------------------------------------------------------------ the window
class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_window_opens_at_slot_and_closes_at_first_boundary_after_seconds():
    clk = FakeClock()
    opened = []
    rec = Recorder(open_slot=3, seconds=2.0, clock=clk,
                   on_open=lambda: opened.append(clk.t))
    for t in range(3):                       # warm-up slots: logged, not timed
        rec.boundary(t)
        clk.t += 0.5
    assert rec.t_open is None and rec.ops == [("advance", t) for t in range(3)]
    rec.boundary(3)
    assert opened == [clk.t] and rec.t_open == clk.t and rec.slot_open == 3
    clk.t += 0.9
    rec.boundary(4)
    clk.t += 0.9
    rec.boundary(5)                          # 1.8 s: still open
    clk.t += 0.3
    with pytest.raises(WindowClosed):
        rec.boundary(6)                      # 2.1 s: closes here
    assert rec.slot_close == 6 and rec.slots == 3
    assert rec.window_s == pytest.approx(2.1)
    assert rec.ops == [("advance", t) for t in range(6)]


def test_rates_and_percentiles_over_the_window():
    from harness.window import Batch
    clk = FakeClock()
    rec = Recorder(open_slot=0, seconds=10.0, clock=clk)
    rec.boundary(0)
    rec.batches = [Batch(0, [1, 2, 3], 0.3, {}), Batch(1, [4], 0.1, {}),
                   Batch(2, [5, 6], 0.2, {})]
    clk.t += 4.0
    rec.t_close, rec.slot_close = clk.t, 4
    assert rec.decisions() == 6
    assert rec.jobs_per_s() == pytest.approx(6 / 4.0)
    samples = rec.decide_samples()
    assert sorted(samples) == [0.1, 0.2, 0.2, 0.3, 0.3, 0.3]
    assert percentile(samples, 50) == 0.2
    assert percentile(samples, 95) == 0.3
    assert rec.engine_ms_per_slot() == pytest.approx((4.0 - 0.6) / 4 * 1e3)


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert percentile(xs, 50) == 50
    assert percentile(xs, 95) == 95
    assert percentile(xs, 100) == 100
    assert percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        percentile([], 50)


# --------------------------------------------------------- found by name
def test_every_cell_finds_its_files_by_name():
    for cell in BENCH["workloads"]:
        cfg = cells.config_for(BENCH, cell, REPO)
        assert cfg.name == cell["config"]
        assert cells.traffic_file(cell["traffic"]).exists()
        lim = json.loads((BENCH_DIR / "limits" / f"{cell['name']}.json").read_text())
        assert set(lim) == {"unanswered", "invalid", "ledger_gap", "payoff_gap"}


def test_every_per_layer_metric_has_a_reader():
    for m in BENCH["per_layer"]:
        read = cells.metric_reader(m["name"])
        assert callable(read)
    with pytest.raises(FileNotFoundError):
        cells.metric_reader("no.such_metric")


def test_readers_return_nothing_when_nothing_was_recorded():
    red = trace.Reduction(window_s=1.0, busy_s=0.2, op_s={}, op_count={},
                          gaps=[])
    ctx = {"recorder": None, "phase": {}, "offers": 0, "reduction": red,
           "compiles": 0, "config": None, "peaks": None}
    for name in ("offer.plan_ms_per_job", "lp.ms_per_job", "dp.ms_per_job",
                 "price.ms_per_job", "price_bundle_roofline",
                 "minplus_roofline"):
        assert cells.metric_reader(name)(ctx) is None
    assert cells.metric_reader("device.idle_share")(ctx) == pytest.approx(80.0)


def test_benchmark_json_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.25 and m["source"] in ("host_clock",
                                                          "device_trace")
    cell_names = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cell_names
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for c in BENCH["configs"]:
        assert (REPO / c["file"]).is_relative_to(REPO / BENCH["paths"][0])


# ------------------------------------------------------- kernel work counts
def test_price_bundle_work_by_hand():
    flops, nbytes = work.price_bundle(64 * 1024, 4, 1)
    assert flops == 2 * 3 * 4 * 65536                # 3,145,728
    assert nbytes == 4 * (65536 * 4 + 3 * 4 + 3 * 65536)   # 1,835,056
    flops, nbytes = work.price_bundle(2 * 65536 + 1024, 4, 3)
    assert flops == 2 * 3 * 4 * 132096               # 3,170,304
    assert nbytes == 4 * (132096 * 4 + 3 * 4 * 3 + 3 * 132096)  # 3,698,832


def test_minplus_work_by_hand():
    flops, nbytes = work.minplus(16)
    assert flops == 2 * (17 * 18 // 2)              # 306
    assert nbytes == 4 * 3 * 17                      # 204


def test_roofline_share_and_its_bound():
    pk = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    pct, bound = work.roofline_pct(flops=50.0, nbytes=20.0, seconds=4.0, pk=pk)
    assert bound == "memory" and pct == pytest.approx(100.0 * 2.0 / 4.0)
    pct, bound = work.roofline_pct(flops=500.0, nbytes=20.0, seconds=10.0, pk=pk)
    assert bound == "compute" and pct == pytest.approx(50.0)


def test_peaks_known_device_and_unknown_is_an_error():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


# -------------------------------------------------------- trace reduction
def test_union_and_gaps_by_hand():
    busy = trace.union([(5, 7), (0, 2), (1, 3), (9, 9), (6, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert trace.gaps(busy, -1, 10) == [(-1, 0), (3, 5), (8, 10)]


def test_reduce_synthetic_two_devices():
    dev = trace.DeviceEvents(
        per_device={
            "/device:TPU:0": [("minplus", 1000.0, 3000.0),
                              ("price_bundle", 2000.0, 6000.0),
                              ("fusion.1", 8000.0, 9000.0)],
            "/device:TPU:1": [("minplus", 4000.0, 5000.0)],
        },
        marker_ns=1000.0)
    # host clock 1.0 s is the marker; window [1.0 s, 1.0 s + 10 us]
    red = trace.reduce(dev, 1.0, 1.0 + 10e-6, 1.0)
    assert red.window_s == pytest.approx(10e-6)
    # device 0 busy [1000, 6000] + [8000, 9000] = 6 us; device 1: 1 us
    assert red.busy_s == pytest.approx(3.5e-6)
    assert red.kernel("minplus") == (pytest.approx(3e-6), 2)
    assert red.kernel("price_bundle") == (pytest.approx(4e-6), 1)
    gaps = [(round(a * 1e6, 6), round(b * 1e6, 6)) for a, b in red.gaps]
    assert gaps == [(1e6 + 5, 1e6 + 7), (1e6 + 8, 1e6 + 10)]


def test_name_gaps_by_innermost_span():
    spans = [("offer.batch", 0.0, 10.0, 0), ("dp.sweep", 2.0, 6.0, 1),
             ("lp.solve", 7.0, 8.0, 1)]
    gaps = [(3.0, 4.0), (7.2, 7.6), (8.5, 9.5), (11.0, 13.0)]
    named = trace.name_gaps(gaps, spans)
    assert named == [["engine", 2.0], ["dp.sweep", 1.0], ["offer.batch", 1.0],
                     ["lp.solve", pytest.approx(0.4)]]


def test_reduce_recorded_cpu_trace():
    meta = json.loads((BENCH_DIR / "testdata" / "cpu_small.json").read_text())
    dev = trace.load(str(BENCH_DIR / "testdata" / "cpu_small.xplane.pb"),
                     device_prefix="/host:CPU",
                     op_line=lambda n: n.startswith("tf_XLAPjRtCpuClient"))
    assert dev.marker_ns == 21415.0
    red = trace.reduce(dev, meta["marker_host"], meta["close_host"],
                       meta["marker_host"])
    assert red.op_count["dot_general.1"] == 4
    assert red.op_s["dot_general.1"] == pytest.approx(0.00299434, rel=1e-6)
    assert red.window_s == pytest.approx(0.025220077, rel=1e-6)
    assert red.busy_s == pytest.approx(0.003980802, rel=1e-6)
    assert 0 < red.busy_s < red.window_s
    idle = sum(b - a for a, b in red.gaps)
    assert idle == pytest.approx(red.window_s - red.busy_s, rel=1e-6)


def test_reduce_refuses_a_trace_without_marker_or_device():
    with pytest.raises(ValueError):
        trace.reduce(trace.DeviceEvents({"/device:TPU:0": []}, None), 0, 1, 0)
    with pytest.raises(ValueError):
        trace.reduce(trace.DeviceEvents({}, 5.0), 0, 1, 0)
    assert math.isfinite(trace.reduce(
        trace.DeviceEvents({"/device:TPU:0": []}, 0.0), 0, 1, 0).window_s)
