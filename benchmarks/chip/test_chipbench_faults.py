"""The reference must catch a broken timed path: a CPU run of the whole
harness after the device check (``run.execute``) at a size a test can
hold, once sound and once with each fault of ``planted.py`` planted
underneath, must come out ``correct`` and not ``correct`` respectively.
``stale_prices`` is the control. A one-chip cell has no exchange between
chips, so that fault has no case here.

The same holds on a fleet of two machine classes under a traffic that
states every job-mix key (``testdata/mixed_fleet.*.json``), and on that
fleet, or on 24 machines of its half-size class, under contention (12
arrivals a slot at ``workload_scale`` 0.05), where the prices fall
towards L and a schedule may cost 1e-18: sound runs read no shortfall
beyond the reference's tie band, planted faults far beyond it."""
import importlib.util
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parent
REPO = BENCH_DIR.parents[1]
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(REPO / "src"))

import planted  # noqa: E402
from gen.traffic import Traffic, load_traffic  # noqa: E402
from harness import engine as eng, reference  # noqa: E402
from harness.cells import Config, MachineClass, check_demands, load_config  # noqa: E402

_spec = importlib.util.spec_from_file_location("chipbench_run", BENCH_DIR / "run.py")
bench_run = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_run)

CFG = Config("tiny", 24, 12, 8, "ethernet", (MachineClass(
    "tiny", 24, {"cpu": 180.0, "gpu": 72.0, "mem": 576.0, "storage": 180.0}),))
TRAFFIC = Traffic(preset="google", arrival_rate=6.0, workload_scale=0.02,
                  failure_rate=0.1, warm_slots=6)
MIXED_CFG = load_config(BENCH_DIR / "testdata" / "mixed_fleet.config.json")
MIXED_TRAFFIC = load_traffic(BENCH_DIR / "testdata" / "mixed_fleet.traffic.json")
BENCH = {"end_to_end": [{"name": n, "unit": "u"} for n in
                        ("jobs_per_s", "decide_p50_ms", "decide_p90_ms",
                         "setup_s")], "per_layer": []}
CELL = {"name": "tiny.cell", "chips": 1}
LIMITS = json.loads((BENCH_DIR / "limits" / "google1024.light.json").read_text())
SEED = 2**31 + 17
CONTENDED = replace(MIXED_TRAFFIC, arrival_rate=12.0, workload_scale=0.05)
HALF_CFG = Config("half-fleet", 24, 12, 8, None, (
    MachineClass("half", 24, MIXED_CFG.classes[1].capacity),))


def _execute(monkeypatch, fault=None, backend="numpy", close_slot=16,
             trace=False, bench=BENCH, cell=CELL, cfg=CFG, traffic=TRAFFIC,
             seed=SEED):
    """One whole run on the CPU, its window closed at ``close_slot``, with
    ``fault`` planted in the built run. The build is put back when the
    run ends, so a second run in one test builds from the program's own
    and plants only its own fault."""
    build, undo = eng.build, []

    def planted_build(*a, **kw):
        run = build(*a, **kw)
        run.recorder.close_slot = close_slot
        if fault is not None:
            undo.append(fault(run))
        return run

    with monkeypatch.context() as m:
        m.setattr(eng, "build", planted_build)
        try:
            return bench_run.execute(bench, cell, cfg, traffic, LIMITS, seed,
                                     1e9, trace, backend=backend,
                                     t_start=time.perf_counter())
        finally:
            for u in undo:
                u()


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_sound_run_is_correct(monkeypatch, backend):
    res, nums = _execute(monkeypatch, backend=backend)
    assert res["correct"], res["checks"]
    assert nums.offers >= 30 and nums.offers == res["attempted"]
    assert list(res)[-1] == "checks"


# the jax backend (on the CPU here) clamps a release silently, as on the
# chip; the numpy backend would assert before the reference could look
@pytest.mark.parametrize("fault, number, backend", [
    (planted.state_unchanged, "ledger_gap", "jax"),
    (planted.half_batch, "unanswered", "numpy"),
    (planted.answer_altered, "invalid", "numpy"),
    (planted.answer_rejected, "payoff_gap", "numpy"),
    (planted.stale_prices, "payoff_gap", "numpy"),
    (planted.no_splits, "payoff_gap", "numpy"),
])
def test_planted_fault_is_not_correct(monkeypatch, fault, number, backend):
    res, _ = _execute(monkeypatch, fault=fault, backend=backend)
    assert res["correct"] is False
    got = res["checks"][number]
    assert got["value"] > got["limit"]


def test_no_splits_puts_the_program_back():
    from repro.core import solve_plan, subproblem
    before = (solve_plan._prune_fill, subproblem._prune_stats)
    planted.no_splits(None)()
    assert (solve_plan._prune_fill, subproblem._prune_stats) == before


def test_traced_run_reads_the_per_layer_metrics(monkeypatch):
    """The ``--trace 1`` path end to end on the CPU: the profiler trace is
    reduced (the CPU's op line stands in for the TPU's), every reader
    runs on a real context, and the result carries busy and window
    seconds and a breakdown."""
    import functools
    from harness import trace, work
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = dict(bench["workloads"][0])
    monkeypatch.setattr(trace, "load", functools.partial(
        trace.load, device_prefix="/host:CPU",
        op_line=lambda n: n.startswith("tf_XLAPjRtCpuClient")))
    v5e = work.peaks("TPU v5 lite")
    monkeypatch.setattr(work, "peaks", lambda kind: v5e)
    res, _ = _execute(monkeypatch, backend="jax", close_slot=12, trace=True,
                      bench=bench, cell=cell)
    assert res["correct"], res["checks"]
    dev = res["device"]
    assert 0 < dev["busy_s"] < dev["window_s"]
    got = res["metrics"]
    for name in ("engine.ms_per_slot", "offer.plan_ms_per_job",
                 "dp.ms_per_job", "price.ms_per_job", "device.idle_share",
                 "device.compiles"):
        assert name in got, name
    assert 0 < got["device.idle_share"]["value"] < 100
    # no Pallas kernel runs off a TPU, so their rooflines stay silent
    assert "price_bundle_roofline" not in got and "minplus_roofline" not in got
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]
    assert list(res)[-1] == "checks"


# ------------------------------------------------- a fleet of two classes
def test_mixed_fleet_files_load_and_state_every_key():
    cfg, tr = MIXED_CFG, MIXED_TRAFFIC
    check_demands(cfg, tr, "mixed_fleet")
    assert [(c.name, c.count) for c in cfg.classes] == [("full", 16), ("half", 8)]
    cap = cfg.capacity_array()
    assert cap.shape == (24, 4)
    assert (cap[:16] == 2 * cap[16:].max(axis=0)).all()
    raw = json.loads((BENCH_DIR / "testdata" / "mixed_fleet.traffic.json").read_text())
    assert {"mix", "burst", "worker_demand", "ps_demand", "batch",
            "size_tail"} <= set(raw)
    assert len(tr.batch) == 3 and tr.size_tail["cap"] == 40.0


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_mixed_fleet_sound_run_is_correct(monkeypatch, backend):
    res, nums = _execute(monkeypatch, backend=backend, cfg=MIXED_CFG,
                         traffic=MIXED_TRAFFIC)
    assert res["correct"], res["checks"]
    assert nums.offers >= 30 and nums.offers == res["attempted"]
    assert 0 < nums.admitted < nums.offers and nums.split_schedules > 0


# every fault on the jax backend; on numpy all but the unchanged state,
# which the numpy backend asserts on before the reference can look
@pytest.mark.parametrize("fault, number, backends", [
    pytest.param(planted.state_unchanged, "ledger_gap", ["jax"],
                 id="state_unchanged"),
    pytest.param(planted.half_batch, "unanswered", ["numpy", "jax"],
                 id="half_batch"),
    pytest.param(planted.answer_altered, "invalid", ["numpy", "jax"],
                 id="answer_altered"),
    pytest.param(planted.answer_rejected, "payoff_gap", ["numpy", "jax"],
                 id="answer_rejected"),
    pytest.param(planted.stale_prices, "payoff_gap", ["numpy", "jax"],
                 id="stale_prices"),
    pytest.param(planted.no_splits, "payoff_gap", ["numpy", "jax"],
                 id="no_splits"),
])
def test_mixed_fleet_planted_fault_is_not_correct(monkeypatch, fault, number,
                                                  backends):
    for backend in backends:
        res, _ = _execute(monkeypatch, fault=fault, backend=backend,
                          cfg=MIXED_CFG, traffic=MIXED_TRAFFIC)
        assert res["correct"] is False, backend
        got = res["checks"][number]
        assert got["value"] > got["limit"], backend


# ------------------------------------------------------ under contention
@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("seed", [SEED, 11, 2**31 + 351, 4_000_000_001])
def test_contended_mixed_fleet_sound_run_is_correct(monkeypatch, seed, backend):
    res, nums = _execute(monkeypatch, backend=backend, cfg=MIXED_CFG,
                         traffic=CONTENDED, seed=seed)
    assert res["correct"], res["checks"]
    assert res["checks"]["payoff_gap"]["value"] == 0.0
    # contended: offers rejected, and some short of the reference by a tie
    assert nums.admitted < nums.offers / 2 and nums.tied > 0


# Algorithm 4's LP (core/cover_packing.py, the replay of core/lp.py's
# simplex) prices out and enters columns by absolute gates (|c| > 1e-12,
# reduced cost < -1e-9): where every price lies below them it returns its
# phase-1 vertex, blind to price. On this seed job 88's schedule costs
# 3.4e-11 where the LP's own optimum costs 2.8e-18: 1.4e-12 of its payoff
# and 3.5e6 of the reference's cost beyond the band
LP_BLIND = pytest.mark.xfail(strict=True, reason=(
    "the external LP stops at its phase-1 vertex when every price lies "
    "below its absolute tolerances (job 88: 3.4e-11 against 2.8e-18)"))


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("seed", [pytest.param(SEED, marks=LP_BLIND), 11])
def test_contended_half_fleet_sound_run_is_correct(monkeypatch, seed, backend):
    res, nums = _execute(monkeypatch, backend=backend, cfg=HALF_CFG,
                         traffic=CONTENDED, seed=seed)
    assert res["correct"], res["checks"]
    assert nums.admitted < nums.offers / 2


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("fault", [planted.stale_prices, planted.no_splits,
                                   planted.answer_rejected],
                         ids=lambda f: f.__name__)
def test_contended_planted_fault_is_not_correct(monkeypatch, fault, backend):
    res, nums = _execute(monkeypatch, fault=fault, backend=backend,
                         cfg=MIXED_CFG, traffic=CONTENDED)
    assert res["correct"] is False
    got = res["checks"]["payoff_gap"]
    assert got["value"] > got["limit"]
    assert nums.shortfall_rel > 1e5 * reference.TIE_REL
