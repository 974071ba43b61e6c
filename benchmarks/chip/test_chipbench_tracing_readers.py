"""CPU tests of the readers of the device-boundary and engine-slot
metrics, on a hand-built in-window phase table (``run.in_window_spans``'s
shape: count, total_s and self_s per span name)."""
import sys
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))

from harness import cells  # noqa: E402

#: 4 offers over 3 whole slots in the window
PHASE = {
    "sim.slot": {"count": 3, "total_s": 3.0, "self_s": 0.030},
    "sim.advance": {"count": 3, "total_s": 0.006, "self_s": 0.003},
    "sim.arrivals": {"count": 4, "total_s": 2.9, "self_s": 0.012},
    "offer.batch": {"count": 4, "total_s": 2.888, "self_s": 0.5},
    "plan.finish": {"count": 4, "total_s": 1.0, "self_s": 1.0},
    "dp.sweep": {"count": 4, "total_s": 0.6, "self_s": 0.1},
    "device.launch": {"count": 300, "total_s": 0.09, "self_s": 0.09},
    "device.sync": {"count": 280, "total_s": 0.42, "self_s": 0.42},
}


def _ctx(phase, offers=4):
    return {"phase": phase, "offers": offers}


@pytest.mark.parametrize("name,expected", [
    ("device.launches_per_job", 300 / 4),
    ("device.launch_ms_per_job", 0.09 / 4 * 1e3),
    ("device.syncs_per_job", 280 / 4),
    ("device.sync_ms_per_job", 0.42 / 4 * 1e3),
    ("engine.self_ms_per_slot", (0.030 + 0.003 + 0.012) / 3 * 1e3),
])
def test_reader_on_a_hand_built_phase_table(name, expected):
    assert cells.metric_reader(name)(_ctx(PHASE)) == pytest.approx(expected)


@pytest.mark.parametrize("name", [
    "device.launches_per_job", "device.launch_ms_per_job",
    "device.syncs_per_job", "device.sync_ms_per_job",
    "engine.self_ms_per_slot",
])
def test_reader_returns_nothing_without_its_spans(name):
    """A program without the spans (or a window with no offers) gives
    None, never a raise: the parent of the change that added them."""
    bare = {k: v for k, v in PHASE.items()
            if not k.startswith("device.") and k != "sim.slot"}
    read = cells.metric_reader(name)
    assert read(_ctx(bare)) is None
    assert read(_ctx({})) is None
    if name.startswith("device."):
        assert read(_ctx(PHASE, offers=0)) is None


def test_engine_self_time_reads_only_sim_spans():
    """Offer and device self time under sim.arrivals is not the engine's."""
    read = cells.metric_reader("engine.self_ms_per_slot")
    heavier = dict(PHASE, **{
        "plan.finish": {"count": 4, "total_s": 9.0, "self_s": 9.0},
        "device.sync": {"count": 900, "total_s": 5.0, "self_s": 5.0}})
    assert read(_ctx(heavier)) == read(_ctx(PHASE))


def test_new_metrics_are_declared_for_the_light_cell():
    bench = cells.load_benchmark()
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name, layer, moves in (
            ("device.launches_per_job", "Device", "decide_p50_ms"),
            ("device.launch_ms_per_job", "Device", "decide_p50_ms"),
            ("device.syncs_per_job", "Device", "decide_p50_ms"),
            ("device.sync_ms_per_job", "Device", "decide_p50_ms"),
            ("engine.self_ms_per_slot", "Engine", "jobs_per_s")):
        m = per_layer[name]
        assert (m["layer"], m["moves"]) == (layer, moves)
        assert m["workloads"] == ["google1024.light"]
