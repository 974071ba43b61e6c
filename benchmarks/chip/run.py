"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

from the root of a checkout, on a machine whose first JAX device is a
TPU. A run builds the online PD-ORS scheduler the way users run it
(``SimEngine``, batched, ``pdors`` policy, ``RollingWindow`` over a
``jax``-backend cluster, the backend's own kernel selection), replays
the cell's seeded job backlog through it, warms up for the traffic's
``warm_slots`` slots (every shape compiles there), measures from that
slot boundary to the first slot boundary after ``--seconds``, then stops
the engine without draining it and checks every decision of the window
against the plain reference (``harness/reference.py``).

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` records
the program's spans and a profiler trace over the window and prints the
per-layer metrics, each read by ``layer_metrics/<name>.py``. The last
line of standard output is one JSON object; the numbers compared with
the reference are the last lines of standard error. Without a TPU, or
with fewer chips than the cell asks for, the run exits with code 2 and
prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
REPO = BENCH_DIR.parents[1]
OUT_DIR = BENCH_DIR / ".out"
CACHE_DIR = REPO / ".jax_cache"
#: jax's monitoring event for one backend compile (or persistent-cache load)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def fail(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        ap.error("--seconds must be positive")
    return args


def prepare_env() -> None:
    """The run selects nothing: the backend's own kernels, no span tracer
    unless --trace 1, the compile cache at a fixed path in the checkout,
    and the program imported from the checkout."""
    for var in ("REPRO_PRICE_KERNEL", "REPRO_BACKEND", "REPRO_TRACE"):
        os.environ.pop(var, None)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")     # no logs under /tmp
    sys.path.insert(0, str(REPO / "src"))


def in_window_spans(tracer, t_open, t_close):
    """The program's spans that ran inside the window: a phase table
    (count, total_s, self_s per name) and (name, start, end, depth)."""
    table, spans = {}, []
    for sp in tracer.spans:
        if sp.dur is None or sp.t0 < t_open or sp.t0 + sp.dur > t_close:
            continue
        row = table.setdefault(sp.name, {"count": 0, "total_s": 0.0,
                                         "self_s": 0.0})
        row["count"] += 1
        row["total_s"] += sp.dur
        row["self_s"] += max(0.0, sp.dur - sp.child_dur)
        spans.append((sp.name, sp.t0, sp.t0 + sp.dur, sp.depth))
    return table, spans


def execute(bench: dict, cell: dict, cfg, tr, limits: dict, seed: int,
            seconds: float, trace: bool, backend: str = "jax",
            t_start: float = T_START):
    """One run after the device check: build, warm up, measure, check.
    Returns the result object and the reference's numbers."""
    import jax
    import numpy as np

    from harness import engine as eng, reference, trace as tr_reduce, work
    from harness.cells import cell_metrics, metric_reader
    from harness.window import percentile

    compiles = {"window": 0}
    run = None

    def on_compile(event, duration, **kw):
        if event == COMPILE_EVENT and run is not None and run.recorder.is_open:
            compiles["window"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)

    tracer, marker = None, {}
    trace_dir = OUT_DIR / f"trace-{cell['name']}"
    if trace:
        from repro.obs.trace import Tracer
        tracer = Tracer()
        shutil.rmtree(trace_dir, ignore_errors=True)

    def start_profiler():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        ann = jax.profiler.TraceAnnotation(tr_reduce.MARKER)
        ann.__enter__()
        marker["host"] = time.perf_counter()
        ann.__exit__(None, None, None)

    run = eng.build(cfg, tr, seed, backend, seconds, tracer=tracer,
                    on_open=start_profiler if trace else None)
    cluster = run.engine.window.cluster
    if cluster.backend.is_device:
        eng.warm_scatter_widths(cluster, tr)
    try:
        eng.drive(run, tr, seed)
    finally:
        if trace and run.recorder.t_open is not None:
            jax.profiler.stop_trace()
    rec = run.recorder

    dev = jax.devices()[0]
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    final_used = np.asarray(cluster.backend.to_host(cluster._used))
    final_now = run.engine.window.now
    phase, spans = ({}, []) if tracer is None else in_window_spans(
        tracer, rec.t_open, rec.t_close)
    run.engine = cluster = None             # free the program's state

    nums = reference.check(run, cfg.capacity_array(), cfg.resources,
                           cfg.quanta, final_used, final_now)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": None, "attempted": rec.decisions(),
              "failed": rec.failed, "metrics": {}, "device": device}

    if trace:
        red = tr_reduce.reduce(
            tr_reduce.load(tr_reduce.find_xplane(str(trace_dir))),
            rec.t_open, rec.t_close, marker["host"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        ctx = {"recorder": rec, "phase": phase, "offers": rec.decisions(),
               "reduction": red, "compiles": compiles["window"],
               "config": cfg, "peaks": work.peaks(dev.device_kind)}
        for m in cell_metrics(bench, cell["name"], "per_layer"):
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        ops = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": tr_reduce.name_gaps(red.gaps, spans),
        }
    else:
        samples = rec.decide_samples()
        values = {"jobs_per_s": rec.jobs_per_s(),
                  "decide_p50_ms": percentile(samples, 50) * 1e3,
                  "decide_p90_ms": percentile(samples, 90) * 1e3,
                  "setup_s": rec.t_open - t_start}
        for m in cell_metrics(bench, cell["name"], "end_to_end"):
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}

    checks = {"unanswered": nums.unanswered, "invalid": nums.invalid,
              "ledger_gap": nums.ledger_gap, "payoff_gap": nums.payoff_gap}
    result["correct"] = bool(
        rec.failed == 0 and nums.offers > 0
        and all(checks[k] <= limits[k] for k in checks))
    result["checks"] = {k: {"value": v, "limit": limits[k]}
                        for k, v in checks.items()}
    for note in nums.notes:
        print(f"chipbench: {note}", file=sys.stderr)
    margins = sorted(nums.split_margins)
    print(f"chipbench: set-up {rec.t_open - t_start!r} s, "
          f"window slots [{rec.slot_open}, {rec.slot_close}) "
          f"{rec.window_s!r} s, {rec.decisions()} decisions in "
          f"{len(rec.batches)} batches, {nums.admitted} admitted "
          f"({nums.split_schedules} split over machines), "
          f"{nums.offers} scored against the reference, "
          f"{compiles['window']} compiles in the window", file=sys.stderr)
    spread = (f"{margins[0]!r} {margins[len(margins) // 2]!r} {margins[-1]!r}"
              if margins else "none")
    print("chipbench: beyond the tie band, in the payoff gap's unit: payoff "
          "above the co-located case, min/median/max over admitted offers, "
          f"{spread}; {nums.tied} offers short by no more than the band, "
          f"program/reference cost up to {nums.tie_cost_ratio!r}; largest "
          f"shortfall {nums.shortfall_rel!r} of the payoff", file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k} {v!r} limit {limits[k]!r}", file=sys.stderr)
    sys.stderr.flush()
    return result, nums


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, str(BENCH_DIR))
    from harness.cells import load_benchmark, load_cell

    if not (REPO / "src" / "repro").is_dir():
        fail(f"the system under test is not in this checkout ({REPO / 'src'})")
    bench = load_benchmark(REPO)
    c = load_cell(bench, args.workload, REPO)

    prepare_env()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX's first device is {devices[0].platform!r}")
    if len(devices) < int(c.spec["chips"]):
        fail(f"{len(devices)} chips, the cell asks for {c.spec['chips']}")
    from repro.backend import get_backend
    get_backend("jax")          # configures the persistent compile cache

    result, _ = execute(bench, c.spec, c.config, c.traffic, c.limits,
                        args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
