"""Readings for a cell's limits: whole runs of the cell, sound and with a
fault planted under the timed path, many in one process.

    python3 benchmarks/chip/control.py --workload <cell> --seconds 51 \\
        --runs sound:11,12,13 stale_prices:21,22,23 no_splits:31,32,33

Each run goes through ``run.execute`` as a benchmark run does (same
build, warm-up, window and check), with the fault of ``planted.py`` of
that name planted in the built run; ``sound`` plants nothing. One JSON
line per run: ``correct``, the compared numbers with their limits, and
what the reference saw. ``stale_prices`` is the control. The benchmark's
own runs never plant anything; ``PERF.md`` gives the readings each limit
was set from.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
REPO = BENCH_DIR.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--runs", nargs="+", required=True,
                    help="<fault or sound>:<seed>,<seed>,...")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH_DIR))
    import run as bench_run
    bench_run.prepare_env()

    import jax
    import planted
    from harness import engine as eng
    from harness.cells import load_benchmark, load_cell

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    from repro.backend import get_backend
    get_backend("jax")
    bench = load_benchmark(REPO)
    c = load_cell(bench, args.workload, REPO)
    cell = c.spec
    build = eng.build
    for spec in args.runs:
        name, seeds = spec.split(":")
        for seed in (int(s) for s in seeds.split(",")):
            undo = []

            def planted_build(*a, **kw):
                run = build(*a, **kw)
                if name != "sound":
                    undo.append(planted.FAULTS[name](run))
                return run

            eng.build = planted_build
            t0 = time.perf_counter()
            try:
                result, nums = bench_run.execute(
                    bench, cell, c.config, c.traffic, c.limits, seed,
                    args.seconds, False,
                    t_start=t0)
            finally:
                eng.build = build
                for u in undo:
                    u()
            margins = sorted(nums.split_margins)
            print(json.dumps({
                "cell": cell["name"], "run": name, "seed": seed,
                "correct": result["correct"], "attempted": result["attempted"],
                "admitted": nums.admitted, "split": nums.split_schedules,
                "split_margin": [margins[0], margins[len(margins) // 2],
                                 margins[-1]] if margins else None,
                "checks": result["checks"],
                "seconds": time.perf_counter() - t0,
                "notes": nums.notes[:4]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
