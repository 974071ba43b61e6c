"""CPU rehearsal of a cell's cluster-side quantities, no chip needed.

    PYTHONPATH=src python benchmarks/chip/rehearse.py --workload <cell> \\
        --seed <n> [--slots 16] [--backend numpy]

Runs the cell's configuration and traffic through the same engine, warm-
up and window as ``run.py``, but on the host (numpy backend) and for a
fixed number of window slots instead of a fixed time. What it prints does
not depend on the device: admissions, batch sizes, occupancy, LP solves
and simplex fallbacks per offer, and the reference's numbers for the
window. Timings printed here are host timings of the numpy backend and
stand for nothing on the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from collections import Counter
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--backend", default="numpy")
    args = ap.parse_args(argv)

    import numpy as np
    from harness import engine as eng, reference
    from harness.cells import load_benchmark, load_cell
    from repro.obs.trace import Tracer

    c = load_cell(load_benchmark(), args.workload)
    cfg, tr = c.config, c.traffic
    tracer = Tracer()
    t0 = time.perf_counter()
    run = eng.build(cfg, tr, args.seed, args.backend, float("inf"),
                    tracer=tracer)
    run.recorder.close_slot = tr.warm_slots + args.slots
    eng.drive(run, tr, args.seed)
    rec = run.recorder
    window = run.engine.window
    cl = window.cluster
    util = cl.utilization(0)
    used = np.asarray(cl.backend.to_host(cl._used))
    phase = Counter()
    for sp in tracer.spans:
        if sp.dur is not None and sp.t0 >= rec.t_open:
            phase[sp.name] += 1
    cap = cfg.capacity_array()
    nums = reference.check(run, cap, cfg.resources, cfg.quanta,
                           used, window.now)
    sizes = [len(b.job_ids) for b in rec.batches]
    out = {
        "cell": args.workload, "seed": args.seed,
        "window_slots": [rec.slot_open, rec.slot_close],
        "decisions": rec.decisions(), "admitted": nums.admitted,
        "admission_rate": nums.admitted / max(1, rec.decisions()),
        "batch_mean": float(np.mean(sizes)) if sizes else 0.0,
        "batch_max": max(sizes) if sizes else 0,
        "utilization_now": util,
        "ledger_fill_mean": float((used / cap[None]).mean()),
        "per_offer": {k: phase[k] / max(1, rec.decisions())
                      for k in ("lp.solve", "lp.replay", "lp.simplex",
                                "dp.sweep", "price.prewarm", "plan.build")},
        "checks": {"unanswered": nums.unanswered, "invalid": nums.invalid,
                   "ledger_gap": nums.ledger_gap, "payoff_gap": nums.payoff_gap},
        "split_schedules": nums.split_schedules,
        "notes": nums.notes,
        "host_seconds": time.perf_counter() - t0,
    }
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
