"""Benchmark harness: one function per paper table/figure (deliverable d).

Prints ``name,us_per_call,derived`` CSV rows.  ``--full`` uses paper-scale
sweeps (slow); default sizes finish on one CPU core in ~15 minutes.
"""
import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sweeps")
    ap.add_argument("--only", default=None,
                    help="comma-separated figure names (e.g. fig6,fig10)")
    args = ap.parse_args()

    from . import (
        fig6_machines, fig7_jobs, fig8_oasis, fig9_median_time,
        fig10_competitive, fig11_gdelta, fig12_13_trace, fig14_17_jobmix,
        roofline_table,
    )
    figures = {
        "fig6": fig6_machines.run,
        "fig7": fig7_jobs.run,
        "fig8": fig8_oasis.run,
        "fig9": fig9_median_time.run,
        "fig10": fig10_competitive.run,
        "fig11": fig11_gdelta.run,
        "fig12_13": fig12_13_trace.run,
        "fig14_17": fig14_17_jobmix.run,
        "roofline": roofline_table.run,
    }
    only = set(args.only.split(",")) if args.only else None
    failures = []
    for name, fn in figures.items():
        if only and name not in only:
            continue
        t0 = time.time()
        print(f"# === {name} ===")
        try:
            if name in ("roofline",):
                fn()
            else:
                fn(full=args.full)
        except Exception:
            traceback.print_exc()
            failures.append(name)
        print(f"# {name} done in {time.time() - t0:.1f}s")
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)


if __name__ == "__main__":
    main()
