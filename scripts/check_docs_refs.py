"""Docs-reference check: every repo path mentioned in docs/*.md exists,
and every registered public symbol exists in code AND is documented.

Cheap grep-based gate for the equations-to-code map: extracts every
backtick-quoted repo path (``src/...``, ``scripts/...``, ``tests/...``,
``benchmarks/...``, ``docs/...``, top-level ``*.md``)
and every dotted ``repro.foo.bar`` module reference from the markdown
files under docs/ (plus README.md), and fails listing anything that no
longer exists — so module renames cannot silently rot the architecture
docs.

``PUBLIC_SYMBOLS`` additionally pins the public API surfaces the docs
promise to cover: for each (source file, symbol) entry the symbol must
be defined in that file (a rename fails here) and mentioned in at least
one checked markdown file (dropping its documentation fails here).  Add
an entry for every public symbol a PR introduces.

Usage:  python scripts/check_docs_refs.py  [docfile ...]
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

PATH_RE = re.compile(
    r"`((?:src|scripts|tests|benchmarks|examples|docs)/[\w./\-]+"
    r"|[A-Z][\w\-]*\.md)`"
)
MODULE_RE = re.compile(r"`(repro(?:\.\w+)+)`")

# public API surfaces the docs must keep covering: file -> symbols that
# must be defined there and mentioned in docs/*.md or README.md
PUBLIC_SYMBOLS = {
    "src/repro/core/cover_packing.py": [
        "CoverPackingLP",
        "TemplateCache",
        "detect_cover_packing",
        "solve_cover_packing_batch",
        "solve_lp_batch",
        "subset_template_cache",
    ],
    "src/repro/core/lp.py": [
        "linprog_batch",
        "linprog_batch_built",
        "TableauTemplate",
        "lazy_rhs",
    ],
    "src/repro/core/solve_plan.py": ["SolvePlan", "solve_plans",
                                    "patch"],
    "src/repro/core/subproblem.py": ["SubproblemConfig", "rng_mode",
                                     "lp_solver", "SolverFault",
                                     "SolverTimeout", "lp_fault_hook"],
    "src/repro/core/cluster.py": ["set_capacity_mask",
                                  "machine_overcommitted",
                                  "slot_version", "release_group"],
    "src/repro/core/job.py": ["QualityCurve", "ElasticProfile",
                              "at_level", "marginal_floor",
                              "damper_loss"],
    "src/repro/sim/faults.py": ["FaultPlan", "FaultIncident",
                                "SolverFaultInjector",
                                "merge_event_streams"],
    "src/repro/sim/engine.py": ["LedgerInvariantError", "SimKilled",
                                "checkpoint_every", "refail_rate",
                                "engine_mode", "admission_latency",
                                "reshape_cooldown", "ElasticState"],
    "src/repro/sim/policy.py": ["ResilientPolicy", "on_reshape"],
    "src/repro/sim/metrics.py": ["samples_trained", "P2Quantile",
                                 "job_done", "job_closed",
                                 "deadline_hit", "slo_hit"],
    "src/repro/sim/events.py": ["pop_slot", "RESHAPE"],
    "src/repro/sim/traces.py": ["elastic_frac", "deadline_frac",
                                "slo_frac"],
    "src/repro/sim/window.py": ["release_many", "holders_at", "regrant"],
    "src/repro/sim/service.py": ["OfferService", "poll", "heartbeat",
                                 "metrics_text", "start_http"],
    "src/repro/backend/__init__.py": ["lp_solver_default"],
    "src/repro/obs/trace.py": ["Tracer", "Span", "phase_table",
                               "total_self_s", "activate", "device_get"],
    "src/repro/obs/metrics.py": ["MetricsRegistry", "Counter", "Gauge",
                                 "Histogram", "get_registry",
                                 "warn_once_event", "render", "snapshot"],
    "src/repro/obs/pd_gap.py": ["PDGapTracker", "record_offer",
                                "dual_price_term"],
}


def module_exists(dotted: str) -> bool:
    rel = Path("src", *dotted.split("."))
    return (
        (ROOT / rel).with_suffix(".py").exists()
        or (ROOT / rel / "__init__.py").exists()
    )


def check_file(doc: Path) -> list:
    text = doc.read_text()
    missing = []
    for m in PATH_RE.finditer(text):
        ref = m.group(1)
        if not (ROOT / ref).exists():
            missing.append((doc.name, ref))
    for m in MODULE_RE.finditer(text):
        ref = m.group(1)
        if not module_exists(ref):
            missing.append((doc.name, ref))
    return missing


def check_symbols(docs: list) -> list:
    """(origin, complaint) pairs for PUBLIC_SYMBOLS violations."""
    corpus = "\n".join(d.read_text() for d in docs if d.exists())
    out = []
    for rel, symbols in PUBLIC_SYMBOLS.items():
        path = ROOT / rel
        if not path.exists():
            out.append(("PUBLIC_SYMBOLS", f"{rel} (file gone)"))
            continue
        src = path.read_text()
        for sym in symbols:
            # a symbol must be defined (def/class/field/assignment)
            ident = re.escape(sym)
            defined = re.search(
                rf"(?:def {ident}\b|class {ident}\b|^\s*{ident}\s*[:=])",
                src, re.M,
            ) is not None
            if not defined:
                out.append(("PUBLIC_SYMBOLS",
                            f"{rel}: symbol {sym!r} not defined"))
            if sym not in corpus:
                out.append(("PUBLIC_SYMBOLS",
                            f"{rel}: symbol {sym!r} undocumented "
                            "(no mention in docs/ or README)"))
    return out


def main(argv=None) -> int:
    args = (argv if argv is not None else sys.argv[1:])
    docs = [Path(a) for a in args] if args else sorted(
        (ROOT / "docs").glob("*.md")
    ) + [ROOT / "README.md"]
    missing = []
    checked = 0
    for doc in docs:
        if not doc.exists():
            missing.append(("<cli>", str(doc)))
            continue
        checked += 1
        missing.extend(check_file(doc))
    if not args:      # symbol coverage runs against the full default set
        missing.extend(check_symbols(docs))
    for doc, ref in missing:
        print(f"check_docs_refs: {doc}: missing reference {ref!r}")
    print(f"check_docs_refs: {checked} file(s) checked, "
          f"{len(missing)} stale reference(s)")
    return 1 if missing else 0


if __name__ == "__main__":
    sys.exit(main())
