#!/usr/bin/env bash
# Tier-1 CI gate: correctness only, in three legs.
#
#   1. the full test suite;
#   2. the docs check, which fails if docs/*.md reference modules or
#      names that no longer exist;
#   3. the backend parity suite rerun with REPRO_BACKEND=jax as the
#      process-wide default (skipped cleanly when jax is not importable).
#
# Speed is measured only by the chip benchmark (BENCHMARK.json,
# benchmarks/chip/run.py; results in PERF.md and PERF_LEDGER.jsonl).
# No leg here gates a merge on a host-clock number.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

python -m pytest -x -q
python scripts/check_docs_refs.py
REPRO_BACKEND=jax python -m pytest tests/test_backend.py -q
