#!/usr/bin/env bash
# Tier-1 CI gate: full test suite + scheduler-throughput smoke + simulator
# smoke + bench-regression guard.
#
# The scheduler smoke benchmark runs the vectorized PD-ORS core against the
# frozen pre-PR reference on a tiny grid (< 60 s) and exits nonzero if their
# admission decisions or total utility diverge — catching both perf-path
# regressions and semantic drift without the multi-minute full sweep
# (python -m benchmarks.bench_scheduler for that).
#
# The scheduler smoke grid covers BOTH regimes: the online
# many-small-jobs point and a heavy-contention (workload_scale=0.3,
# LP-bound) point exercising the batched solve-plan path end to end.
#
# The sim smoke replays a short google-trace stream (completions, failures/
# preemption, departures) through all four policies via the unified
# registry (python -m benchmarks.bench_sim for the full sweep); the chaos
# smoke leg reruns it under the fault-domain harness (machine crashes,
# stragglers, injected LP faults). The docs
# check fails if docs/*.md reference modules that no longer exist. The jax
# leg reruns the backend parity suite with REPRO_BACKEND=jax as the
# process-wide default (skipped cleanly when jax is not importable — e.g.
# a CPU-only box without the toolchain). Finally the guard fails if the
# fresh pdors smoke jobs/sec drops >30% below the smoke baseline recorded
# in BENCH_scheduler.json at the same backend- and shape-aware grid key
# (a grid edit with no matching baseline fails loudly), or if the
# heavy-contention point's in-process speedup over the frozen core falls
# under 2.5x at the FULL heavy point (25x20x50, best-of-2 — the ratio
# is only stable at scale; the cover/packing exact-replay solver lands
# ~3.5x there on recorded best-of rows, and a broken fast path shows
# up as ~1x; see
# docs/SOLVER.md and docs/BENCHMARKS.md). BENCH_GUARD_SKIP=1 bypasses
# entirely on known-noisy runners.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

python -m pytest -x -q
python scripts/check_docs_refs.py
REPRO_BACKEND=jax python -m pytest tests/test_backend.py -q
python -m benchmarks.bench_scheduler --smoke --repeat-best-of 2 \
  --out BENCH_scheduler_smoke.json
# traced smoke: the same grid with observability on (REPRO_TRACE=1 +
# --profile). The benchmark exits nonzero on any decision divergence
# from the frozen reference, so this leg asserts the tracer's
# zero-interference contract (instrumented decisions bit-identical) on
# every CI run — see docs/OBSERVABILITY.md
REPRO_TRACE=1 python -m benchmarks.bench_scheduler --smoke --profile \
  --baselines "" --out BENCH_scheduler_trace_smoke.json
python -m benchmarks.bench_sim --smoke --out BENCH_sim_smoke.json
# chaos smoke: the same trace under correlated machine crashes,
# stragglers, and injected LP faults (pdors resilient-wrapped) — every
# policy must finish with the ledger invariant intact (check_ledger is
# always on in the engine; a violation raises LedgerInvariantError)
python -m benchmarks.bench_sim --smoke --faults \
  --out BENCH_sim_chaos_smoke.json
# stream smoke: the scaled-down 100k-job configuration — one long google
# stream through the batched engine (streaming metrics) plus a pdors
# service-latency row through the asyncio OfferService boundary. The
# guard enforces absolute floors on the fresh rows: sustained jobs/sec,
# process peak RSS (the streaming-metrics O(1)-rows contract), and the
# admission-latency p99 SLO (see docs/BENCHMARKS.md)
python -m benchmarks.bench_sim --smoke-scale \
  --out BENCH_sim_stream_smoke.json
python scripts/bench_guard.py BENCH_sim_stream_smoke.json \
  --stream-min-jobs-per-sec 400 --stream-max-rss-mb 1024 \
  --stream-max-p99-ms 2000
# elastic smoke: a reshape storm (SLAQ shrink + adadamp grow triggers,
# deadlines and loss SLOs) replayed per policy; every row must report
# batched-vs-event bit-parity on the elastic trace, reshapes actually
# firing, and the loss-SLO attainment floor (see docs/BENCHMARKS.md)
python -m benchmarks.bench_sim --smoke --elastic \
  --out BENCH_sim_elastic_smoke.json
python scripts/bench_guard.py BENCH_sim_elastic_smoke.json \
  --elastic-require-parity --elastic-min-reshapes 1 \
  --elastic-min-slo-attainment 0.5
python scripts/bench_guard.py BENCH_scheduler_smoke.json BENCH_scheduler.json \
  --max-drop 0.30 --min-speedup 2.5 --min-speedup-scale 0.3 \
  --min-speedup-point 25x20x50
