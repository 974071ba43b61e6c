"""Chip smoke: drive the PD-ORS device path once on one TPU and check it.

    python chip_smoke.py        # from the repo root, on a machine with a TPU

One process, four phases, each printing its own lines:

  device   fail unless JAX's first device is a TPU;
  kernels  the row-tiled Pallas pricing kernel on a (64 x 1024, 4) price
           operand and the min-plus kernel at Q = 32, against their NumPy
           references; the min-plus sweep of 64 steps in one device call,
           bit for bit against 64 single steps; and proof that the pricing
           kernel and the sweep lowered to a Mosaic ``tpu_custom_call``
           (not interpret mode);
  served   ``OfferService`` over ``PDORS`` on ``jax``-backend clusters:
           200 light jobs on 1024 machines x 64 slots, then 50 contended
           jobs on 256 x 32, all submitted concurrently;
  online   ``SimEngine`` (batched) with the ``pdors`` policy over a
           300-job google stream with failures on a 128 x 32 window.

Correctness rule, against the NumPy backend on the same jobs in the same
process. The jax backend runs each served and online phase on two kernel
paths (``kernel_path``):

  f64      float64 jnp bundle pass + host float64 min-plus step, with the
           emulated-float64 ledger on the chip. Exact: identical admitted
           sets and per-slot worker/PS allocations (served), identical
           engine summary counts and JCT percentiles (online), total
           utility within a relative 1e-9 (float sums in another order).
           This is the rule the CPU golden tests hold the jax backend to.
  default  what the backend auto-selects; on a TPU the float32 Pallas
           pricing and min-plus kernels. Total utility within a relative
           1e-3 of NumPy's; differing decisions are counted and printed,
           not failed. On the chip the float32 min-plus step picks other
           equal-utility schedules among near-tied DP candidates (NumPy
           keeps the first candidate within 1e-12 of the row minimum), so
           per-slot allocations may differ while utility does not.

The f64 path runs twice: the first run includes every compile (``cold``),
the second reuses the compiled executables (``warm``) and must trace
nothing new. Times are host wall-clock seconds on the machine that holds
the chip.

The last line of standard output is one JSON object naming the device.
Any failure raises, so the script exits non-zero and prints no result.
"""
from __future__ import annotations

import asyncio
import contextlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

from repro.backend import get_backend  # noqa: E402
from repro.core import (  # noqa: E402
    SubproblemConfig,
    WorkloadConfig,
    make_cluster,
    synthetic_jobs,
)
from repro.core.pdors import PDORS  # noqa: E402
from repro.core.pricing import estimate_price_params  # noqa: E402
from repro.kernels import minplus, pricing  # noqa: E402
from repro.sim import (  # noqa: E402
    OfferService,
    RollingWindow,
    SimEngine,
    TraceConfig,
    calibrate_prices,
    make_policy,
    stream,
)

#: summary keys held to exact equality across backends (as in
#: tests/test_backend.py::test_sim_trace_equivalence_numpy_vs_jax)
SUMMARY_KEYS = ("jobs_admitted", "jobs_completed", "admission_rate",
                "completion_rate", "jct_p50", "jct_p95")
#: total-utility tolerance against the NumPy backend, per kernel path:
#: float sums in another order for the f64 path; float32 near-ties for the
#: Pallas kernels (see the module docstring)
UTILITY_RTOL = {"f64": 1e-9, "default": 1e-3}


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def trace_counts() -> dict:
    return {**get_backend("jax").trace_counts, **pricing.TRACE_COUNTS}


# ------------------------------------------------------------------ device
def phase_device() -> dict:
    dev = jax.devices()[0]
    out = {"platform": dev.platform, "kind": dev.device_kind,
           "count": len(jax.devices())}
    log("device", f"jax {jax.__version__} platform={out['platform']} "
                  f"kind={out['kind']} count={out['count']}")
    if out["platform"] != "tpu":
        raise RuntimeError(
            f"no TPU: JAX's first device is {out['platform']!r}")
    return out


# ----------------------------------------------------------------- kernels
def _lowered_to_mosaic(fn, *args, **kw) -> bool:
    return "tpu_custom_call" in fn.lower(*args, **kw).as_text()


def phase_kernels(slots: int = 64, machines: int = 1024, resources: int = 4,
                  quanta: int = 32, seed: int = 0, repeats: int = 5) -> dict:
    interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(seed)
    price = rng.uniform(0.1, 8.0, (slots, machines, resources))
    free = rng.uniform(0.0, 30.0, (slots, machines, resources))
    wdem = rng.uniform(0.0, 3.0, resources) * (rng.random(resources) > 0.3)
    sdem = rng.uniform(0.0, 3.0, resources) * (rng.random(resources) > 0.3)
    gamma = 4.0
    with jax.enable_x64(True):          # the jax backend's calling scope
        price_dev = jax.device_put(price)
        t0 = time.perf_counter()
        got = pricing.price_bundle_batch_pallas(price_dev, free, wdem, sdem,
                                                gamma)
        first = time.perf_counter() - t0
        steady = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            pricing.price_bundle_batch_pallas(price_dev, free, wdem, sdem,
                                              gamma)
            steady.append(time.perf_counter() - t0)
        price_mosaic = _lowered_to_mosaic(
            pricing._get_pallas_bundle(), price_dev,
            pricing.bundle_weights(wdem, sdem, gamma), interpret=interpret)
    ref = pricing.price_bundle_batch_numpy(price, free, wdem, sdem, gamma)
    for a, b in zip(ref[:3], got[:3]):
        np.testing.assert_allclose(b, a, rtol=2e-4, atol=2e-4)
    for a, b in zip(ref[3:], got[3:]):
        np.testing.assert_array_equal(b, a)   # integer head-room: exact
    log("kernels", f"pricing rows={slots * machines} R={resources} "
                   f"first_s={first:.6f} steady_s={np.median(steady):.6f} "
                   f"tpu_custom_call={price_mosaic} match=ok")

    Q1 = quanta + 1
    prev = rng.uniform(0.0, 100.0, Q1)
    tcost = rng.uniform(0.0, 100.0, Q1)
    prev[rng.random(Q1) < 0.2] = np.inf
    tcost[rng.random(Q1) < 0.2] = np.inf
    prev[0] = tcost[0] = 0.0
    t0 = time.perf_counter()
    cur, choice = minplus.minplus_pallas(prev, tcost)
    mp_first = time.perf_counter() - t0
    mp_steady = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        minplus.minplus_pallas(prev, tcost)
        mp_steady.append(time.perf_counter() - t0)
    P = minplus.ROW_TILE * -(-Q1 // minplus.ROW_TILE)
    ref_cur, ref_choice = minplus.minplus_numpy(prev, tcost)
    finite = np.isfinite(ref_cur)
    if not (np.isfinite(cur) == finite).all():
        raise AssertionError("min-plus kernel: reachable states differ")
    np.testing.assert_allclose(cur[finite], ref_cur[finite],
                               rtol=2e-6, atol=2e-4)
    if not ((choice < 0) == (ref_choice < 0)).all():
        raise AssertionError("min-plus kernel: backtrack pointers differ")
    log("kernels", f"minplus Q={quanta} P={P} first_s={mp_first:.6f} "
                   f"steady_s={np.median(mp_steady):.6f} match=ok")

    # the DP's path: one sweep of ``slots`` steps in one device call, bit
    # for bit the single step fed its own output ``slots`` times
    tcosts = rng.uniform(0.0, 100.0, (slots, Q1))
    tcosts[rng.random((slots, Q1)) < 0.2] = np.inf
    tcosts[:, 0] = 0.0
    start = np.full(Q1, np.inf)
    start[0] = 0.0
    t0 = time.perf_counter()
    best, bchoice = minplus.minplus_sweep_pallas(start, tcosts)
    sw_first = time.perf_counter() - t0
    sw_steady = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        minplus.minplus_sweep_pallas(start, tcosts)
        sw_steady.append(time.perf_counter() - t0)
    row = start
    for i in range(slots):
        row, ch = minplus.minplus_pallas(row, tcosts[i])
        if not (np.array_equal(row, best[i])
                and np.array_equal(ch, bchoice[i])):
            raise AssertionError(
                f"min-plus sweep: step {i} differs from the single step")
    mp_mosaic = _lowered_to_mosaic(
        minplus._get_pallas_sweep(), np.zeros(P, np.float32),
        np.zeros((slots, P), np.float32), np.int32(slots),
        interpret=interpret)
    log("kernels", f"minplus_sweep Q={quanta} P={P} steps={slots} "
                   f"first_s={sw_first:.6f} "
                   f"steady_s={np.median(sw_steady):.6f} "
                   f"tpu_custom_call={mp_mosaic} match=exact")
    return {"pricing_mosaic": price_mosaic, "minplus_mosaic": mp_mosaic}


# ------------------------------------------------------- kernel paths
@contextlib.contextmanager
def kernel_path(path: str):
    """Select the jax backend's snapshot-bundle and min-plus kernels.

    ``f64``: the float64 jnp bundle pass and the host float64 min-plus
    step (``REPRO_PRICE_KERNEL=jnp``, ``minplus_backend="numpy"``);
    ``default``: whatever the backend auto-selects — on a TPU the float32
    Pallas pricing and min-plus kernels. Yields the ``SubproblemConfig``
    the scheduler is built with."""
    price_kernel, minplus_backend = {"f64": ("jnp", "numpy"),
                                     "default": (None, None)}[path]
    saved = os.environ.pop("REPRO_PRICE_KERNEL", None)
    if price_kernel is not None:
        os.environ["REPRO_PRICE_KERNEL"] = price_kernel
    try:
        yield SubproblemConfig(minplus_backend=minplus_backend)
    finally:
        os.environ.pop("REPRO_PRICE_KERNEL", None)
        if saved is not None:
            os.environ["REPRO_PRICE_KERNEL"] = saved


def check_utility(what: str, path: str, want: float, got: float) -> None:
    rtol = UTILITY_RTOL[path]
    if abs(got - want) > rtol * abs(want):
        raise AssertionError(f"{what}: utility {got!r} vs numpy {want!r} "
                             f"exceeds rtol {rtol}")


# ------------------------------------------------------------------ served
def decision_trace(records) -> list:
    out = []
    for r in records:
        slots = None
        if r.schedule is not None:
            slots = {
                t: (sorted(a.workers.items()), sorted(a.ps.items()))
                for t, a in r.schedule.slots.items()
            }
        out.append((r.job.job_id, r.admitted, slots))
    return out


async def _serve(scheduler: PDORS, jobs) -> tuple:
    svc = await OfferService(scheduler, heartbeat_timeout=0).start()
    records = await asyncio.gather(*[svc.submit(j) for j in jobs])
    await svc.close()
    return list(records), svc.admission_latency()


def _served_run(backend: str, path: str, machines: int, horizon: int,
                jobs) -> tuple:
    cluster = make_cluster(machines, horizon, backend=backend)
    params = estimate_price_params(jobs, cluster, cluster.horizon)
    with kernel_path(path) as cfg:
        t0 = time.perf_counter()
        records, lat = asyncio.run(_serve(PDORS(cluster, params, cfg=cfg),
                                          jobs))
        return records, lat, time.perf_counter() - t0


def phase_served(machines: int, horizon: int, num_jobs: int,
                 workload_scale: float, seed: int = 0,
                 name: str = "served") -> dict:
    jobs = synthetic_jobs(WorkloadConfig(
        num_jobs=num_jobs, horizon=horizon, seed=seed,
        workload_scale=workload_scale))
    ref, _, ref_s = _served_run("numpy", "f64", machines, horizon, jobs)
    want, want_u = decision_trace(ref), sum(r.utility for r in ref)
    before = trace_counts()
    cold, _, cold_s = _served_run("jax", "f64", machines, horizon, jobs)
    mid = trace_counts()
    warm, lat, warm_s = _served_run("jax", "f64", machines, horizon, jobs)
    retraced = {k: v - mid[k] for k, v in trace_counts().items()
                if v != mid[k]}
    dflt, _, dflt_s = _served_run("jax", "default", machines, horizon, jobs)
    for label, path, got in (("f64 cold", "f64", cold),
                             ("f64 warm", "f64", warm),
                             ("default", "default", dflt)):
        diff = [a[0] for a, b in zip(want, decision_trace(got)) if a != b]
        if diff and path == "f64":
            raise AssertionError(
                f"{name}: jax {label} decisions differ from numpy on jobs "
                f"{diff[:10]} ({len(diff)} in all)")
        check_utility(f"{name} ({label})", path, want_u,
                      sum(r.utility for r in got))
    if retraced:
        raise AssertionError(f"{name}: warm run retraced {retraced}")
    dflt_diff = sum(a != b for a, b in zip(want, decision_trace(dflt)))
    admitted = sum(r.admitted for r in ref)
    log(name, f"H={machines} T={horizon} jobs={num_jobs} "
              f"scale={workload_scale} admitted={admitted} "
              f"utility={want_u!r} f64_decisions=identical "
              f"default_decisions_differing={dflt_diff} "
              f"default_admitted={sum(r.admitted for r in dflt)} "
              f"default_utility={sum(r.utility for r in dflt)!r}")
    log(name, f"numpy_s={ref_s:.3f} f64_cold_s={cold_s:.3f} "
              f"f64_warm_s={warm_s:.3f} compile_s~={cold_s - warm_s:.3f} "
              f"default_s={dflt_s:.3f} "
              f"f64_warm_latency_p50_ms={lat['p50_ms']:.3f} "
              f"p99_ms={lat['p99_ms']:.3f}")
    log(name, f"traces_in_cold_run="
              f"{ {k: v - before[k] for k, v in mid.items()} }")
    return {"admitted": admitted, "default_differing": dflt_diff}


# ------------------------------------------------------------------ online
def _online_run(backend: str, path: str, machines: int, horizon: int,
                tcfg: TraceConfig) -> tuple:
    cluster = make_cluster(machines, horizon, backend=backend)
    with kernel_path(path) as cfg:
        policy = make_policy("pdors", cfg=cfg,
                             price_params=calibrate_prices(tcfg, cluster))
        engine = SimEngine(RollingWindow(cluster), policy,
                           patience=tcfg.patience, engine_mode="batched")
        t0 = time.perf_counter()
        report = engine.run(stream(tcfg))
        return report.summary, time.perf_counter() - t0


def phase_online(machines: int = 128, horizon: int = 32,
                 num_jobs: int = 300, arrival_rate: float = 16.0,
                 failure_rate: float = 0.05, seed: int = 0) -> dict:
    tcfg = TraceConfig(preset="google", num_jobs=num_jobs,
                       arrival_rate=arrival_rate, failure_rate=failure_rate,
                       seed=seed)
    ref, ref_s = _online_run("numpy", "f64", machines, horizon, tcfg)
    before = trace_counts()
    cold, cold_s = _online_run("jax", "f64", machines, horizon, tcfg)
    mid = trace_counts()
    warm, warm_s = _online_run("jax", "f64", machines, horizon, tcfg)
    retraced = {k: v - mid[k] for k, v in trace_counts().items()
                if v != mid[k]}
    dflt, dflt_s = _online_run("jax", "default", machines, horizon, tcfg)
    for label, path, got in (("f64 cold", "f64", cold),
                             ("f64 warm", "f64", warm),
                             ("default", "default", dflt)):
        bad = {k: (ref[k], got[k]) for k in SUMMARY_KEYS if ref[k] != got[k]}
        if bad and path == "f64":
            raise AssertionError(
                f"online: jax {label} summary differs from numpy: {bad}")
        check_utility(f"online ({label})", path, ref["total_utility"],
                      got["total_utility"])
    if retraced:
        raise AssertionError(f"online: warm run retraced {retraced}")
    dflt_bad = [k for k in SUMMARY_KEYS if ref[k] != dflt[k]]
    log("online", f"H={machines} T={horizon} jobs={num_jobs} "
                  f"rate={arrival_rate} failures={failure_rate} "
                  + " ".join(f"{k}={ref[k]!r}" for k in SUMMARY_KEYS)
                  + f" utility={ref['total_utility']!r} f64_summary=identical"
                  f" default_keys_differing={dflt_bad}"
                  f" default_utility={dflt['total_utility']!r}")
    log("online", f"numpy_s={ref_s:.3f} f64_cold_s={cold_s:.3f} "
                  f"f64_warm_s={warm_s:.3f} compile_s~={cold_s - warm_s:.3f} "
                  f"default_s={dflt_s:.3f}")
    log("online", f"traces_in_cold_run="
                  f"{ {k: v - before[k] for k, v in mid.items()} }")
    return {"admitted": ref["jobs_admitted"], "default_differing": dflt_bad}


# -------------------------------------------------------------------- main
def main() -> int:
    device = phase_device()
    get_backend("jax")                  # configures the compile cache
    log("device", "compile_cache_dir="
                  f"{jax.config.jax_compilation_cache_dir}")
    kernels = phase_kernels()
    if not (kernels["pricing_mosaic"] and kernels["minplus_mosaic"]):
        raise AssertionError(f"a kernel ran in interpret mode: {kernels}")
    phase_served(1024, 64, 200, 0.003, name="served-light")
    phase_served(256, 32, 50, 0.3, name="served-contended")
    phase_online()
    stats = jax.devices()[0].memory_stats() or {}
    log("device", f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    log("device", f"trace_counts={trace_counts()}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
