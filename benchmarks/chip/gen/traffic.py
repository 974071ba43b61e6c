"""Seeded job streams and price calibration, frozen from the program.

``job_stream`` is ``repro.sim.traces.job_stream`` without the elastic
annotations, and ``draw_job`` is ``repro.core.workload.draw_job``: job i's
parameters, its interarrival gap and its failure slot come from a
generator derived from ``SeedSequence((seed, 7, i))``, in the frozen draw
order, so any job of a stream is reproducible on its own. The jobs are
built as the program's ``JobSpec`` because that is the input the
scheduler takes; nothing else of the program is used here.

A run replays ``backlog``: one fixed stream (``base_seed``) whose jobs a
run's seed reorders within each arrival slot, so that runs with
different seeds do the same work in the same batches. A traffic file (``traffic/<name>.json``) holds the
parameters of a ``Traffic``; ``load_traffic`` reads one by name.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass, field, fields, replace
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from .jobmath import (PlainJob, max_resource_slots, min_completion_slots,
                      utility)

_TAG_TRACE = 7
#: (insensitive, sensitive, critical) utility-class mix per preset
MIXES = {
    "google": (0.30, 0.69, 0.01),
}


@dataclass(frozen=True)
class Traffic:
    """One traffic mix: the stream's parameters plus the run's warm-up."""

    preset: str = "google"
    arrival_rate: float = 4.0            # mean arrivals per slot
    workload_scale: float = 0.05         # scales K (samples per epoch)
    failure_rate: float = 0.0            # share of jobs that fail once
    failure_delay: Tuple[int, int] = (1, 8)
    patience: int = 48                   # slots a never-served job waits
    batch: Tuple[int, int] = (8, 64)     # global batch size range
    calib_jobs: int = 64                 # stream prefix used for prices
    warm_slots: int = 32                 # slots run before the window
    base_seed: int = 20210806            # the one stream every run replays
    # job-parameter ranges of the paper's §5 generator
    epochs: Tuple[int, int] = (50, 200)
    samples: Tuple[int, int] = (20_000, 500_000)
    grad_mb: Tuple[float, float] = (30.0, 575.0)
    tau: Tuple[float, float] = (1e-5, 1e-4)
    gamma: Tuple[float, float] = (1.0, 10.0)
    bw_internal: Tuple[float, float] = (5e6, 2e7)
    ext_over_int: float = 0.2
    theta1: Tuple[float, float] = (1.0, 100.0)
    theta3: Tuple[float, float] = (1.0, 15.0)
    # where each value comes from, and which were chosen: not read
    sources: Dict[str, str] = field(default_factory=dict)
    assumed: Dict[str, str] = field(default_factory=dict)
    notes: Dict[str, str] = field(default_factory=dict)

    def __post_init__(self) -> None:
        if self.preset not in MIXES:
            raise ValueError(f"unknown preset {self.preset!r}")


def load_traffic(path: Path) -> Traffic:
    raw = json.loads(Path(path).read_text())
    known = {f.name for f in fields(Traffic)}
    unknown = set(raw) - known
    if unknown:
        raise ValueError(f"{path}: unknown traffic keys {sorted(unknown)}")
    kw = {k: (tuple(v) if isinstance(v, list) else v) for k, v in raw.items()}
    return Traffic(**kw)


def burst_factor(preset: str, t: float) -> float:
    """Arrival-rate modulation at (fractional) slot t: for google, a
    double diurnal burst of period 48."""
    phase = (t % 48.0) / 48.0
    return (1.0 + 2.0 * math.exp(-((phase - 0.3) ** 2) / 0.02)
            + 1.5 * math.exp(-((phase - 0.7) ** 2) / 0.03)) / 1.9


def _draw_utility(rng: np.random.Generator, tr: Traffic):
    from repro.core.job import SigmoidUtility
    mix = MIXES[tr.preset]
    u = rng.random()
    t1 = rng.uniform(*tr.theta1)
    t3 = rng.uniform(*tr.theta3)
    if u < mix[0]:
        t2 = 0.0
    elif u < mix[0] + mix[1]:
        t2 = rng.uniform(0.01, 1.0)
    else:
        t2 = rng.uniform(4.0, 6.0)
    return SigmoidUtility(theta1=t1, theta2=t2, theta3=t3)


def draw_job(rng: np.random.Generator, tr: Traffic, job_id: int,
             arrival: int):
    """One job's parameters in the frozen draw order: E, K, F, g, tau,
    gamma, b_int, worker demands, PS demands, utility."""
    from repro.core.job import JobSpec
    E = int(rng.integers(tr.epochs[0], tr.epochs[1] + 1))
    K = int(rng.integers(tr.samples[0], tr.samples[1] + 1))
    if tr.workload_scale != 1.0:
        K = max(1, int(K * tr.workload_scale))
    F = int(rng.integers(tr.batch[0], tr.batch[1] + 1))
    g = rng.uniform(*tr.grad_mb)
    tau = rng.uniform(*tr.tau)
    gamma = rng.uniform(*tr.gamma)
    b_int = rng.uniform(*tr.bw_internal)
    worker = {
        "gpu": float(rng.integers(0, 5)),
        "cpu": float(rng.integers(1, 11)),
        "mem": float(rng.integers(2, 33)),
        "storage": float(rng.integers(5, 11)),
    }
    ps = {
        "gpu": 0.0,
        "cpu": float(rng.integers(1, 11)),
        "mem": float(rng.integers(2, 33)),
        "storage": float(rng.integers(5, 11)),
    }
    return JobSpec(
        job_id=job_id, arrival=int(arrival), epochs=E, num_samples=K,
        batch_size=F, tau=tau, grad_size=g, gamma=gamma,
        bw_internal=b_int, bw_external=b_int * tr.ext_over_int,
        worker_demand=worker, ps_demand=ps,
        utility=_draw_utility(rng, tr),
    )


def job_stream(tr: Traffic, seed: int,
               num_jobs: Optional[int] = None) -> Iterator[Tuple[object, Optional[int]]]:
    """Yield (job, fail_at) pairs in arrival order; unbounded when
    ``num_jobs`` is None."""
    clock = 0.0
    seed = int(seed)
    seed = seed if seed >= 0 else (1 << 63) - seed
    i = 0
    while num_jobs is None or i < num_jobs:
        rng = np.random.default_rng(np.random.SeedSequence((seed, _TAG_TRACE, i)))
        gap = rng.exponential(1.0 / tr.arrival_rate) \
            / max(burst_factor(tr.preset, clock), 1e-6)
        clock += gap
        arrival = int(clock)
        job = draw_job(rng, tr, i, arrival)
        fail_at: Optional[int] = None
        if tr.failure_rate > 0 and rng.random() < tr.failure_rate:
            lo, hi = tr.failure_delay
            fail_at = arrival + int(rng.integers(lo, hi + 1))
        yield job, fail_at
        i += 1


_TAG_ORDER = 0xB10C


def backlog(tr: Traffic, seed: int) -> Iterator[Tuple[object, Optional[int]]]:
    """The backlog a run replays: the stream of ``tr.base_seed``, with the
    jobs of each arrival slot reordered by a permutation drawn from
    ``seed``. Each slot keeps its arrivals and its jobs (each with its own
    failure delay); only their order within the slot's batch changes. So
    every seed offers the same batches of the same jobs: a run's seed
    changes the order of the work, not the amount or its grouping."""
    base = job_stream(tr, tr.base_seed)
    s = int(seed)
    s = s if s >= 0 else (1 << 63) - s
    pending = next(base)
    next_id = 0
    while True:
        slot = pending[0].arrival
        group = []
        while pending[0].arrival == slot:
            group.append(pending)
            pending = next(base)
        rng = np.random.default_rng(np.random.SeedSequence((s, _TAG_ORDER, slot)))
        for src in rng.permutation(len(group)):
            job, fail_at = group[src]
            yield replace(job, job_id=next_id), fail_at
            next_id += 1


def arrival_events(tr: Traffic, seed: int):
    """The run's backlog as the engine's ARRIVAL events (failure slot
    attached)."""
    from repro.sim.events import Event, EventKind
    for job, fail_at in backlog(tr, seed):
        yield Event(time=job.arrival, kind=EventKind.ARRIVAL, job=job,
                    fail_at=fail_at)


@dataclass(frozen=True)
class Prices:
    """U^r, L and mu of the exponential price function (Eqs. 12-14)."""

    U: Dict[str, float]
    L: float
    mu: float


def calibrate(jobs: List[PlainJob], capacity: Dict[str, float],
              machines: int, horizon: int) -> Prices:
    """Eqs. (13)-(14) from a calibration prefix whose arrivals are taken
    as slot 0 (the window offers every job at relative slot 0)."""
    if not jobs:
        raise ValueError("need at least one job to calibrate prices")
    resources = sorted(capacity)
    total_cap = float(sum(sum(capacity.values()) for _ in range(machines)))

    def dsum(j: PlainJob) -> float:
        wd, sd = dict(j.worker_demand), dict(j.ps_demand)
        return sum(wd.get(r, 0.0) + sd.get(r, 0.0) for r in resources)

    inv_mu = min(max_resource_slots(j) * dsum(j) / (horizon * total_cap)
                 for j in jobs)
    mu = 1.0 / max(inv_mu, 1e-12)
    U: Dict[str, float] = {}
    for r in resources:
        best = 0.0
        for j in jobs:
            wd, sd = dict(j.worker_demand), dict(j.ps_demand)
            denom = wd.get(r, 0.0) + sd.get(r, 0.0)
            if denom <= 0:
                continue
            best = max(best, utility(j, max(min_completion_slots(j), 1)) / denom)
        U[r] = best if best > 0 else 1.0
    L = float("inf")
    for j in jobs:
        denom = max_resource_slots(j) * dsum(j)
        if denom <= 0:
            continue
        L = min(L, (1.0 / (2.0 * mu)) * utility(j, horizon - j.arrival) / denom)
    if not math.isfinite(L) or L <= 0:
        L = 1e-9
    for r in resources:
        U[r] = max(U[r], L * math.e)
    return Prices(U=U, L=L, mu=mu)


def calibration_jobs(tr: Traffic) -> List[PlainJob]:
    """The base stream's first ``calib_jobs`` jobs, arrivals moved to slot
    0: the same prices for every run of a traffic mix."""
    out = []
    for job, _ in job_stream(tr, tr.base_seed, tr.calib_jobs):
        out.append(replace(PlainJob.of(job), arrival=0))
    return out
