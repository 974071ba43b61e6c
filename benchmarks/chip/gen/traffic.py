"""Seeded job streams and price calibration, frozen from the program.

``job_stream`` is ``repro.sim.traces.job_stream`` without the elastic
annotations, and ``draw_job`` is ``repro.core.workload.draw_job``: job i's
parameters, its interarrival gap and its failure slot come from a
generator derived from ``SeedSequence((seed, 7, i))``, in the frozen draw
order, so any job of a stream is reproducible on its own. The jobs are
built as the program's ``JobSpec`` because that is the input the
scheduler takes; nothing else of the program is used here.

A traffic file may also state its job mix (``mix``, ``burst``,
``worker_demand``, ``ps_demand``, weighted ``batch`` buckets,
``size_tail``). Each new draw comes from the job's own generator, and
only where its key is given; a file that gives none, or gives a
preset's values, draws exactly the program's stream at that preset.

A run replays ``backlog``: one fixed stream (``base_seed``) whose jobs a
run's seed reorders within each arrival slot, so that runs with
different seeds do the same work in the same batches. A traffic file
(``traffic/<name>.json``) holds the parameters of a ``Traffic``;
``load_traffic`` reads one by name.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass, field, fields, replace
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple, Union

import numpy as np

from .jobmath import (PlainJob, max_resource_slots, min_completion_slots,
                      utility)

_TAG_TRACE = 7

#: the job-mix keys a preset fills in where a traffic file leaves them out:
#: the (insensitive, sensitive, critical) utility-class mix, the arrival
#: modulation, and each resource's worker and server demand, in draw order
#: (an integer range [lo, hi] takes one draw; a number is fixed)
PRESETS = {
    "google": {
        "mix": (0.30, 0.69, 0.01),
        "burst": "google",
        "worker_demand": {"gpu": (0, 4), "cpu": (1, 10), "mem": (2, 32),
                          "storage": (5, 10)},
        "ps_demand": {"gpu": 0.0, "cpu": (1, 10), "mem": (2, 32),
                      "storage": (5, 10)},
    },
}
_SHARE_TOL = 1e-9


@dataclass(frozen=True)
class Traffic:
    """One traffic mix: the stream's parameters plus the run's warm-up.

    ``mix``, ``burst``, ``worker_demand`` and ``ps_demand`` default to the
    preset's (``PRESETS``). ``batch`` is F's range ``(lo, hi)``, or
    weighted buckets ``((share, lo, hi), ...)``: one uniform picks the
    bucket, then F is drawn in it. ``burst`` is ``"google"`` (a double
    burst of period 48), ``"none"`` or ``{"sine": amplitude, "period":
    slots}``. ``size_tail`` ``{"sigma": s, "cap": c or None}`` multiplies K
    by a lognormal of mean 1 (log-mean -s^2/2), capped at c."""

    preset: str = "google"
    arrival_rate: float = 4.0            # mean arrivals per slot
    workload_scale: float = 0.05         # scales K (samples per epoch)
    failure_rate: float = 0.0            # share of jobs that fail once
    failure_delay: Tuple[int, int] = (1, 8)
    patience: int = 48                   # slots a never-served job waits
    batch: tuple = (8, 64)               # F: a range or weighted buckets
    calib_jobs: int = 64                 # stream prefix used for prices
    warm_slots: int = 32                 # slots run before the window
    base_seed: int = 20210806            # the one stream every run replays
    # the job mix; None takes the preset's
    mix: Optional[Tuple[float, float, float]] = None
    burst: Union[None, str, Dict[str, float]] = None
    worker_demand: Optional[Dict[str, Union[float, Tuple[int, int]]]] = None
    ps_demand: Optional[Dict[str, Union[float, Tuple[int, int]]]] = None
    size_tail: Optional[Dict[str, Optional[float]]] = None
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
        if self.preset not in PRESETS:
            raise ValueError(f"preset: unknown preset {self.preset!r}")
        base = PRESETS[self.preset]
        norm = {
            "mix": _mix(base["mix"] if self.mix is None else self.mix),
            "burst": _burst(base["burst"] if self.burst is None else self.burst),
            "worker_demand": _demand("worker_demand", base["worker_demand"]
                                     if self.worker_demand is None
                                     else self.worker_demand),
            "ps_demand": _demand("ps_demand", base["ps_demand"]
                                 if self.ps_demand is None else self.ps_demand),
            "batch": _batch(self.batch),
            "size_tail": None if self.size_tail is None
            else _size_tail(self.size_tail),
        }
        for k, v in norm.items():
            object.__setattr__(self, k, v)

    @property
    def max_batch(self) -> int:
        """The largest F a job of this mix can have."""
        if _is_range(self.batch):
            return self.batch[1]
        return max(hi for _, _, hi in self.batch)

    def demand_resources(self) -> set:
        return set(self.worker_demand) | set(self.ps_demand)


def _is_int(x) -> bool:
    return isinstance(x, int) and not isinstance(x, bool)


def _is_number(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool)


def _is_range(v) -> bool:
    return (isinstance(v, (list, tuple)) and len(v) == 2
            and all(_is_int(x) for x in v) and 0 <= v[0] <= v[1])


def _shares(key: str, shares) -> None:
    if any(not _is_number(s) or s < 0 for s in shares) \
            or abs(sum(shares) - 1.0) > _SHARE_TOL:
        raise ValueError(f"{key}: shares {list(shares)} must be >= 0 and "
                         "sum to 1")


def _mix(v) -> Tuple[float, float, float]:
    if not isinstance(v, (list, tuple)) or len(v) != 3:
        raise ValueError(f"mix: {v!r} is not three shares (insensitive, "
                         "sensitive, critical)")
    _shares("mix", v)
    return tuple(float(s) for s in v)


def _burst(v):
    if v in ("google", "none"):
        return v
    if isinstance(v, dict) and set(v) == {"sine", "period"} \
            and _is_number(v["sine"]) and 0 <= v["sine"] < 1 \
            and _is_number(v["period"]) and v["period"] > 0:
        return {"sine": float(v["sine"]), "period": float(v["period"])}
    raise ValueError(f"burst: {v!r} is not \"google\", \"none\" or "
                     "{\"sine\": amplitude in [0, 1), \"period\": slots > 0}")


def _demand(key: str, v) -> Dict[str, Union[float, Tuple[int, int]]]:
    if not isinstance(v, dict) or not v:
        raise ValueError(f"{key}: {v!r} is not a map of resource to demand")
    out: Dict[str, Union[float, Tuple[int, int]]] = {}
    for r, d in v.items():
        if _is_range(d):
            out[r] = (int(d[0]), int(d[1]))
        elif _is_number(d) and d >= 0:
            out[r] = float(d)
        else:
            raise ValueError(f"{key}: {r} {d!r} is neither an integer "
                             "range [lo, hi] with 0 <= lo <= hi nor a "
                             "number >= 0")
    return out


def _batch(v) -> tuple:
    if _is_range(v) and v[0] >= 1:
        return (int(v[0]), int(v[1]))
    ok = isinstance(v, (list, tuple)) and len(v) > 0 and all(
        isinstance(b, (list, tuple)) and len(b) == 3 and _is_range(b[1:])
        and b[1] >= 1 for b in v)
    if not ok:
        raise ValueError(f"batch: {v!r} is neither [lo, hi] with "
                         "1 <= lo <= hi nor [[share, lo, hi], ...]")
    _shares("batch", [b[0] for b in v])
    return tuple((float(s), int(lo), int(hi)) for s, lo, hi in v)


def _size_tail(v) -> Dict[str, Optional[float]]:
    if isinstance(v, dict) and set(v) == {"sigma", "cap"} \
            and _is_number(v["sigma"]) and v["sigma"] > 0 \
            and (v["cap"] is None or (_is_number(v["cap"]) and v["cap"] > 0)):
        return {"sigma": float(v["sigma"]),
                "cap": None if v["cap"] is None else float(v["cap"])}
    raise ValueError(f"size_tail: {v!r} is not {{\"sigma\": s > 0, "
                     "\"cap\": c > 0 or null}")


def load_traffic(path: Path) -> Traffic:
    raw = json.loads(Path(path).read_text())
    known = {f.name for f in fields(Traffic)}
    unknown = set(raw) - known
    if unknown:
        raise ValueError(f"{path}: unknown traffic keys {sorted(unknown)}")
    kw = {k: (tuple(v) if isinstance(v, list) else v) for k, v in raw.items()}
    try:
        return Traffic(**kw)
    except ValueError as e:
        raise ValueError(f"{path}: {e}") from None


def burst_factor(burst, t: float) -> float:
    """Arrival-rate modulation at (fractional) slot t: ``"google"``, a
    double diurnal burst of period 48; a sine of the given amplitude and
    period; ``"none"``, flat."""
    if burst == "google":
        phase = (t % 48.0) / 48.0
        return (1.0 + 2.0 * math.exp(-((phase - 0.3) ** 2) / 0.02)
                + 1.5 * math.exp(-((phase - 0.7) ** 2) / 0.03)) / 1.9
    if burst == "none":
        return 1.0
    p = burst["period"]
    return 1.0 + burst["sine"] * math.sin(2.0 * math.pi * (t % p) / p)


def _draw_utility(rng: np.random.Generator, tr: Traffic):
    from repro.core.job import SigmoidUtility
    mix = tr.mix
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


def _draw_batch(rng: np.random.Generator, batch: tuple) -> int:
    if _is_range(batch):
        lo, hi = batch
    else:
        u, acc = rng.random(), 0.0
        for share, lo, hi in batch:
            acc += share
            if u < acc:
                break
    return int(rng.integers(lo, hi + 1))


def _draw_demand(rng: np.random.Generator, demand) -> Dict[str, float]:
    return {r: float(rng.integers(d[0], d[1] + 1)) if isinstance(d, tuple)
            else d for r, d in demand.items()}


def size_multiplier(rng: np.random.Generator, tail) -> float:
    """A lognormal job-size multiplier of mean 1, capped at ``cap``."""
    s = tail["sigma"]
    mult = float(rng.lognormal(mean=-s ** 2 / 2.0, sigma=s))
    return mult if tail["cap"] is None else min(mult, tail["cap"])


def draw_job(rng: np.random.Generator, tr: Traffic, job_id: int,
             arrival: int):
    """One job's parameters in the frozen draw order: E, K, F, g, tau,
    gamma, b_int, worker demands, PS demands, utility, then the size
    tail where the mix has one."""
    from repro.core.job import JobSpec
    E = int(rng.integers(tr.epochs[0], tr.epochs[1] + 1))
    K = int(rng.integers(tr.samples[0], tr.samples[1] + 1))
    if tr.workload_scale != 1.0:
        K = max(1, int(K * tr.workload_scale))
    F = _draw_batch(rng, tr.batch)
    g = rng.uniform(*tr.grad_mb)
    tau = rng.uniform(*tr.tau)
    gamma = rng.uniform(*tr.gamma)
    b_int = rng.uniform(*tr.bw_internal)
    worker = _draw_demand(rng, tr.worker_demand)
    ps = _draw_demand(rng, tr.ps_demand)
    utility = _draw_utility(rng, tr)
    if tr.size_tail is not None:
        K = max(1, int(K * size_multiplier(rng, tr.size_tail)))
    return JobSpec(
        job_id=job_id, arrival=int(arrival), epochs=E, num_samples=K,
        batch_size=F, tau=tau, grad_size=g, gamma=gamma,
        bw_internal=b_int, bw_external=b_int * tr.ext_over_int,
        worker_demand=worker, ps_demand=ps, utility=utility,
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
            / max(burst_factor(tr.burst, clock), 1e-6)
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


def calibrate(jobs: List[PlainJob], rows: List[Dict[str, float]],
              horizon: int) -> Prices:
    """Eqs. (13)-(14) from a calibration prefix whose arrivals are taken
    as slot 0 (the window offers every job at relative slot 0), over the
    cluster's per-machine capacity rows in machine order."""
    if not jobs:
        raise ValueError("need at least one job to calibrate prices")
    resources = sorted(rows[0])
    total_cap = float(sum(sum(row.values()) for row in rows))

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
