"""The paper's job arithmetic (Eqs. 1-3, the sigmoid utility of §5) on
plain job records, independent of the program's ``JobSpec`` methods.

A ``PlainJob`` is read field by field from whatever object the program
was handed; everything the benchmark's calibration and reference compute
about a job goes through the functions below.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Tuple


@dataclass(frozen=True)
class PlainJob:
    job_id: int
    arrival: int
    epochs: int
    num_samples: int
    batch_size: int
    tau: float
    grad_size: float
    gamma: float
    bw_internal: float
    bw_external: float
    worker_demand: Tuple[Tuple[str, float], ...]
    ps_demand: Tuple[Tuple[str, float], ...]
    theta: Tuple[float, float, float]      # sigmoid utility (theta1..3)

    @classmethod
    def of(cls, job) -> "PlainJob":
        u = job.utility
        return cls(
            job_id=int(job.job_id), arrival=int(job.arrival),
            epochs=int(job.epochs), num_samples=int(job.num_samples),
            batch_size=int(job.batch_size), tau=float(job.tau),
            grad_size=float(job.grad_size), gamma=float(job.gamma),
            bw_internal=float(job.bw_internal),
            bw_external=float(job.bw_external),
            worker_demand=tuple(sorted(
                (str(r), float(a)) for r, a in job.worker_demand.items())),
            ps_demand=tuple(sorted(
                (str(r), float(b)) for r, b in job.ps_demand.items())),
            theta=(float(u.theta1), float(u.theta2), float(u.theta3)),
        )

    def demand(self, resources) -> Tuple[Tuple[float, ...], Tuple[float, ...]]:
        wd: Dict[str, float] = dict(self.worker_demand)
        sd: Dict[str, float] = dict(self.ps_demand)
        return (tuple(wd.get(r, 0.0) for r in resources),
                tuple(sd.get(r, 0.0) for r in resources))


def utility(job: PlainJob, latency: float) -> float:
    """u(t) = theta1 / (1 + exp(theta2 (t - theta3))), overflow-safe."""
    t1, t2, t3 = job.theta
    z = t2 * (latency - t3)
    if z >= 0:
        return t1 * math.exp(-z) / (1.0 + math.exp(-z)) if z < 50 else 0.0
    return t1 / (1.0 + math.exp(z))


def total_workload(job: PlainJob) -> float:
    """V = E K samples."""
    return float(job.epochs) * float(job.num_samples)


def time_per_sample(job: PlainJob, internal: bool) -> float:
    """tau + (gamma / F) 2 g / b, with b the internal or external rate."""
    b = job.bw_internal if internal else job.bw_external
    return job.tau + (job.gamma / job.batch_size) * (2.0 * job.grad_size / b)


def min_completion_slots(job: PlainJob) -> int:
    return int(math.ceil(total_workload(job) / job.batch_size
                         * time_per_sample(job, True)))


def max_resource_slots(job: PlainJob) -> float:
    return math.ceil(total_workload(job) * time_per_sample(job, False))


def samples_trained(job: PlainJob, workers: Dict[int, int],
                    ps: Dict[int, int]) -> float:
    """Eq. (1) summed over machines; the internal rate applies iff the
    workers and the parameter servers sit on one and the same machine."""
    w = sum(workers.values())
    if w == 0:
        return 0.0
    wm = [h for h, n in workers.items() if n > 0]
    pm = [h for h, n in ps.items() if n > 0]
    internal = len(wm) == 1 and len(pm) == 1 and wm[0] == pm[0]
    return w / time_per_sample(job, internal)
