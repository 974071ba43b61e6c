"""Build and drive the system under test: ``SimEngine`` (batched) with the
``pdors`` policy on a ``RollingWindow`` over a ``Cluster`` of the
configuration's machines.

The policy runs at the backend's own kernel selection; nothing here sets
a kernel-selection variable. Prices come from the benchmark's frozen
calibration (``gen.traffic.calibrate``) and are handed to the program.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np

from gen.traffic import Traffic, arrival_events, calibrate, calibration_jobs

from .cells import Config
from .window import Recorder, WindowClosed


@dataclass
class Run:
    engine: object
    recorder: Recorder
    arrivals: List[tuple]                   # (job_id, arrival slot) pulled
    prices: object


def make_cluster_of(cfg: Config, backend: str):
    """The program's ``Cluster`` of one ``Machine`` a capacity row, as
    ``make_cluster`` builds it. Where the configuration names a program
    preset, every class must have the preset's capacities."""
    from repro.core.cluster import Cluster, Machine, make_cluster

    if cfg.preset is not None:
        want = dict(make_cluster(1, 1, preset=cfg.preset,
                                 backend="numpy").machines[0].capacity)
        for c in cfg.classes:
            if c.capacity != want:
                raise ValueError(f"preset {cfg.preset!r} capacities {want} "
                                 f"!= class {c.name!r} {c.capacity}")
    machines = [Machine(h, dict(row))
                for h, row in enumerate(cfg.capacity_rows())]
    return Cluster(machines=machines, horizon=cfg.window_slots,
                   backend=backend)


def build(cfg: Config, tr: Traffic, seed: int, backend: str,
          seconds: float, tracer=None, on_open=None) -> Run:
    from repro.core.pricing import PriceParams
    from repro.sim import RollingWindow, SimEngine, make_policy

    cluster = make_cluster_of(cfg, backend)
    prices = calibrate(calibration_jobs(tr), cfg.capacity_rows(),
                       cfg.window_slots)
    policy = make_policy("pdors", quanta=cfg.quanta, price_params=PriceParams(
        U=dict(prices.U), L=prices.L, mu=prices.mu))
    window = RollingWindow(cluster)
    engine = SimEngine(window, policy, seed=seed, patience=tr.patience,
                       engine_mode="batched", trace=tracer)
    recorder = Recorder(open_slot=tr.warm_slots, seconds=seconds,
                        on_open=on_open)
    recorder.install(engine)
    return Run(engine, recorder, [], prices)


def events(run: Run, tr: Traffic, seed: int):
    """The run's unbounded backlog, recording each job the engine pulls."""
    for ev in arrival_events(tr, seed):
        run.arrivals.append((ev.job.job_id, ev.time))
        yield ev


def drive(run: Run, tr: Traffic, seed: int) -> None:
    """Run the engine until the window closes. The stream never ends, so
    an engine that returns on its own has stopped early: an error."""
    try:
        run.engine.run(events(run, tr, seed))
    except WindowClosed:
        return
    raise RuntimeError("the engine stopped before the window closed")


def warm_scatter_widths(cluster, tr: Traffic) -> None:
    """Compile the ledger's scatter-add and clamped scatter-sub at every
    power-of-two width a commit or release of this traffic can have (the
    backend pads each to one of these), on a scratch ledger. One job's
    row in one slot touches at most F worker and F server machines."""
    be = cluster.backend
    widest = min(cluster.num_machines, 2 * tr.max_batch)
    R = len(cluster.resources)
    scratch = be.zeros((cluster.horizon, cluster.num_machines, R))
    width = 1
    while True:
        k = min(width, widest)
        needs = [(h, np.ones(R)) for h in range(k)]
        scratch = be.ledger_add(scratch, 0, needs)
        scratch = be.ledger_sub_clamped(scratch, 0, needs)
        if k >= widest:
            break
        width <<= 1
    be.to_host(scratch)
