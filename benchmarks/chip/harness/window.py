"""The measured window, recorded from the benchmark's side of the engine.

``Recorder.install`` wraps four methods of one engine's rolling window and
its policy's ``offer`` on the instances (the program's code is unchanged):

* ``advance_to`` marks slot boundaries. The window opens at the first
  boundary at or after ``open_slot`` and closes at the first boundary
  after ``seconds`` have passed; closing raises ``WindowClosed`` before
  the slot starts, which stops the engine without draining it.
* ``offer`` times each arrival-batch offer inside the window. Every job
  of a batch waits for the whole batch, so each job gets the batch's
  wall time as its decision latency.
* ``commit``, ``release_from`` and ``release_many`` are logged from the
  moment of install, warm-up included, and the window's offers with
  them, in order: the plain reference replays this log from an empty
  ledger to rebuild the ledger each offer saw.
* the array backend's two bundle passes (``snapshot_bundle_batch`` and
  ``snapshot_bundle``) count the (slot, machine) rows they price inside
  the window: the work of the ``price_bundle`` kernel. The backend is a
  process-wide object, so these two are put back when the window closes.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np


class WindowClosed(Exception):
    """Raised at the slot boundary that closes the measured window."""


def percentile(samples: Sequence[float], p: float) -> float:
    """Exact nearest-rank percentile: the smallest sample with at least
    p % of the samples at or below it."""
    if not samples:
        raise ValueError("no samples")
    xs = sorted(samples)
    k = max(1, math.ceil(p / 100.0 * len(xs)))
    return float(xs[k - 1])


@dataclass
class Batch:
    slot: int
    job_ids: List[int]
    seconds: float
    admitted: Dict[int, bool]


@dataclass
class Recorder:
    open_slot: int
    seconds: float
    clock: Callable[[], float] = time.perf_counter
    on_open: Optional[Callable[[], None]] = None
    close_slot: Optional[int] = None        # rehearsals and tests: stop here
    t_open: Optional[float] = None
    t_close: Optional[float] = None
    slot_open: Optional[int] = None
    slot_close: Optional[int] = None
    batches: List[Batch] = field(default_factory=list)
    ops: List[tuple] = field(default_factory=list)
    start_now: Optional[int] = None         # the window's slot at install
    failed: int = 0
    bundle_rows: int = 0                    # rows priced by bundle passes
    bundle_calls: int = 0
    _restore: List[Callable[[], None]] = field(default_factory=list)

    @property
    def is_open(self) -> bool:
        return self.t_open is not None and self.t_close is None

    # -------------------------------------------------------- the hooks
    def boundary(self, t: int) -> None:
        """Called at the start of slot ``t``, before the window moves."""
        if self.t_open is None:
            if t < self.open_slot:
                self.ops.append(("advance", t))
                return
            if self.on_open is not None:
                self.on_open()
            self.slot_open = t
            self.t_open = self.clock()
        elif self.t_close is None and (
                self.clock() - self.t_open >= self.seconds
                or (self.close_slot is not None and t >= self.close_slot)):
            self.t_close = self.clock()
            self.slot_close = t
            for undo in self._restore:
                undo()
            raise WindowClosed(t)
        self.ops.append(("advance", t))

    def install(self, engine) -> None:
        from repro.sim.events import EventKind

        window, policy = engine.window, engine.policy
        self.start_now = window.now
        advance, commit = window.advance_to, window.commit
        release_from, release_many = window.release_from, window.release_many
        offer = policy.offer
        rec = self

        def advance_to(t_abs):
            rec.boundary(t_abs)
            return advance(t_abs)

        def commit_(t_abs, job, alloc):
            rec.ops.append(("commit", t_abs, job, dict(alloc.workers),
                            dict(alloc.ps)))
            return commit(t_abs, job, alloc)

        def release_from_(job_id, from_abs):
            rec.ops.append(("release", job_id, from_abs))
            return release_from(job_id, from_abs)

        def release_many_(pairs):
            rec.ops.append(("release_many", list(pairs)))
            return release_many(pairs)

        def offer_(event, view):
            if event.kind != EventKind.ARRIVAL or not rec.is_open:
                return offer(event, view)
            jobs = list(event.jobs)
            rec.ops.append(("offer", event.time, jobs))
            t0 = rec.clock()
            try:
                dec = offer(event, view)
            except Exception:
                rec.failed += len(jobs)
                raise
            dt = rec.clock() - t0
            admitted = dict(dec.admitted)
            rec.ops.append(("decided", admitted))
            rec.batches.append(Batch(event.time, [j.job_id for j in jobs],
                                     dt, admitted))
            return dec

        window.advance_to = advance_to
        window.commit = commit_
        window.release_from = release_from_
        window.release_many = release_many_
        policy.offer = offer_
        be = window.cluster.backend
        for name in ("snapshot_bundle_batch", "snapshot_bundle"):
            fn = getattr(be, name)

            def counted(price, *args, fn=fn, **kw):
                if rec.is_open:
                    rec.bundle_rows += int(np.prod(np.shape(price)[:-1]))
                    rec.bundle_calls += 1
                return fn(price, *args, **kw)

            setattr(be, name, counted)
            self._restore.append(lambda name=name, fn=fn: setattr(be, name, fn))

    # ------------------------------------------------------ the numbers
    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    @property
    def slots(self) -> int:
        return self.slot_close - self.slot_open

    def decide_samples(self) -> List[float]:
        return [b.seconds for b in self.batches for _ in b.job_ids]

    def decisions(self) -> int:
        return sum(len(b.job_ids) for b in self.batches)

    def jobs_per_s(self) -> float:
        return self.decisions() / self.window_s

    def offer_s(self) -> float:
        return sum(b.seconds for b in self.batches)

    def engine_ms_per_slot(self) -> float:
        """Window wall time outside arrival-batch offers, per slot."""
        return (self.window_s - self.offer_s()) / self.slots * 1e3
