"""Faults planted under the timed path, to show that ``correct`` catches
them: each takes a built run (``harness.engine.build``), breaks the
program underneath it before it runs, and returns a function that puts
back whatever it changed outside the run's own objects.

``stale_prices`` is the control: it breaks the configurations'
``pricing`` guarantee, that every offer is priced at the ledger as it
stands, by pricing every job of an arrival batch at the ledger the batch
started from (no repricing after an admission: the saving a later change
could be tempted by). The others are the faults a one-chip cell can
have, and the loss of Algorithm 4's split candidates.
"""
from __future__ import annotations

from dataclasses import replace


def _nothing() -> None:
    return None


def stale_prices(run):
    """Every job of an arrival batch is priced at the batch's first
    ledger; the free capacities it sees stay current."""
    pol = run.engine.policy
    inner = pol.on_arrivals
    prices = pol.prices
    device_tensor, price_matrix = prices.device_tensor, prices.price_matrix
    held = {}

    def held_tensor():
        if "all" not in held:
            held["all"] = device_tensor()
        return held["all"]

    def held_matrix(t):
        if t not in held:
            held[t] = price_matrix(t)
        return held[t]

    def on_arrivals(event, view):
        held.clear()
        prices.device_tensor, prices.price_matrix = held_tensor, held_matrix
        try:
            return inner(event, view)
        finally:
            prices.device_tensor, prices.price_matrix = device_tensor, price_matrix

    pol.on_arrivals = on_arrivals
    return _nothing


def no_splits(run):
    """Algorithm 4's external (split) case finds no machines: only the
    co-located candidates are left."""
    from repro.core import solve_plan, subproblem
    import numpy as np

    none = (np.zeros(0, dtype=int), 0.0, 0.0)
    fill, stats = solve_plan._prune_fill, subproblem._prune_stats
    solve_plan._prune_fill = lambda *a, **k: none
    subproblem._prune_stats = lambda *a, **k: none

    def undo():
        solve_plan._prune_fill, subproblem._prune_stats = fill, stats

    return undo


def state_unchanged(run):
    """Commits reach the window's books but never the ledger."""
    run.engine.window.cluster.commit = lambda t, job, alloc: None
    return _nothing


def half_batch(run):
    """The policy decides only the first half of every arrival batch."""
    pol = run.engine.policy
    inner = pol.on_arrivals

    def on_arrivals(event, view):
        keep = event.jobs[: max(1, len(event.jobs) // 2)]
        return inner(replace(event, jobs=keep), view)

    pol.on_arrivals = on_arrivals
    return _nothing


def answer_altered(run):
    """Each admitted schedule loses its last slot where it is produced."""
    pol = run.engine.policy
    inner = pol._offer_one

    def offer_one(job, view, **kw):
        sched = inner(job, view, **kw)
        if sched and len(sched) > 1:
            sched = dict(sched)
            del sched[max(sched)]
        return sched

    pol._offer_one = offer_one
    return _nothing


def answer_rejected(run):
    """Every third offer is answered with a rejection where it is made."""
    pol = run.engine.policy
    inner = pol._offer_one
    calls = [0]

    def offer_one(job, view, **kw):
        calls[0] += 1
        sched = inner(job, view, **kw)
        return None if calls[0] % 3 == 0 else sched

    pol._offer_one = offer_one
    return _nothing


FAULTS = {f.__name__: f for f in (stale_prices, no_splits, state_unchanged,
                                  half_batch, answer_altered, answer_rejected)}
