"""The plain reference that decides ``correct``.

It imports nothing of the program. From the run it takes only the
inputs (each offered job's parameters, the calibrated prices the
benchmark handed the program) and the program's answers (every commit
and release from the start of the run, warm-up included, and each window
offer's admissions), and it rebuilds the ledger itself, in float64
numpy, from an empty ledger. Four numbers are compared:

``unanswered``  jobs that arrived in the window and were never offered,
                plus offered jobs the policy returned no decision for.
``invalid``     committed rows that break a guarantee the configuration
                states: a ledger cell over capacity after a commit
                (beyond the program's own 1e-9 fit tolerance), a schedule
                that trains fewer samples than the job's workload
                V = E K, more workers than the global batch F in a slot,
                fewer parameter servers than ceil(workers / gamma), a row
                outside the window; or rows committed for a job that was
                not admitted.
``ledger_gap``  the largest difference between the program's device
                ledger after the window and the reference's replay.
``payoff_gap``  over every offer of the window: how far the program's
                decision falls below the reference's best schedule beyond
                the tie band, in units of that schedule's cost (the
                payoff is utility minus cost, and on a light cluster the
                cost is a millionth of the utility, so a share of the
                utility would hide any pricing fault). A rejection scores
                payoff 0; where the reference finds no schedule, the gap
                is read in units of the job's theta_1.

The tie band. The program and the reference both take payoffs within
1e-12 of each other as equal when they pick a completion slot, and
Algorithm 4 rounds its LP to whole workers: on a contended fleet, where
the prices fall towards L (about 1e-25) and a schedule may cost 1e-18,
the program may pick a schedule whose payoff lies up to about 1e-12
below the reference's, and that difference divided by such a cost reads
1e4 or more "cost units". So a shortfall counts only beyond ``TIE_REL`` of the
larger payoff: ``max(0, B - P - TIE_REL max(|B|, |P|)) / unit``. A
planted fault loses 70 % of the worst offer's payoff or more, eleven
decades above the band. The band is no wider than those ties: a shortfall
that a decision defect makes stays in the gap where it exceeds it.

The reference's schedule is Algorithms 2-3 over Algorithm 4's two
locality cases, each by a plain rule, in float64 at the prices of the
ledger the offer saw: for every slot and workload level the co-located
placement (all workers and servers on the one machine of least
co-located price that holds them, at the internal rate) and a split
placement (at the external rate: workers filled onto the machines of
least worker price in turn, then servers onto the machines of least
server price that hold none of those workers), the cheaper of the two,
a min-plus DP over the slots and the completion slot of best payoff.
Both placements are feasible schedules, so a program that searches
Algorithm 4's candidates, split ones included, at the prices of the
ledger it saw cannot fall far below it; a program that drops the split
candidates, or prices at a stale ledger, does.

Beside the numbers it reports, not compared: how far the program's
payoff lies above the co-located case alone beyond the band, in the
payoff gap's unit (``split_margins``); how many admitted schedules place
a slot on more than one machine; the largest shortfall as a share of the
payoff (``shortfall_rel``); and the offers that the band forgave
(``tied``), with the largest ratio of the program's schedule cost to the
reference's among them (``tie_cost_ratio``): at a price near L a tie may
cost many times the reference's schedule, on a fuller machine.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from gen.jobmath import (PlainJob, samples_trained, time_per_sample,
                         total_workload, utility)

#: the program's capacity tolerance for a fit (``Cluster.fits``)
FIT_TOL = 1e-9
#: the program's completion tolerance on trained samples (engine)
WORK_TOL = 1e-6
#: ties of Algorithm 1's objective, utility minus cost, as a share of the
#: payoff: the program takes payoffs within 1e-12 as equal, and sound
#: offers whose LPs were solved to optimality fall short by at most
#: 3.2e-13 of the payoff; planted faults by 0.70 or more (PERF.md, "How
#: correct is decided")
TIE_REL = 1e-12


@dataclass
class Numbers:
    unanswered: int = 0
    invalid: int = 0
    ledger_gap: float = 0.0
    payoff_gap: float = 0.0
    offers: int = 0
    admitted: int = 0
    split_schedules: int = 0       # admitted, some slot on two machines
    split_margins: List[float] = field(default_factory=list)
    shortfall_rel: float = 0.0     # largest (B - P) / max(|B|, |P|)
    tied: int = 0                  # offers short by no more than the band
    tie_cost_ratio: float = 0.0    # largest program / reference cost of those
    notes: List[str] = field(default_factory=list)

    def flag(self, msg: str) -> None:
        if len(self.notes) < 20:
            self.notes.append(msg)


class Ledger:
    """The reference's own ledger: a dense float64 (W, H, R) array whose
    row k is absolute slot ``now + k``, plus each job's commitments."""

    def __init__(self, cap: np.ndarray, resources: List[str], W: int,
                 now: int):
        self.cap = cap
        self.resources = resources
        self.W = W
        self.now = now
        self.used = np.zeros((W,) + cap.shape)
        self.held: Dict[int, Dict[int, list]] = {}
        self._dem: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}

    def demand(self, pj: PlainJob) -> Tuple[np.ndarray, np.ndarray]:
        wd, sd = pj.demand(self.resources)
        return np.array(wd), np.array(sd)

    def _rows(self, pj, workers, ps):
        wd, sd = self.demand(pj)
        for h in set(workers) | set(ps):
            yield h, wd * workers.get(h, 0) + sd * ps.get(h, 0)

    def commit(self, t_abs: int, pj: PlainJob, workers, ps) -> float:
        """Add the rows; returns the worst excess over capacity they leave
        on the cells they touch."""
        k = t_abs - self.now
        if not 0 <= k < self.W:
            return math.inf
        worst = -math.inf
        for h, need in self._rows(pj, workers, ps):
            self.used[k, h] += need
            worst = max(worst, float((self.used[k, h] - self.cap[h]).max()))
        slots = self.held.setdefault(pj.job_id, {})
        prev = slots.get(t_abs)
        if prev is None:
            slots[t_abs] = [pj, dict(workers), dict(ps)]
        else:
            for h, n in workers.items():
                prev[1][h] = prev[1].get(h, 0) + n
            for h, n in ps.items():
                prev[2][h] = prev[2].get(h, 0) + n
        return worst

    def release(self, job_id: int, from_abs: int) -> None:
        slots = self.held.get(job_id)
        if not slots:
            return
        for t_abs in [t for t in slots if t >= from_abs]:
            pj, w, s = slots.pop(t_abs)
            k = t_abs - self.now
            if 0 <= k < self.W:
                for h, need in self._rows(pj, w, s):
                    self.used[k, h] = np.maximum(self.used[k, h] - need, 0.0)
        if not slots:
            del self.held[job_id]

    def advance(self, t_abs: int) -> None:
        steps = t_abs - self.now
        if steps < 0:
            raise ValueError("the window moved backwards")
        if steps == 0:
            return
        k = min(steps, self.W)
        self.used[:self.W - k] = self.used[k:]
        self.used[self.W - k:] = 0.0
        self.now = t_abs
        for jid in list(self.held):
            for t in [t for t in self.held[jid] if t < t_abs]:
                del self.held[jid][t]
            if not self.held[jid]:
                del self.held[jid]

    def prices(self, U: Dict[str, float], L: float) -> np.ndarray:
        """Eq. (12) over the whole ledger: L (U^r / L) ** clip(rho / C)."""
        u = np.array([max(U.get(r, L), L * (1.0 + 1e-9))
                      for r in self.resources])
        frac = np.clip(self.used / self.cap[None], 0.0, 1.0)
        return L * (u / L) ** frac




@dataclass
class Best:
    """The best payoff of one search and its schedule's cost."""

    payoff: float
    cost: float


def beyond_tie(x: float, y: float) -> float:
    """``x - y`` less the tie band ``TIE_REL max(|x|, |y|)``, toward 0."""
    d = x - y
    band = TIE_REL * max(abs(x), abs(y))
    return math.copysign(max(0.0, abs(d) - band), d)


def _floor_fit(free: np.ndarray, d: np.ndarray) -> np.ndarray:
    """How many units of demand ``d`` each (slot, machine) cell holds."""
    act = np.flatnonzero(d > 0.0)
    n = np.floor((free[..., act] + FIT_TOL) / d[act]).min(axis=-1)
    return np.maximum(n, 0.0)


def level_costs(pj: PlainJob, free: np.ndarray, price: np.ndarray,
                resources: List[str], quanta: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """(W, Q + 1) costs of training v of Q workload levels in each slot:
    the co-located placement and the split placement (inf where none
    fits; level 0 costs 0)."""
    wd, sd = (np.array(x) for x in pj.demand(resources))
    W, H, _ = free.shape
    V = total_workload(pj)
    Q = max(1, min(quanta, int(math.ceil(V))))
    unit = V / Q
    F = pj.batch_size
    wprice, sprice = price @ wd, price @ sd
    rows = np.arange(W)

    coloc = np.full((W, Q + 1), np.inf)
    coloc[:, 0] = 0.0
    order = price @ (wd * pj.gamma + sd)
    act = np.flatnonzero((wd != 0.0) | (sd != 0.0))
    tps = time_per_sample(pj, True)
    for v in range(1, Q + 1):
        w = max(1, int(math.ceil((v * unit) * tps)))
        if w > F:
            continue
        s = max(1, int(math.ceil(w / pj.gamma)))
        need = wd * w + sd * s - FIT_TOL
        ok = (free[:, :, act] >= need[act]).all(axis=2)
        h = np.where(ok, order, np.inf).argmin(axis=1)
        feas = ok[rows, h]
        c = wprice[rows, h] * w + sprice[rows, h] * s
        coloc[feas, v] = c[feas]

    split = np.full((W, Q + 1), np.inf)
    split[:, 0] = 0.0
    W1 = np.arange(1, Q + 1) * unit * time_per_sample(pj, False)
    nw = np.ceil(W1 - 1e-9)
    ns = np.maximum(1.0, np.ceil(nw / pj.gamma))
    lv = np.flatnonzero(nw <= F)
    if lv.size:
        nw, ns = nw[lv], ns[lv]
        maxw, maxs = _floor_fit(free, wd), _floor_fit(free, sd)
        rank = np.empty(H, dtype=np.int64)
        for t in range(W):
            wo = np.argsort(wprice[t], kind="stable")
            cw = np.cumsum(maxw[t, wo])
            cwc = np.cumsum(maxw[t, wo] * wprice[t, wo])
            k = np.searchsorted(cw, nw)            # last worker machine
            ok = k < H
            k = np.minimum(k, H - 1)
            before = np.where(k > 0, cw[k - 1], 0.0)
            wcost = np.where(k > 0, cwc[k - 1], 0.0) \
                + (nw - before) * wprice[t, wo[k]]
            rank[wo] = np.arange(H)
            so = np.argsort(sprice[t], kind="stable")
            room = np.where(rank[so][None, :] <= k[:, None], 0.0,
                            maxs[t, so][None, :])
            cs = np.cumsum(room, axis=1)
            csc = np.cumsum(room * sprice[t, so][None, :], axis=1)
            ok &= cs[:, -1] >= ns
            j = np.minimum(np.argmax(cs >= ns[:, None], axis=1), H - 1)
            q = np.arange(len(lv))
            sbefore = np.where(j > 0, cs[q, j - 1], 0.0)
            scost = np.where(j > 0, csc[q, j - 1], 0.0) \
                + (ns - sbefore) * sprice[t, so[j]]
            split[t, lv[ok] + 1] = (wcost + scost)[ok]
    return coloc, split


def best_schedule(pj: PlainJob, cost: np.ndarray) -> Optional[Best]:
    """Algorithms 2-3 over (W, Q + 1) level costs: a min-plus DP over the
    slots, C_t(u) = min_v C_{t-1}(u - v) + cost_t(v), and the completion
    slot of best positive payoff."""
    W, Q1 = cost.shape
    Q = Q1 - 1
    idx = np.arange(Q1)
    diff = idx[:, None] - idx[None, :]
    prev = np.full(Q1, np.inf)
    prev[0] = 0.0
    best: Optional[Best] = None
    for t in range(W):
        prev = (np.where(diff >= 0, prev[np.abs(diff)], np.inf)
                + cost[t][None, :]).min(axis=1)
        if np.isfinite(prev[Q]):
            payoff = utility(pj, t) - float(prev[Q])
            if payoff > (best.payoff if best else 0.0) + 1e-12:
                best = Best(payoff, float(prev[Q]))
    return best


def check(run, cap: np.ndarray, resources: List[str], quanta: int,
          final_used: np.ndarray, final_now: int) -> Numbers:
    """Replay the run's log from an empty ledger and compute the four
    numbers over the window's offers."""
    rec = run.recorder
    W = final_used.shape[0]
    out = Numbers()
    plains: Dict[int, PlainJob] = {}

    def plain(job) -> PlainJob:
        pj = plains.get(id(job))
        if pj is None:
            pj = plains[id(job)] = PlainJob.of(job)
        return pj

    led = Ledger(cap, resources, W, rec.start_now)
    U, L = run.prices.U, run.prices.L

    offered = set()
    batch: List = []
    pos = 0
    visits: Dict[int, tuple] = {}   # job_id -> (pj, price, full, coloc)
    commits: Dict[int, List[tuple]] = {}

    def visit(job) -> None:
        out.offers += 1
        pj = plain(job)
        price = led.prices(U, L)
        coloc, split = level_costs(pj, cap[None] - led.used, price,
                                   resources, quanta)
        visits[pj.job_id] = (pj, price,
                             best_schedule(pj, np.minimum(coloc, split)),
                             best_schedule(pj, coloc))

    def settle(upto_job: Optional[int]) -> None:
        nonlocal pos
        while pos < len(batch):
            job = batch[pos]
            if upto_job is not None and job.job_id == upto_job:
                if job.job_id not in visited:
                    visited.add(job.job_id)
                    visit(job)
                return
            if job.job_id not in visited:
                visited.add(job.job_id)
                visit(job)
            pos += 1

    visited: set = set()
    for op in rec.ops:
        kind = op[0]
        if kind == "advance":
            led.advance(op[1])
        elif kind == "offer":
            batch, pos, visited = op[2], 0, set()
            commits = {}
            offered.update(j.job_id for j in batch)
        elif kind == "commit":
            _, t_abs, job, w, s = op
            settle(job.job_id)
            excess = led.commit(t_abs, plain(job), w, s)
            if excess > FIT_TOL:
                out.invalid += 1
                out.flag(f"job {job.job_id} slot {t_abs}: {excess!r} over capacity")
            commits.setdefault(job.job_id, []).append((t_abs, plain(job), w, s))
        elif kind == "release":
            led.release(op[1], op[2])
        elif kind == "release_many":
            for jid, from_abs in op[1]:
                led.release(jid, from_abs)
        elif kind == "decided":
            settle(None)
            _judge(out, batch, op[1], commits, visits, led, resources)
            batch, visits = [], {}
        else:
            raise ValueError(f"unknown log entry {kind!r}")

    lo, hi = rec.slot_open, rec.slot_close
    missing = [jid for jid, a in run.arrivals if lo <= a < hi and jid not in offered]
    if missing:
        out.unanswered += len(missing)
        out.flag(f"never offered: jobs {missing[:10]}")
    if led.now != final_now:
        out.ledger_gap = math.inf
        out.flag(f"reference window at slot {led.now}, program at {final_now}")
    else:
        out.ledger_gap = float(np.abs(final_used - led.used).max())
    return out


def _judge(out: Numbers, batch, admitted: Dict[int, bool],
           commits: Dict[int, List[tuple]], visits: Dict[int, tuple],
           led: Ledger, resources: List[str]) -> None:
    """Hold one batch's answers to the stated guarantees and score the
    offers against the reference's best schedule."""
    now = led.now
    for job in batch:
        jid = job.job_id
        if jid not in admitted:
            out.unanswered += 1
            out.flag(f"job {jid}: no decision")
            continue
        rows = commits.get(jid, [])
        if admitted[jid] != bool(rows):
            out.invalid += 1
            out.flag(f"job {jid}: admitted={admitted[jid]} with {len(rows)} rows")
            continue
        if rows:
            out.admitted += 1
            pj = rows[0][1]
            per_slot: Dict[int, Tuple[Dict[int, int], Dict[int, int]]] = {}
            for t_abs, _, w, s in rows:
                ws, ss = per_slot.setdefault(t_abs, ({}, {}))
                for h, n in w.items():
                    ws[h] = ws.get(h, 0) + n
                for h, n in s.items():
                    ss[h] = ss.get(h, 0) + n
            trained = sum(samples_trained(pj, ws, ss) for ws, ss in per_slot.values())
            V = total_workload(pj)
            if trained < V - (WORK_TOL + 1e-9 * V):
                out.invalid += 1
                out.flag(f"job {jid}: trains {trained!r} of {V!r} samples")
            if any(len({h for h, n in {**ws, **ss}.items() if n}) > 1
                   for ws, ss in per_slot.values()):
                out.split_schedules += 1
            for t_abs, (ws, ss) in per_slot.items():
                nw, ns = sum(ws.values()), sum(ss.values())
                if not 0 <= t_abs - now < led.W:
                    out.invalid += 1
                    out.flag(f"job {jid}: row at slot {t_abs} outside the window")
                if nw > pj.batch_size:
                    out.invalid += 1
                    out.flag(f"job {jid}: {nw} workers > batch {pj.batch_size}")
                if nw > 0 and ns < max(1, int(math.ceil(nw / pj.gamma))):
                    out.invalid += 1
                    out.flag(f"job {jid}: {ns} servers for {nw} workers")
        v = visits.get(jid)
        if v is None:
            continue
        pj, price, full, coloc = v
        if rows:
            wd, sd = (np.array(x) for x in pj.demand(resources))
            cost = 0.0
            for t_abs, (ws, ss) in per_slot.items():
                k = t_abs - now
                for h, n in ws.items():
                    cost += float(price[k, h] @ wd) * n
                for h, n in ss.items():
                    cost += float(price[k, h] @ sd) * n
            P = utility(pj, max(per_slot) - now) - cost
        else:
            P = 0.0
        B, unit = (full.payoff, full.cost) if full else (0.0, pj.theta[0])
        gap = max(0.0, beyond_tie(B, P)) / unit
        if B > P:
            out.shortfall_rel = max(out.shortfall_rel,
                                    (B - P) / max(abs(B), abs(P)))
            if gap == 0.0:
                out.tied += 1
                if rows and full:
                    out.tie_cost_ratio = max(out.tie_cost_ratio,
                                             cost / full.cost)
        if gap > out.payoff_gap:
            out.payoff_gap = gap
            if gap > 1e-6:
                out.flag(f"job {jid}: payoff {P!r} below the reference's "
                         f"{B!r} by {gap!r} of its cost")
        if rows and coloc is not None:
            out.split_margins.append(beyond_tie(P, coloc.payoff) / unit)
