"""The plain reference's search, by hand: the co-located and the split
placement of each workload level, the DP over slots, and how an offer's
shortfall is scored beyond the tie band (CPU only)."""
import math
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))

from gen.jobmath import PlainJob, utility  # noqa: E402
from harness import reference  # noqa: E402

RES = ["cpu", "mem"]
# one sample a worker a slot, V = 4 samples in Q = 4 levels, at most 4
# workers, a server for every two; a worker takes 10 cpu, a server 5
JOB = PlainJob(job_id=1, arrival=0, epochs=1, num_samples=4, batch_size=4,
               tau=1.0, grad_size=0.0, gamma=2.0, bw_internal=1.0,
               bw_external=1.0, worker_demand=(("cpu", 10.0), ("mem", 0.0)),
               ps_demand=(("cpu", 5.0), ("mem", 0.0)),
               theta=(1000.0, 1.0, 0.5))


def _cluster(slots):
    """Three empty machines of 25 cpu; machine h costs h + 1 a cpu."""
    free = np.tile(np.array([25.0, 100.0]), (slots, 3, 1))
    price = np.ones((slots, 3, 2))
    price[:, :, 0] = [1.0, 2.0, 3.0]
    return free, price


def test_level_costs_by_hand():
    free, price = _cluster(1)
    coloc, split = reference.level_costs(JOB, free, price, RES, 4)
    # co-located on machine 0: v workers and ceil(v / 2) servers while
    # 10 v + 5 ceil(v / 2) <= 25
    assert coloc[0].tolist() == [0.0, 15.0, 25.0, math.inf, math.inf]
    # split: two workers a machine, cheapest first; servers on the
    # cheapest machines that hold no worker
    assert split[0].tolist() == [0.0, 20.0, 30.0, 70.0, 90.0]


def test_level_costs_respect_what_is_used():
    free, price = _cluster(1)
    free[0, 0, 0] = 4.0                    # machine 0 holds no worker
    coloc, split = reference.level_costs(JOB, free, price, RES, 4)
    assert coloc[0].tolist() == [0.0, 30.0, 50.0, math.inf, math.inf]
    # workers on 1 (and 2), servers on what is left: machine 0 fits none
    # of 5 cpu, so v = 1 puts its server on machine 2
    assert split[0].tolist() == [0.0, 35.0, 55.0, math.inf, math.inf]


def test_best_schedule_by_hand():
    free, price = _cluster(2)
    coloc, split = reference.level_costs(JOB, free, price, RES, 4)
    u0 = 1000.0 / (1.0 + math.exp(-0.5))
    u1 = 1000.0 / (1.0 + math.exp(0.5))
    full = reference.best_schedule(JOB, np.minimum(coloc, split))
    # finishing in slot 0 takes the split placement of all 4 levels (90)
    assert full.payoff == pytest.approx(u0 - 90.0)
    assert full.cost == pytest.approx(90.0)
    alone = reference.best_schedule(JOB, coloc)
    # co-located alone needs both slots: 2 levels each at 25
    assert alone.payoff == pytest.approx(u1 - 50.0)
    assert reference.best_schedule(JOB, coloc[:1]) is None


def _one_commit_run(machine):
    """A log of one offer whose job commits 2 workers (6 cpu each) and a
    server (2 cpu) on ``machine`` in slot 0, admitted."""
    from types import SimpleNamespace as NS
    job = NS(job_id=1, arrival=0, epochs=1, num_samples=2, batch_size=2,
             tau=1.0, grad_size=0.0, gamma=2.0, bw_internal=1.0,
             bw_external=1.0, worker_demand={"cpu": 6.0, "mem": 1.0},
             ps_demand={"cpu": 2.0, "mem": 1.0},
             utility=NS(theta1=1000.0, theta2=0.0, theta3=1.0))
    rec = NS(start_now=0, slot_open=0, slot_close=1, ops=[
        ("offer", 0, [job]),
        ("commit", 0, job, {machine: 2}, {machine: 1}),
        ("decided", {1: True})])
    return NS(recorder=rec, arrivals=[(1, 0)],
              prices=NS(U={"cpu": 10.0, "mem": 10.0}, L=1.0))


@pytest.mark.parametrize("machine, over", [(0, 0), (1, 1)])
def test_commit_is_held_to_its_machine_class(machine, over):
    """Two classes: machine 0 holds 20 cpu, machine 1 half that. A commit
    of 14 cpu fits the large class and not the small one."""
    cap = np.array([[20.0, 100.0], [10.0, 50.0]])
    nums = reference.check(_one_commit_run(machine), cap, RES, 4,
                           np.zeros((2, 2, 2)), 0)
    assert nums.invalid == over
    assert sum("over capacity" in n for n in nums.notes) == over
    assert nums.admitted == 1 and nums.unanswered == 0


# ------------------------------------------------------- the tie band
# JOB's schedule in _score: 4 workers (10 cpu each) and 2 servers (5 cpu)
# on machine 0 in slot 0, at 0.25 a unit of each resource
COST = 4 * 10 * 0.25 + 2 * 5 * 0.25
P_ADMIT = utility(JOB, 0) - COST


def _score(B, admitted=True, unit=2.0, job=JOB):
    """The numbers of one offer of ``job`` against a reference whose best
    schedule pays ``B`` and costs ``unit`` (``B`` None: no schedule)."""
    price = np.full((1, 1, 2), 0.25)
    commits = {1: [(0, job, {0: 4}, {0: 2})]} if admitted else {}
    full = None if B is None else reference.Best(B, unit)
    out = reference.Numbers()
    reference._judge(out, [SimpleNamespace(job_id=1)], {1: admitted},
                     commits, {1: (job, price, full, None)},
                     reference.Ledger(np.array([[100.0, 100.0]]), RES, 1, 0),
                     RES)
    assert out.invalid == 0 and out.unanswered == 0, out.notes
    return out


def test_a_shortfall_of_a_thousand_ulps_is_a_tie():
    out = _score(P_ADMIT + 1000 * math.ulp(P_ADMIT))
    assert out.payoff_gap == 0.0
    assert out.tied == 1 and out.tie_cost_ratio == pytest.approx(COST / 2.0)
    assert 0 < out.shortfall_rel < reference.TIE_REL


def test_a_shortfall_beyond_the_band_is_read_in_cost_units():
    short = 10 * reference.TIE_REL * P_ADMIT
    out = _score(P_ADMIT + short, unit=1e-20)
    band = reference.TIE_REL * (P_ADMIT + short)
    assert out.payoff_gap == pytest.approx((short - band) / 1e-20, rel=1e-3)
    assert out.payoff_gap > 0 and out.tied == 0


def test_a_rejection_is_scored_against_the_reference_schedule():
    """The program rejects what the reference admits at payoff 5: the
    whole payoff, less the band, in units of the reference's cost."""
    out = _score(5.0, admitted=False, unit=2.0)
    assert out.payoff_gap == pytest.approx(5.0 * (1 - reference.TIE_REL) / 2.0)
    assert out.shortfall_rel == 1.0 and out.tied == 0


def test_a_loss_where_the_reference_finds_none_is_read_in_theta1():
    """No reference schedule (B = 0): an admission at a loss of 12.5
    is read in units of the job's theta_1."""
    job = PlainJob(**{**JOB.__dict__, "theta": (1.0, 1.0, 0.5)})
    out = _score(None, job=job)
    loss = COST - utility(job, 0)
    assert out.payoff_gap == pytest.approx(loss * (1 - reference.TIE_REL) / 1.0)


def test_no_payoff_on_either_side_reads_zero():
    out = _score(None, admitted=False)
    assert out.payoff_gap == 0.0 and out.shortfall_rel == 0.0
    assert out.tied == 0


@pytest.mark.parametrize("x, y, want", [
    (10.0, 10.0, 0.0),
    (10.0 + 1e-12, 10.0, 0.0),                 # inside 1e-11
    (10.0 + 1e-10, 10.0, 1e-10 - 1e-11),
    (10.0, 10.0 + 1e-10, -(1e-10 - 1e-11)),
])
def test_beyond_tie(x, y, want, monkeypatch):
    monkeypatch.setattr(reference, "TIE_REL", 1e-12)
    assert reference.beyond_tie(x, y) == pytest.approx(want, rel=1e-3, abs=1e-25)
