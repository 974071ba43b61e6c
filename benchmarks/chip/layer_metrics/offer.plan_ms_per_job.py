"""Offer path (``core/solve_plan.py`` under ``PDORSPolicy.on_arrivals``):
summed self time of the ``plan.*`` spans per job offered, in ms."""


def read(ctx):
    rows = [r for name, r in ctx["phase"].items() if name.startswith("plan.")]
    if not rows or not ctx["offers"]:
        return None
    return sum(r["self_s"] for r in rows) / ctx["offers"] * 1e3
