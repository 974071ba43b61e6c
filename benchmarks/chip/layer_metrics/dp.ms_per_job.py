"""DP layer (``core/dp.py``): summed time of the ``dp.sweep`` spans per job
offered, in ms; it holds the min-plus kernel launches and their syncs."""


def read(ctx):
    row = ctx["phase"].get("dp.sweep")
    if row is None or not ctx["offers"]:
        return None
    return row["total_s"] / ctx["offers"] * 1e3
