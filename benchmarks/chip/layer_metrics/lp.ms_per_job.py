"""LP layer (``core/cover_packing.py``, ``core/lp.py``): summed time of the
``lp.solve`` spans per job offered, in ms."""


def read(ctx):
    row = ctx["phase"].get("lp.solve")
    if row is None or not ctx["offers"]:
        return None
    return row["total_s"] / ctx["offers"] * 1e3
