"""Pricing layer (``core/pricing.py``, ``backend/jax_backend.py``): summed
time of the ``price.prewarm`` spans (device repricing and its host copy)
per job offered, in ms."""


def read(ctx):
    row = ctx["phase"].get("price.prewarm")
    if row is None or not ctx["offers"]:
        return None
    return row["total_s"] / ctx["offers"] * 1e3
