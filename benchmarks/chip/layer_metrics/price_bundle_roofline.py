"""Kernels layer: share of its roofline the ``price_bundle`` Pallas kernel
(``kernels/pricing.py``) reaches, in %: the least time of its algorithm's
work over the rows the window's bundle passes priced (``harness/work.py``)
over the kernel's summed device time in the trace."""
from harness import work


def read(ctx):
    red = ctx["reduction"]
    seconds, launches = red.kernel("price_bundle")
    rec = ctx["recorder"]
    if launches == 0 or seconds <= 0 or rec.bundle_rows == 0:
        return None
    flops, nbytes = work.price_bundle(rec.bundle_rows, len(ctx["config"].resources),
                                      rec.bundle_calls)
    pct, _ = work.roofline_pct(flops, nbytes, seconds, ctx["peaks"])
    return pct
