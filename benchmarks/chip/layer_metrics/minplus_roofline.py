"""Kernels layer: share of its roofline the ``minplus`` Pallas kernel
(``kernels/minplus.py``) reaches, in %: the least time of the DP steps'
work over all its launches (``harness/work.py``) over its summed device
time in the trace."""
from harness import work


def read(ctx):
    red = ctx["reduction"]
    seconds, launches = red.kernel("minplus")
    if launches == 0 or seconds <= 0:
        return None
    flops, nbytes = work.minplus(ctx["config"].quanta)
    pct, _ = work.roofline_pct(flops * launches, nbytes * launches, seconds,
                               ctx["peaks"])
    return pct
