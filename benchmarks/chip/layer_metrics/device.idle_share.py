"""Device layer: share of the traced window in which no operation ran on
the chip, in % (1 - busy union / window, from the profiler trace)."""


def read(ctx):
    red = ctx["reduction"]
    if red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
