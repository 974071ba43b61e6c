"""Engine layer (``sim/engine.py`` batched loop, ``sim/window.py``): window
wall time outside the arrival-batch offers, per slot run, in ms."""


def read(ctx):
    rec = ctx["recorder"]
    return rec.engine_ms_per_slot() if rec.slots > 0 else None
