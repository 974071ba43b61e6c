"""Engine layer (``sim/engine.py``, ``sim/window.py``): the engine's own
host time per slot, in ms, from the program's spans: the summed self time
of the ``sim.*`` spans (``sim.slot``, ``sim.advance``, ``sim.arrivals``,
...) over the number of ``sim.slot`` spans in the window. Self time
leaves out every child span: the offers under ``sim.arrivals`` and the
device launches and syncs the engine issues.

One-slot bias: the window opens inside the first slot's ``sim.advance``,
so that slot's ``sim.slot`` and ``sim.advance`` start before the window
and are left out, while its ``sim.arrivals`` is kept. The sum holds one
slot's arrival handling beyond the slots it is divided by, about 1/slots
of the engine's time high."""


def read(ctx):
    phase = ctx["phase"]
    slots = phase.get("sim.slot")
    if slots is None or not slots["count"]:
        return None
    own = sum(r["self_s"] for name, r in phase.items()
              if name.startswith("sim."))
    return own / slots["count"] * 1e3
