"""Device layer: ``device.launch`` spans per job offered in the window,
each a host call that enqueued device work (``repro.obs.trace``): the
offers' kernels and ledger ops, and the engine's per-slot ledger advance
and oversubscription check."""


def read(ctx):
    row = ctx["phase"].get("device.launch")
    if row is None or not ctx["offers"]:
        return None
    return row["count"] / ctx["offers"]
