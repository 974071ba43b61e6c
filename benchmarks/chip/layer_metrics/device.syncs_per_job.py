"""Device layer: ``device.sync`` spans per job offered in the window, each
a blocking device-to-host read (``repro.obs.trace``): one per min-plus
DP step, the bundle and price reads, the engine's per-slot check."""


def read(ctx):
    row = ctx["phase"].get("device.sync")
    if row is None or not ctx["offers"]:
        return None
    return row["count"] / ctx["offers"]
