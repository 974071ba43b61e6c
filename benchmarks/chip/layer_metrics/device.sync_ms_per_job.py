"""Device layer: summed time of the ``device.sync`` spans per job offered,
in ms: the host blocked on device-to-host reads, device compute still in
flight and the round trip both."""


def read(ctx):
    row = ctx["phase"].get("device.sync")
    if row is None or not ctx["offers"]:
        return None
    return row["total_s"] / ctx["offers"] * 1e3
