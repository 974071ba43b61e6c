"""Device layer: summed time of the ``device.launch`` spans per job
offered, in ms: the host's dispatch cost, the implicit host-to-device
copy of numpy arguments included."""


def read(ctx):
    row = ctx["phase"].get("device.launch")
    if row is None or not ctx["offers"]:
        return None
    return row["total_s"] / ctx["offers"] * 1e3
