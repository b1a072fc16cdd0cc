"""Share of the traced slices in which no operation ran on the device, in
%: 100 * (1 - busy / window), from the profiler traces.  Read for each
cell's split of the quantity (``device.idle_share.import``,
``device.idle_share.gossip``)."""


def read(ctx):
    if not ctx.trace or not ctx.trace["window_s"]:
        return None
    return 100 * (1 - ctx.trace["busy_s"] / ctx.trace["window_s"])
