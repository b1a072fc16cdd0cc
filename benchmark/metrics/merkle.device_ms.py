"""Device busy time per block imported inside the traced slice, in ms
(the profiler stops between imports once ``trace_seconds`` have passed).
In a cell whose block signatures run on the ``fake`` backend every
device operation belongs to the state root's device trees."""


def read(ctx):
    blocks = ctx.traced.get("blocks")
    if not ctx.trace or not blocks:
        return None
    return 1000 * ctx.trace["busy_s"] / blocks
