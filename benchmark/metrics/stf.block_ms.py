"""Mean host time of the system's ``state_transition`` span per block
imported in the window, in ms (the span wraps ``per_block_processing``
in ``chain/block_verification.py``)."""


def read(ctx):
    spans = [end - start for kind, start, end in ctx.spans
             if kind == "state_transition"]
    return 1000 * sum(spans) / len(spans) if spans else None
