"""Host time of the head update per block in the window, in ms: the
system's ``head_update`` spans (``chain/beacon_chain.py``,
``recompute_head``) over its ``block_import`` spans.  None where the
program records no such span."""


def read(ctx):
    stages = [end - start for kind, start, end in ctx.spans
              if kind == "head_update"]
    blocks = sum(kind == "block_import" for kind, _, _ in ctx.spans)
    return 1000 * sum(stages) / blocks if stages and blocks else None
