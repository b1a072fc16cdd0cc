"""Host time of the import's pre-state per block in the window, in ms: the
system's ``pre_state`` spans (``chain/block_verification.py``: the parent
state's lookup, copy and advance to the block's slot) over its
``block_import`` spans.  None where the program records no such span."""


def read(ctx):
    stages = [end - start for kind, start, end in ctx.spans
              if kind == "pre_state"]
    blocks = sum(kind == "block_import" for kind, _, _ in ctx.spans)
    return 1000 * sum(stages) / blocks if stages and blocks else None
