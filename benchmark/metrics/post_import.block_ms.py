"""Host time after the store write per block in the window, in ms: the
system's ``post_import`` spans (``chain/beacon_chain.py``: validator
monitor, caches, events, reprocess wake, light client) over its
``block_import`` spans.  None where the program records no such span."""


def read(ctx):
    stages = [end - start for kind, start, end in ctx.spans
              if kind == "post_import"]
    blocks = sum(kind == "block_import" for kind, _, _ in ctx.spans)
    return 1000 * sum(stages) / blocks if stages and blocks else None
