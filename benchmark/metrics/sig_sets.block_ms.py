"""Host time of building a block's signature sets per block in the window,
in ms: the system's ``signature_sets`` spans
(``chain/block_verification.py``; the verify itself is not in them) over
its ``block_import`` spans.  None where the program records no such
span."""


def read(ctx):
    stages = [end - start for kind, start, end in ctx.spans
              if kind == "signature_sets"]
    blocks = sum(kind == "block_import" for kind, _, _ in ctx.spans)
    return 1000 * sum(stages) / blocks if stages and blocks else None
