"""Time of a block's signature batch per block in the window, in ms: the
system's ``bls_batch_verify`` spans (``crypto/bls/__init__.py``: host
preparation and every device stage) over its ``block_import`` spans.
None where the program records no such span."""


def read(ctx):
    batches = [end - start for kind, start, end in ctx.spans
               if kind == "bls_batch_verify"]
    blocks = sum(kind == "block_import" for kind, _, _ in ctx.spans)
    return 1000 * sum(batches) / blocks if batches and blocks else None
