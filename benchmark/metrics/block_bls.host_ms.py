"""Host time of the BLS backend per block in the window, in ms: the
system's ``bls_parse``, ``bls_prepare`` and ``bls_scalars`` spans
(``crypto/bls/tpu_backend.py``: parsing the sets and finding their keys,
preparing the device inputs, the RLC scalars' bits) over its
``block_import`` spans.  None where the program records no such span."""

HOST = ("bls_parse", "bls_prepare", "bls_scalars")


def read(ctx):
    stages = [end - start for kind, start, end in ctx.spans if kind in HOST]
    blocks = sum(kind == "block_import" for kind, _, _ in ctx.spans)
    return 1000 * sum(stages) / blocks if stages and blocks else None
