"""Device occupancy of the multi-key sets' pubkey sums per block in the
window, in ms: the system's ``bls_pk_aggregate`` device spans
(``crypto/bls/tpu_backend.py``: the table gather and the bucket sums)
over its ``block_import`` spans.  None where the program records no such
span (a backend without a device pubkey table)."""


def read(ctx):
    stages = [end - start for kind, start, end in ctx.spans
              if kind == "bls_pk_aggregate"]
    blocks = sum(kind == "block_import" for kind, _, _ in ctx.spans)
    return 1000 * sum(stages) / blocks if stages and blocks else None
