"""Host time of the BLS backend per batch in the window, in ms: the
system's ``bls_parse``, ``bls_prepare`` and ``bls_scalars`` spans
(``crypto/bls/tpu_backend.py``: parsing the sets, preparing the device
inputs, the RLC scalars' bits) over its ``bls_batch_verify`` spans.
None where the program records no such span."""

HOST = ("bls_parse", "bls_prepare", "bls_scalars")


def read(ctx):
    stages = [end - start for kind, start, end in ctx.spans if kind in HOST]
    batches = sum(kind == "bls_batch_verify" for kind, _, _ in ctx.spans)
    return 1000 * sum(stages) / batches if stages and batches else None
