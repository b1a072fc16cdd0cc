"""Device time of the random-linear-combination stage per batch in the
window, in ms: both scalar multiplications, the per-message and
signature sums and their affine conversions, the system's ``bls_rlc``
device spans (``crypto/bls/tpu_backend.py``) over its
``bls_batch_verify`` spans.  None where the program records no such
span."""


def read(ctx):
    stages = [end - start for kind, start, end in ctx.spans
              if kind == "bls_rlc"]
    batches = sum(kind == "bls_batch_verify" for kind, _, _ in ctx.spans)
    return 1000 * sum(stages) / batches if stages and batches else None
