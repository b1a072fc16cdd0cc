"""Device time of signature decompression per batch in the window, in ms:
the system's ``bls_decompress`` device spans (``crypto/bls/tpu_backend.py``,
stamped by ``obs/tracing.py``'s watcher) over its ``bls_batch_verify``
spans.  None where the program records no such span."""


def read(ctx):
    stages = [end - start for kind, start, end in ctx.spans
              if kind == "bls_decompress"]
    batches = sum(kind == "bls_batch_verify" for kind, _, _ in ctx.spans)
    return 1000 * sum(stages) / batches if stages and batches else None
