"""Mean host time of the system's ``bls_batch_verify`` span per batch in
the window, in ms: the crypto backend's whole verification of a batch,
host preparation and every device stage (``crypto/bls/__init__.py``)."""


def read(ctx):
    spans = [end - start for kind, start, end in ctx.spans
             if kind == "bls_batch_verify"]
    return 1000 * sum(spans) / len(spans) if spans else None
