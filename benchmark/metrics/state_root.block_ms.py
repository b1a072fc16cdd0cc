"""Mean host time of the system's ``state_root`` span per block imported
in the window, in ms: the post-state root, device tree updates and the
read-back included (``chain/block_verification.py``)."""


def read(ctx):
    spans = [end - start for kind, start, end in ctx.spans
             if kind == "state_root"]
    return 1000 * sum(spans) / len(spans) if spans else None
