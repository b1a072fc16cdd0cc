"""Self time of the system's ``block_import`` span per block in the
window, in ms: its length less the union of the window's other spans
that lie inside it, the part of an import that no span names.  The
block cell has one import in flight, so the spans inside an import are
its own."""


def read(ctx):
    imports = [(start, end) for kind, start, end in ctx.spans
               if kind == "block_import"]
    if not imports:
        return None
    total = 0.0
    for lo, hi in imports:
        inside = sorted((start, end) for kind, start, end in ctx.spans
                        if lo <= start and end <= hi
                        and (start, end) != (lo, hi))
        named, reach = 0.0, lo
        for start, end in inside:
            if end > reach:
                named += end - max(start, reach)
                reach = end
        total += hi - lo - named
    return 1000 * total / len(imports)
