"""100 x one counter over the sum of several (`num`, `den` in the file)."""


def read(ctx, spec):
    c = ctx["rec"].counters
    den = sum(c.get(k, 0.0) for k in spec["den"])
    if not den or spec["num"] not in c:
        return None
    return 100.0 * c[spec["num"]] / den
