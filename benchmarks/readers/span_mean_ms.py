"""Mean length, in milliseconds, of the harness's spans of one name
(`span` in the metric's file)."""


def read(ctx, spec):
    spans = ctx["rec"].spans.get(spec["span"])
    if not spans or ctx["rehearse"]:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
