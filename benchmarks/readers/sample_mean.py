"""Mean of one of the harness's sample lists, times `scale`."""


def read(ctx, spec):
    values = ctx["rec"].samples.get(spec["sample"])
    if not values:
        return None
    return float(spec.get("scale", 1.0)) * sum(values) / len(values)
