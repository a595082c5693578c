"""A percentile (`q`) of one of the harness's sample lists (`sample`),
times `scale`. Nothing to read gives nothing."""
from benchmarks.harness import percentile


def read(ctx, spec):
    values = ctx["rec"].samples.get(spec["sample"])
    if not values:
        return None
    return float(spec.get("scale", 1.0)) * percentile(values, spec["q"])
