"""Device milliseconds per call under one host span of the program
(`span`, a jax.profiler.TraceAnnotation the engine writes)."""


def read(ctx, spec):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    seconds, n = tr.device_seconds_under(spec["span"])
    return 1e3 * seconds / n if n else None
