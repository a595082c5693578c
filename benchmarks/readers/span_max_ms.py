"""The longest single host span among those named in `spans`, in
milliseconds, over the spans that touch the window. Among the engine's
launches, reads and `serving/gc` it is about one decode chunk in a sound
run and seconds in one that stalled: the flag that a run's other
per-layer numbers are not to be trusted. None of the spans in the trace
gives nothing."""


def read(ctx, spec):
    tr = ctx["trace"]
    if tr is None or not tr.window:
        return None
    lengths = [b - a for name in spec["spans"]
               for a, b in tr.host_spans(name)]
    return max(lengths) / 1e6 if lengths else None
