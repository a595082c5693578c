"""Share of the device's busy time that falls under host spans of the
given names (`spans`)."""


def read(ctx, spec):
    tr = ctx["trace"]
    if tr is None or not tr.devices or not tr.busy_s:
        return None
    under = sum(tr.device_seconds_under(s)[0] for s in spec["spans"])
    return 100.0 * under / tr.busy_s
