"""Idle share of the device in the traced window: 1 minus the union of
the intervals in which an operation ran, averaged over the chips used."""


def read(ctx, spec):
    tr = ctx["trace"]
    if tr is None or not tr.devices or not tr.window_s:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
