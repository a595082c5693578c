"""How much of the traced window the device's line holds: 100 x (the end
of the last operation recorded, less the window's start) over the window.
100 in a whole trace; less where the profiler's buffer filled before the
window closed, and then every share of the WINDOW (`device_idle_pct.*`,
`idle_*_pct.*`) reads the cut as idle. The by-program readers confine
themselves to the recorded part (readers/program_device.py)."""
from benchmarks.readers.program_device import recorded


def read(ctx, spec):
    rec = recorded(ctx)
    tr = ctx["trace"]
    if rec is None or not tr.window_s:
        return None
    return 100.0 * (rec[1] - rec[0]) / 1e9 / tr.window_s
