"""The device's idle time by the program it was waiting for: every gap in
the first device's busy union goes to the program whose operation ENDS it
(the `XLA Modules` event the gap's end lies in), as a share of the
recorded part of the window (readers/program_device.py). Device clock
only: `idle_*_pct.*` say what the host stood under during a gap and need
the two clocks to agree; these say which program's start the chip waited
for, and need none. A gap between two operations of one program is that
program's.

The metric's file gives `programs` (names of `paddle_tpu/jit/api.py`'s
table) and, for the rest, `others: true`: every gap that no listed
program ends (another program of the table, one under no name of it, or
no program at all). The files of one cell between them cover every gap,
so their metrics add up to the recorded part's idle share. A trace with
no run of any listed program gives nothing."""
import bisect

from benchmarks.readers.program_device import module_events, recorded

_EPS_NS = 1.0


def idle_by_program(ctx) -> dict:
    """Idle ns of the recorded part by the program that ends each gap
    (None: no program's event holds the gap's end)."""
    if "idle_by_program" in ctx:
        return ctx["idle_by_program"]
    tr, (w0, end) = ctx["trace"], recorded(ctx)
    busy = tr.busy_union(sorted(tr.devices)[0])
    edges = [w0] + [min(t, end) for iv in busy for t in iv] + [end]
    events = module_events(ctx)
    starts = [e[0] for e in events]
    by = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        k = bisect.bisect_right(starts, b + _EPS_NS) - 1
        name = events[k][2] if k >= 0 and events[k][1] > b - _EPS_NS \
            else None
        by[name] = by.get(name, 0.0) + (b - a)
    ctx["idle_by_program"] = by
    return by


def read(ctx, spec):
    rec = recorded(ctx)
    if rec is None:
        return None
    listed = {f"jit_{p}" for p in spec["programs"]}
    if not any(e[2] in listed for e in module_events(ctx)):
        return None
    others = bool(spec.get("others"))
    idle = sum(ns for name, ns in idle_by_program(ctx).items()
               if (name in listed) != others)
    return 100.0 * idle / (rec[1] - rec[0])
