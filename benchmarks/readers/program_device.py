"""Device time of the programs the package names, off the trace's `XLA
Modules` line. That line holds one event per run of a program on the
device's own clock, called `jit_<name>(<hash>)`; the hash changes with
every compile, the name is the program's role (`paddle_tpu/jit/api.py`'s
table: `serve_decode` one decode chunk, `serve_prefill` one prefill
window, `pure_step` one optimizer step). No host span and no agreement of
two clocks is involved: a chunk that runs while the host stands elsewhere
is still that chunk.

The metric's file gives `programs` (names of the table, without `jit_`)
and `stat`: `mean_ms`, the mean length of one run, or `share_pct`, 100 x
the runs' time over the device's busy time. Only runs that start in the
RECORDED part of the window count: from the window's start to the last
operation the device's line holds, so that a trace the profiler's buffer
cut short (readers/trace_recorded.py) reads what it recorded and not the
cut as idle. A trace with no run of these programs (a commit that names
them otherwise) gives nothing.

The helpers serve the other by-program readers too."""


def program_of(event_name: str) -> str:
    """`jit_serve_decode(1489675396959)` -> `jit_serve_decode`."""
    return event_name.split("(", 1)[0]


def recorded(ctx):
    """(start, end) in ns of the recorded part of the window on the first
    device, or None; kept in the run's own context."""
    if "recorded_window" not in ctx:
        tr, out = ctx["trace"], None
        if tr is not None and tr.devices and tr.window:
            ops = tr.devices[sorted(tr.devices)[0]]
            last = max((e[1] + e[2] for e in ops), default=tr.window[0])
            end = min(last, tr.window[1])
            out = (tr.window[0], end) if end > tr.window[0] else None
        ctx["recorded_window"] = out
    return ctx["recorded_window"]


def module_events(ctx):
    """[(start, end, program)] of the first device's `XLA Modules` line,
    by start; the whole trace, not the window. Kept in the run's own
    context: a cell's readers share it."""
    if "module_events" not in ctx:
        tr = ctx["trace"]
        line = tr.modules[sorted(tr.modules)[0]] \
            if tr is not None and tr.modules else []
        ctx["module_events"] = sorted(
            (s, s + d, program_of(n)) for n, s, d in line)
    return ctx["module_events"]


def runs(ctx, programs):
    """[(start, end)] of the runs of `programs` that start in the recorded
    part of the window."""
    rec = recorded(ctx)
    if rec is None:
        return []
    want = {f"jit_{p}" for p in programs}
    return [(a, b) for a, b, name in module_events(ctx)
            if name in want and rec[0] <= a < rec[1]]


def read(ctx, spec):
    found = runs(ctx, spec["programs"])
    if not found:
        return None
    if spec["stat"] == "mean_ms":
        return sum(b - a for a, b in found) / len(found) / 1e6
    if spec["stat"] == "share_pct":
        tr, (_, end) = ctx["trace"], recorded(ctx)
        if not tr.busy_s:
            return None
        return 100.0 * sum(min(b, end) - a for a, b in found) \
            / 1e9 / tr.busy_s
    raise KeyError(spec["stat"])
