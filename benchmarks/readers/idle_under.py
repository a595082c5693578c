"""Share of the traced window in which the device was idle while the host
stood under one of the listed spans: 100 x idle seconds under them, over
the window. Every gap in the first device's busy union is cut at the
host spans' boundaries and each piece goes to the innermost (shortest)
span open over it, so a gap that begins in one read, crosses the engine's
bookkeeping and ends in the next launch is shared out between the three.
(`TraceSummary.idle_gaps` gives a whole gap to the span at its middle:
right for naming long gaps in a breakdown, but with spans of 0.06-7 ms
inside one 7 ms gap a shift of a millisecond moves a second of idle from
one name to another; PERF.md, PR 26.)

The metric's file gives `spans` (whole names), `prefixes` (every span
whose name starts so) and `no_span` (true: pieces under no span at all,
the harness between two calls into the program). Groups that between them
name every span the loader keeps add up to the cell's `device_idle_pct.*`
of the same run. A group none of whose spans the trace holds gives
nothing: the program at that commit does not write them."""
from benchmarks.trace import WINDOW_SPAN


def innermost_pieces(host, window):
    """[(t0, t1, name or None)]: the window cut at every span boundary,
    each piece named by the shortest span open over it."""
    w0, w1 = window
    spans = [(max(s, w0), min(s + d, w1), d, n) for n, s, d in host
             if n != WINDOW_SPAN and s + d > w0 and s < w1 and d > 0]
    edges = sorted({w0, w1} | {t for a, b, _, _ in spans for t in (a, b)})
    opens = sorted(spans)
    out, active, k = [], [], 0
    for t0, t1 in zip(edges, edges[1:]):
        while k < len(opens) and opens[k][0] <= t0:
            active.append(opens[k])
            k += 1
        active = [sp for sp in active if sp[1] > t0]
        inner = min(active, key=lambda sp: sp[2], default=None)
        out.append((t0, t1, inner[3] if inner else None))
    return out


def idle_by_span(tr) -> dict:
    """Idle seconds of the first device by the innermost host span open
    (None: no span), every gap cut at the spans' boundaries."""
    busy = tr.busy_union(sorted(tr.devices)[0])
    edges = [tr.window[0]] + [t for iv in busy for t in iv] + [tr.window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    pieces = innermost_pieces(tr.host, tr.window)
    by, j = {}, 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            t0, t1, name = pieces[k]
            by[name] = by.get(name, 0.0) + (min(b, t1) - max(a, t0)) / 1e9
            k += 1
    return by


def read(ctx, spec):
    tr = ctx["trace"]
    if tr is None or not tr.devices or not tr.window_s:
        return None
    spans = set(spec.get("spans", ()))
    prefixes = tuple(spec.get("prefixes", ()))

    def listed(name):
        if name is None:
            return bool(spec.get("no_span"))
        return name in spans or (prefixes and name.startswith(prefixes))
    if not spec.get("no_span") and not any(listed(n) for n, _, _ in tr.host):
        return None
    # a cell reads three groups off one trace: the cut is made once and
    # kept in the run's own context
    if "idle_by_span" not in ctx:
        ctx["idle_by_span"] = idle_by_span(tr)
    idle = sum(sec for name, sec in ctx["idle_by_span"].items()
               if listed(name))
    return 100.0 * idle / tr.window_s
