"""From the host's launch to the device's start, in milliseconds: a
percentile (`q`) over k of the k-th `span` of the trace (a host span that
encloses exactly one launch of `program`) to the start of the k-th run of
`program` on the `XLA Modules` line, both counted from the trace's start
(the engine keeps the one-launch-a-span rule, tests/test_serving_spans.py
holds it; the warm-up has drained before the trace starts). With chunk
n + 1 queued behind chunk n this is about one chunk; a stall BEFORE the
program started shows here, one after it in the host's read
(readers/span_max_ms.py). Pairs whose span starts in the recorded part of
the window count. The host's and the device's clocks agree to about a
millisecond. No run of the program gives nothing."""
from benchmarks.harness import percentile
from benchmarks.readers.program_device import module_events, recorded


def read(ctx, spec):
    rec = recorded(ctx)
    if rec is None:
        return None
    tr, name = ctx["trace"], f"jit_{spec['program']}"
    starts = [a for a, _, n in module_events(ctx) if n == name]
    spans = sorted(s for n, s, _ in tr.host if n == spec["span"])
    waits = [(dev - host) / 1e6 for host, dev in zip(spans, starts)
             if rec[0] <= host < rec[1]]
    return percentile(waits, spec["q"]) if waits else None
