"""A host span's own time, in milliseconds per span: the mean over the
spans named `span` of the span's length less the spans named in
`children` that lie inside it. With `serving/step` less `serving/decode`
and `serving/prefill` it is the serving engine's own host time per step:
admission, state shipping, token delivery and bookkeeping, without the
model calls and the reads that wait for them. No such span in the trace
gives nothing."""
import bisect


def read(ctx, spec):
    tr = ctx["trace"]
    if tr is None or not tr.window_s:
        return None
    spans = sorted(tr.host_spans(spec["span"]))
    if not spans:
        return None
    inner = sorted(iv for name in spec["children"]
                   for iv in tr.host_spans(name))
    starts = [a for a, _ in inner]
    own = 0.0
    for a, b in spans:
        i = bisect.bisect_left(starts, a)
        covered = 0.0
        while i < len(inner) and inner[i][0] < b:
            if inner[i][1] <= b:
                covered += inner[i][1] - inner[i][0]
            i += 1
        own += (b - a) - covered
    return own / len(spans) / 1e6
