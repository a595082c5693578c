"""From a profiler trace to numbers: the device's busy union, per-operation
sums, device time under a host span, and idle gaps named by what the host
was doing. The reduction works on a plain form,

    {"window": [t0_ns, t1_ns],
     "devices": {plane: [[name, start_ns, dur_ns], ...]},
     "host":    [[name, start_ns, dur_ns], ...]}

so that a test checks it on a trace small enough to count by hand
(benchmarks/tests/data/mini_trace.json). `load_xplane` makes that form from
the `.xplane.pb` the JAX profiler writes; the busy/idle union follows
paddle_tpu/profiler/trace_analysis.py's `_union`, copied here so that no
later PR can change the yardstick.

On the installed runtime (jax 0.9.0, TPU v5 lite) a device plane is named
"/device:TPU:<n>" and its line "XLA Ops" holds one event per executed HLO
operation, nested operations (a while loop, a fusion's caller) included;
each event is summed at its own time, without what lies inside it, so
nothing counts twice.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench/window"
HOST_PREFIXES = ("bench/", "serving/")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_EPS_NS = 1.0     # timestamps come in picoseconds, rounded
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def op_label(name: str) -> str:
    """On this runtime an operation's event is named by its whole HLO
    instruction, `%fusion.12 = bf16[3072,50304]{...} fusion(...)`. The
    label is `opcode result-type`: the same operation of every layer falls
    under one label, and the text stays short."""
    if " = " not in name:
        return name[:100]
    lhs, rhs = name.split(" = ", 1)
    m = _OPCODE.search(" " + rhs)
    if not m:
        return lhs[:100]
    typ = re.sub(r"\{[^}]*\}", "", rhs[:max(m.start() - 1, 0)])
    return f"{m.group(1)} {typ}"[:100]


_LAYOUT = re.compile(r"\{[^}]*\}")
_OPERAND = re.compile(r" ?%[\w.\-]+")


def signature(name: str) -> str:
    """An operation's HLO instruction without its own name, its layouts,
    its operands' names and its attributes: `(bf16[48,2048,128],
    f32[48,8,2048]) custom-call(bf16[48,2048,128], ...)`. The installed
    runtime gives a Pallas kernel no name of its own (`kernel_metadata={}`
    in every event), so a kernel is known by its result and operand types;
    the patterns in a metric's file are pieces of this text."""
    if " = " not in name:
        return name
    rhs = name.split(" = ", 1)[1]
    m = _OPCODE.search(" " + rhs)
    end = rhs.find("), ", m.start()) if m else -1
    rhs = rhs[:end + 1] if end >= 0 else rhs
    return _OPERAND.sub("", _LAYOUT.sub("", rhs))


def matches(name: str, patterns) -> bool:
    """One of `patterns` fits: a pattern is a substring of the signature,
    or a list of substrings that all have to be in it."""
    sig = None
    for p in patterns:
        if sig is None:
            sig = signature(name)
        parts = [p] if isinstance(p, str) else p
        if all(part in sig for part in parts):
            return True
    return False


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, modules, host, window = {}, {}, [], None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    ev = [[e.name, float(e.start_ns), float(e.duration_ns)]
                          for e in line.events]
                    (devices if line.name == OPS_LINE
                     else modules)[plane.name] = ev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = [float(e.start_ns),
                                  float(e.start_ns + e.duration_ns)]
                    if e.name.startswith(HOST_PREFIXES):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
    return {"window": window, "devices": devices, "modules": modules,
            "host": host}


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _self_times(events):
    """[name, start, dur, self] per event: `self` is the event's duration
    less what the events inside it cover. A loop or a call that only wraps
    other operations is left with next to nothing, so nothing counts twice;
    a kernel that merely has a zero-length marker or an asynchronous copy
    inside its interval keeps its time (a rule that dropped every event
    with another inside it lost 5% of the device time of a training trace,
    PERF.md, PR 25)."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []          # stack: [name, start, dur, covered]

    def close(top):
        out.append([top[0], top[1], top[2], max(top[2] - top[3], 0.0)])
    for name, start, dur in ev:
        end = start + dur
        while stack and (stack[-1][1] + stack[-1][2] <= start
                         or end > stack[-1][1] + stack[-1][2] + _EPS_NS):
            close(stack.pop())   # ended, or overlapped and outlived
        if stack:
            stack[-1][3] += dur
        stack.append([name, start, dur, 0.0])
    while stack:
        close(stack.pop())
    return out


class TraceSummary:
    def __init__(self, plain: dict):
        w = plain.get("window")
        self.devices = {k: _self_times(v)
                        for k, v in plain["devices"].items()}
        if w is None and self.devices:
            starts = [e[1] for v in self.devices.values() for e in v]
            ends = [e[1] + e[2] for v in self.devices.values() for e in v]
            w = [min(starts), max(ends)] if starts else None
        self.window = w
        self.host = plain.get("host", [])
        self.modules = plain.get("modules", {})   # for tools/trace_dump

    def _clip(self, a, b):
        return max(a, self.window[0]), min(b, self.window[1])

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9 if self.window else 0.0

    def _inside(self, s, d, self_ns):
        """The part of an event's own time that falls in the window."""
        a, b = self._clip(s, s + d)
        return self_ns * (b - a) / d if b > a and d > 0 else 0.0

    def busy_union(self, plane):
        """Intervals in which any operation ran, wrappers included: a union
        counts nothing twice."""
        iv = []
        for _, s, d, _own in self.devices[plane]:
            a, b = self._clip(s, s + d)
            if b > a:
                iv.append((a, b))
        return _union(iv)

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = sum(sum(b - a for a, b in self.busy_union(p))
                  for p in self.devices)
        return tot / len(self.devices) / 1e9

    def op_seconds(self) -> dict:
        """Device seconds by operation name, inside the window, averaged
        over the devices."""
        out = {}
        for ev in self.devices.values():
            for name, s, d, own in ev:
                sec = self._inside(s, d, own) / 1e9
                if sec > 0:
                    out[name] = out.get(name, 0.0) + sec
        n = max(len(self.devices), 1)
        return {k: v / n for k, v in out.items()}

    def seconds_matching(self, patterns) -> tuple[float, int]:
        """(device seconds, events) of operations whose signature fits
        one of the patterns (see `matches`), as written in a metric's
        file."""
        tot, cnt, memo = 0.0, 0, {}
        for ev in self.devices.values():
            for name, s, d, own in ev:
                if name not in memo:
                    memo[name] = matches(name, patterns)
                if memo[name] and s + d > self.window[0] \
                        and s < self.window[1]:
                    tot += self._inside(s, d, own) / 1e9
                    cnt += 1
        n = max(len(self.devices), 1)
        return tot / n, cnt // n

    def host_spans(self, name: str):
        return [(s, s + d) for n, s, d in self.host
                if n == name and s + d > self.window[0]
                and s < self.window[1]]

    def device_seconds_under(self, span_name: str) -> tuple[float, int]:
        """Device busy seconds inside host spans of that name (first
        device), and the number of spans."""
        spans = self.host_spans(span_name)
        if not spans or not self.devices:
            return 0.0, len(spans)
        plane = sorted(self.devices)[0]
        busy = self.busy_union(plane)
        tot, j = 0.0, 0
        for a, b in sorted(spans):
            while j < len(busy) and busy[j][1] <= a:
                j += 1
            k = j
            while k < len(busy) and busy[k][0] < b:
                tot += min(b, busy[k][1]) - max(a, busy[k][0])
                k += 1
        return tot / 1e9, len(spans)

    def idle_gaps(self, top: int = 10):
        """Idle seconds of the first device by the innermost host span
        open at the middle of each gap."""
        if not self.devices or not self.window:
            return []
        plane = sorted(self.devices)[0]
        busy = self.busy_union(plane)
        edges = [self.window[0]] + [t for iv in busy for t in iv] \
            + [self.window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        spans = sorted(((s, s + d, n) for n, s, d in self.host
                        if n != WINDOW_SPAN), key=lambda x: x[0])
        starts = [s[0] for s in spans]
        import bisect
        by = {}
        for a, b in gaps:
            mid = (a + b) / 2
            hi = bisect.bisect_right(starts, mid)
            best = None
            for s0, s1, n in spans[max(0, hi - 64):hi]:
                if s0 <= mid < s1 and (best is None
                                       or s1 - s0 < best[1] - best[0]):
                    best = (s0, s1, n)
            name = best[2] if best else "(no span open)"
            by[name] = by.get(name, 0.0) + (b - a) / 1e9
        return sorted(by.items(), key=lambda kv: -kv[1])[:top]

    def breakdown(self, top: int = 10) -> dict:
        by = {}
        for name, sec in self.op_seconds().items():
            label = op_label(name)
            by[label] = by.get(label, 0.0) + sec
        ops = sorted(by.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps(top)]}
