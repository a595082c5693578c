"""What every runner shares: the clock's zero, the compile counter, spans
and counters recorded from the benchmark's own files, percentiles."""
from __future__ import annotations

import contextlib
import math
import time

PROCESS_T0 = time.perf_counter()     # the zero of setup_s


def skip_cloud_logger() -> None:
    """orbax, which the program imports for its checkpoints, imports its
    optional Google Cloud logger where it can (`except ImportError: pass`);
    on the chip's machine that import alone takes 23 to 45 s (PERF.md,
    PR 25) and, with no network, can log nothing. An entry point calls
    this before it imports the program; nothing else is kept from it."""
    import sys
    sys.modules.setdefault("google.cloud.logging", None)


class NoChip(SystemExit):
    """Raised before anything is run; the process exits with code 3."""

    def __init__(self, why: str):
        import sys
        print(f"{why} Nothing was run.", file=sys.stderr)
        super().__init__(3)


def start_program(chips: int, rehearse: bool = False) -> dict:
    """What every entry point does before it touches the program: looks
    for the chips (and fails without them, unless a test rehearses on the
    CPU), imports the program and lets it place the compile cache. Returns
    the device as JAX reports it."""
    skip_cloud_logger()
    import jax
    say("jax imported")
    info = device_info()
    say(f"devices: {info}")
    if not rehearse:
        if info["platform"] != "tpu":
            raise NoChip(f"the benchmark needs a TPU; JAX selected "
                         f"{info['platform']!r} ({info['kind']}).")
        if info["count"] < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found "
                         f"{info['count']}.")
    from paddle_tpu.device import enable_compile_cache
    say("program imported")
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    say(f"{info['platform']} {info['kind']} x{info['count']}; compile "
        f"cache at {cache}")
    return info


def say(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - PROCESS_T0:7.1f}s] {msg}",
          flush=True)


class CompileCounter:
    """Backend compiles and persistent-cache hits and misses, from
    jax.monitoring (after chip_smoke.py's CompileClock)."""

    def __init__(self):
        import jax
        self.compiles, self.seconds, self.hits, self.misses = 0, 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Recording:
    """Spans (name, t0, t1 on time.perf_counter) and counters kept in
    memory; per-layer readers read them after the window."""

    def __init__(self):
        self.spans: dict[str, list] = {}
        self.counters: dict[str, float] = {}
        self.samples: dict[str, list] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans.setdefault(name, []).append(
                (t0, time.perf_counter()))

    def count(self, name: str, by: float = 1.0):
        self.counters[name] = self.counters.get(name, 0.0) + by

    def sample(self, name: str, value: float):
        self.samples.setdefault(name, []).append(value)


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    v = sorted(values)
    if not v:
        return float("nan")
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(n: int) -> int:
    """The allocator's peak on the fullest of the first n devices. On this
    runtime it does not seem to count a program's temporaries (PERF.md
    section 7); the planned bytes go on an earlier line."""
    import jax
    peak = 0
    for d in jax.devices()[:n]:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak
