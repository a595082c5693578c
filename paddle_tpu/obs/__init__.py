"""paddle_tpu.obs — the fleet's sensory layer (ISSUE 12).

Everything the repo measures (serving request metrics, step telemetry,
goodput attribution, prefix-cache/spec counters) was reachable only by
in-process Python calls; before a router or autoscaler can act on a
replica, the replica needs an ops surface over the wire. This package is
that surface, stdlib-only:

  MetricsRegistry    composes every exposition producer into ONE
                     collision-checked, lint-clean Prometheus page
                     (registry.py; the promtool-style `lint_exposition`
                     rides the shared profiler/_metrics parser).
  TelemetryServer    threaded HTTP server: /metrics, /healthz (drain +
                     queue depth + overloaded_total — the autoscaler
                     inputs), /statusz (config/occupancy snapshot),
                     /tracez (server.py).
  TraceBuffer        bounded per-request trace retention with TAIL
                     sampling: every failure + the slowest decile always
                     kept (tracez.py).
  SLOMonitor         declarative TTFT/TPOT/e2e/goodput objectives
                     evaluated as multi-window burn rates over the
                     existing log-bucket histograms, alerting through
                     the structured JSONL path (slo.py; `parse_slo` /
                     `evaluate_slo` judge a whole run).

Fleet scope (ISSUE 13) — one replica's surface is not a fleet's:

  FleetAggregator    scrapes N TelemetryServers into ONE merged,
                     lint-clean page (counters summed, gauges labeled
                     {replica=...}, histograms pooled bucket-wise) plus
                     the /fleet/healthz roll-up and the trace_id-merged
                     /fleet/tracez; a dead member goes stale and is
                     degraded around, never a scrape 500 (fleet.py).
  CollectiveLedger   per-collective comm attribution (bytes, bus
                     bandwidth, exposed-vs-overlapped time) from a
                     captured trace — the decomposition of the r13
                     overlap_ratio gauge — plus shard-wall stitching for
                     the StepMonitor straggler gauges (collectives.py).

Flight-recorder scope (ISSUE 17) — alerts that die as JSONL rows can't
explain a regression:

  FlightRecorder     a bounded ring of profiler captures — periodic
                     low-duty-cycle background captures plus captures
                     PINNED by the trigger bus (SLO alerts, straggler
                     transitions, recompiles, numerics events), with an
                     eviction policy that never drops pinned evidence
                     before periodic baseline, a cooldown so an alert
                     storm yields ONE capture, and the live `/profilez`
                     route (list captures / render KernelView tables /
                     download the raw trace) merged fleet-wide like
                     tracez (flightrec.py; `tools/perf_diff.py` diffs
                     two captures at kernel granularity).

HBM-ledger scope (ISSUE 18) — where the time went was answerable, where
the HBM went was not:

  MemoryLedger       owner-attributed device-memory accounting (model
                     params, optimizer state, KV pools, prefix-cache
                     overlays, spill/checkpoint host tiers) reconciled
                     against `device.memory_allocated()` — attributed +
                     unattributed ≡ the allocator view, host counters
                     only (a /memz read never syncs). Exposes the /memz
                     route (fleet-merged by FleetAggregator.fleet_memz),
                     hbm_bytes{owner=...}/hbm_headroom_bytes gauges, a
                     headroom-low flight-recorder trigger, and the OOM
                     post-mortem artifact tools/oom_report.py renders
                     (memz.py).

Active-probing scope (ISSUE 19) — everything above is passive; none of
it can see a replica serving WRONG answers at perfect latency:

  Prober             golden-canary correctness sentinels: synthetic
                     requests through the REAL serving path (paged
                     admission, prefix hit/miss, spec decode), output
                     asserted BITWISE equal to goldens minted once per
                     config fingerprint via generate_static_ragged.
                     Tagged end-to-end out of user-facing SLO/goodput
                     accounting; failures are structured {"probe_fail"}
                     rows (flight-recorder trigger + memz census) and a
                     `failing` /probez state the FleetRouter ejects on
                     (probez.py; fleet-merged by fleet_probez with
                     config-drift detection).
  InvariantAuditor   deep host-side audits on the poller cadence:
                     BlockPool conservation, per-owner rows ≅ refcounts,
                     radix-trie ↔ pool cross-check, int8 scale
                     co-residency — invariant_* gauges + structured
                     findings on violation (probez.py).

`ServingEngine.serve_telemetry()` wires all of these around a live
engine (and owns the SLO burn-rate poll cadence via `poll_interval=`);
`hapi.callbacks.ProfilerCallback(telemetry=...)` exports a TRAINING
loop's StepMonitor + live goodput gauges through the same server.
"""
from .collectives import (CollectiveLedger, feed_shard_walls,  # noqa: F401
                          load_shard_walls)
from .fleet import (FleetAggregator, FleetMergeError,  # noqa: F401
                    bucket_percentile, merge_exposition)
from .flightrec import (FixtureBackend, FlightRecorder,  # noqa: F401
                        JaxProfilerBackend)
from .memz import MemoryLedger, looks_like_oom  # noqa: F401
from .probez import (GoldenStore, InvariantAuditor, Prober,  # noqa: F401
                     config_fingerprint)
from .registry import (ExpositionError, MetricsCollisionError,  # noqa: F401
                       MetricsRegistry, lint_exposition)
from .server import Raw, TelemetryServer  # noqa: F401
from .slo import (SLOMonitor, SLOTarget, evaluate_slo,  # noqa: F401
                  format_slo_table, parse_slo)
from .tracez import TraceBuffer, chrome_trace  # noqa: F401

__all__ = ["ExpositionError", "MetricsCollisionError", "MetricsRegistry",
           "lint_exposition", "TelemetryServer", "Raw", "SLOMonitor",
           "SLOTarget", "parse_slo", "evaluate_slo", "format_slo_table",
           "TraceBuffer", "chrome_trace", "FleetAggregator",
           "FleetMergeError", "merge_exposition", "bucket_percentile",
           "CollectiveLedger", "load_shard_walls", "feed_shard_walls",
           "FlightRecorder", "JaxProfilerBackend", "FixtureBackend",
           "MemoryLedger", "looks_like_oom", "Prober", "GoldenStore",
           "InvariantAuditor", "config_fingerprint"]
