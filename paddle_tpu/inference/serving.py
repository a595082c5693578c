"""paddle_tpu.inference.serving — an instrumented continuous-batching
engine over a paged KV block pool, with request-level observability as
the headline.

The training side has step metrics (profiler.StepMonitor, r7) and numerics
sentinels (debugging, r8); serving quality is judged by a DIFFERENT set of
signals — TTFT/TPOT latency distributions, queue wait, batch fill and
KV occupancy under load (cf. the ragged-paged-attention and Gemma-on-TPU
serving studies, PAPERS.md). This module provides:

  ServingEngine   admits per-request prompts into a bounded queue and
                  runs each of `max_batch` batch SLOTS against blocks of
                  a KV pool (inference/kv_cache.py + the ragged paged
                  attention op) through the model's `prefill_paged` /
                  `decode_paged` executables. One `step()` is admit,
                  launch, land:

                  admit   queued requests are spliced into free slots
                          mid-flight (`_admit_paged`: trie match, block
                          mapping, copy-on-write; no model call). EOS
                          or budget frees a slot's blocks immediately,
                          so nothing waits for a batch to drain, and
                          anything that fits the pool is admittable.
                  launch  every slot in prefill gets its next window
                          ([1, prompt_cap], or [1, prefill_chunk]) and
                          the decodable rows one fixed-shape [B,
                          decode_chunk] decode chunk, enqueued without a
                          read: each row's pending token is picked on the
                          device from the last chunk's outputs.
                  land    only then are the tokens of the step BEFORE
                          copied to the host, while the chip works.
                          Tokens reach a request one step after their
                          launch; a budget's end is known at launch, an
                          EOS one chunk late (`eos_late_rows`).

                  Every shape is pinned by the config, so after the
                  {prefill, decode, two small helpers} set compiles once
                  the loop adds ZERO jit compilations — guarded at
                  runtime via the PR-2 cache-miss counter, with a
                  shape-delta warning through
                  `StepMonitor.record_compile` when a request would force
                  a new executable (it is rejected instead). The pool
                  buffers are DONATED through every call, so XLA updates
                  KV in place.

  RequestTrace    per-request span timestamps (enqueue → admit → prefill →
                  first token → finish); each engine phase also runs under
                  a `jax.profiler.TraceAnnotation` so a device trace gives
                  kernel time, and every gap between kernels, to what the
                  host was doing. One engine step is "serving/step".
                  "serving/admit" is one queued request (trie match,
                  block mapping, copy-on-write, slot set-up);
                  "serving/prefill" encloses one window's
                  "serving/prefill_launch"; "serving/decode_prep" is the
                  KV snapshot and the chunk's inputs staged;
                  "serving/decode" encloses "serving/decode_launch" (this
                  step's chunk enqueued) and then the reads of what the
                  step BEFORE launched, "serving/prefill_read" (its final
                  windows' first tokens) and "serving/decode_read" (its
                  chunk's tokens: the host waits here while the chip runs
                  this step's chunk); "serving/deliver" (tokens handed to
                  requests, finished rows freed and recorded) and
                  "serving/bookkeep" (batch gauges, compile accounting,
                  the monitor's step) close the step. "serving/gc" is one
                  collection of the host's garbage collector, wherever it
                  fell (one `gc.callbacks` hook a process while an engine
                  is open; `close()` of the last takes it out). PERF.md
                  section 3 names the metric that reads each.

                  The device's side of the same trace is its `XLA
                  Modules` line: one event per program run, on the
                  device's own clock, called `jit_<name>(<hash>)` after
                  `jit.api`'s table of program names. `jit_serve_prefill`
                  is one window, `jit_serve_decode` one chunk
                  (`jit_serve_verify` a speculative window),
                  `jit_serve_stage` the pending tokens picked,
                  `jit_serve_put_first` a final window's first token
                  kept, `jit_serve_page_copy` a copy-on-write,
                  `jit_serve_state_move` a state row zeroed, saved or
                  restored, `jit_serve_spill` a spilled page written
                  back. Each "serving/prefill_launch" encloses exactly
                  one `jit_serve_prefill` launch and each
                  "serving/decode_launch" exactly one `jit_serve_decode`
                  (or verify) launch, in order: the k-th span of a trace
                  caused the k-th event of that program. Whatever else
                  shows on that line (today `jit__threefry_seed` and two
                  companions a call: the sampling key made eagerly) was
                  sent under no name of the table.
                  `benchmarks/tools/trace_dump.py` prints the line as
                  `modules_ms_count` (milliseconds and runs by name).

  ServingMetrics  log-bucketed latency histograms (TTFT, per-output-token
                  time, end-to-end, queue wait — p50/p90/p99 derived from
                  buckets, no per-request retention), gauges (queue depth,
                  batch-fill ratio, KV occupancy) and counters
                  (requests/tokens in+out/rejections/timeouts/batches),
                  rendered to Prometheus exposition text by the SAME
                  `profiler._metrics` formatter StepMonitor uses (among
                  the counters: `programs_launched{program=...}`, one a
                  launch the engine handed to the chip, by the table's
                  name; `host_gc_pauses` and `host_gc_pause_ms`, the
                  collections while the engine was open), plus one
                  JSONL record per finished request (the StepMonitor row
                  convention: a nested payload under "request" + "ts").

Greedy engine output is bit-identical to `model.generate_static_ragged`
on the same prompts (tested): slot company and chunking change nothing —
attention masks make cache length and batch company value-invariant, and
chunked greedy decode replays the same argmax chain.
(Bit-identity caveat: bf16 models on TPU route through the f32-score
Pallas paged kernel while the static path stores bf16 scores, so parity
there is approximate near argmax ties — exact whenever both sides share
a numerics class: f32 models anywhere, or the CPU reference path; see
ops/pallas/paged_attention.py, and chip_smoke.py's serve phase for the
agreement required of a bf16 model on the chip.)

`ServingConfig(prefix_cache=True)` (ISSUE 10) adds the radix-trie PREFIX
CACHE (inference/prefix_cache.py): admission matches each prompt against
cached full-block token prefixes, maps shared refcounted pool blocks into
the request's table, and prefills only the uncached suffix — a full hit
skips prefill entirely (the last prompt token re-enters as the decode
pending token, so TTFT is one decode step, with copy-on-write of the
last shared block when the hit is block-aligned). With
`cache_dtype="int8"` the pools carry int8 codes + per-block factored
scales, holding ~2x the resident requests. Greedy output stays
bit-identical with the cache on vs off, and the steady loop still adds
zero compilations — the suffix-prefill and COW executables are part of
the warmup set.

`ServingConfig(spec_decode=True)` (ISSUE 11) turns each decode step into
a DRAFT-VERIFY window through the ragged [B, k] multi-token
paged-attention kernel: a draft proposes `spec_k` tokens per row, the
target model scores pending + drafts in ONE fixed-shape call
(`model.verify_paged`), and the longest-accepted-prefix rule emits
1..spec_k+1 tokens per launch with greedy output BIT-IDENTICAL to the
plain chain. The default drafter is prompt-lookup from the prefix radix
trie — a matched node's cached continuation tokens ARE the draft, and
finished requests cache their generated chains too, so repeated /
agentic traffic drafts its own future with no draft model at all
(`spec_draft` also takes a callable; `model_draft_fn` adapts a tiny
GPT). Rejected-position KV writes land below the next window's start
(or in the trash block past a row's budget), so acceptance is data, not
shape: one verify executable per window size, zero steady recompiles.
A speculative engine reads each call before the next (the accepted
count sets a row's next length).
`prefill_chunk=N` additionally caps per-step prefill work at [1, N]
tokens through the same start-offset executable, so a cap-length prompt
no longer monopolizes the engine for one monolithic prefill call.
"""
from __future__ import annotations

import gc
import json
import logging
import time
import uuid
import weakref
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..jit.api import (DECODE_PROGRAM, PAGE_COPY_PROGRAM, PREFILL_PROGRAM,
                       PUT_FIRST_PROGRAM, SPILL_PROGRAM, STAGE_PROGRAM,
                       STATE_MOVE_PROGRAM, VERIFY_PROGRAM, named_program)
from ..profiler import StepMonitor
from .kv_cache import STATE_LOAD, STATE_SAVE, STATE_ZERO
from ..profiler.monitor import _jit_cache_misses
from ..profiler._metrics import (LogHistogram, counter_lines, gauge_lines,
                                 histogram_lines, labeled_counter_lines)

_logger = logging.getLogger("paddle_tpu.inference.serving")

# Host spans on the device trace's clock (the module docstring lists
# them). A no-op while no profiler session is open; a span takes no
# keyword argument, formats no string and reads no clock of its own.
_span = jax.profiler.TraceAnnotation


class _GcWatch:
    """The process's one `gc.callbacks` hook: a `serving/gc` span from a
    collection's start to its stop (a device trace then names the pause
    as the innermost span over whatever gap it left) and, on the metrics
    of every open engine, `host_gc_pauses` and `host_gc_pause_ms`. It
    reads the clock twice a collection. The first engine installs it,
    `ServingEngine.close()` of the last takes it out; an engine dropped
    without `close()` leaves it in, counting for nobody."""

    def __init__(self):
        self._sinks = weakref.WeakSet()       # ServingMetrics of engines
        self._open = None                     # (span, start) of a pause
        # its own references: a collection at interpreter exit finds the
        # module's globals gone
        self._span, self._clock = _span, time.perf_counter

    def __call__(self, phase, info):
        if phase == "start":
            span = self._span("serving/gc")
            span.__enter__()
            self._open = (span, self._clock())
        elif self._open is not None:
            span, t0 = self._open
            self._open = None
            ms = 1e3 * (self._clock() - t0)
            span.__exit__(None, None, None)
            for m in self._sinks:
                m.counters["host_gc_pauses"] += 1
                m.counters["host_gc_pause_ms"] += ms

    def add(self, metrics):
        if self not in gc.callbacks:
            gc.callbacks.append(self)
        self._sinks.add(metrics)

    def discard(self, metrics):
        self._sinks.discard(metrics)
        if not self._sinks and self in gc.callbacks:
            gc.callbacks.remove(self)


_gc_watch = _GcWatch()


# where a paged row's pending token and done flag are read from when a
# decode chunk is launched (ServingEngine._stage_decode_inputs)
_SRC_HOST, _SRC_CHUNK, _SRC_FIRST = 0, 1, 2


# what an engine over a pool with state planes counts (ServingMetrics
# gains them only then)
_STATE_COUNTERS = ("state_snapshots_taken", "state_snapshots_restored",
                   "state_snapshot_evictions", "prefix_match_cut_tokens")


@dataclass
class _Flight:
    """What one engine step launched and has not read: the final
    prefill windows' first tokens and one decode chunk's tokens, still on
    the device. The step after reads them (`ServingEngine._land`)."""
    # (slot, request, first-token Tensor [1], event name, launch time)
    firsts: List[tuple] = field(default_factory=list)
    # (slot, request, tokens of the chunk that count against its budget)
    rows: List[tuple] = field(default_factory=list)
    toks: object = None          # the chunk's tokens, Tensor [B, chunk]
    t0: float = 0.0              # the chunk's launch time
    stats: object = None         # the model's step counters of these calls


# --------------------------------------------------------------- requests

@dataclass
class RequestTrace:
    """Span TREE of one request's life (engine clock seconds).

    enqueue → admit is queue wait; admit → prefill_done is the request's
    prefill windows, the last of which samples first_token; finish is
    stamped when the host has read the decode CHUNK in which the row hit
    EOS or its budget (chunk granularity — a short request co-batched
    with long ones is not charged for decode chunks past its own
    completion). first_token and finish are stamped with the time the
    tokens reached the host, one step after their launch.

    `trace_id` names the request across export surfaces (JSONL rows, the
    /tracez ring, logs); `events` are the engine-call WINDOWS the request
    rode, appended as (name, t0, t1) tuples — "prefill",
    "suffix_prefill", "prefill_chunk", "decode", "spec_verify" — so an
    exported trace explains WHERE a slow e2e went (ISSUE 12: one window
    per device call the row participated in; a zero-prefill cache hit
    shows no prefill window at all, which is the point). `span_tree()`
    renders the stamps + windows as one structured tree."""
    t_enqueue: Optional[float] = None
    t_admit: Optional[float] = None
    t_prefill_done: Optional[float] = None
    t_first_token: Optional[float] = None
    t_finish: Optional[float] = None
    batch_id: Optional[int] = None
    trace_id: Optional[str] = None
    events: List[tuple] = field(default_factory=list)

    @property
    def queue_s(self) -> Optional[float]:
        if self.t_admit is None or self.t_enqueue is None:
            return None
        return self.t_admit - self.t_enqueue

    @property
    def ttft_s(self) -> Optional[float]:
        if self.t_first_token is None or self.t_enqueue is None:
            return None
        return self.t_first_token - self.t_enqueue

    @property
    def e2e_s(self) -> Optional[float]:
        if self.t_finish is None or self.t_enqueue is None:
            return None
        return self.t_finish - self.t_enqueue

    def tpot_s(self, n_out: int) -> Optional[float]:
        """Per-output-token time over the post-first-token stretch."""
        if self.t_finish is None or self.t_first_token is None or n_out < 2:
            return None
        return (self.t_finish - self.t_first_token) / (n_out - 1)

    def to_dict(self) -> dict:
        d = {k: getattr(self, k) for k in
             ("t_enqueue", "t_admit", "t_prefill_done", "t_first_token",
              "t_finish", "batch_id")}
        return {k: v for k, v in d.items() if v is not None}

    def span_tree(self) -> dict:
        """The structured trace a /tracez consumer renders: the request
        root span plus its children — the derived queue span and every
        engine-call window this request rode, in time order."""
        spans = []
        if self.t_enqueue is not None and self.t_admit is not None:
            spans.append({"name": "queue", "t0": self.t_enqueue,
                          "t1": self.t_admit})
        for name, a, b in self.events:
            spans.append({"name": name, "t0": a, "t1": b})
        spans.sort(key=lambda s: s["t0"])
        return {"trace_id": self.trace_id,
                "t0": self.t_enqueue, "t1": self.t_finish,
                "spans": spans}


@dataclass(eq=False)     # holds an ndarray: identity, not value, equality
class Request:
    """One admitted (or refused) generation request."""
    id: int
    prompt: np.ndarray                      # 1-D int token ids
    max_new_tokens: int
    status: str = "queued"   # queued|active|done|rejected|timeout
    reason: Optional[str] = None            # rejection/timeout detail
    # rejection taxonomy (ISSUE 14 satellite): True = the refusal is
    # replica-local (overloaded/draining/queue_full — retry ELSEWHERE),
    # False = terminal everywhere (kv_oom never fits, shape-recompile
    # rejects) so a router cannot hot-loop a request no replica will
    # ever accept; None until a rejection stamps it
    retriable: Optional[bool] = None
    deadline_s: Optional[float] = None      # max queue wait before admit
    tokens: Optional[np.ndarray] = None     # generated ids (done only)
    n_out: int = 0                          # tokens up to & incl. EOS
    # speculative decoding (ISSUE 11): draft tokens proposed for this
    # request across its verify windows, and how many the target accepted
    spec_proposed: int = 0
    spec_accepted: int = 0
    # active probing (ISSUE 19): golden-canary requests ride the normal
    # submit()/decode path but are excluded end-to-end from user-facing
    # SLO/latency/goodput accounting — they feed probe_* families instead
    probe: bool = False
    trace: RequestTrace = field(default_factory=RequestTrace)

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def n_produced(self) -> int:
        """Tokens the engine has delivered to this request so far
        (0 until its first lands; tokens launched and not yet read by the
        host do not count); `n_out` is final, this one moves."""
        return getattr(self, "_produced", 0)

    def record(self) -> dict:
        """The JSONL payload ServingMetrics streams per finished request."""
        t = self.trace
        rec = {"id": self.id, "status": self.status,
               "prompt_tokens": self.prompt_len,
               "output_tokens": self.n_out,
               "spans": t.to_dict()}
        if t.trace_id is not None:
            rec["trace_id"] = t.trace_id
        if self.probe:
            rec["probe"] = True
        if self.retriable is not None:
            rec["retriable"] = self.retriable
        if t.events:
            # the engine-call windows (ISSUE 12): rounded for the wire,
            # ordering preserved — span_tree() derives the tree view
            rec["events"] = [[n, round(a, 6), round(b, 6)]
                             for n, a, b in t.events]
        if self.reason:
            rec["reason"] = self.reason
        if self.spec_proposed:
            rec["spec"] = {"proposed": self.spec_proposed,
                           "accepted": self.spec_accepted,
                           "accept_rate": round(
                               self.spec_accepted / self.spec_proposed, 4)}
        for key, val in (("queue_s", t.queue_s), ("ttft_s", t.ttft_s),
                         ("tpot_s", t.tpot_s(self.n_out)),
                         ("e2e_s", t.e2e_s)):
            if val is not None:
                rec[key] = round(val, 6)
        return rec


# submit() rejection-reason taxonomy (ISSUE 14 satellite): which
# refusals a fleet router may retry on ANOTHER replica vs which are
# terminal everywhere (identically-configured replicas refuse them too)
_REJECT_RETRIABLE = {
    "draining": True,         # this replica is shutting down; others serve
    "overloaded": True,       # load shed — exactly the retry-elsewhere hint
    "queue_full": True,       # hard cap here; another queue may have room
    "prompt_shape": False,    # would force a new executable on any replica
    "kv_oom": False,          # never fits the pool even fully drained
    "max_new_tokens": False,  # unservable by construction
}


# ---------------------------------------------------------------- metrics

class ServingMetrics:
    """Request-level serving telemetry: histograms + gauges + counters.

    Latency series are LogHistograms — percentiles derive from bucket
    counts, so memory stays O(buckets) however many requests pass through.
    `record_request` consumes a finished Request; `observe_call` is the
    light entry point `inference.Predictor.run` uses under
    `Config.enable_profile()` (one call = one request, e2e only).
    Mirrors StepMonitor's reporting surface: `jsonl_path` streams one row
    per request, `on_record` is the exporter hook, `summary()` returns the
    aggregate dict and `metrics_text()` the Prometheus exposition."""

    HISTS = (("ttft_seconds", "time to first token (enqueue -> token 1)"),
             ("tpot_seconds", "per-output-token time after the first"),
             ("e2e_seconds", "end-to-end request latency"),
             ("queue_seconds", "queue wait (enqueue -> admit)"),
             ("spec_accept_len", "tokens emitted per speculative verify "
                                 "window (accepted drafts + the bonus "
                                 "token)"))

    def __init__(self, *, jsonl_path: Optional[str] = None,
                 on_record: Optional[Callable[[dict], None]] = None,
                 trace_buffer=None,
                 hist_lo: float = 1e-4, hist_hi: float = 1e3,
                 per_decade: int = 10):
        self.jsonl_path = jsonl_path
        self.on_record = on_record
        # obs.TraceBuffer (ISSUE 12): every terminal request record also
        # lands in the tail-sampling ring the /tracez endpoint snapshots
        self.trace_buffer = trace_buffer
        self.hists = {name: LogHistogram(lo=hist_lo, hi=hist_hi,
                                         per_decade=per_decade)
                      for name, _ in self.HISTS
                      if name != "spec_accept_len"}
        # the accept-length series (ISSUE 11) counts 1..spec_k+1 tokens,
        # not latencies: half-integer bounds resolve every integer
        # exactly, so the derived percentiles are exact, not interpolated
        self.hists["spec_accept_len"] = LogHistogram(
            bounds=[i + 0.5 for i in range(33)])
        self.counters = {"requests": 0, "completed": 0, "rejected": 0,
                         "overloaded": 0, "timeout": 0, "errors": 0,
                         "tokens_in": 0, "tokens_out": 0, "items": 0,
                         "batches": 0,
                         # prefix cache (ISSUE 10): admissions that
                         # mapped >= 1 cached block / that mapped none,
                         # and prompt tokens whose prefill was skipped
                         # because their KV was already pooled
                         "prefix_hit": 0, "prefix_miss": 0,
                         "prefill_tokens_saved": 0,
                         # speculative decoding (ISSUE 11): draft tokens
                         # proposed / accepted across verify windows, and
                         # where each window's draft came from
                         "spec_windows": 0, "spec_proposed": 0,
                         "spec_accepted": 0, "spec_drafts_trie": 0,
                         "spec_drafts_model": 0,
                         # HBM ledger (ISSUE 18): oversubscription-wait
                         # episodes (admission stalled on the free list)
                         "mem_pressure_episodes": 0,
                         # the paged step's overlap: decode chunks
                         # launched, those launched while an earlier one
                         # was still unread, chunk rows spent on a
                         # request whose EOS the host had not seen yet,
                         # and chunk rows that rode neutral (no request,
                         # or one not decodable yet: they attend nothing)
                         "decode_chunks": 0,
                         "decode_chunks_overlapped": 0,
                         "eos_late_rows": 0,
                         "decode_rows_idle": 0,
                         # collections of the host's garbage collector
                         # while an engine was open, and what they took
                         # (`_GcWatch`; each is a `serving/gc` span)
                         "host_gc_pauses": 0,
                         "host_gc_pause_ms": 0.0}
        # launches by program (`jit.api`'s table): one a call the engine
        # hands to the chip, so the k-th `serving/decode_launch` span of
        # a trace caused the k-th `jit_serve_decode` event of its `XLA
        # Modules` line, and so for prefill
        self.programs_launched: Dict[str, int] = {}
        self.gauges = {"queue_depth": 0, "inflight": 0,
                       "batch_fill_ratio": None, "kv_occupancy": None,
                       "kv_slots_occupancy": None,
                       "kv_shared_tokens": None}
        # active probing (ISSUE 19): golden-canary requests are accounted
        # HERE, never in the user-facing counters/hists above — probe
        # traffic must not move SLO burn rates, goodput, or the r12
        # autoscaler's overload signal. Rejection reasons keep their own
        # dimension (the satellite fix: a probe shed during drain is
        # prober noise, not a user-facing rejected_total increment).
        # Rendered by probe_metrics_text() as a separate producer so a
        # no-prober exposition stays byte-identical by construction.
        self.probe_counters = {"requests": 0, "completed": 0,
                               "rejected": 0, "timeout": 0, "errors": 0}
        self.probe_reject_reasons: Dict[str, int] = {}

    # -- recording ------------------------------------------------------
    def observe_call(self, e2e_s: float, items: int = 1):
        """One synchronous predictor call: e2e latency + item (batch-row)
        count — NOT tokens; a Predictor serves arbitrary feeds."""
        self.counters["requests"] += 1
        self.counters["completed"] += 1
        self.counters["items"] += int(items)
        self.hists["e2e_seconds"].observe(e2e_s)

    def record_request(self, req: Request):
        if req.probe:
            # golden-canary traffic (ISSUE 19): full exclusion from the
            # user-facing families — no counter, no histogram, no trace
            # ring. The request stream stays a complete audit log (the
            # row just carries its own key).
            return self._record_probe_request(req)
        self.counters["requests"] += 1
        if req.status == "done":
            self.counters["completed"] += 1
            self.counters["tokens_in"] += req.prompt_len
            self.counters["tokens_out"] += req.n_out
            t = req.trace
            for name, val in (("ttft_seconds", t.ttft_s),
                              ("tpot_seconds", t.tpot_s(req.n_out)),
                              ("e2e_seconds", t.e2e_s),
                              ("queue_seconds", t.queue_s)):
                if val is not None:
                    self.hists[name].observe(max(val, 0.0))
        elif req.status == "timeout":
            self.counters["timeout"] += 1
            # the longest queue waits in the system are the expired ones —
            # leaving them out would make queue_seconds p99 look healthy
            # exactly when queueing collapsed
            t = req.trace
            if t.t_finish is not None and t.t_enqueue is not None:
                self.hists["queue_seconds"].observe(
                    max(t.t_finish - t.t_enqueue, 0.0))
        elif req.status == "rejected":
            self.counters["rejected"] += 1
            if req.reason == "overloaded":
                # the autoscaler signal — kept in lockstep with the
                # request record by construction, so any future shed
                # site that sets reason="overloaded" counts too
                self.counters["overloaded"] += 1
        elif req.status == "error":
            self.counters["errors"] += 1
        rec = req.record()
        if self.trace_buffer is not None:
            self.trace_buffer.add(rec)
        return self._emit({"request": rec, "ts": time.time()})

    def _record_probe_request(self, req: Request) -> dict:
        pc = self.probe_counters
        pc["requests"] += 1
        if req.status == "done":
            pc["completed"] += 1
        elif req.status == "rejected":
            pc["rejected"] += 1
            reason = req.reason or "unknown"
            self.probe_reject_reasons[reason] = \
                self.probe_reject_reasons.get(reason, 0) + 1
        elif req.status == "timeout":
            pc["timeout"] += 1
        elif req.status == "error":
            pc["errors"] += 1
        # distinct row key: consumers counting {"request"} rows (tracez,
        # stitchers) never see probe traffic; the flight recorder's
        # trigger bus ignores unknown keys
        return self._emit({"probe_request": req.record(),
                           "ts": time.time()})

    def probe_metrics_text(self,
                           prefix: str = "paddle_tpu_probe_serving") \
            -> str:
        """The engine-side probe families (submit/admission accounting;
        the Prober renders verdicts separately). A separate producer on
        purpose: metrics_text() is byte-identical with or without a
        prober attached."""
        lines: List[str] = []
        helps = {"requests": "probe requests observed at terminal "
                             "status",
                 "completed": "probe requests served to completion",
                 "rejected": "probe requests refused at submit "
                             "(prober noise, never user-facing "
                             "rejected_total)",
                 "timeout": "probe requests expired in queue",
                 "errors": "probe requests lost to engine exceptions"}
        for name, value in self.probe_counters.items():
            lines.extend(counter_lines(prefix, f"{name}_total", value,
                                       helps[name]))
        if self.probe_reject_reasons:
            p = prefix
            lines += [f"# HELP {p}_rejected_reason_total probe "
                      f"rejections by reason (the probe label "
                      f"dimension of the submit taxonomy)",
                      f"# TYPE {p}_rejected_reason_total counter"]
            lines += [f'{p}_rejected_reason_total{{reason="{r}"}} {c}'
                      for r, c in
                      sorted(self.probe_reject_reasons.items())]
        return "\n".join(lines) + "\n"

    def _emit(self, row: dict) -> dict:
        """One emission path for per-request and drain-summary rows —
        JSONL append + exporter hook stay in lockstep."""
        if self.jsonl_path:
            with open(self.jsonl_path, "a") as f:
                f.write(json.dumps(row) + "\n")
        if self.on_record is not None:
            self.on_record(row)
        return row

    def record_batch(self, *, n_real: int, capacity: int,
                     kv_tokens: int, kv_slots: int, kv_capacity: int,
                     queue_depth: int, kv_shared_tokens: int = 0):
        """kv_tokens = PHYSICAL live (attendable) KV rows — a block
        mapped into several requests' tables (prefix sharing) counts
        ONCE; kv_slots = rows the allocation granularity pins (reserved
        blocks); kv_capacity = total pooled rows. kv_occupancy is the
        true-token gauge; kv_slots_occupancy the block-granular one.
        kv_shared_tokens (ISSUE 10) is the LOGICAL
        volume served out of shared blocks — summed over requests, so
        (kv_shared_tokens - distinct shared rows) is exactly the HBM the
        prefix cache is saving right now."""
        self.counters["batches"] += 1
        self.gauges["batch_fill_ratio"] = n_real / max(capacity, 1)
        self.gauges["kv_occupancy"] = kv_tokens / max(kv_capacity, 1)
        self.gauges["kv_slots_occupancy"] = kv_slots / max(kv_capacity, 1)
        self.gauges["kv_shared_tokens"] = kv_shared_tokens
        self.gauges["queue_depth"] = queue_depth

    # -- reporting ------------------------------------------------------
    def summary(self) -> dict:
        out = {**{f"{k}_total": v for k, v in self.counters.items()},
               "programs_launched_total": dict(self.programs_launched),
               **{k: v for k, v in self.gauges.items()}}
        for name, _ in self.HISTS:
            h = self.hists[name]
            if h.count:
                out[name] = h.summary()
        return out

    def flush(self) -> dict:
        """Drain-time flush: zero the liveness gauges (an empty engine
        must not keep advertising its last batch's occupancy) and emit one
        terminal `{"drain": summary}` row to the JSONL stream/on_record
        hook — the scrape a collector takes after graceful shutdown."""
        for k in ("queue_depth", "inflight"):
            self.gauges[k] = 0
        for k in ("batch_fill_ratio", "kv_occupancy",
                  "kv_slots_occupancy", "kv_shared_tokens"):
            self.gauges[k] = None
        return self._emit({"drain": self.summary(), "ts": time.time()})

    def metrics_text(self, prefix: str = "paddle_tpu_serving") -> str:
        """Prometheus text exposition — same format/renderer as
        StepMonitor.metrics_text, so one scrape handler concatenates
        both."""
        lines: List[str] = []
        helps = {"requests": "requests observed (all terminal statuses)",
                 "completed": "requests finished successfully",
                 "rejected": "requests refused at submit "
                             "(queue full / shape / draining)",
                 "overloaded": "requests shed at the queue high-watermark "
                               "(subset of rejected)",
                 "timeout": "requests expired in queue past their deadline",
                 "errors": "requests lost to an engine exception "
                           "mid-batch",
                 "tokens_in": "prompt tokens admitted",
                 "tokens_out": "tokens generated (up to and incl. EOS)",
                 "items": "batch rows processed by profiled predictor "
                          "calls",
                 "batches": "micro-batches executed",
                 "prefix_hit": "admissions that mapped >= 1 cached "
                               "prefix block",
                 "prefix_miss": "admissions that found no cached prefix",
                 "prefill_tokens_saved": "prompt tokens whose prefill "
                                         "was skipped (KV already "
                                         "pooled)",
                 "spec_windows": "speculative verify windows run "
                                 "(drafted rows only)",
                 "spec_proposed": "draft tokens proposed to the target "
                                  "model",
                 "spec_accepted": "draft tokens the target accepted "
                                  "(longest matching prefix)",
                 "spec_drafts_trie": "verify windows whose draft came "
                                     "from the prefix-trie prompt "
                                     "lookup",
                 "spec_drafts_model": "verify windows whose draft came "
                                      "from the draft-model hook",
                 "mem_pressure_episodes": "admission stalls waiting on "
                                          "KV blocks (one per episode, "
                                          "not per step)",
                 "decode_chunks": "paged decode chunks launched",
                 "decode_chunks_overlapped": "paged decode chunks launched "
                                             "while an earlier chunk's "
                                             "tokens were still unread",
                 "eos_late_rows": "rows of a decode chunk launched after "
                                  "their request's EOS and before the "
                                  "host read it (tokens discarded)",
                 "decode_rows_idle": "rows of launched decode chunks that "
                                     "rode neutral (max_batch less the "
                                     "rows decoding): they attend nothing",
                 "host_gc_pauses": "collections of the host's garbage "
                                   "collector while the engine was open",
                 "host_gc_pause_ms": "milliseconds those collections took "
                                     "(each a serving/gc span)",
                 # expert layers (models that hold a share of a sparse
                 # layer's experts; absent otherwise)
                 "expert_assignments_here": "(token, expert) assignments "
                                            "computed by experts held "
                                            "here",
                 "expert_assignments_made": "(token, expert) assignments "
                                            "the routers made (top-k a "
                                            "live token and layer)",
                 "experts_hit": "held experts that got >= 1 token, "
                                "summed over expert-layer calls",
                 "expert_tokens_max": "tokens of the fullest held expert, "
                                      "summed over expert-layer calls",
                 "expert_layer_calls": "expert-layer calls (one a layer "
                                       "and model step)",
                 # block-sparse attention and recurrent state (models
                 # that have them; absent otherwise)
                 "sparse_rows": "decode row-steps of a sparse-attention "
                                "layer that selected their pages",
                 "dense_rows": "decode row-steps of a sparse-attention "
                               "layer short enough to attend every page",
                 "sparse_blocks_attended": "pages walked by selecting "
                                           "decode row-steps (all KV "
                                           "heads)",
                 "dense_blocks_attended": "pages walked by dense decode "
                                          "row-steps (all KV heads)",
                 "sparse_keys_scored": "compressed keys scored by "
                                       "selecting queries (prefill and "
                                       "decode)",
                 "state_rows_updated": "decode row-steps of a recurrent "
                                       "layer that updated their state",
                 "attn_pairs": "(query, token) pairs attended a "
                               "sparse or grouped attention layer over "
                               "page lists (prefill and decode)",
                 # state-space layers beside paged attention layers
                 "ssm_rows_updated": "decode row-steps of a state-space "
                                     "layer that moved their state",
                 "ssm_tokens_scanned": "live prefill tokens a state-space "
                                       "layer scanned",
                 "ssm_windows_scanned": "prefill windows a state-space "
                                        "layer scanned (a window and "
                                        "layer)",
                 "attn_pages_walked": "pages walked by the decode "
                                      "row-steps of a paged attention "
                                      "layer over page lists (all KV "
                                      "heads)",
                 "state_snapshots_taken": "recurrent-state snapshots "
                                          "saved beside a cached prefix",
                 "state_snapshots_restored": "admissions that restored a "
                                             "slot's state from a snapshot",
                 "state_snapshot_evictions": "snapshots dropped for a "
                                             "newer one or with their "
                                             "trie node",
                 "prefix_match_cut_tokens": "tokens of a prefix match "
                                            "given up for want of a "
                                            "state snapshot"}
        for name, value in self.counters.items():
            lines.extend(counter_lines(prefix, f"{name}_total", value,
                                       helps[name]))
        lines.extend(labeled_counter_lines(
            prefix, "programs_launched_total", "program",
            sorted(self.programs_launched.items()),
            "launches the engine handed to the chip, by the program's "
            "name on a device trace's XLA Modules line (less its jit_)"))
        ghelp = {"queue_depth": "requests waiting in the admission queue",
                 "inflight": "requests currently being served",
                 "batch_fill_ratio": "real rows / batch capacity of the "
                                     "last micro-batch",
                 "kv_occupancy": "live (attendable) KV rows / pooled "
                                 "capacity — true-token occupancy",
                 "kv_slots_occupancy": "allocation-granular KV rows "
                                       "(reserved blocks) "
                                       "/ pooled capacity",
                 "kv_shared_tokens": "logical KV rows served from "
                                     "shared prefix blocks (summed over "
                                     "requests)",
                 "state_slots_occupancy": "rows of the recurrent-state "
                                          "planes in use (live slots and "
                                          "snapshots) / rows held"}
        for name, value in self.gauges.items():
            lines.extend(gauge_lines(prefix, name, value, ghelp[name]))
        for name, help_ in self.HISTS:
            lines.extend(histogram_lines(prefix, name, self.hists[name],
                                         help_))
        return "\n".join(lines) + "\n"


# ----------------------------------------------------------------- engine

@dataclass
class ServingConfig:
    """Fixed-shape envelope of a ServingEngine. Everything that affects a
    compiled signature lives here — the engine NEVER recompiles to fit a
    request; requests that don't fit are rejected with a logged shape
    delta."""
    max_batch: int = 4              # batch slots (one request each)
    prompt_cap: int = 64            # longest prompt; longer = rejected
    max_new_tokens: int = 32        # per-request budget ceiling
    decode_chunk: Optional[int] = None  # tokens per post-first-token call;
    #                                 default max_new_tokens-1 = one chunk
    queue_capacity: int = 256       # bounded admission queue
    # load shedding (ISSUE 7 satellite): queue depth at/above this sheds
    # new requests with a structured "overloaded" rejection BEFORE the
    # queue hits capacity — the backpressure signal a frontend can act on
    # (retry elsewhere) while the engine still has headroom; None = shed
    # only at queue_capacity
    queue_high_watermark: Optional[int] = None
    deadline_s: Optional[float] = None  # default queue-wait deadline
    eos_token_id: Optional[int] = None
    pad_token_id: int = 0
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    weight_dtype: Optional[str] = None   # "int8" -> weight-only int8 GEMMs
    cache_dtype: Optional[str] = None    # "int8" -> int8 KV cache
    # --- paged KV pool (ISSUE 5): slot-level continuous batching ---
    # the padded engine this once switched away from is gone; the keyword
    # stays, as a value that can only be True, until the benchmark's
    # runners stop passing it (ROADMAP D2)
    paged: bool = True
    kv_block: int = 16              # KV rows per pool block
    kv_blocks: Optional[int] = None  # total pool blocks INCL. trash block;
    #                            default = worst case for max_batch rows
    # --- multi-chip tensor-parallel serving (ISSUE 16): shard the paged
    # pools' HEAD axis over an `mp` mesh of this many devices. The
    # executables run through the mpu tensor-parallel layers; block
    # tables, the allocator, refcounts and the radix trie stay host-side
    # and replicated. None/1 = single-chip (no mesh built). Requires
    # num_heads % shards == 0; greedy output is bit-identical across
    # shard counts (the per-shard invariant suite).
    shards: Optional[int] = None
    # --- prefix cache (ISSUE 10): radix-trie prefix reuse over the pool.
    # A full-block-aligned cached prefix maps shared (refcounted) blocks
    # straight into the new request's table — full hit skips prefill
    # entirely (TTFT = one decode step, COW on the last block), partial
    # hit prefills only the suffix.
    prefix_cache: bool = False
    prefix_cache_bytes: Optional[int] = None  # LRU eviction budget for
    #                            cached (refcount-free) blocks; None =
    #                            bounded by the pool itself (admission
    #                            reclaims cached blocks under pressure)
    # --- host-RAM spill tier (ISSUE 14): LRU-evicted full prefix blocks
    # serialize to pinned host arrays instead of vanishing; a later trie
    # hit rehydrates via ONE host→device copy — cached-prefix capacity
    # becomes host-memory-sized instead of HBM-sized. The value is the
    # host byte budget; None disables (eviction stays final).
    spill_host_bytes: Optional[int] = None
    # --- speculative decoding (ISSUE 11): draft-verify through the
    # ragged [B, k] multi-token paged-attention kernel. Each decode step
    # scores `spec_k` drafted tokens + the pending token in ONE
    # fixed-shape verify call; the longest-accepted-prefix rule keeps
    # greedy output bit-identical to the plain chain, and rows advance
    # 1..spec_k+1 tokens per launch. Requires greedy sampling
    # (temperature 0 — acceptance IS argmax equality).
    spec_decode: bool = False
    spec_k: int = 4                 # draft tokens per verify window
    # draft source: "trie" = prompt-lookup from the prefix radix trie (a
    # matched node's cached continuation tokens ARE the draft — requires
    # prefix_cache=True; finished requests' generated chains are cached
    # too, so repeated/agentic traffic drafts its own future. NOTE
    # drafts are BLOCK-granular: a finished chain contributes drafts
    # only once its generated tokens fill at least one pool block past
    # the prompt — keep kv_block below the typical generation length);
    # or a callable (context_tokens: np.ndarray, k: int) -> up-to-k
    # token ids (see `model_draft_fn` for the tiny-GPT adapter). A
    # callable composes with the trie: the trie drafts when it can, the
    # callable fills the misses.
    spec_draft: object = "trie"
    # --- chunked prefill (ISSUE 11 satellite): cap per-step prefill work
    # at [1, prefill_chunk] tokens so one long prompt never monopolizes
    # the engine for a whole prefill — offsets are DATA through the
    # start-form prefill executable (zero new executables per prompt
    # length). None = the whole prompt (or uncached suffix) in one
    # window.
    prefill_chunk: Optional[int] = None
    # --- recurrent state (a model whose geometry states `state_shapes`):
    # rows of the state planes kept for the prefix trie's snapshots, each
    # the state of every such layer where a cached prefix ends. Counted in
    # the pool's memory (`BlockPool.state_bytes`); unused by other models.
    state_snapshots: int = 8
    # --- static analysis (ISSUE 6): True / "error" / analysis.GraphLint —
    # the engine audits each of its {prefill, decode} executables with
    # the graph lint once, the first step it is built (findings
    # accumulate on engine.lint_findings; guard mode raises before the
    # steady-state loop proceeds)
    lint: object = None

    def __post_init__(self):
        from ..analysis.findings import ConfigValidationError, Finding
        if self.paged is not True:
            raise ValueError(
                f"paged={self.paged!r}: the padded engine was removed; "
                f"ServingEngine is the paged engine (drop the keyword)")
        if self.max_batch < 1 or self.prompt_cap < 1 \
                or self.max_new_tokens < 1:
            raise ValueError("max_batch, prompt_cap and max_new_tokens "
                             "must be >= 1")
        if self.decode_chunk is None:
            self.decode_chunk = max(1, self.max_new_tokens - 1)
        elif self.decode_chunk < 1:
            raise ValueError(f"decode_chunk must be >= 1, "
                             f"got {self.decode_chunk}")
        if self.queue_high_watermark is not None and \
                not (1 <= self.queue_high_watermark <= self.queue_capacity):
            raise ValueError(
                f"queue_high_watermark must be in [1, queue_capacity="
                f"{self.queue_capacity}], got {self.queue_high_watermark}")
        if self.shards is not None and self.shards < 1:
            raise ValueError(f"shards must be >= 1, got {self.shards}")
        if self.spill_host_bytes is not None and not self.prefix_cache:
            raise ValueError("spill_host_bytes requires prefix_cache="
                             "True (the spill tier holds EVICTED trie "
                             "blocks; without the trie nothing is ever "
                             "evicted into it)")
        if self.spec_decode:
            if not (1 <= self.spec_k <= 31):
                # the upper bound keeps the spec_accept_len histogram's
                # exact-integer buckets (bounds cover counts <= 32 =
                # spec_k + 1) honest; windows wider than that are far
                # past any useful acceptance length anyway
                raise ValueError(f"spec_k must be in [1, 31], "
                                 f"got {self.spec_k}")
            if self.temperature > 0.0:
                raise ValueError(
                    "spec_decode=True requires greedy sampling "
                    "(temperature=0): the bit-exact acceptance rule is "
                    "argmax equality; sampled speculative decoding needs "
                    "a rejection-sampling rule this engine does not "
                    "implement")
            if self.spec_draft == "trie":
                if not self.prefix_cache:
                    raise ValueError(
                        "spec_draft='trie' requires prefix_cache=True "
                        "(prompt-lookup drafts are the radix trie's "
                        "cached continuation tokens); pass a callable "
                        "spec_draft to use a draft model instead")
            elif not callable(self.spec_draft):
                raise ValueError(f"spec_draft must be 'trie' or a "
                                 f"callable (context, k) -> tokens; got "
                                 f"{self.spec_draft!r}")
        if self.prefill_chunk is not None and \
                not (1 <= self.prefill_chunk <= self.prompt_cap):
            raise ValueError(
                f"prefill_chunk must be in [1, prompt_cap="
                f"{self.prompt_cap}], got {self.prefill_chunk}")
        if self.cache_dtype not in (None, "int8"):
            # a structured config-validation finding (same schema as the
            # graph passes) so tools print WHY — ConfigValidationError is
            # a ValueError
            raise ConfigValidationError(Finding(
                "config", "paged_cache_dtype", "error",
                f"cache_dtype={self.cache_dtype!r} is not supported: the "
                f"pools carry the MODEL dtype or the int8 (codes, "
                f"factored-scale) form. Use cache_dtype='int8' (halves "
                f"resident KV) or cache_dtype=None",
                executable="ServingConfig",
                data={"cache_dtype": str(self.cache_dtype)}))
        if self.kv_block < 1:
            raise ValueError(f"kv_block must be >= 1, "
                             f"got {self.kv_block}")
        if self.kv_blocks is None:
            # worst case: every slot holds a cap prompt decoding its
            # full budget (+1 for the reserved trash block). Smaller
            # pools oversubscribe deliberately — admission then waits
            # on freed blocks.
            self.kv_blocks = self.max_batch * self.table_width + 1

    @property
    def row_kv_rows(self) -> int:
        """Worst-case KV rows one request can write: cap prompt + full
        budget, minus the never-written last sampled token."""
        return self.prompt_cap + self.max_new_tokens - 1

    @property
    def table_width(self) -> int:
        """Block-table columns per batch slot (worst-case blocks/row)."""
        return -(-self.row_kv_rows // self.kv_block)


class ServingEngine:
    """Continuous-batching serving loop over a paged KV block pool.

    Synchronous by design: `submit()` enqueues, `step()` admits, launches
    one round of model calls and reads the round before, `drain()` loops
    until the queue empties and every slot finished.
    The engine is NOT internally synchronized — submit/step touch shared
    state beyond the queue (request ids, metrics counters/gauges, the
    JSONL stream), so a frontend thread driving submit while a worker
    loops step() must hold one lock around every engine call. The calls
    are short on the submit side; step() blocks for one read.

    `clock` is injectable (tests drive deadlines deterministically).
    """

    def __init__(self, model, config: ServingConfig, *,
                 metrics: Optional[ServingMetrics] = None,
                 monitor: Optional[StepMonitor] = None,
                 chaos=None,
                 clock: Callable[[], float] = time.monotonic):
        self.model = model
        self.config = config
        # a model family that implements only part of the engine's surface
        # (models/pangu_moe.py) refuses the rest here
        if hasattr(model, "check_serving_config"):
            model.check_serving_config(config)
        self.metrics = metrics or ServingMetrics()
        # counters the model's own steps report (expert routing): they
        # arrive with each decode chunk and finished prefill
        for name in getattr(model, "step_counter_names", ()):
            self.metrics.counters.setdefault(name, 0)
        # fault injection (ISSUE 12 Injector): fired at serving.step so
        # the OOM post-mortem path is rehearsable without a real OOM
        self.chaos = chaos
        # HBM ledger (ISSUE 18): attach_memory_ledger wires the pool /
        # prefix-cache / spill owners; None = unattributed engine
        self._memz = None
        self._mem_pressure_t0 = None   # oversubscription-wait episode
        # active probing (ISSUE 19): serve_telemetry wires a Prober /
        # InvariantAuditor here; the config fingerprint is cached (env
        # and versions are process-stable)
        self._prober = None
        self._invariants = None
        self._fingerprint = None
        # the monitor carries batch step timing + the recompile guard; the
        # serving engine measures dispatch-to-sync walls (truthful: every
        # step ends in a host sync for the token handoff)
        self.monitor = monitor or StepMonitor(unit="tokens/s",
                                              track_memory=False)
        self.clock = clock
        from ..analysis import GraphLint
        from ..analysis.recompile import abstract_signature
        # graph lint (ISSUE 6): audit the engine's {prefill, decode}
        # executables right after the warmup batch builds them
        self._lint = GraphLint.coerce(config.lint)
        self._lint_seen = set()   # executables already audited
        self.lint_findings = None
        # the abstract batch signature the engine's executables key on —
        # the "old" side of the preflight recompile differ
        self._engine_abstract = abstract_signature(
            jax.ShapeDtypeStruct((config.max_batch, config.prompt_cap),
                                 np.int64),
            jax.ShapeDtypeStruct((config.max_batch,), np.int32))
        self._queue: deque = deque()
        self._draining = False     # graceful drain: stop admitting
        self._next_id = 0
        self._batch_id = 0
        self._t_start = self.clock()    # statusz uptime anchor
        # trace ids are unique across engine incarnations: a fleet's
        # collectors merge many replicas' JSONL/tracez streams, where a
        # bare per-engine request counter would collide instantly
        self._run_id = uuid.uuid4().hex[:8]
        self._rejected_shapes = set()   # shape-delta warned once per shape
        # the engine's one-and-only batch signature (leaves shaped like
        # StepMonitor.record_compile expects for shape_delta rendering)
        self._shape_sig = (((config.max_batch, config.prompt_cap), "int64"),
                           ((config.max_batch,), "int32"))
        self._spill = None     # host spill tier (prefix_cache + spill)
        # multi-chip serving (ISSUE 16): a private mp mesh over the first
        # `shards` devices. The engine activates it around pool creation
        # and every step — NOT globally — so interleaved engines at
        # different shard counts (the bit-identity suite, the bench's
        # single-chip twin) never see each other's mesh.
        self._mesh = None
        if (config.shards or 1) > 1:
            from ..distributed import mesh as _dist_mesh
            shards = int(config.shards)
            devs = jax.devices()
            if len(devs) < shards:
                raise ValueError(
                    f"shards={shards} needs {shards} devices, have "
                    f"{len(devs)} (CPU hosts: set "
                    f"--xla_force_host_platform_device_count)")
            nh = model.config.num_heads
            if nh % shards != 0:
                raise ValueError(
                    f"shards={shards} must divide num_heads={nh} (pools "
                    f"shard the head axis)")
            self._mesh = _dist_mesh.build_mesh({"mp": shards},
                                               devs[:shards])
        # slot-level continuous batching over a paged block pool: each
        # batch slot runs its own request; EOS/budget frees the slot's
        # blocks immediately and _admit_paged splices a queued request
        # into the vacancy mid-flight. Device state is the donated
        # per-layer pools; tables/lens are tiny host vectors edited
        # per slot and shipped with every chunk; pending/done are the
        # host's only for rows it has read (`_src`).
        from .kv_cache import BlockPool
        B, MB = config.max_batch, config.table_width
        self._pool = BlockPool.for_model(model,
                                         num_blocks=config.kv_blocks,
                                         block_size=config.kv_block,
                                         cache_dtype=config.cache_dtype,
                                         state_rows=B,
                                         snapshot_rows=config.state_snapshots)
        if self._pool.has_state:
            # engine-side counters of the state planes (the model's own
            # per-call counters arrive through step_counter_names)
            for name in _STATE_COUNTERS:
                self.metrics.counters.setdefault(name, 0)
            self.metrics.gauges.setdefault("state_slots_occupancy", None)
        with self._mesh_scope():
            self._pools = self._pool.make_pools()
        self._slots: List[Optional[Request]] = [None] * B
        self._tables = np.zeros((B, MB), np.int32)
        self._lens = np.zeros((B,), np.int32)
        self._pending = np.zeros((B,), np.int32)
        self._done = np.ones((B,), bool)
        self._calls = 0            # PRNG stream cursor (sampling mode)
        self._paged_seen = set()   # executables already compiled
        self._kv_snapshot = (0, 0, 0)  # (physical live tokens, slot
        #                      rows, logical shared tokens) at the
        #                      last step's decode entry
        # prefix cache (ISSUE 10): per-slot count of lens tokens that
        # live in blocks the request mapped SHARED from the trie —
        # the kv_shared_tokens gauge and the hit bookkeeping
        self._shared_tok = np.zeros((B,), np.int64)
        self._prefix = None
        if config.prefix_cache:
            from .prefix_cache import PrefixCache
            self._prefix = PrefixCache(
                self._pool, byte_budget=config.prefix_cache_bytes)
            if config.spill_host_bytes is not None:
                # host-RAM spill tier (ISSUE 14): the cache owns the
                # trie mechanics; the engine owns the device pools,
                # so both transfer directions are closures over it
                from .kv_cache import HostSpillTier
                self._spill = HostSpillTier(
                    bytes_per_block=self._pool.bytes_per_block,
                    byte_budget=config.spill_host_bytes)
                self._prefix.attach_spill(
                    self._spill,
                    reader=lambda blk: self._pool.read_block(
                        self._pools, blk),
                    writer=self._spill_write)
        # next prompt position to prefill per slot (one window of
        # prefill_chunk tokens a step, or the whole uncached suffix
        # at once); -1 = not in prefill (a decode row)
        self._prefill_pos = np.full((B,), -1, np.int64)
        # where each row's pending token and done flag live when the
        # next chunk is launched: on the host (_pending / _done), in
        # the last launched chunk's outputs, or in the first-token
        # vector a final prefill window wrote (`_stage_decode_inputs`)
        self._src = np.full((B,), _SRC_HOST, np.int32)
        self._flight: Optional[_Flight] = None   # launched, unread
        self._reset_device_carry()
        # spec decoding (ISSUE 11): the optional draft-model hook —
        # the trie (when present) drafts first, the hook fills misses
        self._draft_fn = config.spec_draft \
            if callable(config.spec_draft) else None
        _gc_watch.add(self.metrics)

    def close(self):
        """Stop counting the host's collections for this engine; the
        last open engine's `close()` takes the process's `gc.callbacks`
        hook out. Serving after it works, uncounted."""
        _gc_watch.discard(self.metrics)

    def _reset_device_carry(self):
        """Placeholders for what a launch reads from the launch before
        it, in the shapes and dtypes the real outputs have, so the first
        chunk runs the executables every later chunk runs."""
        B, c = self.config.max_batch, self.config.decode_chunk
        with self._mesh_scope():
            self._toks_prev = jnp.zeros((B, c), jnp.int64)
            self._done_prev = jnp.zeros((B,), bool)
            self._firsts = jnp.zeros((B,), jnp.int32)

    def _mesh_scope(self):
        """Activate the engine's private mp mesh (multi-chip serving) for
        the duration of a step — a no-op nullcontext on single-chip
        engines. Every compiled-signature component that depends on the
        shard count reads `mesh_axis_size("mp")` under this scope, so
        engines at different shard counts never collide in the compiled-
        runner caches."""
        import contextlib
        if self._mesh is None:
            return contextlib.nullcontext()
        from ..distributed import mesh as _dist_mesh
        return _dist_mesh.mesh_scope(self._mesh)

    # -- admission ------------------------------------------------------
    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def busy(self) -> bool:
        """Work remains: queued requests, or live batch slots still
        decoding, or a launched chunk whose tokens the host has not read —
        the public loop condition drain() and external replayers share."""
        # host-side deque/slot-list reads  # lint: allow(tracer-bool)
        return bool(self._queue) or bool(self._live()) \
            or self._flight is not None  # lint: allow(tracer-bool)

    def preflight(self, prompt, max_new_tokens: Optional[int] = None):
        """Static admission check (analysis.recompile): Findings for
        everything about this request that would force a new executable
        or is statically unservable — BEFORE any tracing happens. Empty
        findings = admissible (dynamic conditions like queue capacity
        are submit()'s business). `submit` rejects through this, so the
        refusal reason and the would-be recompile explanation come from
        the same differ the lint suite uses."""
        from ..analysis.findings import Finding, Findings
        from ..analysis.recompile import (abstract_signature,
                                          diff_signatures)
        cfg = self.config
        p = np.asarray(prompt, dtype=np.int64).reshape(-1)  # lint: allow(tracer-asarray)
        want = cfg.max_new_tokens if max_new_tokens is None \
            else min(int(max_new_tokens), cfg.max_new_tokens)
        out = Findings()
        if want < 1:
            out.add(Finding(
                "config", "max_new_tokens", "error",
                f"token budget {want} < 1 is unservable (the caller "
                f"asked to pay for nothing)", executable="serving"))
        plen = int(p.shape[0])
        if plen < 1 or plen > cfg.prompt_cap:
            # ShapeDtypeStructs, not real arrays: the rejection path must
            # not allocate a [max_batch, plen] buffer for an oversized
            # prompt just to describe its shape
            req_sig = abstract_signature(
                jax.ShapeDtypeStruct((cfg.max_batch, plen), np.int64),
                jax.ShapeDtypeStruct((cfg.max_batch,), np.int32))
            diffs = diff_signatures(
                self._engine_abstract, req_sig,
                executable="serving_batch",
                names=("input_ids", "prompt_lens"))
            why = "; ".join(f.message for f in diffs) \
                or f"prompt length {plen} outside [1, {cfg.prompt_cap}]"
            out.add(Finding(
                "recompile_hazard", "prompt_shape", "error",
                f"prompt length {plen} would force a new prefill "
                f"executable: {why}", executable="serving_batch",
                data={"prompt_len": plen, "cap": cfg.prompt_cap}))
        if plen >= 1 and want >= 1 \
                and not self._pool.fits_ever(plen + want - 1):
            msg = (f"request needs {plen + want - 1} KV rows — more than "
                   f"the whole pool holds even fully drained")
            data = {"rows": plen + want - 1}
            if self._memz is not None:
                # the ledger's census answers the operator's next question
                # ("who do I evict to make room?") inside the reject itself
                top = self._memz.top_owners(3)
                if top:
                    data["top_owners"] = top
                    msg += "; top HBM owners: " + ", ".join(
                        f"{t['owner']}={t['bytes']}B" for t in top)
            out.add(Finding(
                "config", "kv_oom", "error", msg,
                executable="serving", data=data))
        return out

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               deadline_s: Optional[float] = None,
               enqueue_at: Optional[float] = None,
               probe: bool = False) -> Request:
        """Admit one prompt into the bounded queue.

        Returns the Request; check `.status` — "queued" on success,
        "rejected" (queue full, or a shape the engine's executables cannot
        serve) otherwise. `enqueue_at` backdates the enqueue span for
        open-loop replay: queue-wait/TTFT are then
        measured from the request's SCHEDULED arrival, not from when the
        single-threaded replayer got around to calling submit. Backdating
        only — a future timestamp clamps to now (a request cannot be
        served before it arrives; negative queue waits would corrupt the
        accounting this engine exists to make honest)."""
        cfg = self.config
        prompt = np.asarray(prompt, dtype=np.int64).reshape(-1)  # lint: allow(tracer-asarray)
        want = cfg.max_new_tokens if max_new_tokens is None \
            else min(int(max_new_tokens), cfg.max_new_tokens)
        # probe tag stamped BEFORE any rejection path (ISSUE 19): a probe
        # shed here (draining/overload/queue_full) lands in the probe
        # families, never in the user-facing rejection counters
        req = Request(id=self._next_id, prompt=prompt,
                      max_new_tokens=want, probe=probe,
                      deadline_s=cfg.deadline_s if deadline_s is None
                      else deadline_s)
        self._next_id += 1
        req.trace.trace_id = f"{self._run_id}-{req.id}"
        now = self.clock()
        req.trace.t_enqueue = now if enqueue_at is None \
            else min(enqueue_at, now)
        # static admission: the recompile-hazard differ decides BEFORE any
        # tracing whether this request fits the engine's one executable
        # set (preflight's findings carry the exact would-be shape delta).
        # A "prompt_shape" refusal additionally logs through the r7
        # recompile channel — count=False keeps the compiles/recompiles
        # COUNTERS a pure signal of real executable churn (nothing was
        # built — the request was refused precisely so nothing would be);
        # each offending shape WARNS once per engine, abusive traffic must
        # not spam the recompile log/event stream. Every refusal still
        # counts in rejected_total and gets its per-request JSONL record:
        # the request stream is the audit log, deliberately complete.
        # A "kv_oom" refusal means the pool could not hold the request
        # even fully drained — anything smaller is ADMITTABLE (it waits
        # for freed blocks at worst; no bucket-mismatch rejection inside
        # the cap).
        # graceful drain (ISSUE 7): a draining engine finishes what it has
        # and admits nothing — the structured refusal tells the frontend
        # to route elsewhere, not to retry here
        if self._draining:
            req.status, req.reason = "rejected", "draining"
            req.retriable = _REJECT_RETRIABLE["draining"]
            self.metrics.record_request(req)
            return req
        pf = self.preflight(prompt, want)
        if pf:
            finding = pf[0]
            req.status, req.reason = "rejected", finding.code
            req.retriable = _REJECT_RETRIABLE.get(finding.code, False)
            if finding.code == "prompt_shape":
                plen = int(prompt.shape[0])
                if plen not in self._rejected_shapes:
                    self._rejected_shapes.add(plen)
                    self.monitor.record_compile(
                        "serving_reject",
                        (((cfg.max_batch, plen), "int64"),
                         self._shape_sig[1]),
                        prev_sig=self._shape_sig, count=False)
            self.metrics.record_request(req)
            return req
        # load shedding: at the high-watermark the engine is still alive
        # but past its SLO-holding depth — shed with a reason the metrics
        # count separately (overloaded_total is the autoscaler signal;
        # queue_full means the hard cap, i.e. shedding came too late)
        if cfg.queue_high_watermark is not None and \
                len(self._queue) >= cfg.queue_high_watermark:
            req.status, req.reason = "rejected", "overloaded"
            req.retriable = _REJECT_RETRIABLE["overloaded"]
            self.metrics.record_request(req)
            return req
        if len(self._queue) >= cfg.queue_capacity:
            req.status, req.reason = "rejected", "queue_full"
            req.retriable = _REJECT_RETRIABLE["queue_full"]
            self.metrics.record_request(req)
            return req
        self._queue.append(req)
        self.metrics.gauges["queue_depth"] = len(self._queue)
        return req

    # -- the batch loop -------------------------------------------------
    def step(self) -> List[Request]:
        """Run ONE engine step (`_step_paged`: admit, launch, land);
        returns every request that reached a terminal status this step —
        served rows AND queue-deadline timeouts (excluding expired traffic
        from the results would hide exactly the overload signal the
        metrics exist for).

        If a call dies mid-flight (device OOM, interrupt), the requests in
        the slots are recorded as status="error" before the exception
        propagates — an accounting layer must not lose in-flight requests.

        With `ServingConfig(lint=...)`, every step runs under
        `analysis.lint_capture` and each executable the engine builds is
        audited by GraphLint ONCE, the first step it appears — covering
        the whole {prefill, decode} set even when early traffic finishes
        at prefill (budget-1 / instant-EOS) and decode only compiles
        later. Findings accumulate on `self.lint_findings` (stored BEFORE
        the guard fires, so a caller catching GraphLintError can still
        read them); a guard-mode lint raises as soon as an audited
        executable violates — after that batch was served, since the
        program must exist to be lowered."""
        with self._mesh_scope():
            return self._step_inner()

    def _step_inner(self) -> List[Request]:
        if self._lint is None:
            with _span("serving/step"):
                return self._step_paged()
        from ..analysis import lint_capture
        from ..analysis.findings import Findings
        from ..analysis.lint import _kind_name
        with lint_capture() as calls, _span("serving/step"):
            out = self._step_paged()
        new = [c for c in calls
               if (id(c[1]), _kind_name(c[0])) not in self._lint_seen]
        if new:
            for kind, fn, _ in new:
                self._lint_seen.add((id(fn), _kind_name(kind)))
            if self.lint_findings is None:
                self.lint_findings = Findings()
            fs = self._lint.check_calls(new, guard=False)
            self.lint_findings.extend(fs)
            self._lint._guard(fs, "serving executables")
        return out

    # ------------------------------------------- slot-level batching loop
    def _live(self) -> List[int]:
        return [i for i, r in enumerate(self._slots) if r is not None]

    def _step_paged(self) -> List[Request]:
        """One engine step: admit, launch, land.

        Queued requests are spliced into free slots; every slot in
        prefill gets its next window and the decodable rows one decode
        chunk, all enqueued without a read; only then the host reads
        what the step BEFORE launched (`_land`), while the chip runs what
        this one did. A chunk's inputs never wait for the host: lengths,
        budgets and blocks are known at launch, and each row's pending
        token and done flag are picked on the device from the previous
        chunk's outputs (`_stage_decode_inputs`). So a request's tokens
        reach the host one step after their chunk was launched, a row's
        EOS is seen one chunk late (it rides that chunk as a done row,
        `eos_late_rows`), and a budget's end, known beforehand, costs
        nothing. A speculative engine reads each call before the next:
        the accepted count sets a row's next length.

        Executable set = {prefill [1, cap or chunk], decode [B, c], two
        small helpers} — each compiles once, so a steady mixed-length
        loop adds zero jit cache misses however requests arrive."""
        miss0 = _jit_cache_misses()
        ran = set()
        self.monitor.begin_step()
        out_tokens = 0
        live_entry: List[int] = []
        # spill/rehydrate device calls ride admission (match/evict): tag
        # them into `ran` so their one-time compiles are warmup, not
        # shape churn, in the recompile accounting below
        spill0 = (self._spill.spilled_total, self._spill.rehydrated_total) \
            if self._spill is not None else (0, 0)
        try:
            if self.chaos is not None:
                # rehearsal seam for the OOM forensics path: an injected
                # AllocFailure raises here exactly like a device
                # RESOURCE_EXHAUSTED unwinding out of the chunk call
                self.chaos.fire("serving.step", step=self._batch_id,
                                queue_depth=len(self._queue))
            expired = self._admit_paged(ran)
            flight = _Flight()
            self._launch_prefills(flight, ran)
            if self.config.spec_decode:
                finished, _ = self._land(flight)
                live_entry = self._decodable()
                if live_entry:
                    chunk_done, out_tokens = self._decode_chunk_spec(
                        live_entry, ran)
                    finished.extend(chunk_done)
            else:
                live_entry = self._decodable()
                if live_entry:
                    with _span("serving/decode_prep"):
                        self._snapshot_kv()
                        staged = self._stage_decode_inputs(live_entry)
                with _span("serving/decode"):
                    if live_entry:
                        self._launch_decode(flight, live_entry, staged)
                        ran.add("decode")
                    prev, self._flight = \
                        self._flight, self._close_flight(flight)
                    landed = self._read_flight(prev)
                finished, out_tokens = self._deliver(prev, landed)
            if ran and not live_entry:
                # a step without a chunk (admission, prefill windows)
                # still reports the pool as it left it
                self._snapshot_kv()
        except BaseException as step_exc:
            # OOM forensics (ISSUE 18): dump the census BEFORE the
            # recovery below resets the pool — the artifact must show the
            # occupancy that failed, not the post-reset emptiness
            if self._memz is not None:
                from ..obs.memz import looks_like_oom
                if looks_like_oom(step_exc):
                    inflight = [
                        {"id": r.id, "prompt_len": len(r.prompt),
                         "n_out": r.n_out}
                        for r in self._slots if r is not None]
                    self._memz.post_mortem(
                        error=step_exc,
                        context={"site": "serving.step",
                                 "batch_id": self._batch_id,
                                 "queue_depth": len(self._queue),
                                 "inflight": inflight})
            now = self.clock()
            for i, r in enumerate(self._slots):
                if r is not None:
                    r.status, r.reason = "error", "engine_exception"
                    r.trace.t_finish = now
                    self.metrics.record_request(r)
                    self._slots[i] = None
                    self._pool.free(r.id)
                    self._clear_slot(i)
            # the failed call may have CONSUMED the donated pools — rebuild
            # so the engine stays usable.
            # pool.reset() wiped the refcounts, so the prefix cache's
            # entries point at reissued blocks: drop them WITHOUT deref
            if self._prefix is not None:
                self._prefix.clear(release=False)
            self._pool.reset()
            self._pools = self._pool.make_pools()
            # what was launched and not read belongs to the failed slots
            self._flight = None
            self._reset_device_carry()
            self.metrics.gauges["inflight"] = 0
            self.monitor.end_step(items=0)
            raise
        with _span("serving/bookkeep"):
            self.metrics.gauges["inflight"] = len(self._live())
            if self._pool.has_state:
                held = 0 if self._prefix is None \
                    else self._prefix.snapshots_held
                self.metrics.gauges["state_slots_occupancy"] = \
                    (len(self._live()) + held) / (
                        self._pool.state_rows + self._pool.snapshot_rows)
            if ran:
                # gauges describe the step's micro-batch: fill = rows of
                # the chunk it launched (a step without one: the requests
                # its landing finished, budget-1 / instant-EOS traffic);
                # occupancy is snapshotted at chunk entry too — the state
                # the step actually served, not the post-free emptiness
                n_real = len(live_entry) if live_entry else \
                    min(len(finished), len(self._slots))
                kv_tokens, kv_slots, kv_shared = self._kv_snapshot
                self.metrics.record_batch(
                    n_real=n_real, capacity=len(self._slots),
                    kv_tokens=kv_tokens, kv_slots=kv_slots,
                    kv_capacity=self._pool.capacity_tokens,
                    queue_depth=len(self._queue),
                    kv_shared_tokens=kv_shared)
            if self._spill is not None:
                if self._spill.spilled_total > spill0[0]:
                    ran.add("spill")
                if self._spill.rehydrated_total > spill0[1]:
                    ran.add("rehydrate")
            # compile accounting BEFORE closing the step, so the monitor
            # marks this record `compiled` and keeps it out of the steady-
            # state median. Warmth is per executable, not per engine: a
            # request that ends at its first token leaves decode uncompiled,
            # and its eventual first compile is not shape churn. A miss
            # while every executable this step ran was already seen is:
            # log it as a recompile through the r7 detector
            dm = _jit_cache_misses() - miss0
            if dm:
                self.monitor.record_compile(
                    "serving_batch", (("jit_cache_misses", dm),),
                    prev_sig=(("jit_cache_misses", 0),)
                    if ran and ran <= self._paged_seen else None)
            self._paged_seen |= ran
            self.monitor.end_step(items=out_tokens)
        return expired + finished

    def _clear_slot(self, slot: int):
        self._tables[slot] = 0         # trash block: writes go nowhere
        self._lens[slot] = 0
        self._pending[slot] = 0
        self._done[slot] = True
        self._src[slot] = _SRC_HOST
        self._shared_tok[slot] = 0
        self._prefill_pos[slot] = -1

    def _decodable(self) -> List[int]:
        """The next decode chunk's rows: live slots whose prefill has
        been launched to its end and whose budget the chunks launched so
        far do not exhaust. The others ride as neutral rows
        (`_stage_decode_inputs`): a row in prefill, and a row whose last
        tokens are launched and not read yet."""
        return [i for i in self._live() if self._prefill_pos[i] < 0
                and self._slots[i]._launched < self._slots[i].max_new_tokens]

    def _count_launch(self, program: str):
        launched = self.metrics.programs_launched
        launched[program] = launched.get(program, 0) + 1

    def _device_helper(self, name: str, program: str, build):
        """One of the engine's two small fixed-shape programs, kept in
        the model's compiled-runner cache like the model's own (a build
        counts as a jit cache miss; the graph lint sees the call). The
        fetch is the launch: it is counted under `program`."""
        from ..distributed import mesh as _dist_mesh
        cfg = self.config
        sig = (name, cfg.max_batch, cfg.decode_chunk, cfg.eos_token_id,
               _dist_mesh.mesh_axis_size("mp"))
        self._count_launch(program)
        return self.model._gen_cache_get(
            sig, lambda: named_program(build, program))

    def _stage_decode_inputs(self, live: List[int]):
        """(tables, lens, pending, done) of a decode or verify call over
        the rows `live`; every other row is neutral (trash table row,
        done), so the fixed-[B] call can neither write into nor attend
        the blocks of a slot in prefill or of one waiting for its last
        read. Tables and lengths are the host's, copied, so later slot
        edits cannot reach a call still in flight. `pending` and `done`
        are picked ON THE DEVICE, row by row: from the last launched
        chunk's last column and done flags (a row that rode it: the host
        has not read them yet), from the first token a final prefill
        window wrote (done if it is EOS), or from the host's own values
        (a zero-prefill admission, a row the host has read). Nothing
        here waits for the chip."""
        eos = self.config.eos_token_id
        ride = np.zeros((len(self._slots),), bool)
        ride[live] = True
        tables = np.where(ride[:, None], self._tables, 0)
        lens = np.where(ride, self._lens, 0)
        src = np.where(ride, self._src, _SRC_HOST)
        pending_h = np.where(ride, self._pending, 0)
        done_h = np.where(ride, self._done, True)

        def stage(toks_prev, done_prev, firsts, src, pending_h, done_h):
            chunk, first = src == _SRC_CHUNK, src == _SRC_FIRST
            pending = jnp.where(chunk, toks_prev[:, -1].astype(jnp.int32),
                                jnp.where(first, firsts, pending_h))
            first_done = jnp.zeros_like(done_h) if eos is None \
                else firsts == eos
            done = jnp.where(chunk, done_prev,
                             jnp.where(first, first_done, done_h))
            return pending, done

        pending, done = self._device_helper(
            "paged_stage", STAGE_PROGRAM, stage)(
            self._toks_prev, self._done_prev, self._firsts, src,
            pending_h, done_h)
        return tables, lens, pending, done

    def _kv_physical(self):
        """(physical live tokens, logical shared tokens) over live slots.

        Physical occupancy counts each DISTINCT block once (ISSUE 10
        satellite — summing per-slot lens would bill a shared prefix once
        per request): walk every live slot's owned blocks in position
        order, credit each block its live rows, and take the max where
        two slots map the same block (shared prefix blocks are full, so
        the max is just bs). Logical shared tokens = the per-slot
        shared-mapped volume summed — what the requests are READING out
        of blocks they did not allocate."""
        bs = self._pool.block_size
        rows: dict = {}
        shared = 0
        for s in self._live():
            ln = int(self._lens[s])
            shared += int(self._shared_tok[s])
            for j, blk in enumerate(self._pool.owned(self._slots[s].id)):
                r = min(max(ln - j * bs, 0), bs)
                if r == 0:
                    break
                rows[blk] = max(rows.get(blk, 0), r)
        return sum(rows.values()), shared

    def _snapshot_kv(self):
        phys, shared = self._kv_physical()
        self._kv_snapshot = (
            phys, self._pool.used_blocks * self._pool.block_size, shared)

    def _insert_prefix(self, req: Request, blocks, written: int,
                       tokens=None):
        """Cache the request's blocks whose KV is WRITTEN — the full
        blocks among positions [0, written). The partial tail keeps
        taking decode writes and is never shared; a block whose rows are
        not on device yet (the zero-prefill pending position) must not
        be cached either. Shared runs dedup against their own nodes.
        `tokens` defaults to the prompt; the spec-decode finish path
        passes the prompt + generated chain (ISSUE 11) so later
        identical traffic can zero-prefill AND prompt-lookup-draft its
        continuation from these blocks' token keys."""
        if self._prefix is None:
            return
        bs = self._pool.block_size
        toks = req.prompt if tokens is None else tokens
        n_full = min(int(written), len(toks)) // bs
        if n_full:
            self._prefix.insert(toks[:n_full * bs], blocks[:n_full])

    def warmup_prefix_cache(self, vocab_size: int, *, seed: int = 2,
                            clear: bool = True):
        """Compile the prefix-cache executable set before measuring: a
        full-prefill miss, an identical block-aligned repeat (the COW
        copy), and a mid-prefix divergence (suffix prefill), each run to
        completion so decode compiles too. With spec_decode the same
        choreography also lowers the verify executable — the repeated
        prompt's decode drafts the first run's cached chain from the
        trie — and with prefill_chunk the chunked-window executable
        replaces the one-shot prefill pair. `clear=True` then drops the
        warmup's cached prefixes so measured traffic starts cold.
        Steady-state zero-recompile assertions are only meaningful after
        this whole set has lowered."""
        if self._prefix is None:
            raise ValueError("warmup_prefix_cache needs "
                             "ServingConfig(prefix_cache=True)")
        bs = self.config.kv_block
        aligned = (self.config.prompt_cap // bs) * bs
        if aligned < max(bs, 2):
            raise ValueError(f"prompt_cap {self.config.prompt_cap} holds "
                             f"no full kv_block ({bs}); nothing to warm")
        rng = np.random.RandomState(seed)
        p = rng.randint(1, vocab_size, (aligned,)).astype(np.int64)
        for prompt in (p, p):        # miss, then aligned full hit (COW)
            self.submit(prompt)
            self.drain()
        if aligned > bs:             # partial hit -> suffix prefill
            d = p.copy()
            d[bs:] = rng.randint(1, vocab_size, (aligned - bs,))
            self.submit(d)
            self.drain()
        if self._spill is not None:
            # spill + rehydrate leg: force every cached block through
            # the host tier and back so the stacked d2h gather and the
            # donated h2d scatter executables lower during warmup too —
            # the zero-post-warmup-miss assertions cover them
            self._prefix.evict(self._prefix.cached_blocks)
            self.submit(p)
            self.drain()
        if clear:
            self._prefix.clear()
        return self

    def _spill_write(self, blk: int, payload):
        """Rehydrate one spilled payload into pool block `blk`: the ONE
        host→device copy (the stacked payload ships as a single jit
        input) through the pool's donated scatter executable — the
        engine re-binds its pools because the call consumed them."""
        self._count_launch(SPILL_PROGRAM)
        self._pools = self._pool.write_block(self._pools, blk, payload)

    def _cow_copy(self, src: int, dst: int):
        """Copy one pool block (every layer, K and V — codes AND scales
        in int8 mode) into a private block: the copy-on-write an aligned
        full-prefix hit needs before its re-decode of the last prompt
        token writes at position plen-1, INSIDE the last shared block.
        src/dst are data inputs of one tiny donated executable — steady
        COW traffic adds zero compilations."""
        import jax as _jax
        from ..distributed import mesh as _dist_mesh
        sig = ("paged_cow", self._pool.num_blocks, self._pool.block_size,
               self._pool.num_layers, str(self._pool.dtype),
               self._pool.cache_dtype, _dist_mesh.mesh_axis_size("mp"))

        def build():
            def run(pools, s, d):
                return _jax.tree_util.tree_map(
                    lambda p: p.at[d].set(p[s]), pools)
            return named_program(run, PAGE_COPY_PROGRAM,
                                 donate_argnums=(0,))

        fn = self.model._gen_cache_get(sig, build)
        self._count_launch(PAGE_COPY_PROGRAM)
        self._pools = fn(self._pools, np.int32(src), np.int32(dst))

    def _admit_paged(self, ran: set) -> List[Request]:
        """Fill every free slot from the queue: consult the prefix trie,
        map shared blocks / allocate fresh ones, set the slot up. No
        model call is made here: what the cache does not already hold is
        prefilled by `_launch_prefills`, this step and after. Returns
        the requests whose queue deadline expired; device calls made
        (copy-on-write) are tagged into `ran`.

        Prefix-cache admission (ISSUE 10) splits three ways on the
        matched full-block token count t vs the prompt length plen:

          t == 0           full prefill, exactly the ISSUE-5 path;
          0 < t < plen-1   partial hit: prefill ONLY the suffix (start=t
                           suffix-prefill executable — attends across
                           the shared prefix blocks);
          t >= plen-1      zero-prefill hit: every prompt position except
                           the last already has pooled KV. The last
                           token re-enters as the decode `pending` token
                           (lens = plen-1), so TTFT is ONE decode step
                           and prefill runs on 0 tokens. When t == plen
                           (block-aligned full hit) that re-decode would
                           write INTO the last shared block — it is
                           copy-on-write'd into a private block first;
                           shared blocks are never mutated.

        Every admitted prompt's full blocks are inserted into the trie
        once its last window is launched (dedup'd), so the NEXT identical
        prefix hits."""
        bs = self._pool.block_size
        expired: List[Request] = []
        free = [i for i, r in enumerate(self._slots) if r is None]
        while self._queue and free:
            with _span("serving/admit"):
                now = self.clock()
                req = self._queue[0]
                if req.deadline_s is not None and \
                        now - req.trace.t_enqueue > req.deadline_s:
                    self._queue.popleft()
                    req.status, req.reason = "timeout", "queue_deadline"
                    req.trace.t_finish = now
                    self.metrics.record_request(req)
                    expired.append(req)
                    continue
                plen = req.prompt_len
                need_rows = plen + req.max_new_tokens - 1
                snap = None
                if self._prefix is None:
                    matched, t = [], 0
                elif self._pool.has_state:
                    # pages are only reusable under a state that saw
                    # them: the match ends at the deepest snapshot, and
                    # before the prompt's last token (which is prefilled
                    # or re-decoded), so it never needs a copy-on-write
                    matched, t, snap, cut = self._prefix.match_state(
                        req.prompt, plen - 1)
                    self.metrics.counters["prefix_match_cut_tokens"] += cut
                else:
                    matched, t = self._prefix.match(req.prompt)
                # COW: an aligned full hit (t == plen) shares all matched
                # blocks EXCEPT the last, which is replaced by a private copy
                # (the re-decode write lands in it); otherwise the shared run
                # is the matched run and fresh blocks carry the suffix
                cow = t == plen and t > 0
                shared = matched[:-1] if cow else matched
                blocks = self._pool.alloc(req.id, need_rows, shared=shared)
                if blocks is None and self._prefix is not None:
                    # cached-but-idle prefixes are SOFT capacity: evict LRU
                    # refcount-free entries before deciding to wait —
                    # protecting the whole matched run (`shared` plus the
                    # COW source) from being reclaimed out from under this
                    # very admission
                    n_fresh = self._pool.blocks_needed(need_rows) - len(shared)
                    if self._prefix.reclaim(n_fresh, protect=matched):
                        blocks = self._pool.alloc(req.id, need_rows,
                                                  shared=shared)
                    if blocks is None and not self._live():
                        # nothing in flight will ever free blocks, so waiting
                        # cannot help: a request that fits the pool alone
                        # (preflight's fits_ever) must not starve on its own
                        # protected cached prefix — drop the hit, reclaim
                        # freely, full-prefill
                        matched, t, cow, shared, snap = [], 0, False, [], None
                        if self._prefix.reclaim(
                                self._pool.blocks_needed(need_rows)):
                            blocks = self._pool.alloc(req.id, need_rows)
                if blocks is None:
                    # oversubscription wait: queued head outsizes the free
                    # list. One structured row per EPISODE (ISSUE 18) — the
                    # enter transition carries the flight-recorder trigger
                    # key; steady-state waiting stays silent
                    self._mem_pressure_enter(req, need_rows)
                    break            # wait for live rows to free their blocks
                self._mem_pressure_exit()
                self._queue.popleft()
                slot = free.pop(0)
                req.status = "active"
                req.trace.t_admit = now
                req.trace.batch_id = self._batch_id
                # install into the slot BEFORE any device call: if one
                # dies mid-flight, _step_paged's handler finds the request
                # here and records it as status="error" — the engine's
                # in-flight accounting contract
                self._slots[slot] = req
                self._tables[slot] = self._pool.table_row(
                    req.id, self._tables.shape[1])
                self._shared_tok[slot] = len(shared) * bs
                if self._pool.has_state:
                    # the slot's state: the snapshot's, or none
                    self._pools = self._pool.state_move(
                        self._pools, STATE_ZERO if snap is None
                        else STATE_LOAD, slot, snap or 0)
                    self._count_launch(STATE_MOVE_PROGRAM)
                    ran.add("state_move")
                    if snap is not None:
                        self.metrics.counters["state_snapshots_restored"] += 1
                    req._state_from = t
                # tokens the host has read / tokens launched: the budget
                # is kept against the second, so a chunk is sized before
                # the one before it is read
                req._chunks = []
                req._produced = req._launched = 0
                # probe admissions (ISSUE 19) stay out of the cache-efficiency
                # counters: a prober's hit/miss variants are DESIGNED to
                # always hit / always miss, so counting them would turn the
                # fleet hit-rate and prefill-savings signals into artifacts
                # of the probe cadence
                if self._prefix is not None and not req.probe:
                    self.metrics.counters[
                        "prefix_hit" if t else "prefix_miss"] += 1
                if t >= plen - 1 and t > 0:
                    # zero-prefill admission: the whole prompt (minus the
                    # re-decoded last token) is served from cached blocks
                    if cow:
                        self._cow_copy(matched[-1], int(blocks[len(shared)]))
                        ran.add("cow")
                    self._lens[slot] = plen - 1
                    self._pending[slot] = int(req.prompt[plen - 1])
                    self._done[slot] = False
                    req.trace.t_prefill_done = now   # nothing to prefill
                    if not req.probe:
                        self.metrics.counters["prefill_tokens_saved"] += \
                            plen - 1
                    # re-stamp the matched chain; only positions < t hold
                    # written KV here (the pending re-decode hasn't run), so
                    # the insert must not cache any fresh block yet
                    self._insert_prefix(req, blocks, t)
                else:
                    # `_launch_prefills` takes it from position t: one
                    # [1, prefill_chunk] window a step (ISSUE 11 satellite:
                    # a cap-length prompt costs cap/chunk STEPS of bounded
                    # work and the decode batch keeps stepping between
                    # windows), or the whole suffix in one [1, cap] call.
                    # The slot's decode state stays neutral until the last
                    # window samples the first token.
                    self._prefill_pos[slot] = t
                    if t and not req.probe:
                        self.metrics.counters["prefill_tokens_saved"] += t
                self._batch_id += 1
        if not self._queue:
            # waiting head left some other way (deadline expiry, error
            # recovery draining the queue): close the episode truthfully
            self._mem_pressure_exit()
        self.metrics.gauges["queue_depth"] = len(self._queue)
        return expired

    def _launch_prefills(self, flight: _Flight, ran: set):
        """Enqueue the next prefill window of every slot in prefill; no
        result is read. With `prefill_chunk` a window is [1, chunk] tokens
        from the slot's offset (the offset is DATA through the start-form
        executable: ONE program serves every (offset, remainder) of every
        prompt length); without, the whole uncached suffix goes in one
        [1, prompt_cap] call. A slot's LAST window samples the request's
        first token: it stays on the device, written into the first-token
        vector the next chunk's `pending` is picked from, and is read
        with the flight (`_land`); intermediate windows' samples are
        never read at all. The row joins this step's decode chunk."""
        cfg = self.config
        pc = cfg.prefill_chunk
        for slot in self._live():
            off = int(self._prefill_pos[slot])
            if off < 0:
                continue
            req = self._slots[slot]
            plen = req.prompt_len
            width = cfg.prompt_cap if pc is None else pc
            clen = min(width, plen - off)
            final = off + clen >= plen
            ids = np.full((1, width), cfg.pad_token_id, dtype=np.int64)
            ids[0, :clen] = req.prompt[off:off + clen]
            # the one-shot forms keep their two executables (absolute
            # positions for a whole prompt, offset for a suffix)
            start = None if pc is None and off == 0 \
                else np.asarray([off], np.int32)  # lint: allow(tracer-asarray)
            name = "prefill_chunk" if pc is not None else \
                "prefill" if off == 0 else "suffix_prefill"
            t_pf0 = self.clock()
            kw = {}
            if self._pool.has_state:
                kw["state_slots"] = np.asarray([slot], np.int32)  # lint: allow(tracer-asarray)
                if final:
                    self._snapshot_state(slot, req, off)
            with _span("serving/prefill"):
                with _span("serving/prefill_launch"):
                    self._pools, first = self.model.prefill_paged(
                        ids, np.asarray([clen], np.int32),  # lint: allow(tracer-asarray)
                        self._pools, self._tables[slot][None].copy(),
                        temperature=cfg.temperature, top_k=cfg.top_k,
                        top_p=cfg.top_p, seed=cfg.seed + self._calls,
                        weight_dtype=cfg.weight_dtype,
                        cache_dtype=cfg.cache_dtype, start=start, **kw)
                    if final:
                        self._firsts = self._device_helper(
                            "paged_put_first", PUT_FIRST_PROGRAM,
                            lambda firsts, i, tok: firsts.at[i].set(tok[0])
                        )(self._firsts, np.int32(slot), first._data)
            self._count_launch(PREFILL_PROGRAM)
            self._calls += 1
            ran.add("prefix_prefill" if name == "suffix_prefill" else name)
            if not final:
                req.trace.events.append((name, t_pf0, self.clock()))
                self._prefill_pos[slot] = off + clen
                continue
            # every prompt row is written (enqueued): the slot is a decode
            # row from here, and its full blocks can be shared. Whoever
            # reads them does so in a later call on the same pools.
            self._prefill_pos[slot] = -1
            self._lens[slot] = plen
            self._src[slot] = _SRC_FIRST
            req._launched = 1
            self._insert_prefix(req, self._pool.owned(req.id), plen)
            flight.firsts.append((slot, req, first, name, t_pf0))

    def _snapshot_state(self, slot: int, req: Request, off: int):
        """Before a prompt's LAST prefill window is launched: the slot's
        state is the state after the `off` tokens the windows before it
        covered. Where that is a block boundary past what the trie
        matched, cache those blocks now and save the state beside them
        (one snapshot a prompt, at the longest prefix a later prompt can
        share whole windows of)."""
        bs = self._pool.block_size
        if self._prefix is None or off <= req._state_from or off % bs:
            return
        self._insert_prefix(req, self._pool.owned(req.id), off)
        evicted = self._prefix.snapshot_evictions
        row = self._prefix.snapshot(req.prompt, off)
        if row is not None:
            self._pools = self._pool.state_move(self._pools, STATE_SAVE,
                                                slot, row)
            self._count_launch(STATE_MOVE_PROGRAM)
            mt = self.metrics.counters
            mt["state_snapshots_taken"] += 1
            mt["state_snapshot_evictions"] += \
                self._prefix.snapshot_evictions - evicted

    def _launch_decode(self, flight: _Flight, live: List[int], staged):
        """Enqueue one fixed-shape decode chunk over the whole slot batch
        (rows outside `live` write the trash block and are ignored) and
        book what the host knows without reading it: every row's KV grew
        by the chunk, and `take` of its tokens count against the row's
        budget."""
        cfg = self.config
        c = cfg.decode_chunk
        tables, lens, pending, done = staged
        mt = self.metrics.counters
        mt["decode_chunks"] += 1
        mt["decode_rows_idle"] += cfg.max_batch - len(live)
        if self._flight is not None and self._flight.toks is not None:
            mt["decode_chunks_overlapped"] += 1
        flight.t0 = self.clock()
        with _span("serving/decode_launch"):
            flight.toks, self._pools, _, self._done_prev = \
                self.model.decode_paged(
                    self._pools, tables, lens, pending, done, c,
                    temperature=cfg.temperature, top_k=cfg.top_k,
                    top_p=cfg.top_p, seed=cfg.seed + self._calls,
                    eos_token_id=cfg.eos_token_id,
                    weight_dtype=cfg.weight_dtype,
                    cache_dtype=cfg.cache_dtype)
        self._count_launch(DECODE_PROGRAM)
        self._calls += 1
        self._toks_prev = flight.toks._data
        for slot in live:
            req = self._slots[slot]
            take = min(c, req.max_new_tokens - req._launched)
            req._launched += take
            flight.rows.append((slot, req, take))
            self._lens[slot] += c     # device wrote c rows regardless
            self._src[slot] = _SRC_CHUNK

    def _close_flight(self, flight: _Flight) -> Optional[_Flight]:
        """Seal what a step launched: None if nothing of it will be read
        (intermediate prefill windows only). The model's step counters of
        these calls are taken as the device array they are, and the
        copies to the host are started."""
        if not flight.firsts and flight.toks is None:
            return None
        detach = getattr(self.model, "detach_step_counters", None)
        if detach is not None:
            flight.stats = detach()
        for t in [f[2] for f in flight.firsts] + [flight.toks]:
            if t is not None:
                t._data.copy_to_host_async()
        return flight

    def _read_flight(self, flight: Optional[_Flight]):
        """Wait for a flight's results and copy them to the host: the
        first tokens of its final prefill windows, then its chunk's
        tokens (device order), each stamped with the time it arrived.
        Returns (first tokens, their time, chunk tokens or None, their
        time), or None for no flight."""
        if flight is None:
            return None
        firsts, arr = [], None
        if flight.firsts:
            with _span("serving/prefill_read"):
                firsts = [int(np.asarray(f[2].numpy())[0])  # lint: allow(tracer-asarray)
                          for f in flight.firsts]
        t_first = self.clock()
        if flight.toks is not None:
            with _span("serving/decode_read"):
                arr = np.asarray(flight.toks.numpy())  # lint: allow(tracer-asarray)
        t_chunk = self.clock()
        if flight.stats is not None:
            # a few floats beside tokens that were just read: no wait
            names = self.model.step_counter_names
            for name, value in zip(names, np.asarray(flight.stats).tolist()):  # lint: allow(tracer-asarray)
                self.metrics.counters[name] += value
        return firsts, t_first, arr, t_chunk

    def _deliver(self, flight: Optional[_Flight], landed):
        """Hand a read flight's tokens to their requests; finish + free
        every row that hit EOS or its budget. A row whose request ended
        at an earlier landing (its EOS was in the chunk before, or in its
        prefill's first token) rode this chunk as a done row: its tokens
        are dropped. Returns (finished, real tokens delivered)."""
        finished: List[Request] = []
        out_tokens = 0
        if landed is None:                 # no flight: nothing was read
            return finished, out_tokens
        cfg = self.config
        firsts, t_first, arr, t = landed
        with _span("serving/deliver"):
            for (slot, req, _, name, t_pf0), tok in zip(flight.firsts,
                                                        firsts):
                req.trace.events.append((name, t_pf0, t_first))
                if self._complete_prefill(slot, req, tok, t_first):
                    finished.append(req)
            for slot, req, take in flight.rows:
                if self._slots[slot] is not req:
                    if not req.probe:   # a probe's rows are not traffic
                        self.metrics.counters["eos_late_rows"] += 1
                    continue
                req.trace.events.append(("decode", flight.t0, t))
                fresh = arr[slot, :take]
                req._chunks.append(fresh)
                req._produced += take
                out_tokens += take
                if req.trace.t_first_token is None:
                    # zero-prefill admission (prefix cache): this chunk's
                    # first token IS the request's first token — TTFT was
                    # one decode step, measured not estimated
                    req.trace.t_first_token = t
                # EOS scan covers only the FRESH slice: earlier chunks were
                # checked when they landed (an EOS there already finished the
                # row), so the per-generation host cost stays O(n)
                if req._produced >= req.max_new_tokens or \
                        _hit_eos(fresh, cfg.eos_token_id):
                    self._finish_paged_row(slot, t)
                    finished.append(req)
        return finished, out_tokens

    def _land(self, flight: _Flight):
        """Read and deliver a flight at once: the serial order a
        speculative engine keeps."""
        flight = self._close_flight(flight)
        return self._deliver(flight, self._read_flight(flight))

    def _decode_chunk_paged(self, live: List[int]):
        """One plain decode chunk, launched and read at once (the
        speculative engine's step when no row has a draft). Returns
        (finished, real tokens)."""
        with _span("serving/decode_prep"):
            self._snapshot_kv()
            staged = self._stage_decode_inputs(live)
        flight = _Flight()
        with _span("serving/decode"):
            self._launch_decode(flight, live, staged)
            flight = self._close_flight(flight)
            landed = self._read_flight(flight)
        return self._deliver(flight, landed)

    def _complete_prefill(self, slot: int, req: Request, tok: int,
                          tp: float) -> bool:
        """A prefill's first token has reached the host: stamp it, and
        finish a budget-1 / instant-EOS request on the spot. Returns True
        when the request finished (the slot is free again)."""
        cfg = self.config
        req.trace.t_prefill_done = tp
        req.trace.t_first_token = tp  # sampled with the prefill
        req._chunks = [np.asarray([tok], np.int64)]  # lint: allow(tracer-asarray)
        req._produced = 1
        if req._produced >= req.max_new_tokens or \
                (cfg.eos_token_id is not None and tok == cfg.eos_token_id):
            self._finish_paged_row(slot, tp)
            return True
        return False

    def _draft_context(self, req: Request):
        """The slot's draft context — prompt plus every emitted token
        (the pending token INCLUDED, since drafts continue after it) —
        maintained INCREMENTALLY: chunks land once each, so per-window
        host cost is O(new tokens), not O(history) re-concatenation."""
        ctx = getattr(req, "_ctx", None)
        if ctx is None:
            ctx = req._ctx = [int(t) for t in req.prompt]
            req._ctx_chunks = 0
        for c in req._chunks[req._ctx_chunks:]:
            ctx.extend(int(t) for t in c)
        req._ctx_chunks = len(req._chunks)
        return ctx

    def _draft_for_slot(self, slot: int):
        """Up to spec_k draft tokens for the slot's next positions + the
        source tag ("trie" | "model" | None)."""
        cfg = self.config
        req = self._slots[slot]
        context = self._draft_context(req)
        if self._prefix is not None:
            d = self._prefix.lookup_continuation(context, cfg.spec_k)
            if d:
                return np.asarray(d, np.int32), "trie"  # lint: allow(tracer-asarray)
        if self._draft_fn is not None:
            d = np.asarray(self._draft_fn(context,  # lint: allow(tracer-asarray)
                                          cfg.spec_k)).reshape(-1)
            if d.size:
                return d[:cfg.spec_k].astype(np.int32), "model"
        return None, None

    def _decode_chunk_spec(self, live: List[int], ran: set):
        """One speculative verify window over the slot batch (ISSUE 11):
        a fixed-shape [B, spec_k + 1] call through model.verify_paged.
        Rows with a draft advance by their accepted length + 1; rows
        without one ride along on pad drafts and advance by >= 1 (a pad
        column that happens to match the chain is a REAL acceptance —
        every emitted token is argmax-correct by construction). Steps
        where NO row has a draft fall back to the plain decode chunk —
        both executables are in the warm set, so the per-step choice is
        host data, never a compile. Read before it returns: the accepted
        count sets each row's next length. Returns (finished, real
        tokens); the executable run is tagged into `ran`."""
        cfg = self.config
        B = len(self._slots)
        drafts = np.full((B, cfg.spec_k), cfg.pad_token_id, np.int32)
        src = {}
        for slot in live:
            d, tag = self._draft_for_slot(slot)
            if d is not None:
                drafts[slot, :len(d)] = d
                src[slot] = (tag, len(d))
        if not src:
            ran.add("decode")
            return self._decode_chunk_paged(live)
        ran.add("spec_verify")
        with _span("serving/decode_prep"):
            self._snapshot_kv()
            tables, lens, pending, done = self._stage_decode_inputs(live)
        t_c0 = self.clock()
        with _span("serving/decode"):
            with _span("serving/decode_launch"):
                toks, n_acc, self._pools, done_d = self.model.verify_paged(
                    self._pools, tables, lens, pending, drafts, done,
                    eos_token_id=cfg.eos_token_id,
                    weight_dtype=cfg.weight_dtype,
                    cache_dtype=cfg.cache_dtype)
            with _span("serving/decode_read"):
                arr = np.asarray(toks.numpy())      # host sync per window  # lint: allow(tracer-asarray)
                acc = np.asarray(n_acc)  # lint: allow(tracer-asarray)
        self._count_launch(VERIFY_PROGRAM)
        self._calls += 1
        t = self.clock()
        with _span("serving/deliver"):
            done_new = np.array(done_d)
            finished: List[Request] = []
            out_tokens = 0
            mt = self.metrics
            for slot in live:
                req = self._slots[slot]
                req.trace.events.append(("spec_verify", t_c0, t))
                n_emit = int(acc[slot]) + 1
                take = min(n_emit, req.max_new_tokens - req._produced)
                fresh = arr[slot, :take]
                req._chunks.append(fresh)
                req._produced += take
                out_tokens += take
                if req.trace.t_first_token is None:
                    # zero-prefill admission: this window's first token IS
                    # the request's first token
                    req.trace.t_first_token = t
                req._launched = req._produced
                self._lens[slot] += n_emit   # the accepted frontier
                self._pending[slot] = np.int32(arr[slot, n_emit - 1])
                self._done[slot] = bool(done_new[slot])  # lint: allow(tracer-bool)
                self._src[slot] = _SRC_HOST
                if slot in src:
                    # acceptance accounting covers DRAFTED rows only and
                    # REAL draft tokens only: a short trie draft's pad
                    # filler counts neither as proposed nor (if a pad
                    # accidentally matches) as accepted. A budget-truncated
                    # final window credits only the accepted drafts it
                    # actually EMITTED, so sum over windows ties out against
                    # speculative tokens out and the rate stays honest on
                    # short-budget / block-granular-draft traffic.
                    tag, dlen = src[slot]
                    used = min(int(acc[slot]), take, dlen)
                    req.spec_proposed += dlen
                    req.spec_accepted += used
                    if not req.probe:   # probe windows would skew the
                        #                 acceptance-rate signal (ISSUE 19)
                        mt.counters["spec_windows"] += 1
                        mt.counters["spec_proposed"] += dlen
                        mt.counters["spec_accepted"] += used
                        mt.counters["spec_drafts_trie" if tag == "trie"
                                    else "spec_drafts_model"] += 1
                        mt.hists["spec_accept_len"].observe(take)
                row_done = req._produced >= req.max_new_tokens or \
                    _hit_eos(fresh, cfg.eos_token_id)
                if row_done:
                    self._finish_paged_row(slot, t)
                    finished.append(req)
        return finished, out_tokens

    def _finish_paged_row(self, slot: int, t: float):
        """Terminal bookkeeping for one slot: blocks free IMMEDIATELY (the
        next _admit_paged can splice a queued request into this slot
        mid-flight — no waiting for the batch to drain)."""
        req = self._slots[slot]
        row = np.concatenate(req._chunks)[:req.max_new_tokens]
        req.tokens = row.astype(np.int64)
        req.n_out = _n_out(req.tokens, self.config.eos_token_id)
        req.status = "done"
        req.trace.t_finish = t
        if self.config.spec_decode and self._prefix is not None:
            # cache the WRITTEN chain (prompt + generated minus the
            # never-written last token), not just the prompt: the next
            # identical request then zero-prefills the whole history AND
            # prompt-lookup-drafts its continuation from these blocks'
            # token keys — the agentic/retry free lunch. Insert BEFORE
            # free: the trie's retain must land while the request still
            # holds its block references.
            chain = np.concatenate([req.prompt,
                                    req.tokens[:req.n_out]])
            self._insert_prefix(req, self._pool.owned(req.id),
                                req.prompt_len + req._produced - 1,
                                tokens=chain)
        self._pool.free(req.id)
        self._slots[slot] = None
        self._clear_slot(slot)
        self.metrics.record_request(req)

    @property
    def draining(self) -> bool:
        return self._draining

    def begin_drain(self):
        """Enter graceful-drain mode: submit() refuses new work with a
        structured "draining" rejection while queued + in-flight requests
        keep being served. The shutdown handshake of a preemptible
        serving fleet: SIGTERM → begin_drain() → drain(seal=True) →
        exit — in-flight users finish, the load balancer sees refusals
        and moves on."""
        self._draining = True
        return self

    def resume_admission(self):
        """Leave drain mode (a cancelled shutdown)."""
        self._draining = False
        return self

    def drain(self, max_batches: Optional[int] = None,
              seal: bool = False) -> List[Request]:
        """step() until the queue empties and every live slot finishes
        (or max_batches). `seal=True` is the graceful-shutdown form: stop
        admitting first (begin_drain), and flush the metrics gauges +
        emit the terminal summary row once empty — the engine then
        refuses traffic until resume_admission()."""
        if seal:
            self.begin_drain()
        out: List[Request] = []
        n = 0
        while self.busy:
            if max_batches is not None and n >= max_batches:
                break
            got = self.step()
            n += 1
            if not got and not self.busy:
                break
            out.extend(got)
        if seal:
            if not self.busy:
                self.metrics.flush()
            else:
                # bounded drain ran out of batches with work remaining:
                # the seal did NOT complete — no terminal flush, gauges
                # still live. Say so instead of returning as if the
                # shutdown handshake finished.
                _logger.warning(
                    "drain(seal=True) hit max_batches=%s with work "
                    "remaining (queue+slots still busy): terminal "
                    "metrics flush skipped, engine left in drain mode — "
                    "call drain() again to finish", max_batches)
        return out

    # -- reporting ------------------------------------------------------
    def summary(self) -> dict:
        s = self.metrics.summary()
        s["batch_step"] = self.monitor.report()
        return s

    def metrics_text(self, prefix: str = "paddle_tpu_serving") -> str:
        """The full /metrics payload: request metrics + the engine's batch
        StepMonitor block (steady tokens/s, recompile counters)."""
        return self.metrics.metrics_text(prefix=prefix) + \
            self.monitor.metrics_text(prefix=f"{prefix}_batch")

    # -- ops surface (ISSUE 12) -----------------------------------------
    def health(self) -> dict:
        """The /healthz payload — exactly the autoscaler/router inputs
        the r12 load-shedding work named: drain state, queue depth vs its
        shed thresholds, inflight rows, and the overloaded counter. Pure
        host-side reads; safe from any thread at scrape rate."""
        cfg, m = self.config, self.metrics
        return {"status": "draining" if self._draining else "ok",
                "draining": self._draining,
                "queue_depth": len(self._queue),
                "queue_capacity": cfg.queue_capacity,
                "queue_high_watermark": cfg.queue_high_watermark,
                "inflight": len(self._live()),
                "overloaded_total": m.counters["overloaded"],
                "rejected_total": m.counters["rejected"],
                # goodput inputs (ISSUE 14): the autoscale controller
                # derives completed/requests deltas per tick from here
                "requests_total": m.counters["requests"],
                "completed_total": m.counters["completed"],
                "kv_occupancy": m.gauges["kv_occupancy"]}

    def fingerprint(self) -> dict:
        """Deterministic config/build identity (ISSUE 19): the key
        goldens are minted under and the value fleet drift detection
        compares. Cached — model config, ServingConfig, jax versions
        and PADDLE_TPU_* env are all process-stable."""
        if self._fingerprint is None:
            from ..obs.probez import config_fingerprint
            self._fingerprint = config_fingerprint(self.model.config,
                                                   self.config)
        return self._fingerprint

    def statusz(self) -> dict:
        """The /statusz payload: engine identity + config envelope,
        compile/recompile accounting, KV/prefix-cache occupancy, the
        config/build fingerprint, and the full counter/gauge snapshot —
        the page a human (or a fleet inventory) reads to understand
        WHAT this replica is."""
        out = {"engine": {"run_id": self._run_id,
                          "uptime_s": round(self.clock() - self._t_start,
                                            3),
                          "draining": self._draining,
                          "requests_submitted": self._next_id,
                          "batches": self._batch_id},
               "config": {k: (v if isinstance(v, (int, float, str, bool,
                                                  type(None)))
                              else repr(v))
                          for k, v in vars(self.config).items()},
               "compile": {"compiles": self.monitor.compiles,
                           "recompiles": self.monitor.recompiles,
                           "jit_cache_misses": _jit_cache_misses()},
               "fingerprint": self.fingerprint(),
               "counters": dict(self.metrics.counters),
               "gauges": dict(self.metrics.gauges)}
        pool = self._pool
        kv_tokens, kv_slots, kv_shared = self._kv_snapshot
        out["kv"] = {"blocks_total": pool.num_blocks,
                     "block_size": pool.block_size,
                     "used_blocks": pool.used_blocks,
                     "capacity_tokens": pool.capacity_tokens,
                     "live_tokens": kv_tokens,
                     "slot_tokens": kv_slots,
                     "shared_tokens": kv_shared,
                     "cache_dtype": pool.cache_dtype}
        if self._prefix is not None:
            out["prefix_cache"] = {
                "cached_blocks": self._prefix.cached_blocks,
                "cached_bytes": self._prefix.cached_bytes,
                "spilled_blocks": self._prefix.spilled_blocks,
                "byte_budget": self._prefix.byte_budget}
        if self._spill is not None:
            out["spill"] = self._spill.stats()
        if self._memz is not None:
            # one curl shows compute, KV, and memory state together
            # (ISSUE 18 satellite): ledger summary + spill occupancy
            out["memory"] = self._memz.statusz_block()
        return out

    # -- HBM ledger (ISSUE 18) ------------------------------------------
    def attach_memory_ledger(self, ledger=None):
        """Wire a MemoryLedger to this engine's owners and return it.

        Registers reader-backed owners over accounting the engine already
        keeps host-side (a ledger read must never sync — pinned like
        every other scrape):

          model_params   named-parameter buffer bytes (live device copy)
          kv_pool        the pool's full reservation (num_blocks ×
                         bytes_per_block — the allocator-granularity
                         truth; `used_bytes` rides as detail) with shard
                         geometry in meta
          prefix_cache   retained-block bytes, an OVERLAY — those blocks
                         live inside kv_pool's reservation, reported but
                         never double-counted in the conservation sum
          spill_host     host-RAM tier (device=False: never summed
                         against HBM)

        The pool's `on_change` observer re-samples the pool/cache owners
        on every alloc/free/COW so the delta ring is a faithful growth
        curve; ledger rows (headroom_low, post-mortems) ride the metrics'
        structured-row stream, which is what the flight recorder taps."""
        if ledger is None:
            from ..obs.memz import MemoryLedger
            ledger = MemoryLedger()
        self._memz = ledger

        def _params_bytes():
            return int(sum(p._data.nbytes
                           for _, p in self.model.named_parameters()))
        ledger.register("model_params", _params_bytes, kind="params",
                        replace=True)
        pool = self._pool
        shards = int(self.config.shards or 1)

        def _pool_bytes():
            bpb = pool.bytes_per_block
            return {"bytes": pool.num_blocks * bpb,
                    "used_bytes": pool.used_blocks * bpb,
                    "used_blocks": pool.used_blocks,
                    "free_blocks": pool.free_blocks}
        ledger.register("kv_pool", _pool_bytes, kind="kv",
                        meta={"shards": shards,
                              "block_size": pool.block_size,
                              "num_blocks": pool.num_blocks},
                        replace=True)
        pool.on_change = lambda: ledger.sample("kv_pool", "prefix_cache")
        if self._prefix is not None:
            prefix = self._prefix
            ledger.register(
                "prefix_cache",
                lambda: {"bytes": prefix.cached_bytes,
                         "cached_blocks": prefix.cached_blocks,
                         "spilled_blocks": prefix.spilled_blocks},
                kind="kv", overlay=True, replace=True)
        if self._spill is not None:
            spill = self._spill
            ledger.register("spill_host",
                            lambda: int(spill.host_bytes),
                            kind="spill", device=False, replace=True)
        if ledger.on_row is None:
            ledger.on_row = self.metrics._emit
        # the StepMonitor's per-record memory sample reads the ledger's
        # free host counters instead of rationing live-array scans
        self.monitor.memz = ledger
        ledger.sample()
        return ledger

    def _mem_pressure_enter(self, req, need_rows: int):
        if self._mem_pressure_t0 is not None:
            return                       # already inside the episode
        self._mem_pressure_t0 = self.clock()
        body = {"request": req.id, "need_rows": int(need_rows),
                "free_blocks": self._pool.free_blocks,
                "used_blocks": self._pool.used_blocks,
                "queue_depth": len(self._queue)}
        if self._memz is not None:
            body["top_owners"] = self._memz.top_owners(3)
        self.metrics._emit({"mem_pressure": body, "ts": time.time()})
        self.metrics.counters["mem_pressure_episodes"] += 1

    def _mem_pressure_exit(self):
        if self._mem_pressure_t0 is None:
            return
        waited = self.clock() - self._mem_pressure_t0
        self._mem_pressure_t0 = None
        # *_clear key: inert on the flight-recorder trigger bus by the
        # transition-rows-only convention
        self.metrics._emit({"mem_pressure_clear":
                            {"waited_s": round(waited, 6),
                             "free_blocks": self._pool.free_blocks},
                            "ts": time.time()})

    def metrics_registry(self, prefix: str = "paddle_tpu_serving"):
        """The engine's exposition producers composed through the
        collision-checked obs.MetricsRegistry — the /metrics source
        `serve_telemetry` scrapes (callers add more producers: an SLO
        monitor, a co-hosted training monitor, ...)."""
        from ..obs import MetricsRegistry
        reg = MetricsRegistry()
        reg.register("serving",
                     lambda: self.metrics.metrics_text(prefix=prefix))
        reg.register("serving_batch",
                     lambda: self.monitor.metrics_text(
                         prefix=f"{prefix}_batch"))
        if self._spill is not None:
            # the spill tier's counters ride the same registry (ISSUE
            # 14): one scrape shows blocks spilled/rehydrated next to
            # the request metrics they are saving prefill for
            reg.register("spill",
                         lambda: self._spill.metrics_text(
                             prefix=f"{prefix}_spill"))
        if self._memz is not None:
            # hbm_bytes{owner=...} / hbm_headroom_bytes (ISSUE 18): the
            # gauges the SLO/flight-recorder machinery consumes
            reg.register("memz",
                         lambda: self._memz.metrics_text(
                             prefix="paddle_tpu"))
        if self._prober is not None:
            # probe_* families (ISSUE 19) — separate producers, so an
            # exposition without a prober is byte-identical by
            # construction (the probe/SLO isolation guarantee)
            reg.register("probe", self._prober.metrics_text)
            reg.register("probe_serving", self.metrics.probe_metrics_text)
        if self._invariants is not None:
            reg.register("invariant", self._invariants.metrics_text)
        return reg

    def serve_telemetry(self, *, host: str = "127.0.0.1", port: int = 0,
                        slo=None, poll_interval: Optional[float] = None,
                        registry=None, trace_capacity: int = 256,
                        flightrec=None, prober=None,
                        probe_interval: Optional[float] = None,
                        invariant_interval: Optional[float] = None):
        """Boot the replica's ops surface: a started obs.TelemetryServer
        wired to this engine — /metrics from `metrics_registry()` (+ the
        SLO monitor's burn gauges when one is passed), /healthz from
        `health()`, /statusz from `statusz()`, /tracez from the metrics'
        tail-sampling TraceBuffer (created and attached here when the
        metrics don't carry one yet), /memz from the HBM ledger (ISSUE
        18 — `attach_memory_ledger()` runs here when none is attached
        yet). Returns the server; `.close()` it on shutdown.

        `slo` is an obs.SLOMonitor or a parse_slo spec string
        ("ttft_p99=500ms,goodput=0.95" — built over this engine's
        metrics). With `poll_interval` (seconds) the SERVER owns the
        burn-rate cadence: a timer thread drives slo.poll() for the
        server's lifetime, so alerts fire without any external driver
        and the thread shuts down with the server (the r15 NOTE
        follow-up). The monitor rides `srv.slo` for introspection.

        `flightrec` is an obs.FlightRecorder (ISSUE 17): it attaches to
        this engine's StepMonitor (captures advance at the engine's
        device-call brackets), taps the SLO monitor's alert transitions
        and the metrics' structured rows as capture triggers, exports
        its counters on /metrics, and mounts the /profilez route. It
        rides `srv.flightrec`; detaching at shutdown stays with the
        caller (`flightrec.detach()`).

        `prober` is an obs.Prober (ISSUE 19) or True to build one over
        this engine; it mounts /probez, exports the probe_* families,
        and with `probe_interval` the server drives golden-canary
        cycles on a poller thread. `invariant_interval` schedules the
        deep InvariantAuditor audits the same way —
        both pollers hold the prober's lock; an external step-loop
        thread must share it (`srv.prober.lock`), per the engine's
        one-lock threading contract."""
        from ..obs import (InvariantAuditor, Prober, SLOMonitor,
                           TelemetryServer, TraceBuffer)
        if self.metrics.trace_buffer is None:
            self.metrics.trace_buffer = TraceBuffer(trace_capacity)
        if self._memz is None:
            # every served replica gets the HBM ledger (ISSUE 18): /memz,
            # the hbm_* gauges and the OOM post-mortem come up with the
            # ops surface unless the caller attached their own
            self.attach_memory_ledger()
        if prober is True:
            prober = Prober(self)
        if prober is not None:
            self._prober = prober
        if prober is not None or invariant_interval is not None:
            auditor = InvariantAuditor(
                self, lock=prober.lock if prober is not None else None)
            self._invariants = auditor
            if prober is not None:
                prober.auditor = auditor
        reg = registry if registry is not None else self.metrics_registry()
        if isinstance(slo, str):
            slo = SLOMonitor(slo, self.metrics)
        if slo is not None:
            reg.register("slo", slo.metrics_text)
        elif poll_interval is not None:
            raise ValueError("poll_interval needs an slo monitor/spec "
                             "to poll")
        routes = {"/memz": self._memz.memz}
        if prober is not None:
            routes["/probez"] = prober.probez
        if flightrec is not None:
            # monitor: step brackets + straggler/recompile/numerics rows;
            # metrics: every structured row INCLUDING slo_alert (the SLO
            # monitor emits through metrics._emit — tapping on_alert too
            # would double-count each alert on the trigger bus)
            flightrec.attach(monitor=self.monitor, metrics=self.metrics)
            reg.register("flightrec", flightrec.metrics_text)
            routes["/profilez"] = flightrec.profilez
        srv = TelemetryServer(reg, host=host, port=port,
                              health=self.health, status=self.statusz,
                              tracez=self.metrics.trace_buffer,
                              routes=routes)
        srv.slo = slo
        srv.flightrec = flightrec
        srv.prober = prober
        srv.invariants = self._invariants
        if slo is not None and poll_interval is not None:
            srv.add_poller(slo.poll, poll_interval, name="slo")
        if prober is not None and probe_interval is not None:
            srv.add_poller(prober.probe_once, probe_interval,
                           name="probe")
        if self._invariants is not None and \
                invariant_interval is not None:
            srv.add_poller(self._invariants.audit, invariant_interval,
                           name="invariants")
        return srv.start()


def _hit_eos(row: np.ndarray, eos: Optional[int]) -> bool:
    return eos is not None and bool((row == eos).any())  # lint: allow(tracer-bool)


def _n_out(row: np.ndarray, eos: Optional[int]) -> int:
    """Tokens a row really produced: up to and including the first EOS."""
    if eos is None:
        return int(row.shape[0])
    hits = np.nonzero(row == eos)[0]
    return int(hits[0]) + 1 if hits.size else int(row.shape[0])


def synthetic_traffic(n_requests: int, *, prompt_cap: int, vocab_size: int,
                      rate: float = 50.0, seed: int = 0,
                      min_len: int = 1,
                      length_dist: str = "uniform") -> List[dict]:
    """Open-loop synthetic workload: Poisson arrivals at `rate` req/s,
    ragged prompt lengths in [min_len, prompt_cap]. Returns
    [{"at": arrival_offset_s, "prompt": ids}] sorted by arrival.

    length_dist:
      "uniform"  — lengths uniform over [min_len, prompt_cap];
      "longtail" — Pareto-shaped (alpha≈1.1) lengths clipped to the cap:
                   mostly-short traffic with a heavy tail of cap-length
                   prompts."""
    if length_dist not in ("uniform", "longtail"):
        raise ValueError(f"unknown length_dist {length_dist!r}")
    rng = np.random.RandomState(seed)
    gaps = rng.exponential(1.0 / max(rate, 1e-9), size=n_requests)
    at = np.cumsum(gaps) - gaps[0]
    out = []
    for i in range(n_requests):
        if length_dist == "longtail":
            ln = min(prompt_cap, min_len + int(rng.pareto(1.1) * min_len))
        else:
            ln = int(rng.randint(min_len, prompt_cap + 1))
        out.append({"at": float(at[i]),  # lint: allow(tracer-float)
                    "prompt": rng.randint(1, vocab_size,
                                          (ln,)).astype(np.int64)})
    return out


def shared_prefix_traffic(n_requests: int, *, n_prefixes: int,
                          prefix_len: int, prompt_cap: int,
                          vocab_size: int, rate: float = 50.0,
                          seed: int = 0) -> List[dict]:
    """System-prompt workload (ISSUE 10): every request draws one of
    `n_prefixes` FIXED token prefixes (`prefix_len` tokens — the "system
    prompt") followed by a fresh random suffix, with Poisson arrivals at
    `rate` req/s. The traffic shape prefix caching exists for: after each
    prefix's first request, every later request sharing it should admit
    with only its suffix prefilled. Returns [{"at", "prompt",
    "prefix_id"}] sorted by arrival."""
    if not (1 <= prefix_len < prompt_cap):
        raise ValueError(f"prefix_len must be in [1, prompt_cap), got "
                         f"{prefix_len} vs cap {prompt_cap}")
    if n_prefixes < 1:
        raise ValueError(f"n_prefixes must be >= 1, got {n_prefixes}")
    rng = np.random.RandomState(seed)
    prefixes = rng.randint(1, vocab_size,
                           (n_prefixes, prefix_len)).astype(np.int64)
    gaps = rng.exponential(1.0 / max(rate, 1e-9), size=n_requests)
    at = np.cumsum(gaps) - gaps[0]
    out = []
    for i in range(n_requests):
        p = int(rng.randint(0, n_prefixes))
        ln = int(rng.randint(1, prompt_cap - prefix_len + 1))
        suffix = rng.randint(1, vocab_size, (ln,)).astype(np.int64)
        out.append({"at": float(at[i]),  # lint: allow(tracer-float)
                    "prompt": np.concatenate([prefixes[p], suffix]),
                    "prefix_id": p})
    return out


def repeated_traffic(n_requests: int, *, n_prompts: int, prompt_len: int,
                     vocab_size: int, rate: float = 50.0,
                     seed: int = 0) -> List[dict]:
    """Agentic / retry workload (ISSUE 11): every request is one of
    `n_prompts` FIXED prompts repeated VERBATIM, Poisson arrivals at
    `rate` req/s. The degenerate shared-prefix shape (suffix shared too)
    — and the one where speculative prompt-lookup drafting pays in full:
    after each prompt's first completion, every later identical request
    zero-prefills its KV from the trie AND drafts its entire greedy
    continuation from the cached chain, so verify windows accept
    end-to-end. Returns [{"at", "prompt", "prompt_id"}] sorted by
    arrival."""
    if n_prompts < 1 or prompt_len < 1:
        raise ValueError(f"need n_prompts >= 1 and prompt_len >= 1, got "
                         f"{n_prompts}, {prompt_len}")
    rng = np.random.RandomState(seed)
    prompts = rng.randint(1, vocab_size,
                          (n_prompts, prompt_len)).astype(np.int64)
    gaps = rng.exponential(1.0 / max(rate, 1e-9), size=n_requests)
    at = np.cumsum(gaps) - gaps[0]
    out = []
    for i in range(n_requests):
        p = int(rng.randint(0, n_prompts))
        out.append({"at": float(at[i]),  # lint: allow(tracer-float)
                    "prompt": prompts[p].copy(), "prompt_id": p})
    return out


def model_draft_fn(draft_model, *, window: int = 32):
    """Adapter turning a (small) GPTForCausalLM into a speculative draft
    source for ``ServingConfig(spec_draft=...)`` (ISSUE 11).

    The returned callable greedily continues the last ``window`` context
    tokens through ``draft_model.generate_static_ragged`` — fixed
    [1, window] shape, ragged length as data, so ONE draft executable
    per spec_k serves every request at every depth (it compiles on the
    first draft call; include a drafted request in warmup before
    asserting zero steady-state misses). Each call pays a full
    window-prefill in the draft model: cheap when the drafter is 10-50x
    smaller than the target, which is the configuration speculative
    decoding wants anyway."""
    def fn(context, k):
        ctx = np.asarray(context, dtype=np.int64)[-window:]  # lint: allow(tracer-asarray)
        ln = int(ctx.shape[0])
        ids = np.zeros((1, window), np.int64)
        ids[0, :ln] = ctx
        out = draft_model.generate_static_ragged(ids, [ln],
                                                 max_new_tokens=int(k))
        return np.asarray(out.numpy())[0, window:window + int(k)]  # lint: allow(tracer-asarray)
    return fn
