"""paddle_tpu.inference.fleet — fault-tolerant fleet serving (ISSUE 14).

Millions of users means N engine replicas behind a router, not one
engine. Every ingredient already existed — r12's graceful drain and
`overloaded_total` load-shedding signal, per-replica /healthz (r15),
fleet-scope aggregation (r16), the refcounted prefix-block trie (r11),
and the seeded chaos harness (r12) — this module is the layer that
survives a replica dying mid-request:

  ReplicaRegistry   fleet membership + health-driven ejection. Each
                    replica is a ReplicaHandle over a live ServingEngine
                    (in-process replicas — the same engines a spawned
                    fleet runs one-per-host); `probe()` scrapes every
                    member's health through the chaos site
                    ``fleet.scrape`` and ejects a member whose scrape
                    fails `fail_threshold` consecutive times (503/stale/
                    unreachable). Membership changes mirror into an
                    optional obs.FleetAggregator so the merged telemetry
                    surface tracks the registry, not a stale config.

  FleetRouter       prefix-aware request routing with retry/failover.
                    The routing key is the prompt's FIRST full
                    kv-block token tuple — exactly the radix trie's
                    node key — rendezvous-hashed (HRW) over the serving
                    replicas, so every request sharing a system prompt
                    lands on the replica already holding its blocks and
                    the prefix-cache hit rate becomes a FLEET property.
                    When a replica is ejected, only ITS keys move (each
                    to its own rendezvous successor); every other
                    key→replica assignment is untouched. Dispatch
                    retries replica-local refusals (`Request.retriable`
                    — overloaded/draining/queue_full) on the next
                    candidate, then backs off with the capped
                    exponential schedule of ``resilience.chaos.retry``
                    under a per-request deadline budget; terminal
                    refusals (kv_oom, shape rejects) return immediately
                    — the router never hot-loops a request no replica
                    will ever accept. In-flight requests on a replica
                    that dies mid-traffic (``chaos.ReplicaDown`` at the
                    ``fleet.step`` site) are re-submitted elsewhere;
                    greedy decode is deterministic per prompt, so the
                    redispatched output is bit-identical to a fault-free
                    run (asserted against an oracle in the chaos tests).
                    `step()` also consults each handle's attached
                    obs.Prober (ISSUE 19): a replica whose golden-canary
                    probe reports `failing` is drained + ejected exactly
                    like a dead one — wrong answers are a liveness
                    failure as far as routing is concerned.

  AutoscaleController  goodput-driven scaling over the registry. Each
                    `tick()` reads the members' /healthz payloads — the
                    summed `overloaded_total` delta (r12 named it "the
                    autoscaler signal"), queue depths, and goodput
                    (completed/requests delta) — and decides: scale UP
                    (spawn a replica into the registry) on overload /
                    deep queues / goodput under floor / membership
                    below min (the died-replica replacement); scale
                    DOWN only via the graceful handshake — pick the
                    least-loaded replica, `begin_drain()` (the router
                    stops routing to it), and REMOVE it only once its
                    queue and slots are empty. Never a hard kill.

Everything is synchronous and deterministic: the router's `step()`
drives one engine step per serving replica, chaos faults fire from a
seeded Injector, and the backoff sleep is injectable (the default
"sleep" for an in-process fleet STEPS the fleet instead of wall-
sleeping — while a real frontend waits, real replicas serve). The proof
harness is tools/fleet_chaos_smoke.py + tests/test_fleet_serving.py:
every failover claim is pinned by an injected fault.
"""
from __future__ import annotations

import hashlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from ..resilience.chaos import ReplicaDown, retry
from .serving import Request

__all__ = ["ReplicaHandle", "ReplicaRegistry", "FleetRouter",
           "FleetRequest", "AutoscaleController"]


# ---------------------------------------------------------------- handles

class ReplicaHandle:
    """One fleet member: a named ServingEngine + its liveness state."""

    def __init__(self, name: str, engine, *, url: Optional[str] = None):
        self.name = name
        self.engine = engine
        self.url = url                 # telemetry base url, when served
        self.state = "serving"         # serving | draining | ejected
        self.steps = 0                 # router step attempts (chaos ctx)
        self.consecutive_failures = 0
        self.ejected_reason: Optional[str] = None
        self.prober = None             # obs.Prober, when attached (r19)

    def health(self) -> dict:
        return self.engine.health()

    def __repr__(self):
        return f"ReplicaHandle({self.name}, {self.state})"


class ReplicaRegistry:
    """Fleet membership + health-driven ejection (module docstring)."""

    def __init__(self, replicas=None, *, aggregator=None, chaos=None,
                 fail_threshold: int = 2):
        if fail_threshold < 1:
            raise ValueError(f"fail_threshold must be >= 1, "
                             f"got {fail_threshold}")
        self.aggregator = aggregator   # obs.FleetAggregator (optional)
        self.chaos = chaos             # resilience.chaos.Injector
        self.fail_threshold = int(fail_threshold)
        self._handles: Dict[str, ReplicaHandle] = {}
        self.ejected: Dict[str, ReplicaHandle] = {}   # post-mortem log
        if replicas:
            items = replicas.items() if isinstance(replicas, dict) \
                else replicas
            for name, engine in items:
                self.add(name, engine)

    # ------------------------------------------------------- membership
    def add(self, name: str, engine, *,
            url: Optional[str] = None) -> ReplicaHandle:
        if name in self._handles:
            raise ValueError(f"replica {name!r} already registered")
        h = ReplicaHandle(name, engine, url=url)
        self._handles[name] = h
        if self.aggregator is not None and url is not None:
            self.aggregator.add_replica(name, url)
        return h

    def remove(self, name: str) -> Optional[ReplicaHandle]:
        h = self._handles.pop(name, None)
        if h is not None and self.aggregator is not None:
            self.aggregator.remove_replica(name)
        return h

    def eject(self, name: str, reason: str) -> Optional[ReplicaHandle]:
        """Take a dead/unreachable member out of every candidate set —
        its rendezvous successors absorb its keys on the next rank().
        The handle survives in `self.ejected` for post-mortems."""
        h = self.remove(name)
        if h is not None:
            h.state = "ejected"
            h.ejected_reason = reason
            self.ejected[name] = h
        return h

    def handle(self, name: str) -> ReplicaHandle:
        return self._handles[name]

    def handles(self, states=("serving",)) -> List[ReplicaHandle]:
        return [h for h in self._handles.values() if h.state in states]

    def names(self, states=("serving",)) -> List[str]:
        return [h.name for h in self.handles(states)]

    def __len__(self):
        return len(self._handles)

    def __contains__(self, name):
        return name in self._handles

    # ----------------------------------------------------------- health
    def probe(self) -> Dict[str, dict]:
        """Scrape every member's health (through the ``fleet.scrape``
        chaos site); a failing scrape counts toward `fail_threshold`
        consecutive failures, at which point the member is EJECTED
        (503/stale/unreachable). A draining member answering its scrape
        is healthy — scale-down removal is the autoscaler's graceful
        handshake, never an ejection. Returns {name: health payload}
        for the members that answered."""
        out: Dict[str, dict] = {}
        for h in list(self._handles.values()):
            try:
                if self.chaos is not None:
                    self.chaos.fire("fleet.scrape", replica=h.name)
                payload = h.health()
            except ReplicaDown as e:
                self.eject(h.name, f"unreachable: {e}")
                continue
            except Exception as e:   # noqa: BLE001 — scrape timeout /
                # transport class: degrade toward ejection, per contract
                h.consecutive_failures += 1
                if h.consecutive_failures >= self.fail_threshold:
                    self.eject(h.name,
                               f"{type(e).__name__} x"
                               f"{h.consecutive_failures}: {e}")
                continue
            h.consecutive_failures = 0
            out[h.name] = payload
        return out


# ----------------------------------------------------------------- router

@dataclass(eq=False)
class FleetRequest:
    """One request's life at FLEET scope: which replicas it was offered
    to, where it landed, how often it was redispatched, and the terminal
    engine Request carrying the generated tokens."""
    id: int
    prompt: np.ndarray
    max_new_tokens: Optional[int] = None
    deadline_s: Optional[float] = None      # END-TO-END queue budget:
    #   measured from t_submit, so retries and redispatches spend the
    #   same clock instead of restarting it
    t_submit: Optional[float] = None        # router clock at submit()
    key: bytes = b""
    status: str = "pending"   # pending|done|rejected|timeout|error
    reason: Optional[str] = None
    replica: Optional[str] = None           # current / last assignment
    attempts: List[dict] = field(default_factory=list)
    redispatches: int = 0
    request: Optional[Request] = None       # the engine-side request

    @property
    def tokens(self):
        return None if self.request is None else self.request.tokens

    @property
    def n_out(self) -> int:
        return 0 if self.request is None else self.request.n_out

    def record(self) -> dict:
        rec = {"id": self.id, "status": self.status,
               "replica": self.replica,
               "attempts": self.attempts,
               "redispatches": self.redispatches}
        if self.reason:
            rec["reason"] = self.reason
        return rec


class _AllShed(Exception):
    """Internal: one full candidate-ring pass found only retriable
    refusals — chaos.retry backs off and rings again."""

    def __init__(self, reason):
        self.reason = reason
        super().__init__(str(reason))


class FleetRouter:
    """Prefix-aware router with retry/failover (module docstring)."""

    def __init__(self, registry: ReplicaRegistry, *,
                 policy: str = "prefix",
                 key_tokens: Optional[int] = None,
                 chaos=None,
                 retry_budget_s: float = 1.0,
                 base_delay: float = 0.01,
                 max_delay: float = 0.25,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Optional[Callable[[float], None]] = None,
                 seed: int = 0):
        if policy not in ("prefix", "random"):
            raise ValueError(f"policy must be 'prefix' or 'random', "
                             f"got {policy!r}")
        self.registry = registry
        self.policy = policy
        self.chaos = chaos if chaos is not None else registry.chaos
        self.retry_budget_s = float(retry_budget_s)
        self.base_delay = float(base_delay)
        self.max_delay = float(max_delay)
        self.clock = clock
        # requests that reached a terminal state inside a nested backoff
        # step (below) — surfaced by the NEXT step()/drain() call so no
        # terminal FleetRequest is ever silently dropped
        self._pending_done: List[FleetRequest] = []
        # the in-process backoff "sleep" STEPS the fleet: while a real
        # frontend waits out a shed, real replicas serve — so a backoff
        # can actually free the capacity it is waiting for. Its results
        # are buffered, not discarded. Pass time.sleep for wall-clock
        # pacing against out-of-process replicas.
        self._sleep = sleep if sleep is not None \
            else (lambda delay: self._pending_done.extend(
                self._step_once()))
        self._rng = np.random.RandomState(seed)
        self._key_tokens = key_tokens
        self._next_id = 0
        self._inflight: Dict[str, Dict[int, FleetRequest]] = {}
        self.counters = {"dispatched": 0, "completed": 0, "rejected": 0,
                         "timeout": 0, "errors": 0, "retries": 0,
                         "backoffs": 0, "redispatched": 0,
                         "replicas_lost": 0, "probe_ejected": 0}

    # ---------------------------------------------------------- routing
    def _block_tokens(self) -> int:
        """Routing-key width: one kv block of the replicas' config (the
        trie's node key width)."""
        if self._key_tokens is not None:
            return self._key_tokens
        for h in self.registry.handles(("serving", "draining")):
            return h.engine.config.kv_block
        return 16

    def routing_key(self, prompt) -> bytes:
        """The prompt's first full-block token tuple, serialized — the
        same bytes for every request sharing the block-aligned prefix,
        whatever their suffixes do."""
        bt = self._block_tokens()
        ids = np.asarray(prompt).reshape(-1)[:bt]  # lint: allow(tracer-asarray)
        return b",".join(b"%d" % int(t) for t in ids)

    def rank(self, key: bytes) -> List[str]:
        """Serving replicas in rendezvous (highest-random-weight) order
        for `key`: candidate 0 owns the key; later entries are its
        failover successors. Removing a replica moves ONLY its keys
        (each to its own successor) — the property that keeps the other
        replicas' prefix caches hot through membership churn."""
        names = self.registry.names(("serving",))
        if self.policy == "random":
            names = list(names)
            self._rng.shuffle(names)
            return names

        def score(name: str) -> int:
            h = hashlib.blake2b(digest_size=8)
            h.update(name.encode("utf-8"))
            h.update(b"\x00")
            h.update(key)
            return int.from_bytes(h.digest(), "big")

        return sorted(names, key=score, reverse=True)

    # --------------------------------------------------------- dispatch
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               deadline_s: Optional[float] = None) -> FleetRequest:
        """Route one prompt into the fleet. Returns the FleetRequest:
        "pending" once accepted somewhere (drive `step()`/`drain()` to
        completion), "rejected" when terminal everywhere or the retry
        budget expired with every replica shedding."""
        freq = FleetRequest(id=self._next_id,
                            prompt=np.asarray(prompt),  # lint: allow(tracer-asarray)
                            max_new_tokens=max_new_tokens,
                            deadline_s=deadline_s,
                            t_submit=self.clock())
        self._next_id += 1
        freq.key = self.routing_key(freq.prompt)
        return self._dispatch(freq)

    def _remaining_deadline(self, freq: FleetRequest) -> Optional[float]:
        """The END-TO-END budget left: deadline_s minus time already
        spent since submit() — a retry or redispatch spends the same
        clock, it never restarts it."""
        if freq.deadline_s is None or freq.t_submit is None:
            return freq.deadline_s
        return freq.deadline_s - (self.clock() - freq.t_submit)

    def _dispatch(self, freq: FleetRequest) -> FleetRequest:
        def ring_pass():
            remaining = self._remaining_deadline(freq)
            if remaining is not None and remaining <= 0:
                # the budget expired before any replica accepted it —
                # terminal, exactly as if a queue deadline fired
                freq.status, freq.reason = "timeout", "queue_deadline"
                self.counters["timeout"] += 1
                return
            names = self.rank(freq.key)
            if not names:
                # nobody serving RIGHT NOW — retriable: the autoscaler
                # may be spawning a replacement this very backoff
                raise _AllShed("no_serving_replicas")
            last = None
            for name in names:
                handle = self.registry.handle(name)
                try:
                    req = handle.engine.submit(
                        freq.prompt, freq.max_new_tokens,
                        deadline_s=remaining)
                except ReplicaDown as e:
                    self._replica_lost(name, str(e))
                    continue
                freq.attempts.append({"replica": name,
                                      "status": req.status,
                                      "reason": req.reason})
                if req.status == "queued":
                    freq.replica = name
                    freq.request = req
                    self._inflight.setdefault(name, {})[req.id] = freq
                    self.counters["dispatched"] += 1
                    return
                if req.retriable is False:
                    # terminal everywhere: kv_oom / shape rejects — do
                    # NOT hot-loop it around the ring
                    freq.status, freq.reason = "rejected", req.reason
                    self.counters["rejected"] += 1
                    return
                last = req.reason
                self.counters["retries"] += 1
            raise _AllShed(last or "all_rejected")

        def on_backoff(attempt, delay, exc):
            self.counters["backoffs"] += 1

        try:
            retry(ring_pass, deadline=self.retry_budget_s,
                  base_delay=self.base_delay, max_delay=self.max_delay,
                  retry_on=(_AllShed,), sleep=self._sleep,
                  clock=self.clock, on_retry=on_backoff)
        except _AllShed as e:
            freq.status, freq.reason = "rejected", \
                f"fleet_shed:{e.reason}"
            self.counters["rejected"] += 1
        return freq

    def _replica_lost(self, name: str, detail: str):
        """A replica died under us: eject it and re-submit every
        request that was in flight there — the engine-side partial
        output is gone with the process; greedy decode re-runs to the
        SAME tokens elsewhere (bit-identical by determinism, pinned by
        the chaos tests)."""
        self.registry.eject(name, detail)
        self.counters["replicas_lost"] += 1
        lost = self._inflight.pop(name, {})
        for freq in lost.values():
            freq.redispatches += 1
            self.counters["redispatched"] += 1
            if self._dispatch(freq).status != "pending":
                # the redispatch itself went terminal (budget expired /
                # fleet-wide shed): surface it through the same buffer
                # as backoff-step completions — never silently dropped
                self._pending_done.append(freq)

    def check_probes(self):
        """Eject any replica whose attached Prober reports `failing`
        (ISSUE 19): a correctness-failing replica leaves routing exactly
        like a dead one — drained (stops accepting work it would answer
        wrongly) and ejected, with its in-flight requests redispatched
        elsewhere where greedy determinism re-produces the SAME tokens.
        The LB stops trusting a replica the moment it stops being
        correct, not merely fast."""
        for h in list(self.registry.handles(("serving", "draining"))):
            prober = getattr(h, "prober", None)
            if prober is None or not prober.failing:
                continue
            bad = sorted(n for n, v in prober.probez()["variants"].items()
                         if v.get("failing"))
            try:
                h.engine.begin_drain()
            except Exception:
                pass               # ejection must not depend on the drain
            self.counters["probe_ejected"] += 1
            self._replica_lost(h.name, "probe_fail:" + ",".join(bad))

    # ------------------------------------------------------ the step loop
    def step(self) -> List[FleetRequest]:
        """One engine step on every serving+draining replica (through
        the ``fleet.step`` chaos site — a ReplicaKill fault manifests
        here as ReplicaDown). Consults probe status first — a
        correctness-failing replica is ejected before it can emit more
        wrong tokens. Returns every FleetRequest that reached a terminal
        status — including any that finished inside a backoff step since
        the last call."""
        self.check_probes()
        out, self._pending_done = self._pending_done, []
        out.extend(self._step_once())
        return out

    def _settle(self, freq: FleetRequest, req) -> FleetRequest:
        freq.request = req
        freq.status = req.status
        freq.reason = req.reason
        if req.status == "done":
            self.counters["completed"] += 1
        elif req.status == "timeout":
            self.counters["timeout"] += 1
        elif req.status == "error":
            self.counters["errors"] += 1
        return freq

    def _step_once(self) -> List[FleetRequest]:
        done: List[FleetRequest] = []
        for h in list(self.registry.handles(("serving", "draining"))):
            h.steps += 1
            try:
                if self.chaos is not None:
                    self.chaos.fire("fleet.step", replica=h.name,
                                    step=h.steps)
                finished = h.engine.step() if h.engine.busy else []
            except ReplicaDown as e:
                self._replica_lost(h.name, str(e))
                continue
            pending = self._inflight.get(h.name, {})
            for req in finished:
                freq = pending.pop(req.id, None)
                if freq is None:
                    continue        # a replica-local caller's request
                done.append(self._settle(freq, req))
            # the mirror case: a replica-local step loop on the same
            # engine (a Prober cycle riding real decode) may have driven
            # one of OUR requests terminal — that step()'s `finished`
            # went to the local caller, not here. The Request object is
            # shared, so its status is authoritative; without this sweep
            # the FleetRequest pends forever.
            for rid in [rid for rid, fq in pending.items()
                        if fq.request is not None and fq.request.status
                        in ("done", "timeout", "error")]:
                freq = pending.pop(rid)
                done.append(self._settle(freq, freq.request))
        return done

    @property
    def inflight(self) -> int:
        return sum(len(v) for v in self._inflight.values())

    def drain(self, max_steps: Optional[int] = None,
              tick=None) -> List[FleetRequest]:
        """step() until nothing is in flight anywhere (or `max_steps`).
        `tick` is an optional callable run between steps — the place an
        AutoscaleController.tick rides the serving loop."""
        out: List[FleetRequest] = []
        n = 0
        while self._pending_done or self.inflight or \
                any(h.engine.busy for h in
                    self.registry.handles(("serving", "draining"))):
            if max_steps is not None and n >= max_steps:
                break
            out.extend(self.step())
            n += 1
            if tick is not None:
                tick()
        return out

    # -------------------------------------------------------- reporting
    def fleet_prefix_stats(self) -> dict:
        """Fleet-scope prefix-cache effectiveness: summed hit/miss/saved
        counters over every live member (the A/B number the routing
        policy moves)."""
        hits = misses = saved = 0
        for h in self.registry.handles(("serving", "draining")):
            c = h.engine.metrics.counters
            hits += c["prefix_hit"]
            misses += c["prefix_miss"]
            saved += c["prefill_tokens_saved"]
        total = hits + misses
        return {"prefix_hit": hits, "prefix_miss": misses,
                "prefill_tokens_saved": saved,
                "hit_rate": hits / total if total else None}

    def metrics_text(self, prefix: str = "paddle_tpu_router") -> str:
        """Prometheus exposition of the router's own counters — register
        it beside the members' pages (or the FleetAggregator's merged
        one) so routing behavior is scrapeable like everything else."""
        from ..profiler._metrics import counter_lines, gauge_lines
        helps = {"dispatched": "requests accepted by some replica",
                 "completed": "requests finished successfully",
                 "rejected": "requests refused (terminal or budget "
                             "exhausted)",
                 "timeout": "requests expired in a replica queue",
                 "errors": "requests lost to replica exceptions",
                 "retries": "per-replica refusals retried elsewhere",
                 "backoffs": "full-ring shed passes backed off",
                 "redispatched": "in-flight requests re-submitted after "
                                 "a replica died",
                 "replicas_lost": "replicas ejected after dying "
                                  "mid-traffic",
                 "probe_ejected": "replicas ejected on golden-probe "
                                  "correctness failure"}
        lines: List[str] = []
        for name, value in self.counters.items():
            lines.extend(counter_lines(prefix, f"{name}_total", value,
                                       helps[name]))
        lines.extend(gauge_lines(prefix, "inflight", self.inflight,
                                 "requests currently assigned to a "
                                 "replica"))
        lines.extend(gauge_lines(
            prefix, "replicas_serving",
            len(self.registry.names(("serving",))),
            "registry members accepting new work"))
        return "\n".join(lines) + "\n"


# ------------------------------------------------------------- autoscaler

class AutoscaleController:
    """Goodput-driven scaling over a ReplicaRegistry (module docstring).

    `spawn(name) -> engine` builds a replacement/scale-up replica — in
    process that is a fresh ServingEngine over the SHARED model (shared
    executables: a spawned replica adds zero compiles); a real fleet
    plugs in its pod launcher. Scale-down is only ever the graceful
    handshake: begin_drain → (router reroutes) → remove-once-empty."""

    def __init__(self, registry: ReplicaRegistry,
                 spawn: Callable[[str], object], *,
                 min_replicas: int = 1, max_replicas: int = 8,
                 scale_up_queue_depth: float = 4.0,
                 goodput_floor: float = 0.9,
                 idle_ticks_before_scale_down: int = 3):
        if not (1 <= min_replicas <= max_replicas):
            raise ValueError(f"need 1 <= min_replicas <= max_replicas, "
                             f"got {min_replicas}..{max_replicas}")
        self.registry = registry
        self.spawn = spawn
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.scale_up_queue_depth = float(scale_up_queue_depth)
        self.goodput_floor = float(goodput_floor)
        self.idle_ticks_before_scale_down = int(
            idle_ticks_before_scale_down)
        # PER-REPLICA counter baselines: deltas are computed member by
        # member, so one transiently-unscraped replica contributes zero
        # this tick instead of bouncing the fleet totals down and back
        # up (a bounce would read as phantom overload on recovery)
        self._last: Dict[str, dict] = {}
        self._idle_ticks = 0
        self._spawned = 0
        self.decisions: List[dict] = []

    def _spawn_into_registry(self, action: str) -> str:
        name = f"auto{self._spawned}"
        self._spawned += 1
        engine = self.spawn(name)
        self.registry.add(name, engine)
        self.decisions.append({"action": action, "replica": name})
        return name

    def tick(self) -> dict:
        """One control-loop pass; returns the signal/decision record
        (also appended to `self.decisions` when membership changed)."""
        # finish any graceful scale-down first: a draining member whose
        # queue AND slots emptied leaves the registry — never earlier
        for h in list(self.registry.handles(("draining",))):
            if not h.engine.busy and h.engine.queue_depth == 0:
                self.registry.remove(h.name)
                self.decisions.append({"action": "scale_down_done",
                                       "replica": h.name})
        payloads = self.registry.probe()
        serving = self.registry.handles(("serving",))
        live = {n: p for n, p in payloads.items()
                if n in self.registry and
                self.registry.handle(n).state == "serving"}
        d_over = d_req = d_done = 0
        queue_depth = inflight = 0
        cur: Dict[str, dict] = {}
        for name, p in live.items():
            snap = {"overloaded": p.get("overloaded_total", 0) or 0,
                    "requests": p.get("requests_total", 0) or 0,
                    "completed": p.get("completed_total", 0) or 0}
            base = self._last.get(name, snap)  # first sight: delta 0 —
            # a freshly added replica's history is not this tick's news
            d_over += snap["overloaded"] - base["overloaded"]
            d_req += snap["requests"] - base["requests"]
            d_done += snap["completed"] - base["completed"]
            cur[name] = snap
            queue_depth += p.get("queue_depth", 0)
            inflight += p.get("inflight", 0)
        # members that did not answer keep their old baseline (their
        # delta resumes cleanly when the scrape recovers); baselines of
        # removed/ejected members are pruned
        self._last = {n: cur.get(n, self._last.get(n))
                      for n in self.registry.names(("serving",
                                                    "draining"))
                      if n in cur or n in self._last}
        goodput = d_done / d_req if d_req > 0 else None
        mean_q = queue_depth / max(len(serving), 1)
        rec = {"serving": len(serving), "overloaded_delta": max(d_over, 0),
               "queue_depth": queue_depth, "inflight": inflight,
               "goodput": goodput, "action": None}

        if len(serving) < self.min_replicas:
            # the died-replica replacement: membership dropped below the
            # floor (ejection), restore it
            rec["action"] = "replace"
            rec["replica"] = self._spawn_into_registry("replace")
            self._idle_ticks = 0
        elif (d_over > 0 or mean_q > self.scale_up_queue_depth
              or (goodput is not None and goodput < self.goodput_floor)) \
                and len(serving) < self.max_replicas:
            rec["action"] = "scale_up"
            rec["replica"] = self._spawn_into_registry("scale_up")
            self._idle_ticks = 0
        elif (queue_depth == 0 and inflight == 0 and d_over <= 0
              and d_req == 0 and len(serving) > self.min_replicas):
            self._idle_ticks += 1
            if self._idle_ticks >= self.idle_ticks_before_scale_down:
                # graceful scale-down: drain the least-loaded member —
                # the router stops routing to it NOW; removal happens in
                # a later tick once it is empty (it already is here, but
                # in-flight work on a busier pick would finish first)
                victim = min(serving,
                             key=lambda h: (h.engine.queue_depth,
                                            h.name))
                victim.engine.begin_drain()
                victim.state = "draining"
                rec["action"] = "scale_down_begin"
                rec["replica"] = victim.name
                self.decisions.append({"action": "scale_down_begin",
                                       "replica": victim.name})
                self._idle_ticks = 0
        else:
            self._idle_ticks = 0
        return rec
