"""Fault-tolerant fleet serving (ISSUE 14): prefix-aware routing with
retry/failover, goodput-driven autoscaling, and the fleet fault
taxonomy.

Covers rendezvous routing math (stable keys, successor absorption on
ejection), the retriable rejection taxonomy (overloaded/draining/
queue_full retry ELSEWHERE; kv_oom/shape rejects terminal — surfaced in
the JSONL row), router retry + capped-backoff budgets (deterministic
schedule via the injected sleep), the seeded replica-kill failover
(eject -> redispatch -> bit-identical vs a fault-free oracle), scrape-
timeout ejection thresholds, autoscaler replace/scale-up/graceful-
scale-down, and registry membership mirroring into a FleetAggregator.
Every failover claim is pinned by an injected fault — chaos-first.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (AutoscaleController, FleetRouter,
                                  ReplicaRegistry, ServingConfig,
                                  ServingEngine)
from paddle_tpu.jit.api import compile_cache_misses
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.resilience import (Injector, ReplicaDown, ReplicaKill,
                                   ScrapeTimeout)

CAP, NEW = 12, 5


@pytest.fixture(scope="module")
def served_model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    intermediate_size=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m, cfg


def _engine(m, **kw):
    base = dict(max_batch=2, prompt_cap=CAP, max_new_tokens=NEW,
                decode_chunk=2, kv_block=4,
                prefix_cache=True)
    base.update(kw)
    return ServingEngine(m, ServingConfig(**base))


def _prompts(cfg, n, seed=1, lo=5, hi=None):
    rng = np.random.RandomState(seed)
    hi = hi or CAP
    return [rng.randint(1, cfg.vocab_size,
                        (int(rng.randint(lo, hi + 1)),)).astype(np.int64)
            for _ in range(n)]


# ------------------------------------------------------- routing math

class TestRendezvousRouting:
    def _registry(self, names):
        reg = ReplicaRegistry()
        for n in names:
            reg.add(n, engine=None)
        return reg

    def test_key_is_first_block_tuple(self, served_model):
        m, cfg = served_model
        reg = ReplicaRegistry({"a": _engine(m)})
        router = FleetRouter(reg)
        p = np.arange(1, 11, dtype=np.int64)
        q = np.concatenate([p[:4], np.asarray([90, 91], np.int64)])
        assert router.routing_key(p) == router.routing_key(q)   # kv_block=4
        assert router.routing_key(p) != router.routing_key(p[1:])
        # shorter than one block: the whole prompt is the key
        assert router.routing_key(p[:2]) == router.routing_key(p[:2])

    def test_stable_assignment_and_successor_absorption(self):
        """Removing one replica moves ONLY its keys; every key owned by
        a survivor keeps its owner — the property that keeps survivor
        prefix caches hot through membership churn."""
        reg = self._registry(["r0", "r1", "r2", "r3"])
        router = FleetRouter(reg, key_tokens=4)
        keys = [b"%d" % i for i in range(64)]
        before = {k: router.rank(k)[0] for k in keys}
        assert len(set(before.values())) > 1      # keys actually spread
        reg.eject("r1", "test")
        after = {k: router.rank(k)[0] for k in keys}
        moved = [k for k in keys if before[k] != after[k]]
        assert moved                              # r1 owned something
        for k in keys:
            if before[k] != "r1":
                assert after[k] == before[k]      # survivors untouched
            else:
                # an ejected owner's key lands on ITS successor
                assert after[k] != "r1"

    def test_random_policy_is_seeded(self):
        reg = self._registry(["r0", "r1", "r2"])
        a = FleetRouter(reg, policy="random", key_tokens=4, seed=3)
        b = FleetRouter(reg, policy="random", key_tokens=4, seed=3)
        assert [a.rank(b"k") for _ in range(4)] == \
            [b.rank(b"k") for _ in range(4)]
        with pytest.raises(ValueError, match="policy"):
            FleetRouter(reg, policy="lru")


# ------------------------------------------- retriable rejection taxonomy

class TestRetriableTagging:
    def test_replica_local_rejections_retriable(self, served_model):
        m, _ = served_model
        eng = _engine(m, queue_capacity=1, queue_high_watermark=1)
        eng.begin_drain()
        r = eng.submit(np.asarray([1, 2, 3], np.int64))
        assert (r.status, r.reason, r.retriable) == \
            ("rejected", "draining", True)
        eng.resume_admission()
        eng.submit(np.asarray([1, 2, 3], np.int64))       # fills queue
        r = eng.submit(np.asarray([1, 2, 3], np.int64))
        assert (r.reason, r.retriable) == ("overloaded", True)

    def test_terminal_rejections_not_retriable(self, served_model):
        m, _ = served_model
        eng = _engine(m)
        r = eng.submit(np.ones((CAP + 1,), np.int64))
        assert (r.reason, r.retriable) == ("prompt_shape", False)
        small = _engine(m, kv_blocks=2)         # one usable block
        r = small.submit(np.ones((CAP,), np.int64))
        assert (r.reason, r.retriable) == ("kv_oom", False)

    def test_retriable_lands_in_jsonl_row(self, served_model, tmp_path):
        from paddle_tpu.inference.serving import ServingMetrics
        m, _ = served_model
        path = tmp_path / "reqs.jsonl"
        eng = ServingEngine(m, ServingConfig(
            max_batch=2, prompt_cap=CAP, max_new_tokens=NEW,
            kv_block=4, prefix_cache=True),
            metrics=ServingMetrics(jsonl_path=str(path)))
        eng.begin_drain()
        eng.submit(np.asarray([1, 2], np.int64))
        import json
        row = json.loads(path.read_text().strip().splitlines()[-1])
        assert row["request"]["reason"] == "draining"
        assert row["request"]["retriable"] is True


# ------------------------------------------------- router retry/failover

class TestRouterRetry:
    def test_shed_retries_on_next_candidate(self, served_model):
        """An overloaded replica's shed is retried elsewhere in the SAME
        ring pass — no backoff needed when a sibling has room."""
        m, cfg = served_model
        full = _engine(m, queue_capacity=1, queue_high_watermark=1)
        full.submit(np.asarray([1, 2, 3], np.int64))      # wedge it
        reg = ReplicaRegistry({"full": full, "ok": _engine(m)})
        router = FleetRouter(reg, key_tokens=4, retry_budget_s=1.0)
        # force the wedged replica first in rendezvous order
        router.rank = lambda key: ["full", "ok"]
        freq = router.submit(_prompts(cfg, 1, seed=2)[0])
        assert freq.status == "pending" and freq.replica == "ok"
        assert [a["replica"] for a in freq.attempts] == ["full", "ok"]
        assert freq.attempts[0]["reason"] == "overloaded"
        assert router.counters["retries"] == 1

    def test_terminal_rejection_never_ringed(self, served_model):
        m, cfg = served_model
        reg = ReplicaRegistry({"a": _engine(m), "b": _engine(m)})
        router = FleetRouter(reg, retry_budget_s=1.0)
        freq = router.submit(np.ones((CAP + 1,), np.int64))
        assert freq.status == "rejected"
        assert freq.reason == "prompt_shape"
        assert len(freq.attempts) == 1            # ONE replica asked

    def test_all_shed_backs_off_until_budget(self, served_model):
        """Every replica draining -> full-ring shed passes back off on
        the chaos.retry schedule until the deadline budget expires; the
        injected sleep pins the exact delays (deterministic, capped)."""
        m, cfg = served_model
        engines = {n: _engine(m) for n in ("a", "b")}
        for e in engines.values():
            e.begin_drain()
        reg = ReplicaRegistry(engines)
        delays = []
        t = [0.0]

        def clock():
            return t[0]

        def sleep(d):
            delays.append(d)
            t[0] += d

        router = FleetRouter(reg, retry_budget_s=0.1, base_delay=0.01,
                             max_delay=0.04, clock=clock, sleep=sleep)
        freq = router.submit(_prompts(cfg, 1)[0])
        assert freq.status == "rejected"
        assert freq.reason == "fleet_shed:draining"
        # capped exponential; a 4th 0.04 backoff would cross the 0.1s
        # deadline, so retry() re-raises without sleeping it
        assert delays == [0.01, 0.02, 0.04]
        assert router.counters["backoffs"] == len(delays)

    def test_kill_mid_traffic_redispatch_bit_identical(self, served_model):
        """THE failover contract: a seeded kill mid-traffic ejects the
        replica, its in-flight requests re-submit elsewhere, every
        completed output is bit-identical to the fault-free oracle, and
        the fault demonstrably FIRED."""
        m, cfg = served_model
        prompts = _prompts(cfg, 10, seed=4)
        oracle_eng = _engine(m)
        oracle = {}
        for p in prompts:
            r = oracle_eng.submit(p)
            oracle_eng.drain()
            oracle[p.tobytes()] = r.tokens

        chaos = Injector(5, faults=[ReplicaKill("r0", step=2)])
        reg = ReplicaRegistry({"r0": _engine(m), "r1": _engine(m)},
                              chaos=chaos)
        router = FleetRouter(reg, chaos=chaos, retry_budget_s=5.0)
        freqs = [router.submit(p) for p in prompts]
        router.drain()
        assert chaos.fired("replica_kill") == 1
        assert "r0" in reg.ejected
        assert reg.ejected["r0"].state == "ejected"
        assert router.counters["replicas_lost"] == 1
        assert router.counters["redispatched"] >= 1
        assert all(f.status == "done" for f in freqs)
        for f in freqs:
            np.testing.assert_array_equal(f.tokens,
                                          oracle[f.prompt.tobytes()])
        redone = [f for f in freqs if f.redispatches]
        assert redone and all(f.replica != "r0" for f in redone)

    def test_fleet_prefix_stats_and_metrics_text(self, served_model):
        from paddle_tpu.obs import lint_exposition
        m, cfg = served_model
        reg = ReplicaRegistry({"a": _engine(m), "b": _engine(m)})
        router = FleetRouter(reg)
        p = _prompts(cfg, 1, seed=6, lo=CAP, hi=CAP)[0]
        for _ in range(3):
            router.submit(p)
            router.drain()
        stats = router.fleet_prefix_stats()
        assert stats["prefix_hit"] >= 2           # same key -> same replica
        assert stats["hit_rate"] > 0.5
        text = router.metrics_text()
        lint_exposition(text)
        assert "paddle_tpu_router_completed_total 3" in text


# ------------------------------------------------- registry health/eject

class TestRegistryProbe:
    def test_scrape_timeout_ejects_at_threshold(self, served_model):
        m, _ = served_model
        chaos = Injector(0, faults=[ScrapeTimeout("r0", times=2)])
        reg = ReplicaRegistry({"r0": _engine(m), "r1": _engine(m)},
                              chaos=chaos, fail_threshold=2)
        assert "r0" not in reg.probe()            # 1st timeout: tolerated
        assert "r0" in reg
        assert reg.handle("r0").consecutive_failures == 1
        reg.probe()                               # 2nd: threshold -> eject
        assert "r0" not in reg and "r0" in reg.ejected
        assert "timeout" in reg.ejected["r0"].ejected_reason.lower()
        assert chaos.fired("scrape_timeout") == 2

    def test_transient_timeout_recovers(self, served_model):
        m, _ = served_model
        chaos = Injector(0, faults=[ScrapeTimeout("r0", times=1)])
        reg = ReplicaRegistry({"r0": _engine(m)}, chaos=chaos,
                              fail_threshold=2)
        reg.probe()
        assert reg.handle("r0").consecutive_failures == 1
        payloads = reg.probe()                    # scrape recovers
        assert payloads["r0"]["status"] == "ok"
        assert reg.handle("r0").consecutive_failures == 0

    def test_probe_payload_carries_goodput_inputs(self, served_model):
        m, cfg = served_model
        reg = ReplicaRegistry({"r0": _engine(m)})
        h = reg.probe()["r0"]
        for key in ("requests_total", "completed_total",
                    "overloaded_total", "queue_depth", "inflight"):
            assert key in h

    def test_aggregator_tracks_membership(self, served_model):
        """Registry add/eject mirrors into the obs FleetAggregator so
        the merged telemetry surface follows the fleet, not a config."""
        from paddle_tpu.obs import FleetAggregator
        m, _ = served_model
        agg = FleetAggregator(cache_ttl=0.0)
        try:
            reg = ReplicaRegistry(aggregator=agg)
            reg.add("r0", _engine(m), url="http://127.0.0.1:1/")
            reg.add("r1", _engine(m), url="http://127.0.0.1:2/")
            reg.add("local", _engine(m))          # no url: not scraped
            assert sorted(agg.replicas) == ["r0", "r1"]
            reg.eject("r0", "died")
            assert agg.replicas == ["r1"]
            reg.remove("r1")
            assert agg.replicas == []
        finally:
            agg.close()


# ---------------------------------------------------------- autoscaler

class TestAutoscaler:
    def test_replace_below_min(self, served_model):
        m, _ = served_model
        reg = ReplicaRegistry({"r0": _engine(m), "r1": _engine(m)})
        spawned = []

        def spawn(name):
            spawned.append(name)
            return _engine(m)

        auto = AutoscaleController(reg, spawn, min_replicas=2,
                                   max_replicas=3)
        reg.eject("r1", "test")
        rec = auto.tick()
        assert rec["action"] == "replace"
        assert spawned == ["auto0"]
        assert len(reg.names()) == 2

    def test_scale_up_on_overload_signal(self, served_model):
        """The r12 `overloaded_total` counter delta IS the scale-up
        signal: shed traffic -> next tick spawns."""
        m, cfg = served_model
        eng = _engine(m, queue_capacity=2, queue_high_watermark=1)
        reg = ReplicaRegistry({"r0": eng})
        auto = AutoscaleController(reg, lambda n: _engine(m),
                                   min_replicas=1, max_replicas=2,
                                   scale_up_queue_depth=1e9)
        auto.tick()                               # baseline snapshot
        eng.submit(_prompts(cfg, 1)[0])
        shed = eng.submit(_prompts(cfg, 1, seed=8)[0])
        assert shed.reason == "overloaded"
        rec = auto.tick()
        assert rec["action"] == "scale_up"
        assert rec["overloaded_delta"] == 1
        assert len(reg.names()) == 2
        # and never past max_replicas
        eng.submit(_prompts(cfg, 1, seed=9)[0])
        eng.submit(_prompts(cfg, 1, seed=10)[0])
        assert auto.tick()["action"] is None

    def test_graceful_scale_down_never_hard_kills(self, served_model):
        """Scale-down = begin_drain -> reroute -> remove once EMPTY: the
        drained replica leaves the candidate set immediately but leaves
        the registry only with queue AND slots empty."""
        m, cfg = served_model
        reg = ReplicaRegistry({"r0": _engine(m), "r1": _engine(m)})
        router = FleetRouter(reg)
        auto = AutoscaleController(reg, lambda n: _engine(m),
                                   min_replicas=1, max_replicas=2,
                                   idle_ticks_before_scale_down=2)
        freqs = [router.submit(p) for p in _prompts(cfg, 4, seed=11)]
        router.drain(tick=auto.tick)
        assert all(f.status == "done" for f in freqs)
        for _ in range(6):
            auto.tick()
            router.step()
        acts = [d["action"] for d in auto.decisions]
        assert "scale_down_begin" in acts and "scale_down_done" in acts
        assert len(reg.names(("serving",))) == 1
        victim = next(d["replica"] for d in auto.decisions
                      if d["action"] == "scale_down_begin")
        assert victim not in reg                  # removed, and it was
        # drained through the graceful path (begin_drain flag was set,
        # engine finished everything before removal)
        assert router.inflight == 0

    def test_drained_replica_rejections_route_elsewhere(self, served_model):
        """A draining replica refuses with retriable 'draining'; the
        router lands the request on a serving sibling."""
        m, cfg = served_model
        a, b = _engine(m), _engine(m)
        reg = ReplicaRegistry({"a": a, "b": b})
        router = FleetRouter(reg, retry_budget_s=2.0)
        reg.handle("a").state = "draining"
        a.begin_drain()
        for p in _prompts(cfg, 4, seed=12):
            freq = router.submit(p)
            assert freq.replica == "b"
        done = router.drain()
        assert all(f.status == "done" for f in done)


# --------------------------------------------------- fleet zero-recompile

def test_fleet_steady_loop_zero_recompiles(served_model):
    """Three replicas + a mid-run spawned replacement share one model's
    executables: after one replica's warmup, fleet traffic (incl. the
    replacement) adds zero jit cache misses."""
    m, cfg = served_model
    engines = {f"r{i}": _engine(m) for i in range(3)}
    reg = ReplicaRegistry(engines)
    prompts = _prompts(cfg, 6, seed=13)
    router = FleetRouter(reg, retry_budget_s=2.0)
    for p in prompts[:2]:                         # warmup traffic
        router.submit(p)
    router.drain()
    miss0 = compile_cache_misses()
    reg.add("late", _engine(m))                   # the replacement shape
    for p in prompts[2:]:
        router.submit(p)
    router.drain()
    assert compile_cache_misses() - miss0 == 0


class TestReviewRegressions:
    def test_transient_scrape_miss_no_phantom_scale_up(self, served_model):
        """Found in review: a transiently-unscraped member must not
        bounce the fleet counter baseline — its recovery would read as
        a phantom overloaded delta and spawn a replica for nothing."""
        m, cfg = served_model
        eng = _engine(m, queue_capacity=2, queue_high_watermark=1)
        inj = Injector(0)
        reg = ReplicaRegistry({"r0": eng, "r1": _engine(m)}, chaos=inj,
                              fail_threshold=5)
        auto = AutoscaleController(reg, lambda n: _engine(m),
                                   min_replicas=2, max_replicas=4,
                                   scale_up_queue_depth=1e9)
        eng.submit(_prompts(cfg, 1)[0])
        shed = eng.submit(_prompts(cfg, 1, seed=21)[0])
        assert shed.reason == "overloaded"      # history BEFORE tick 1
        eng.drain()
        assert auto.tick()["action"] is None    # baseline (first sight)
        inj.add(ScrapeTimeout("r0", times=1))
        rec2 = auto.tick()                      # r0 missing this tick
        assert rec2["action"] is None and rec2["overloaded_delta"] == 0
        rec3 = auto.tick()                      # r0 recovers: no bounce
        assert rec3["overloaded_delta"] == 0
        assert rec3["action"] is None
        assert len(reg.names()) == 2            # nothing spawned

    def test_backoff_step_results_not_dropped(self, served_model):
        """Found in review: a request finishing inside the router's
        backoff 'sleep' (which steps the fleet) must still come back
        from step()/drain() — terminal FleetRequests are buffered, not
        discarded."""
        m, cfg = served_model
        eng = _engine(m, max_batch=1, queue_capacity=1,
                      queue_high_watermark=1)
        reg = ReplicaRegistry({"only": eng})
        router = FleetRouter(reg, retry_budget_s=10.0)
        a = router.submit(_prompts(cfg, 1, seed=22)[0])
        # B sheds until A (queued ahead) completes INSIDE the backoff
        # steps; A's terminal FleetRequest lands in the pending buffer
        b = router.submit(_prompts(cfg, 1, seed=23)[0])
        assert b.status == "pending"
        done = router.drain()
        assert {f.id for f in done} == {a.id, b.id}
        assert a.status == "done" and b.status == "done"

    def test_deadline_is_end_to_end_across_failover(self, served_model):
        """Found in review: deadline_s must measure from submit() — a
        redispatch spends the SAME budget, never a fresh one; an
        expired budget is a terminal timeout surfaced by step()."""
        m, cfg = served_model
        t = [0.0]
        reg = ReplicaRegistry({"a": _engine(m)})
        router = FleetRouter(reg, clock=lambda: t[0],
                             sleep=lambda d: None, retry_budget_s=0.2)
        freq = router.submit(_prompts(cfg, 1, seed=30)[0],
                             deadline_s=0.5)
        assert freq.status == "pending"
        assert freq.request.deadline_s == 0.5     # full budget at t=0
        t[0] = 0.6                                # budget burned in queue
        router._replica_lost("a", "test")         # replica dies
        assert freq.status == "timeout"           # redispatch found the
        assert freq.reason == "queue_deadline"    # budget already spent
        got = router.step()                       # ...and it surfaces
        assert got == [freq]
        assert router.counters["timeout"] == 1

    def test_terminal_redispatch_surfaces_via_step(self, served_model):
        """Found in review: a redispatch that goes terminal (every
        survivor shedding past the retry budget) must come back from
        step()/drain(), not vanish."""
        m, cfg = served_model
        a, b = _engine(m), _engine(m, queue_capacity=1,
                                   queue_high_watermark=1)
        b.submit(np.asarray([1, 2, 3], np.int64))   # wedge the survivor
        reg = ReplicaRegistry({"a": a, "b": b})
        t = [0.0]

        def sleep(d):
            t[0] += d                               # no fleet stepping:
            #                                         b stays wedged

        router = FleetRouter(reg, clock=lambda: t[0], sleep=sleep,
                             retry_budget_s=0.05)
        router.rank = lambda key: [n for n in ("a", "b") if n in reg]
        freq = router.submit(_prompts(cfg, 1, seed=31)[0])
        assert freq.status == "pending" and freq.replica == "a"
        router._replica_lost("a", "test")
        assert freq.status == "rejected"
        assert freq.reason.startswith("fleet_shed")
        assert router.step() == [freq]
