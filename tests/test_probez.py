"""Active probing (ISSUE 19): golden-canary sentinels, deep invariant
pollers, probe/SLO isolation, and fleet /probez.

Pins the tentpole guarantees: goldens minted once per config
fingerprint via the reference generate_static_ragged oracle; probes
ride the REAL submit()/step path with zero steady-state jit misses;
chaos-injected KV corruption is detected within ONE probe cycle and
produces exactly one structured probe_fail row (flight-recorder pinned
capture attached) plus router ejection with bit-identical redispatch;
probe traffic leaves the user-facing SLO/latency/goodput accounting
BYTE-identical (structural exclusion, not subtraction); the deep
invariant auditor passes on a healthy engine and fires transition-based
findings on seeded violations; and the r16 straggler-granularity
follow-up (StepMonitor JSONL buffering flushes on every straggler
transition).
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.inference.fleet import FleetRouter, ReplicaRegistry
from paddle_tpu.jit.api import compile_cache_misses
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.obs import (FixtureBackend, FleetAggregator,
                            FlightRecorder, GoldenStore, InvariantAuditor,
                            Prober, SLOMonitor, config_fingerprint)
from paddle_tpu.obs.collectives import load_shard_walls
from paddle_tpu.profiler.monitor import StepMonitor
from paddle_tpu.resilience import CorruptKVBlock, Injector

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "mini_step.trace.json.gz")

CAP, NEW = 8, 6


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


# ------------------------------------------------------------ fingerprint

def test_config_fingerprint_deterministic_and_drift():
    a = config_fingerprint({"h": 32, "v": 96}, {"max_batch": 2},
                           env={"PADDLE_TPU_X": "1"})
    b = config_fingerprint({"v": 96, "h": 32}, {"max_batch": 2},
                           env={"PADDLE_TPU_X": "1"})
    assert a["sha"] == b["sha"]                  # key order is identity-free
    assert json.dumps(a["components"], sort_keys=True) == \
        json.dumps(b["components"], sort_keys=True)
    # any deciding component moves the sha: config, envelope, env
    assert config_fingerprint({"h": 33, "v": 96}, {"max_batch": 2},
                              env={"PADDLE_TPU_X": "1"})["sha"] != a["sha"]
    assert config_fingerprint({"h": 32, "v": 96}, {"max_batch": 4},
                              env={"PADDLE_TPU_X": "1"})["sha"] != a["sha"]
    assert config_fingerprint({"h": 32, "v": 96}, {"max_batch": 2},
                              env={"PADDLE_TPU_X": "2"})["sha"] != a["sha"]
    # callables hash by qualname, never repr (repr embeds the address —
    # identical replicas would fingerprint apart)
    c1 = config_fingerprint({"fn": test_config_fingerprint_deterministic_and_drift})
    c2 = config_fingerprint({"fn": test_config_fingerprint_deterministic_and_drift})
    assert c1["sha"] == c2["sha"]


def test_engine_statusz_carries_fingerprint(served_model):
    m, _ = served_model
    eng = _engine(m)
    fp = eng.statusz()["fingerprint"]
    assert fp["sha"] == eng.fingerprint()["sha"]
    assert set(fp["components"]) == {"model", "serving", "versions", "env"}
    # same model+config => same sha; a different envelope drifts
    assert _engine(m).fingerprint()["sha"] == fp["sha"]
    assert _engine(m, max_batch=4).fingerprint()["sha"] != fp["sha"]


# ----------------------------------------------------------------- prober

def test_prober_passes_with_zero_steady_state_misses(served_model):
    m, cfg = served_model
    eng = _engine(m)
    store = GoldenStore()
    pr = Prober(eng, store=store, replica="r0").warm()
    assert set(pr.variants) == {"decode", "prefix_miss", "prefix_hit"}
    assert store.minted_total == 3               # one golden per variant
    miss0 = compile_cache_misses()
    for _ in range(3):
        out = pr.probe_once()
        assert not out["failing"]
    assert compile_cache_misses() - miss0 == 0   # steady state: no churn
    pz = pr.probez()
    assert pz["state"] == "passing" and pz["failures_total"] == 0
    for st in pz["variants"].values():
        assert st["fail_total"] == 0 and st["pass_total"] >= 3
    # a second replica with the SAME fingerprint shares the goldens:
    # nothing new minted
    Prober(_engine(m), store=store, replica="r1").warm()
    assert store.minted_total == 3
    text = pr.metrics_text()
    assert 'paddle_tpu_probe_pass_total{variant="prefix_hit"}' in text
    assert "paddle_tpu_probe_failing 0" in text


def _user_slice(met):
    """The user-facing accounting the ISSUE pins: every request-scoped
    counter (goodput inputs, token volumes, cache/spec efficiency) and
    the rendered latency histograms. Excludes `batches`, the decode
    chunk counts, the host's collections and the occupancy gauges — those describe MACHINE
    state, which probe rows genuinely occupy."""
    from paddle_tpu.profiler._metrics import histogram_lines
    machine = ("batches", "decode_chunks", "decode_chunks_overlapped",
               "decode_rows_idle", "host_gc_pauses", "host_gc_pause_ms")
    counters = {k: v for k, v in met.counters.items() if k not in machine}
    hists = "\n".join(
        "\n".join(histogram_lines("u", name, met.hists[name], help_))
        for name, help_ in met.HISTS)
    return counters, hists


def test_probe_requests_never_touch_user_accounting(served_model):
    """Satellite: probe/SLO isolation is STRUCTURAL. A probe storm —
    passing, then failing, then recovering — leaves the user-facing
    counters, TTFT/e2e/goodput histograms, and the SLO monitor
    byte-identical to their pre-storm state."""
    m, cfg = served_model
    eng = _engine(m)
    pr = Prober(eng, replica="r0").warm()
    slo = SLOMonitor("ttft_p99=10s,goodput=0.0", eng.metrics)
    rows = []
    eng.metrics.on_record = rows.append

    # some real user traffic first, so the histograms are non-trivial
    rng = np.random.RandomState(3)
    for ln in (CAP, 5, 3):
        eng.submit(rng.randint(1, cfg.vocab_size, (ln,)).astype(np.int64))
    eng.drain()
    slo.poll()
    before = _user_slice(eng.metrics)
    before_alerts = slo.alerts_total

    # the storm: clean cycles, a corruption-induced failure, recovery
    for _ in range(2):
        pr.probe_once()
    blks = pr.probe_blocks("prefix_hit")
    eng.chaos = Injector(0).add(
        CorruptKVBlock(engine=eng, block=blks[0]))
    pr.probe_once()
    assert pr.failing
    eng.chaos = None
    eng._prefix.clear()                          # drop the corrupted block
    pr.probe_once()
    assert not pr.failing                        # recovered
    slo.poll()

    assert _user_slice(eng.metrics) == before    # bitwise unaffected
    assert slo.alerts_total == before_alerts and not slo.breaching
    assert not any("slo_alert" in r for r in rows)
    # ...while the probe-side families saw everything
    assert eng.metrics.probe_counters["requests"] > 0
    assert [r for r in rows if "probe_fail" in r]
    assert [r for r in rows if "probe_clear" in r]


def test_rejected_probe_is_noise_not_user_rejection(served_model):
    """Satellite: rejection reasons gain the probe dimension — a probe
    shed during drain is prober noise, never user-facing rejected_total
    (the r12 autoscaler overload signal stays clean)."""
    m, _ = served_model
    eng = _engine(m)
    pr = Prober(eng, replica="r0").warm()
    eng.begin_drain()
    pr.probe_once()
    assert not pr.failing                        # refusal != wrongness
    assert eng.metrics.counters["rejected"] == 0
    assert eng.metrics.probe_counters["rejected"] == len(pr.variants)
    assert eng.metrics.probe_reject_reasons == {
        "draining": len(pr.variants)}
    text = eng.metrics.probe_metrics_text()
    assert 'rejected_reason_total{reason="draining"}' in text
    st = pr.probez()["variants"]["decode"]
    assert st["noise_total"] == 1 and st["last_status"] == "noise"
    eng.resume_admission()
    pr.probe_once()
    assert pr.probez()["state"] == "passing"


def test_corruption_detected_one_cycle_one_row_pinned_capture(
        served_model, tmp_path):
    """Acceptance: one flipped KV-block region -> the next probe cycle
    fails the hit-path variant, emits exactly ONE structured probe_fail
    row naming variant + first diverging position, and pins a flight-
    recorder capture."""
    m, _ = served_model
    eng = _engine(m)
    rec = FlightRecorder(str(tmp_path / "cap"),
                         backend=FixtureBackend(FIXTURE),
                         trigger_steps=1, cooldown_s=0.0)
    rec.attach(monitor=eng.monitor, metrics=eng.metrics)
    rows = []
    prev = eng.metrics.on_record
    eng.metrics.on_record = lambda r: (prev(r), rows.append(r))
    pr = Prober(eng, replica="r0").warm()
    blks = pr.probe_blocks("prefix_hit")
    assert blks                                  # trie seeded by warm()
    fault = CorruptKVBlock(engine=eng, block=blks[0])
    eng.chaos = Injector(0).add(fault)

    pr.probe_once()                              # detection cycle
    assert fault.fired and fault.corrupted_block == blks[0]
    assert pr.failing
    fails = [r for r in rows if "probe_fail" in r]
    assert len(fails) == 1
    body = fails[0]["probe_fail"]
    assert body["variant"] == "prefix_hit"
    assert body["first_divergence"] is not None
    assert body["fingerprint"] == eng.fingerprint()["sha"]
    assert "memz_census" not in pr.probez().get("last_fail", {})
    # sustained failure stays ONE row (transition machine, not a spam)
    pr.probe_once()
    assert len([r for r in rows if "probe_fail" in r]) == 1
    # the trigger pinned a capture
    caps = [c for c in rec.captures if c.get("pinned")]
    assert caps
    assert [t["kind"] for t in caps[0]["triggers"]] == ["probe_fail"]
    # only the hit-path variant fails: decode + miss bypass the cache
    vs = pr.probez()["variants"]
    assert vs["prefix_hit"]["failing"]
    assert not vs["decode"]["failing"]
    assert not vs["prefix_miss"]["failing"]
    rec.detach()


def test_router_ejects_failing_replica_and_redispatches(served_model):
    """Acceptance: a correctness-failing replica leaves routing like a
    dead one — drained + ejected, in-flight work redispatched elsewhere
    bit-identically — while the fleet keeps serving."""
    m, cfg = served_model
    store = GoldenStore()
    reg = ReplicaRegistry()
    probers = {}
    for i in range(3):
        name = f"r{i}"
        eng = _engine(m)
        reg.add(name, eng)
        pr = Prober(eng, store=store, replica=name).warm()
        reg._handles[name].prober = pr
        probers[name] = pr
    router = FleetRouter(reg)

    lens = [CAP, 5, 3]
    rng = np.random.RandomState(7)
    ids = rng.randint(1, cfg.vocab_size, (len(lens), CAP)).astype(np.int64)
    for r, ln in enumerate(lens):
        ids[r, ln:] = 0
    ref = m.generate_static_ragged(
        paddle.to_tensor(ids), lens, max_new_tokens=NEW).numpy()[:, CAP:]

    # corrupt the cached block of the replica that serves prompt 0: the
    # probe catches it, then freshly-dispatched work lands on the (not
    # yet ejected) victim and the router's next step must eject it and
    # redispatch — the chains still match the oracle bit-for-bit
    victim = router.rank(router.routing_key(ids[0, :lens[0]]))[0]
    pv = probers[victim]
    blks = pv.probe_blocks("prefix_hit")
    pv.engine.chaos = Injector(0).add(
        CorruptKVBlock(engine=pv.engine, block=blks[0]))
    pv.probe_once()                              # sentinel fires
    assert pv.failing
    freqs = [router.submit(ids[i, :lens[i]]) for i in range(len(lens))]
    done = []
    for _ in range(200):
        done += router.step()
        if len(done) == len(lens):
            break
    assert router.counters["probe_ejected"] == 1
    assert victim in reg.ejected
    assert reg.ejected[victim].ejected_reason.startswith("probe_fail:")
    assert victim not in reg.names(("serving",))
    assert len(reg.names(("serving",))) == 2     # fleet keeps serving
    assert [f.status for f in freqs] == ["done"] * len(lens)
    for i, f in enumerate(freqs):
        np.testing.assert_array_equal(f.request.tokens, ref[i])


def test_router_settles_requests_finished_by_a_local_step_loop(
        served_model):
    """A Prober cycle steps its engine to complete the probe — and can
    finish a router-dispatched request along the way. That step()'s
    `finished` list goes to the prober, so the router must settle the
    FleetRequest by the shared Request's terminal status (the
    _step_once sweep); without it the request pends forever."""
    m, cfg = served_model
    reg = ReplicaRegistry()
    eng = _engine(m)
    reg.add("r0", eng)
    pr = Prober(eng, replica="r0").warm()
    reg._handles["r0"].prober = pr
    router = FleetRouter(reg)

    prompt = np.arange(1, 6, dtype=np.int64)
    freq = router.submit(prompt)
    assert freq.status == "pending"
    # probe cycles ride the engine NOW: their internal step loops run
    # the user request to completion and swallow the finished lists
    for _ in range(5):
        pr.probe_once()
        if freq.request.status == "done":
            break
    assert freq.request.status == "done"      # engine-side: terminal
    assert freq.status == "pending"           # router hasn't looked yet
    done = router.step()
    assert freq in done and freq.status == "done"
    padded = np.pad(prompt, (0, CAP - prompt.size)).reshape(1, -1)
    ref = m.generate_static_ragged(
        paddle.to_tensor(padded), [prompt.size],
        max_new_tokens=NEW).numpy()[:, CAP:]
    np.testing.assert_array_equal(freq.request.tokens, ref[0])


# ------------------------------------------------------ invariant auditor

def test_invariant_auditor_clean_engine_all_green(served_model):
    m, cfg = served_model
    eng = _engine(m)
    pr = Prober(eng, replica="r0").warm()
    aud = InvariantAuditor(eng, lock=pr.lock)
    s = aud.audit()
    assert s["ok"] == {c: True for c in InvariantAuditor.CHECKS}
    assert not s["violating"] and s["violations_total"] == 0
    text = aud.metrics_text()
    assert 'paddle_tpu_invariant_ok{check="pool_conservation"} 1' in text
    assert 'paddle_tpu_invariant_ok{check="trie_pool"} 1' in text


def test_invariant_auditor_transition_rows_on_seeded_violations(
        served_model):
    m, _ = served_model
    eng = _engine(m)
    Prober(eng, replica="r0").warm()             # seeds trie + traffic
    rows = []
    eng.metrics.on_record = rows.append
    aud = InvariantAuditor(eng)
    aud.audit()
    assert not aud.violating

    # seed a conservation break: leak one block off the free list
    leaked = eng._pool._free.pop()
    aud.audit()
    assert aud.violating
    v = [r for r in rows if "invariant_violation" in r]
    assert len(v) == 1
    assert v[0]["invariant_violation"]["check"] == "pool_conservation"
    aud.audit()                                  # sustained: still ONE row
    assert len([r for r in rows if "invariant_violation" in r]) == 1
    eng._pool._free.append(leaked)               # repair
    aud.audit()
    assert not aud.violating
    clears = [r for r in rows if "invariant_clear" in r]
    assert len(clears) == 1
    assert clears[0]["invariant_clear"]["check"] == "pool_conservation"

    # a refcount break is the owner_refcounts check's job
    blocks = [b for b, r in eng._pool._refs.items() if r > 0]
    eng._pool._refs[blocks[0]] += 1
    aud.audit()
    assert aud.violating
    kinds = {r["invariant_violation"]["check"]
             for r in rows if "invariant_violation" in r}
    assert "owner_refcounts" in kinds
    eng._pool._refs[blocks[0]] -= 1
    aud.audit()
    assert not aud.violating


# ----------------------------------------------------------- fleet merge

def test_fleet_probez_merges_and_flags_config_drift():
    agg = FleetAggregator()
    findings = []
    agg.on_finding = findings.append
    probez = {
        "r0": {"state": "passing", "variants": {"decode": {}},
               "fingerprint": "aaaa"},
        "r1": {"state": "failing", "variants": {"decode": {
            "failing": True}}, "fingerprint": "bbbb"},
        "r2": {"error": "not found"},            # no prober attached
    }
    statusz = {
        "r0": {"fingerprint": {"sha": "aaaa"}},
        "r1": {"fingerprint": {"sha": "bbbb"}},  # the drifted member
        "r2": {"fingerprint": {"sha": "aaaa"}},
    }
    agg._scrape_route = lambda route, decode, ok_codes=(): \
        dict(probez) if route == "/probez" else dict(statusz)
    out = agg.fleet_probez()
    assert out["summary"]["failing"] == ["r1"]
    assert out["summary"]["with_prober"] == 2
    assert out["summary"]["config_drift"]
    assert out["summary"]["fingerprints"]["r1"] == "bbbb"
    assert len(findings) == 1 and "config_drift" in findings[0]
    assert findings[0]["config_drift"]["fingerprints"]["r2"] == "aaaa"
    agg.fleet_probez()                           # sustained drift: one row
    assert len(findings) == 1
    statusz["r1"]["fingerprint"]["sha"] = "aaaa"  # drift repaired
    out = agg.fleet_probez()
    assert not out["summary"]["config_drift"]
    agg.fleet_probez()                           # re-entry fires again
    statusz["r1"]["fingerprint"]["sha"] = "cccc"
    agg.fleet_probez()
    assert len(findings) == 2
    agg.close()


def test_served_probez_route_and_fleet_scrape(served_model):
    m, _ = served_model
    eng = _engine(m)
    pr = Prober(eng, replica="r0").warm()
    srv = eng.serve_telemetry(prober=pr)
    try:
        agg = FleetAggregator({"r0": srv.url("/")}, cache_ttl=0.0)
        out = agg.fleet_probez()
        assert out["summary"]["with_prober"] == 1
        assert out["summary"]["failing"] == []
        sha = eng.fingerprint()["sha"]
        assert out["summary"]["fingerprints"] == {"r0": sha}
        assert out["per_replica"]["r0"]["state"] == "passing"
        assert "invariants" in out["per_replica"]["r0"]
        page = agg.merged_metrics()
        assert "paddle_tpu_probe_cycles_total" in page
        assert "paddle_tpu_invariant_audits_total" in page
        agg.close()
    finally:
        srv.close()


# ------------------------------------------------- straggler granularity

def test_stepmonitor_flushes_jsonl_on_straggler_transition(tmp_path):
    """Satellite (the r16 NOTE): with a buffered JSONL cadence, a
    straggler/straggler_clear transition forces the flush — a live
    load_shard_walls reader sees skew events at transition granularity,
    never `flush_every` rows late."""
    path = str(tmp_path / "shard_0.jsonl")
    mon = StepMonitor(jsonl_path=path, track_memory=False,
                      jsonl_flush_every=64, straggler_threshold=1.5)
    for step in range(1, 4):
        mon._emit({"step": step, "wall_s": 0.1})
    # buffered: nothing durable yet (3 rows < 64)
    assert not os.path.exists(path) or os.path.getsize(path) == 0
    mon.record_shard_steps({"0": 0.1, "1": 0.9}, step=4)
    with open(path) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert any("straggler" in r for r in lines)  # durable NOW
    assert len(lines) == 4                       # the buffer came along
    mon.record_shard_steps({"0": 0.1, "1": 0.1}, step=5)
    with open(path) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert any("straggler_clear" in r for r in lines)
    mon.close()
    walls = load_shard_walls({"0": path})
    assert set(walls) == {1, 2, 3}               # step rows stitch; the
    #                                              event rows are skipped


def test_stepmonitor_default_flush_unchanged(tmp_path):
    """flush_every=1 (the default) keeps the historical open-per-row
    behavior: every row durable immediately, no handle held."""
    path = str(tmp_path / "m.jsonl")
    mon = StepMonitor(jsonl_path=path, track_memory=False)
    mon._emit({"step": 1, "wall_s": 0.1})
    with open(path) as f:
        assert len(f.readlines()) == 1
    assert mon._jsonl_f is None
    mon.close()
