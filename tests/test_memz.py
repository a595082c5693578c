"""HBM ledger (ISSUE 18) — owner-attributed memory accounting invariants.

The contract under test:

  1. LEDGER — registration/push/pull semantics, the bounded delta ring,
     overlay owners excluded from the conservation sum, host owners never
     summed against HBM, a broken reader degrading to a stale value.
  2. CONSERVATION — census() reconciles attributed + unattributed ≡ the
     allocator view, pinned on a LIVE paged engine under churn
     (admissions, frees, prefix COW) with /memz scraped concurrently at
     ZERO post-warmup jit cache misses.
  3. HEADROOM — one {"headroom_low"} row per episode, armed as a
     flight-recorder trigger; the *_clear twin is inert on the bus.
  4. FORENSICS — post_mortem() writes the census + growth-curve artifact
     (largest owner in the head row), round-trips through
     load_postmortem/render_report, and fires from the real seams: a
     chaos-injected AllocFailure in the serving step and a TrainStep
     launch failure. kv_oom rejects name the top owners; admission
     stalls emit paired mem_pressure episode rows.
  5. WIRING — TrainStep registers params/opt-state after compile,
     CheckpointManager tracks the in-flight snapshot (host tier),
     StepMonitor samples the ledger EVERY record (the r7 rationing fix),
     FleetAggregator merges /memz with dead/ledger-less members degraded
     around, never fatal.
"""
import json
import os
import threading
import urllib.error
from urllib.request import urlopen

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.jit.api import compile_cache_misses
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.obs import (FleetAggregator, MemoryLedger, MetricsRegistry,
                            TelemetryServer, lint_exposition, looks_like_oom)
from paddle_tpu.obs.memz import load_postmortem, render_report
from paddle_tpu.resilience import AllocFailure, Injector


# ------------------------------------------------------------------ ledger

class TestLedgerCore:
    def test_push_pull_and_detail(self):
        led = MemoryLedger(allocated_fn=lambda: 1000)
        led.set("a", 600, kind="params")
        state = {"bytes": 300, "used": 5}
        led.register("b", lambda: state, kind="kv")
        assert led.attributed_bytes() == 900
        c = led.census()
        assert c["attributed_bytes"] == 900
        assert c["allocated_bytes"] == 1000
        assert c["unattributed_bytes"] == 100
        b = next(d for d in c["owners"] if d["owner"] == "b")
        assert b["detail"] == {"used": 5}
        # owners sort largest-first
        assert [d["owner"] for d in c["owners"]] == ["a", "b"]

    def test_duplicate_register_raises_replace_rebinds(self):
        led = MemoryLedger()
        led.register("a", lambda: 1)
        with pytest.raises(ValueError):
            led.register("a", lambda: 2)
        led.register("a", lambda: 2, replace=True)
        assert led.sample().census(reconcile=False)["owners"][0]["bytes"] == 2

    def test_overlay_and_host_excluded_from_conservation_sum(self):
        led = MemoryLedger(allocated_fn=lambda: 500)
        led.set("pool", 400, kind="kv")
        led.set("cache", 250, kind="kv", overlay=True)   # inside pool
        led.set("spill", 9000, kind="spill", device=False)
        assert led.attributed_bytes() == 400
        c = led.census()
        assert c["unattributed_bytes"] == 100            # not -8150
        assert {d["owner"] for d in c["owners"]} == {"pool", "cache"}
        assert [d["owner"] for d in c["host_owners"]] == ["spill"]
        assert next(d for d in c["owners"]
                    if d["owner"] == "cache").get("overlay") is True

    def test_delta_ring_bounded_and_high_watermarks(self):
        led = MemoryLedger(delta_ring=4)
        for i in range(10):
            led.set("a", (i % 3) * 100)
        assert len(led.deltas()) == 4
        assert led.deltas(2) == led.deltas()[-2:]
        c = led.census(reconcile=False)
        assert c["owners"][0]["high_watermark_bytes"] == 200
        # no-change sets append nothing
        n = len(led.deltas())
        led.set("a", led.census(reconcile=False)["owners"][0]["bytes"])
        assert len(led.deltas()) == n

    def test_broken_reader_degrades_to_stale_value(self):
        led = MemoryLedger()
        state = {"v": 100, "boom": False}

        def reader():
            if state["boom"]:
                raise RuntimeError("reader died")
            return state["v"]
        led.register("a", reader)
        state["boom"] = True
        c = led.census(reconcile=False)      # samples; must not raise
        assert c["owners"][0]["bytes"] == 100

    def test_quick_stats_and_top_owners(self):
        led = MemoryLedger()
        led.set("big", 500).set("small", 10).set("zero", 0)
        led.set("host", 999, device=False)
        assert led.top_owners(2) == [{"owner": "big", "bytes": 500},
                                     {"owner": "small", "bytes": 10}]
        led.set("big", 50)
        q = led.quick_stats()
        assert q == {"bytes_in_use": 60, "peak_bytes_in_use": 510,
                     "source": "memz_ledger"}

    def test_looks_like_oom(self):
        assert looks_like_oom(MemoryError())
        assert looks_like_oom(RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 1073741824"))
        assert looks_like_oom(ValueError("failed to allocate 8 bytes"))
        assert not looks_like_oom(KeyError("kv_pool"))


# ------------------------------------------------- headroom + exposition

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "mini_step.trace.json.gz")


class TestHeadroomAndMetrics:
    def test_one_row_per_episode_and_flightrec_trigger(self, tmp_path):
        from paddle_tpu.obs import FixtureBackend, FlightRecorder
        alloc = {"v": 100}
        led = MemoryLedger(capacity_bytes=1000,
                           allocated_fn=lambda: alloc["v"],
                           headroom_low_frac=0.2)
        led.set("pool", 100, kind="kv")
        rec = FlightRecorder(str(tmp_path / "cap"),
                             backend=FixtureBackend(FIXTURE),
                             cooldown_s=0.0)
        led.on_row = rec.tap
        assert led.check_headroom() is None          # plenty of headroom
        alloc["v"] = 950                             # headroom 50 < 200
        row = led.check_headroom()
        assert "headroom_low" in row
        assert row["headroom_low"]["top_owners"][0]["owner"] == "pool"
        assert rec.triggers_total == 1               # capture armed
        assert led.check_headroom() is None          # same episode: 1 row
        assert led.headroom_low_total == 1
        alloc["v"] = 100
        clear = led.check_headroom()
        assert "headroom_low_clear" in clear
        assert rec.triggers_total == 1               # *_clear is inert

    def test_metrics_text_lints_through_registry(self):
        led = MemoryLedger(capacity_bytes=1 << 20,
                           allocated_fn=lambda: 4096)
        led.set("pool", 4000, kind="kv")
        led.set("cache", 100, kind="kv", overlay=True)
        led.set("spill", 77, kind="spill", device=False)
        reg = MetricsRegistry()
        reg.register("memz", lambda: led.metrics_text())
        page = reg.render()
        lint_exposition(page)
        assert 'paddle_tpu_hbm_bytes{owner="pool"} 4000' in page
        assert 'paddle_tpu_host_bytes{owner="spill"} 77' in page
        assert "paddle_tpu_hbm_attributed_bytes 4000" in page
        assert "paddle_tpu_hbm_unattributed_bytes 96" in page
        assert f"paddle_tpu_hbm_headroom_bytes {(1 << 20) - 4096}" in page

    def test_headroom_gauge_absent_without_capacity(self):
        led = MemoryLedger(allocated_fn=lambda: 100)
        led.set("a", 100)
        assert "hbm_headroom_bytes" not in led.metrics_text()


# ------------------------------------------------------------- forensics

class TestPostMortem:
    def _ledger(self, tmp_path):
        led = MemoryLedger(capacity_bytes=1000, allocated_fn=lambda: 900,
                           postmortem_dir=str(tmp_path))
        led.set("kv_pool", 700, kind="kv")
        led.set("model_params", 150, kind="params")
        led.set("spill", 42, kind="spill", device=False)
        return led

    def test_artifact_round_trip_and_rendering(self, tmp_path):
        led = self._ledger(tmp_path)
        err = RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        path = led.post_mortem(error=err, context={"step": 7})
        assert path and os.path.exists(path)
        assert led.postmortems_total == 1
        pm = load_postmortem(path)
        assert pm["oom"]["largest_owner"] == "kv_pool"
        assert pm["oom"]["is_alloc_failure"] is True
        assert pm["oom"]["context"] == {"step": 7}
        assert pm["census"]["unattributed_bytes"] == 50
        assert pm["deltas"]                      # the growth curve rows
        text = render_report(path)
        assert "largest owner: kv_pool" in text
        assert "step=7" in text and "unattributed" in text
        assert "spill" in text                   # host tier rendered

    def test_dump_failure_never_masks_the_oom(self, tmp_path):
        blocker = tmp_path / "not_a_dir"
        blocker.write_text("file where the artifact dir should go")
        led = self._ledger(tmp_path)
        assert led.post_mortem(error=MemoryError(),
                               dir=str(blocker)) is None

    def test_load_rejects_non_artifact(self, tmp_path):
        p = tmp_path / "x.jsonl"
        p.write_text('{"other": 1}\n')
        with pytest.raises(ValueError):
            load_postmortem(str(p))


# ----------------------------------------------------------- live engine

@pytest.fixture(scope="module")
def live():
    """One warmed paged engine + attached ledger, shared by the live
    tests (executable builds dominate this file's wall time)."""
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_heads=2, max_position_embeddings=32,
                    intermediate_size=64)
    model = GPTForCausalLM(cfg)
    model.eval()
    engine = ServingEngine(model, ServingConfig(
        max_batch=2, prompt_cap=8, max_new_tokens=4, decode_chunk=2,
        kv_block=4, kv_blocks=16, prefix_cache=True))
    ledger = engine.attach_memory_ledger(
        MemoryLedger(capacity_bytes=1 << 30))
    rng = np.random.RandomState(0)
    prefix = rng.randint(1, 64, (4,)).astype(np.int64)
    prompts = []
    for i in range(6):
        if i % 2:
            sfx = rng.randint(1, 64, (int(rng.randint(1, 4)),))
            prompts.append(np.concatenate([prefix, sfx]).astype(np.int64))
        else:
            prompts.append(rng.randint(1, 64, (int(rng.randint(3, 8)),))
                           .astype(np.int64))
    for p in prompts:          # build every executable the churn touches
        engine.submit(p)
    engine.drain()
    for p in prompts[:2]:      # the zero-prefill COW admission path
        engine.submit(p)
    engine.drain()
    # the CPU live-array fallback counts EVERY live array in the
    # process — other test files' jit constants and cached models are
    # "foreign" bytes this engine's owners rightly never claim. Baseline
    # the residual post-warmup; conservation under churn is then pinned
    # as "the residual does not DRIFT" (in a fresh process, e.g. the
    # tier-1 memz_smoke leg, the baseline itself is ~0)
    c0 = ledger.census()
    return {"model": model, "cfg": cfg, "engine": engine,
            "ledger": ledger, "prompts": prompts,
            "unattr0": c0["unattributed_bytes"] or 0}


class TestLiveEngine:
    def test_conservation_under_churn_with_concurrent_memz(self, live):
        engine, ledger = live["engine"], live["ledger"]
        prompts = live["prompts"]
        miss0 = compile_cache_misses()
        srv = engine.serve_telemetry()
        errors, scrapes = [], [0]
        stop = threading.Event()

        def scrape():
            while not stop.is_set():
                try:
                    m = json.loads(urlopen(srv.url("/memz?deltas=8"),
                                           timeout=5).read())
                    assert any(o["owner"] == "kv_pool"
                               for o in m["owners"])
                    assert m["allocated_bytes"] is not None
                    scrapes[0] += 1
                except Exception as e:       # noqa: BLE001 — the gate
                    errors.append(f"{type(e).__name__}: {e}")
                    return
                stop.wait(0.02)
        t = threading.Thread(target=scrape, daemon=True)
        t.start()
        try:
            for b in range(3):
                for p in prompts[2 * b:2 * b + 2]:
                    engine.submit(p)
                engine.drain()
                c = ledger.census()
                alloc, unattr = (c["allocated_bytes"],
                                 c["unattributed_bytes"])
                assert alloc is not None
                drift = abs(unattr - live["unattr0"])
                assert drift <= 0.15 * c["attributed_bytes"], c
        finally:
            stop.set()
            t.join(timeout=5)
            srv.close()
        assert not errors, errors
        assert scrapes[0] >= 1
        assert compile_cache_misses() - miss0 == 0   # scrape never syncs
        # statusz carries the compact memory block
        s = engine.statusz()
        assert s["memory"]["owners"]["model_params"] > 0
        assert "kv_pool" in s["memory"]["owners"]

    def test_memz_route_rejects_bad_deltas(self, live):
        with pytest.raises(ValueError):
            live["ledger"].memz({"deltas": "abc"})

    def test_kv_oom_reject_names_top_owners(self, live):
        eng = ServingEngine(live["model"], ServingConfig(
            max_batch=2, prompt_cap=12, max_new_tokens=8, decode_chunk=4,
            kv_block=4, kv_blocks=5))
        eng.attach_memory_ledger()
        # 12 + 8 - 1 = 19 rows > the whole pool (4 usable blocks = 16)
        f = eng.preflight(np.arange(1, 13, dtype=np.int64), 8)
        oom = [x for x in f if x.code == "kv_oom"]
        assert len(oom) == 1
        assert "top HBM owners" in oom[0].message
        owners = [t["owner"] for t in oom[0].data["top_owners"]]
        assert "model_params" in owners and "kv_pool" in owners

    def test_mem_pressure_rows_paired_per_episode(self, live):
        eng = ServingEngine(live["model"], ServingConfig(
            max_batch=2, prompt_cap=12, max_new_tokens=4, decode_chunk=2,
            kv_block=4, kv_blocks=6))
        eng.attach_memory_ledger()
        rows = []
        eng.metrics.on_record = rows.append
        rng = np.random.RandomState(1)
        for _ in range(4):
            eng.submit(rng.randint(1, 64, (10,)).astype(np.int64))
        eng.drain()
        enter = [r for r in rows if "mem_pressure" in r]
        clear = [r for r in rows if "mem_pressure_clear" in r]
        assert len(enter) >= 1 and len(enter) == len(clear)
        body = enter[0]["mem_pressure"]
        assert body["need_rows"] > 0 and "top_owners" in body
        assert (eng.metrics.counters["mem_pressure_episodes"]
                == len(enter))
        assert all("waited_s" in c["mem_pressure_clear"] for c in clear)

    def test_injected_alloc_failure_dumps_post_mortem(self, live, tmp_path):
        engine, ledger = live["engine"], live["ledger"]
        old_dir = ledger.postmortem_dir
        ledger.postmortem_dir = str(tmp_path)
        engine.chaos = Injector(faults=[AllocFailure()])
        try:
            engine.submit(live["prompts"][0])
            with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
                while engine.busy:
                    engine.step()
            assert engine.chaos.fired("alloc_failure") == 1
        finally:
            engine.chaos = None
            ledger.postmortem_dir = old_dir
        arts = sorted(p for p in os.listdir(tmp_path)
                      if p.endswith(".jsonl"))
        assert len(arts) == 1
        pm = load_postmortem(str(tmp_path / arts[0]))
        assert pm["oom"]["context"]["site"] == "serving.step"
        assert pm["oom"]["largest_owner"] in ("model_params", "kv_pool")
        # the engine stays servable after the unwind
        r = engine.submit(live["prompts"][1])
        engine.drain()
        assert r.status == "done"


# ------------------------------------------------------- train/ckpt/monitor

class TestTrainingSeams:
    def test_train_step_registers_params_and_opt_state(self):
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models import GPTPretrainingCriterion
        from paddle_tpu.profiler.monitor import StepMonitor
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                        num_heads=2, max_position_embeddings=16,
                        intermediate_size=64)
        model = GPTForCausalLM(cfg)
        crit = GPTPretrainingCriterion(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        led = MemoryLedger(allocated_fn=lambda: None)
        mon = StepMonitor()
        step = TrainStep(model, opt,
                         lambda ids, lbl: crit(model(ids), lbl),
                         monitor=mon, memz=led)
        ids = paddle.to_tensor(np.random.RandomState(0)
                               .randint(0, 64, (2, 8)).astype("int32"))
        step(ids, ids)
        c = led.census(reconcile=False)
        by = {d["owner"]: d["bytes"] for d in c["owners"]}
        assert by["train_params"] > 0
        # AdamW carries two moments: opt state outweighs the params
        assert by["train_opt_state"] > by["train_params"]
        assert mon.memz is led               # monitor rides the ledger

    def test_launch_oom_dumps_train_post_mortem(self, tmp_path):
        from paddle_tpu.jit import TrainStep
        led = MemoryLedger(postmortem_dir=str(tmp_path))
        led.set("train_opt_state", 500, kind="opt_state")
        model = paddle.nn.Linear(2, 2)
        opt = paddle.optimizer.SGD(learning_rate=1e-2,
                                   parameters=model.parameters())
        ts = TrainStep(model, opt, lambda x: x, memz=led)

        def boom(*_a):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        with pytest.raises(RuntimeError):
            ts._launch(boom)
        arts = [p for p in os.listdir(tmp_path) if p.endswith(".jsonl")]
        assert len(arts) == 1
        pm = load_postmortem(str(tmp_path / arts[0]))
        assert pm["oom"]["context"]["site"] == "train_step.launch"
        assert pm["oom"]["largest_owner"] == "train_opt_state"
        # a NON-OOM failure must not dump an artifact
        def bug(*_a):
            raise ValueError("shape mismatch")
        with pytest.raises(ValueError):
            ts._launch(bug)
        assert len([p for p in os.listdir(tmp_path)
                    if p.endswith(".jsonl")]) == 1

    def test_checkpoint_inflight_snapshot_tracked(self, tmp_path):
        from paddle_tpu.resilience import CheckpointManager
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        led = MemoryLedger()
        mgr.memz = led
        w = np.zeros((64, 64), dtype=np.float32)
        h = mgr.save(1, {"w": w}, async_save=True)
        h.wait()
        c = led.census(reconcile=False)
        owner = next(d for d in c["host_owners"]
                     if d["owner"] == "ckpt_inflight")
        assert owner["bytes"] == 0                    # released on commit
        assert owner["high_watermark_bytes"] == w.nbytes

    def test_monitor_samples_ledger_every_record(self):
        from paddle_tpu.profiler.monitor import StepMonitor
        led = MemoryLedger()
        led.set("train_params", 1234, kind="params")
        mon = StepMonitor()
        mon.memz = led
        for _ in range(5):       # r7 rationing would skip records 2..5
            mon.begin_step()
            mon.end_step(items=1)
        assert all(r.get("hbm_bytes_in_use") == 1234
                   for r in mon.records)


# ------------------------------------------------------------------ fleet

class TestFleetMemz:
    def test_merge_labels_sums_and_degrades(self):
        la = MemoryLedger(capacity_bytes=1100, allocated_fn=lambda: 1000,
                          headroom_low_frac=0.10)
        la.set("kv_pool", 600, kind="kv").set("model_params", 300,
                                              kind="params")
        lb = MemoryLedger(allocated_fn=lambda: None)   # no allocator view
        lb.set("kv_pool", 50, kind="kv")
        srvs = [TelemetryServer(MetricsRegistry(),
                                routes={"/memz": la.memz}).start(),
                TelemetryServer(MetricsRegistry(),
                                routes={"/memz": lb.memz}).start(),
                TelemetryServer(MetricsRegistry()).start()]   # no ledger
        dead = TelemetryServer(MetricsRegistry()).start()
        dead.close()
        try:
            fleet = FleetAggregator(
                {"a": srvs[0], "b": srvs[1], "bare": srvs[2],
                 "dead": dead}, timeout=1.0, cache_ttl=0.0)
            fm = fleet.fleet_memz()
            s = fm["summary"]
            assert s["replicas"] == 4
            assert s["with_ledger"] == 2          # bare 404s, dead is gone
            assert s["attributed_bytes"] == 950
            # b has no allocator view: those sums degrade to None,
            # never invent bytes
            assert s["allocated_bytes"] is None
            assert s["unattributed_bytes"] is None
            # a: headroom 100 < 10% of 1100 -> flagged by replica name
            assert s["headroom_low"] == ["a"]
            top = fm["owners"][0]
            assert (top["owner"], top["replica"],
                    top["bytes"]) == ("kv_pool", "a", 600)
            assert set(fm["per_replica"]) == {"a", "b"}
        finally:
            for srv in srvs:
                srv.close()

    def test_fleet_memz_route_served(self):
        led = MemoryLedger(allocated_fn=lambda: 100)
        led.set("kv_pool", 80, kind="kv")
        srv = TelemetryServer(MetricsRegistry(),
                              routes={"/memz": led.memz}).start()
        fsrv = None
        try:
            fleet = FleetAggregator({"r0": srv}, timeout=1.0)
            fsrv = fleet.serve()
            fm = json.loads(urlopen(fsrv.url("/fleet/memz"),
                                    timeout=5).read())
            assert fm["summary"]["attributed_bytes"] == 80
            assert fm["owners"][0]["replica"] == "r0"
        finally:
            if fsrv is not None:
                fsrv.close()
            srv.close()
