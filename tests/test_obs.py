"""The fleet's sensory layer (ISSUE 12): unified MetricsRegistry +
promtool-style exposition lint, the stdlib telemetry HTTP server
(/metrics /healthz /statusz /tracez), tail-sampled per-request trace
export, and declarative SLO burn-rate monitors.

Acceptance pins: merged exposition pages are collision-checked and
conform (HELP/TYPE ordering, cumulative buckets, +Inf == count — the
per-block invariants from test_serving.py extended to the MERGED page);
tail sampling keeps every timed-out/rejected request and the slowest
decile under a bounded ring; SLO alerts fire deterministically under
injected latency and stay silent on the clean run; a live engine serves
all four endpoints concurrently with decode at zero post-warmup jit
misses.
"""
import json
import threading
import urllib.error
from urllib.request import urlopen

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (Request, ServingConfig, ServingEngine,
                                  ServingMetrics)
from paddle_tpu.jit.api import compile_cache_misses
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.obs import (ExpositionError, MetricsCollisionError,
                            MetricsRegistry, SLOMonitor, TraceBuffer,
                            evaluate_slo, lint_exposition, parse_slo)
from paddle_tpu.profiler import StepMonitor
from paddle_tpu.profiler._metrics import parse_exposition


def _done_request(rid, e2e, *, status="done", ttft=None, n_out=4):
    """A terminal Request with a synthetic trace, for metrics feeding."""
    r = Request(id=rid, prompt=np.arange(1, 5), max_new_tokens=4,
                status=status, n_out=n_out if status == "done" else 0)
    t = r.trace
    t.trace_id = f"t-{rid}"
    t.t_enqueue = 0.0
    t.t_admit = 0.01
    if status == "done":
        t.t_prefill_done = 0.02
        t.t_first_token = ttft if ttft is not None else e2e * 0.5
        t.t_finish = e2e
    else:
        t.t_finish = e2e
        if status == "rejected":
            r.reason = "queue_full"
        elif status == "timeout":
            r.reason = "queue_deadline"
    return r


def _fed_metrics(latencies, **kw):
    met = ServingMetrics(**kw)
    for i, e2e in enumerate(latencies):
        met.record_request(_done_request(i, float(e2e)))
    return met


# ------------------------------------------------- exposition conformance

GOOD = """# HELP demo_requests_total requests
# TYPE demo_requests_total counter
demo_requests_total 5
# HELP demo_lat_seconds latency
# TYPE demo_lat_seconds histogram
demo_lat_seconds_bucket{le="0.1"} 2
demo_lat_seconds_bucket{le="1"} 4
demo_lat_seconds_bucket{le="+Inf"} 5
demo_lat_seconds_sum 3.5
demo_lat_seconds_count 5
"""


class TestExpositionLint:
    def test_good_page_parses_and_lints(self):
        fams = lint_exposition(GOOD)
        assert fams["demo_requests_total"]["type"] == "counter"
        assert fams["demo_lat_seconds"]["type"] == "histogram"

    def test_sample_without_type_rejected(self):
        with pytest.raises(ExpositionError, match="no preceding"):
            parse_exposition("demo_x 1\n")

    def test_type_before_help_rejected(self):
        with pytest.raises(ExpositionError, match="before its HELP"):
            parse_exposition("# TYPE demo_x gauge\ndemo_x 1\n")

    def test_interleaved_families_rejected(self):
        text = ("# HELP a_total a\n# TYPE a_total counter\na_total 1\n"
                "# HELP b b\n# TYPE b gauge\nb 2\n"
                "a_total 3\n")
        with pytest.raises(ExpositionError, match="contiguous|duplicate"):
            parse_exposition(text)

    def test_duplicate_sample_rejected(self):
        text = "# HELP b b\n# TYPE b gauge\nb 2\nb 3\n"
        with pytest.raises(ExpositionError, match="duplicate sample"):
            parse_exposition(text)

    def test_malformed_sample_rejected(self):
        with pytest.raises(ExpositionError, match="malformed"):
            parse_exposition("# HELP b b\n# TYPE b gauge\nb = oops\n")

    def test_counter_must_end_in_total(self):
        text = "# HELP c c\n# TYPE c counter\nc 1\n"
        with pytest.raises(ExpositionError, match="_total"):
            lint_exposition(text)

    def test_noncumulative_buckets_rejected(self):
        bad = GOOD.replace('demo_lat_seconds_bucket{le="1"} 4',
                           'demo_lat_seconds_bucket{le="1"} 1')
        with pytest.raises(ExpositionError, match="cumulative"):
            lint_exposition(bad)

    def test_inf_bucket_must_equal_count(self):
        bad = GOOD.replace("demo_lat_seconds_count 5",
                           "demo_lat_seconds_count 7")
        with pytest.raises(ExpositionError, match="_count"):
            lint_exposition(bad)

    def test_descending_le_rejected(self):
        bad = GOOD.replace('le="0.1"', 'le="2"')
        with pytest.raises(ExpositionError, match="ascend"):
            lint_exposition(bad)


class TestMetricsRegistry:
    def test_merged_engine_blocks_are_conformant(self):
        """The satellite pin: ServingMetrics + StepMonitor + SLO blocks
        composed through ONE registry parse as one conformant page —
        extending test_serving's per-block invariants to the merge."""
        met = _fed_metrics(np.linspace(0.01, 0.4, 30))
        mon = StepMonitor(items_per_step=4, track_memory=False)
        with mon.step():
            pass
        slo = SLOMonitor("e2e_p99=1s", met, long_s=10, short_s=1)
        slo.poll(1.0)
        reg = MetricsRegistry()
        reg.register("serving",
                     lambda: met.metrics_text(prefix="paddle_tpu_serving"))
        reg.register("batch",
                     lambda: mon.metrics_text(
                         prefix="paddle_tpu_serving_batch"))
        reg.register("slo", slo.metrics_text)
        fams = lint_exposition(reg.render())
        assert "paddle_tpu_serving_e2e_seconds" in fams
        assert "paddle_tpu_serving_batch_steps_total" in fams
        assert "paddle_tpu_slo_burn_rate" in fams

    def test_goodput_block_composes(self):
        from paddle_tpu.profiler.goodput import GoodputReport
        from paddle_tpu.profiler.timeline import SpanRecorder
        rec = SpanRecorder()
        rec.record("step", 0.0, 1.0, step=1)
        rec.record("compile", 1.0, 1.5)
        reg = MetricsRegistry()
        reg.register("goodput",
                     lambda: GoodputReport(rec).metrics_text())
        fams = lint_exposition(reg.render())
        assert fams["paddle_tpu_badput_seconds"]["type"] == "gauge"
        # the labeled family carries every taxonomy category incl. zeros
        cats = [s for s in fams["paddle_tpu_badput_seconds"]["samples"]]
        assert len(cats) >= 8

    def test_family_collision_names_both_producers(self):
        met = _fed_metrics([0.1])
        reg = MetricsRegistry()
        reg.register("a", lambda: met.metrics_text(prefix="p"))
        reg.register("b", lambda: met.metrics_text(prefix="p"))
        with pytest.raises(MetricsCollisionError, match="'a' and 'b'"):
            reg.render()

    def test_unregister_clears_collision(self):
        met = _fed_metrics([0.1])
        reg = MetricsRegistry()
        reg.register("a", lambda: met.metrics_text(prefix="p"))
        reg.register("b", lambda: met.metrics_text(prefix="p"))
        assert reg.unregister("b") and not reg.unregister("b")
        lint_exposition(reg.render())

    def test_duplicate_producer_name_rejected(self):
        reg = MetricsRegistry()
        reg.register("a", lambda: "")
        with pytest.raises(ValueError, match="already registered"):
            reg.register("a", lambda: "")

    def test_render_order_is_registration_order(self):
        reg = MetricsRegistry()
        reg.register("z", lambda: "# HELP z z\n# TYPE z gauge\nz 1\n")
        reg.register("a", lambda: "# HELP a a\n# TYPE a gauge\na 1\n")
        page = reg.render()
        assert page.index("z 1") < page.index("a 1")

    def test_empty_producer_skipped(self):
        reg = MetricsRegistry()
        reg.register("empty", lambda: "")
        assert reg.render() == ""

    def test_broken_block_fails_render(self):
        reg = MetricsRegistry()
        reg.register("bad", lambda: "no_type_sample 1\n")
        with pytest.raises(ExpositionError):
            reg.render()


# ------------------------------------------------------- trace buffering

class TestTraceBuffer:
    def test_capacity_is_a_hard_bound(self):
        buf = TraceBuffer(8)
        for i in range(100):
            buf.add({"status": "done", "e2e_s": 0.1, "trace_id": str(i)})
        s = buf.summary()
        assert s["retained"] == 8 and s["seen"] == 100
        assert s["evicted"] == 92

    def test_failures_always_survive_fast_successes(self):
        """Every timed-out/rejected request stays while fast successes
        churn through — the acceptance pin."""
        buf = TraceBuffer(16)
        fail_ids = []
        for i in range(200):
            if i % 40 == 7:
                st = "timeout" if i % 80 == 7 else "rejected"
                buf.add({"status": st, "trace_id": f"f{i}"})
                fail_ids.append(f"f{i}")
            buf.add({"status": "done", "e2e_s": 0.01,
                     "trace_id": f"d{i}"})
        kept = {t["trace_id"] for t in buf.snapshot(limit=None)}
        assert set(fail_ids) <= kept
        assert len(kept) <= 16
        by_status = buf.summary()["by_status"]
        assert by_status["timeout"] + by_status["rejected"] == 5

    def test_slowest_decile_retained(self):
        """100 requests, capacity 20: every member of the slowest decile
        is still in the ring at the end."""
        rng = np.random.RandomState(3)
        lats = list(rng.uniform(0.01, 0.1, 90)) + \
            list(rng.uniform(5.0, 9.0, 10))
        rng.shuffle(lats)
        buf = TraceBuffer(20, slow_quantile=0.9)
        for i, e2e in enumerate(lats):
            buf.add({"status": "done", "e2e_s": float(e2e),
                     "trace_id": f"r{i}"})
        kept = buf.snapshot(order="slowest", limit=None)
        kept_ids = {t["trace_id"] for t in kept}
        slow_ids = {f"r{i}" for i, e2e in enumerate(lats) if e2e >= 5.0}
        assert slow_ids <= kept_ids
        # and the slowest-first view leads with them
        assert {t["trace_id"] for t in kept[:10]} == slow_ids

    def test_snapshot_filters_and_orders(self):
        buf = TraceBuffer(8)
        buf.add({"status": "done", "e2e_s": 0.5, "trace_id": "a"})
        buf.add({"status": "timeout", "trace_id": "b"})
        buf.add({"status": "done", "e2e_s": 0.1, "trace_id": "c"})
        assert [t["trace_id"] for t in buf.snapshot()] == ["c", "b", "a"]
        assert [t["trace_id"] for t in
                buf.snapshot(status="timeout")] == ["b"]
        assert [t["trace_id"] for t in
                buf.snapshot(order="slowest", limit=1)] == ["a"]
        with pytest.raises(ValueError, match="order"):
            buf.snapshot(order="oldest")

    def test_all_failures_still_bounded(self):
        buf = TraceBuffer(4)
        for i in range(10):
            buf.add({"status": "rejected", "trace_id": str(i)})
        ids = [t["trace_id"] for t in buf.snapshot()]
        assert ids == ["9", "8", "7", "6"]     # oldest failures rotate out


# ------------------------------------------------------------ SLO monitor

class TestSLOParsing:
    def test_grammar(self):
        ts = parse_slo("ttft_p99=500ms, e2e_p95=2s,goodput=0.9,"
                       "tpot_p50=0.05")
        by = {t.name: t for t in ts}
        assert by["ttft_p99"].threshold_s == 0.5
        assert by["ttft_p99"].objective == 0.99
        assert abs(by["ttft_p99"].budget - 0.01) < 1e-12
        assert by["e2e_p95"].threshold_s == 2.0
        assert by["goodput"].hist is None
        assert by["goodput"].objective == 0.9
        assert by["tpot_p50"].threshold_s == 0.05

    def test_bad_specs_raise(self):
        for bad in ("nope_p99=1", "ttft_p99", "goodput=1.5", "",
                    "ttft_p0=1"):
            with pytest.raises(ValueError):
                parse_slo(bad)


class TestSLOEvaluate:
    def test_whole_run_burn_and_attainment(self):
        # 90 fast + 10 slow: p95 target on e2e -> bad_frac 0.1, budget
        # 0.05 -> burn 2.0 = breach; p50 target -> burn 0.2 = ok
        met = _fed_metrics([0.01] * 90 + [10.0] * 10)
        rows = evaluate_slo(parse_slo("e2e_p95=1s"), met)
        assert rows[0]["bad"] == 10 and rows[0]["total"] == 100
        assert abs(rows[0]["burn"] - 2.0) < 1e-6 and not rows[0]["ok"]
        rows = evaluate_slo(parse_slo("e2e_p50=1s"), met)
        assert abs(rows[0]["burn"] - 0.2) < 1e-6 and rows[0]["ok"]

    def test_threshold_inside_a_populated_bucket_counts_good(self):
        """Review-regression pin: requests BELOW the target whose bucket
        straddles the threshold must burn ZERO budget — the containing
        bucket's upper bound is the effective threshold. (The first cut
        excluded that bucket: 100 requests at 450ms against a 500ms
        target reported bad_fraction 1.0 — a guaranteed false page.)"""
        met = _fed_metrics([0.45] * 100)      # all meet a 500ms target
        rows = evaluate_slo(parse_slo("e2e_p99=500ms"), met)
        assert rows[0]["bad"] == 0 and rows[0]["burn"] == 0.0
        assert rows[0]["ok"]
        # and a nominal bucket-bound threshold keeps working despite the
        # bound being stored as 1.0000000000000002
        rows = evaluate_slo(parse_slo("e2e_p99=1s"),
                            _fed_metrics([0.9] * 50))
        assert rows[0]["bad"] == 0 and rows[0]["ok"]
        # observations past the threshold's bucket still count bad
        rows = evaluate_slo(parse_slo("e2e_p99=500ms"),
                            _fed_metrics([0.45] * 99 + [3.0]))
        assert rows[0]["bad"] == 1

    def test_goodput_floor_counts_non_completed(self):
        met = _fed_metrics([0.01] * 8)
        met.record_request(_done_request(90, 1.0, status="rejected"))
        met.record_request(_done_request(91, 1.0, status="timeout"))
        rows = evaluate_slo(parse_slo("goodput=0.5"), met)
        assert rows[0]["bad"] == 2 and rows[0]["total"] == 10
        assert rows[0]["ok"]                       # 80% >= 50% floor
        rows = evaluate_slo(parse_slo("goodput=0.9"), met)
        assert not rows[0]["ok"]                   # 80% < 90% floor


class TestSLOMonitorWindows:
    def _monitor(self, met, **kw):
        base = dict(long_s=60.0, short_s=10.0, burn_threshold=2.0)
        base.update(kw)
        return SLOMonitor(parse_slo("e2e_p90=1s"), met, **base)

    def test_clean_run_stays_silent(self):
        met = ServingMetrics()
        mon = self._monitor(met)
        rid = [0]

        def feed(n, e2e):
            for _ in range(n):
                met.record_request(_done_request(rid[0], e2e))
                rid[0] += 1
        for t in range(0, 120, 5):
            feed(10, 0.01)
            mon.poll(float(t))
        assert mon.alerts_total == 0 and not mon.breaching
        assert mon.alerts == []

    def test_alert_fires_on_sustained_injected_latency(self):
        """Injected latency past the target on every request: both
        windows burn at 10x budget -> exactly ONE structured alert
        (transition), visible through the metrics emission path."""
        seen = []
        met = ServingMetrics(on_record=seen.append)
        mon = self._monitor(met)
        rid = [0]

        def feed(n, e2e):
            for _ in range(n):
                met.record_request(_done_request(rid[0], e2e))
                rid[0] += 1
        for t in range(0, 30, 5):      # healthy warm-up
            feed(10, 0.01)
            mon.poll(float(t))
        for t in range(30, 100, 5):    # injected: every request 5s e2e
            feed(10, 5.0)
            mon.poll(float(t))
        assert mon.breaching and mon.alerts_total == 1
        alert_rows = [r for r in seen if "slo_alert" in r]
        assert len(alert_rows) == 1
        a = alert_rows[0]["slo_alert"]
        assert a["target"] == "e2e_p90" and a["breaching"]
        assert a["burn_long"] >= 2.0 and a["burn_short"] >= 2.0

    def test_short_window_recovery_clears(self):
        """After the injected stretch ends, the SHORT window recovers
        first and the breach clears (one slo_clear event) even while the
        long window still remembers the bad stretch — the multi-window
        point: no paging after recovery."""
        seen = []
        met = ServingMetrics(on_record=seen.append)
        mon = self._monitor(met)
        rid = [0]

        def feed(n, e2e):
            for _ in range(n):
                met.record_request(_done_request(rid[0], e2e))
                rid[0] += 1
        for t in range(0, 30, 5):
            feed(10, 5.0)              # bad stretch
            mon.poll(float(t))
        assert mon.breaching
        for t in range(30, 55, 5):
            feed(10, 0.01)             # recovered
            mon.poll(float(t))
        assert not mon.breaching
        kinds = [("alert" if "slo_alert" in r else "clear")
                 for r in seen if "slo_alert" in r or "slo_clear" in r]
        assert kinds == ["alert", "clear"]
        # the long window alone still carries the bad stretch
        last = mon.summary()["last_eval"][0]
        assert last["burn_long"] > 2.0 and last["burn_short"] < 2.0

    def test_metrics_text_is_conformant(self):
        met = _fed_metrics([0.01] * 10)
        mon = self._monitor(met)
        mon.poll(0.0)
        mon.poll(5.0)
        fams = lint_exposition(mon.metrics_text())
        assert fams["paddle_tpu_slo_alerts_total"]["type"] == "counter"

    def test_poll_time_must_be_monotonic(self):
        mon = self._monitor(ServingMetrics())
        mon.poll(5.0)
        with pytest.raises(ValueError, match="backwards"):
            mon.poll(1.0)


# ---------------------------------------------------- live engine + server

CAP, NEW, BATCH = 8, 6, 2


@pytest.fixture(scope="module")
def served_model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    intermediate_size=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m, cfg


def _prompts(cfg, lens, seed=1):
    rng = np.random.RandomState(seed)
    ids = rng.randint(1, cfg.vocab_size, (len(lens), CAP)).astype(np.int64)
    return [ids[r, :ln] for r, ln in enumerate(lens)]


def _get_json(url):
    return json.loads(urlopen(url, timeout=10).read())


class TestTelemetryServer:
    def test_live_engine_all_endpoints_concurrent_zero_misses(
            self, served_model):
        """The acceptance pin: a live engine under traffic serves all
        four endpoints WHILE decoding — every payload validates, the
        steady loop adds zero jit cache misses with the server attached,
        and /tracez explains the requests it retained."""
        m, cfg = served_model
        eng = ServingEngine(m, ServingConfig(
            max_batch=BATCH, prompt_cap=CAP, max_new_tokens=NEW,
            decode_chunk=3))
        prompts = _prompts(cfg, [CAP, 5, 7, 3, 6, CAP])
        srv = eng.serve_telemetry()
        try:
            for p in prompts[:2]:
                eng.submit(p)
            eng.drain()                          # warmup compiles
            miss0 = compile_cache_misses()

            results, errors = {"passes": 0}, []

            def scrape():
                try:
                    while not stop.is_set():
                        lint_exposition(
                            urlopen(srv.url("/metrics"),
                                    timeout=10).read().decode())
                        h = _get_json(srv.url("/healthz"))
                        assert h["status"] == "ok"
                        s = _get_json(srv.url("/statusz"))
                        assert s["kv"]["blocks_total"] == \
                            eng.config.kv_blocks
                        _get_json(srv.url("/tracez"))
                        results["passes"] += 1
                except Exception as e:           # noqa: BLE001
                    errors.append(e)

            stop = threading.Event()
            th = threading.Thread(target=scrape, daemon=True)
            th.start()
            try:
                for _ in range(3):
                    for p in prompts:
                        eng.submit(p)
                    eng.drain()
            finally:
                stop.set()
                th.join(timeout=10)
            assert not errors, errors
            assert results["passes"] >= 1
            assert compile_cache_misses() - miss0 == 0
            assert eng.monitor.recompiles == 0

            tz = _get_json(srv.url("/tracez?order=slowest&limit=100"))
            assert tz["summary"]["retained"] == 20   # 2 warmup + 18
            for tr in tz["traces"]:
                assert tr["trace_id"].startswith(eng._run_id)
                names = [e[0] for e in tr["events"]]
                assert names[0] == "prefill" and "decode" in names
        finally:
            srv.close()

    def test_healthz_drain_flip_and_unknown_route(self, served_model):
        m, cfg = served_model
        eng = ServingEngine(m, ServingConfig(
            max_batch=BATCH, prompt_cap=CAP, max_new_tokens=NEW,
            decode_chunk=3, queue_high_watermark=4))
        srv = eng.serve_telemetry()
        try:
            h = _get_json(srv.url("/healthz"))
            assert h["status"] == "ok" and h["queue_high_watermark"] == 4
            eng.begin_drain()
            with pytest.raises(urllib.error.HTTPError) as ei:
                urlopen(srv.url("/healthz"), timeout=10)
            assert ei.value.code == 503
            assert json.loads(ei.value.read())["status"] == "draining"
            eng.resume_admission()
            assert _get_json(srv.url("/healthz"))["status"] == "ok"
            with pytest.raises(urllib.error.HTTPError) as ei:
                urlopen(srv.url("/nope"), timeout=10)
            assert ei.value.code == 404
        finally:
            srv.close()

    def test_broken_producer_500s_the_scrape_not_the_server(
            self, served_model):
        m, cfg = served_model
        eng = ServingEngine(m, ServingConfig(
            max_batch=BATCH, prompt_cap=CAP, max_new_tokens=NEW,
            decode_chunk=3))
        srv = eng.serve_telemetry()
        try:
            srv.registry.register(
                "broken", lambda: (_ for _ in ()).throw(
                    RuntimeError("boom")))
            with pytest.raises(urllib.error.HTTPError) as ei:
                urlopen(srv.url("/metrics"), timeout=10)
            assert ei.value.code == 500
            assert "boom" in json.loads(ei.value.read())["error"]
            srv.registry.unregister("broken")
            # the server survives: next scrape is clean
            lint_exposition(urlopen(srv.url("/metrics"),
                                    timeout=10).read().decode())
        finally:
            srv.close()

    def test_tracez_keeps_rejects_and_timeouts(self, served_model):
        m, cfg = served_model
        fake = {"t": 0.0}
        eng = ServingEngine(m, ServingConfig(
            max_batch=BATCH, prompt_cap=CAP, max_new_tokens=NEW,
            decode_chunk=3, deadline_s=0.5),
            metrics=ServingMetrics(trace_buffer=TraceBuffer(64)),
            clock=lambda: fake["t"])
        prompts = _prompts(cfg, [4, 4])
        eng.submit(prompts[0])                    # will expire
        eng.submit(np.arange(1, CAP + 3))         # rejected: prompt_shape
        fake["t"] = 1.0
        eng.submit(prompts[1])
        eng.drain()
        buf = eng.metrics.trace_buffer
        by = {t["status"]: t for t in buf.snapshot()}
        assert set(by) == {"done", "timeout", "rejected"}
        assert by["rejected"]["reason"] == "prompt_shape"
        assert by["timeout"]["reason"] == "queue_deadline"

    def test_request_span_tree_shape(self, served_model):
        m, cfg = served_model
        eng = ServingEngine(m, ServingConfig(
            max_batch=BATCH, prompt_cap=CAP, max_new_tokens=NEW,
            decode_chunk=3))
        done = []
        eng.submit(_prompts(cfg, [5])[0])
        done += eng.drain()
        r = done[0]
        tree = r.trace.span_tree()
        assert tree["trace_id"] == r.trace.trace_id
        assert tree["t0"] == r.trace.t_enqueue
        assert tree["t1"] == r.trace.t_finish
        names = [s["name"] for s in tree["spans"]]
        assert names[0] == "queue" and names[1] == "prefill"
        assert names.count("decode") == len(
            [e for e in r.trace.events if e[0] == "decode"])
        for s in tree["spans"]:
            assert tree["t0"] <= s["t0"] <= s["t1"] <= tree["t1"]
        # chunk-granular charging: a request's decode windows are the
        # chunks it was LIVE for, and the JSONL record carries them
        rec = r.record()
        assert rec["trace_id"] == tree["trace_id"]
        assert [e[0] for e in rec["events"]] == names[1:]


class TestPagedTraceEvents:
    def test_suffix_prefill_and_decode_windows(self, served_model):
        """Paged + prefix-cache engine: the repeated prompt's trace shows
        the cache doing its job — a suffix_prefill (or NO prefill at
        all on the zero-prefill hit) instead of a full one."""
        m, cfg = served_model
        eng = ServingEngine(m, ServingConfig(
            max_batch=2, prompt_cap=8, max_new_tokens=4, decode_chunk=2,
            kv_block=4, prefix_cache=True))
        rng = np.random.RandomState(5)
        p = rng.randint(1, cfg.vocab_size, (8,)).astype(np.int64)
        eng.submit(p)
        first = eng.drain()
        assert [e[0] for e in first[0].trace.events][0] == "prefill"
        # identical prompt: block-aligned full hit -> zero-prefill (no
        # prefill window in the trace; TTFT = one decode step)
        eng.submit(p.copy())
        second = eng.drain()
        names = [e[0] for e in second[0].trace.events]
        assert "prefill" not in names and "suffix_prefill" not in names
        assert names and all(n == "decode" for n in names)
        # divergent tail -> suffix prefill window
        d = p.copy()
        d[4:] = rng.randint(1, cfg.vocab_size, (4,))
        eng.submit(d)
        third = eng.drain()
        names = [e[0] for e in third[0].trace.events]
        assert names[0] == "suffix_prefill"
        st = eng.statusz()
        assert st["kv"]["blocks_total"] == eng._pool.num_blocks
        assert st["prefix_cache"]["cached_blocks"] > 0


class TestHapiTelemetry:
    def test_profiler_callback_registers_and_unregisters(self):
        from paddle_tpu.hapi.callbacks import ProfilerCallback
        from paddle_tpu.obs import TelemetryServer
        from paddle_tpu.profiler.timeline import SpanRecorder
        mon = StepMonitor(items_per_step=4, track_memory=False)
        with mon.step():
            pass
        rec = SpanRecorder()
        rec.record("step", 0.0, 0.5, step=1)
        srv = TelemetryServer()                   # bound, not started
        try:
            cb = ProfilerCallback(monitor=mon, summary=False,
                                  timeline=rec, telemetry=srv)
            cb.on_train_begin()
            try:
                assert set(srv.registry.producers) == {"train_monitor",
                                                       "train_goodput"}
                fams = lint_exposition(srv.registry.render())
                assert "paddle_tpu_steps_total" in fams
                assert "paddle_tpu_goodput_ratio" in fams
            finally:
                cb.on_train_end()
            assert srv.registry.producers == []
        finally:
            srv.close()

    def test_young_timeline_renders_empty_not_broken(self):
        from paddle_tpu.hapi.callbacks import ProfilerCallback
        from paddle_tpu.obs import TelemetryServer
        from paddle_tpu.profiler.timeline import SpanRecorder
        srv = TelemetryServer()
        try:
            cb = ProfilerCallback(summary=False, timeline=SpanRecorder(),
                                  telemetry=srv)
            cb.on_train_begin()
            try:
                assert srv.registry.render() == ""
            finally:
                cb.on_train_end()
        finally:
            srv.close()
