"""Serving observability: the ServingEngine, request metrics
(histograms/gauges/counters + JSONL records), the shared Prometheus
renderer, and the wired inference.Config.enable_profile().

Engine acceptance (ISSUE 4): greedy outputs bit-identical to
generate_static_ragged on the same prompts; ZERO jit cache misses across a
steady-state serving loop after warmup; metrics_text() a valid Prometheus
exposition carrying TTFT/TPOT/e2e histograms + queue/batch/KV gauges.
"""
import json
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (Request, ServingConfig, ServingEngine,
                                  ServingMetrics, synthetic_traffic)
from paddle_tpu.jit.api import compile_cache_misses
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.profiler import LogHistogram, StepMonitor


# ---------------------------------------------------------- LogHistogram

class TestLogHistogram:
    def test_percentiles_match_numpy_on_known_samples(self):
        rng = np.random.RandomState(0)
        xs = np.exp(rng.randn(2000) * 0.8 - 2.5)       # lognormal latencies
        h = LogHistogram(lo=1e-4, hi=10.0, per_decade=20)
        for x in xs:
            h.observe(float(x))
        for q in (0.5, 0.9, 0.99):
            got = h.percentile(q)
            want = float(np.percentile(xs, q * 100))
            # derived-from-buckets error bound: one bucket's relative width
            assert abs(got - want) / want < 10 ** (1 / 20) - 1, (q, got, want)

    def test_edges_clamp_to_observed_extremes(self):
        h = LogHistogram(lo=0.01, hi=10, per_decade=4)
        for v in (0.5, 1.5, 3.0):
            h.observe(v)
        assert h.percentile(0.0) == 0.5
        assert h.percentile(1.0) == 3.0
        assert h.count == 3 and abs(h.sum - 5.0) < 1e-12
        assert abs(h.mean - 5.0 / 3) < 1e-12

    def test_overflow_and_underflow_buckets(self):
        h = LogHistogram(lo=0.1, hi=1.0, per_decade=2)
        h.observe(1e-5)                # below lo -> first bucket
        h.observe(50.0)                # beyond hi -> +Inf bucket
        assert h.counts[0] == 1 and h.counts[-1] == 1
        assert h.percentile(1.0) == 50.0

    def test_empty_histogram(self):
        h = LogHistogram()
        assert h.percentile(0.5) is None and h.mean is None
        assert h.summary()["count"] == 0

    def test_rejects_nan_and_bad_q(self):
        h = LogHistogram()
        with pytest.raises(ValueError):
            h.observe(float("nan"))
        h.observe(1.0)
        with pytest.raises(ValueError):
            h.percentile(1.5)


# ------------------------------------------- Prometheus exposition format

_SAMPLE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{(?:le|program)="[^"]+"\})? '
    r'(-?\d+(\.\d+)?([eE][-+]?\d+)?|\+Inf|NaN)$')


def _check_exposition(text):
    """Validate Prometheus text format 0.0.4 invariants; returns
    {metric_name: type}."""
    types, helped = {}, set()
    for line in text.strip().split("\n"):
        if line.startswith("# HELP "):
            helped.add(line.split()[2])
        elif line.startswith("# TYPE "):
            _, _, name, kind = line.split(None, 3)
            types[name] = kind
        else:
            m = _SAMPLE.match(line)
            assert m, f"malformed sample line: {line!r}"
            base = m.group(1)
            root = re.sub(r"_(bucket|sum|count)$", "", base)
            assert base in types or root in types, f"no TYPE for {line!r}"
    assert set(types) == helped, "HELP/TYPE mismatch"
    return types


def _histogram_invariants(text, name):
    """Bucket lines cumulative + ascending le; +Inf equals _count."""
    bucket_re = re.compile(
        rf'^{re.escape(name)}_bucket{{le="([^"]+)"}} (\d+)$', re.M)
    rows = [(le, int(c)) for le, c in bucket_re.findall(text)]
    assert rows and rows[-1][0] == "+Inf"
    counts = [c for _, c in rows]
    assert counts == sorted(counts), "buckets must be cumulative"
    les = [float(le) for le, _ in rows[:-1]]
    assert les == sorted(les), "le bounds must ascend"
    count = int(re.search(rf"^{re.escape(name)}_count (\d+)$", text,
                          re.M).group(1))
    assert rows[-1][1] == count, "+Inf bucket must equal _count"


class TestExpositionFormat:
    def test_serving_metrics_text_is_valid(self):
        met = ServingMetrics()
        rng = np.random.RandomState(1)
        for _ in range(50):
            r = Request(id=0, prompt=np.arange(4), max_new_tokens=4,
                        status="done", n_out=4)
            t = float(rng.uniform(0.001, 2.0))
            r.trace.t_enqueue, r.trace.t_admit = 0.0, 0.1 * t
            r.trace.t_first_token, r.trace.t_finish = 0.5 * t, t
            met.record_request(r)
        met.record_batch(n_real=3, capacity=4, kv_tokens=30, kv_slots=48,
                         kv_capacity=64, queue_depth=2)
        text = met.metrics_text()
        types = _check_exposition(text)
        for h in ("ttft_seconds", "tpot_seconds", "e2e_seconds",
                  "queue_seconds"):
            assert types[f"paddle_tpu_serving_{h}"] == "histogram"
            _histogram_invariants(text, f"paddle_tpu_serving_{h}")
        for g in ("queue_depth", "batch_fill_ratio", "kv_occupancy",
                  "kv_slots_occupancy"):
            assert types[f"paddle_tpu_serving_{g}"] == "gauge"
        for c in ("requests_total", "rejected_total", "timeout_total",
                  "tokens_in_total", "tokens_out_total"):
            assert types[f"paddle_tpu_serving_{c}"] == "counter"
        assert "paddle_tpu_serving_requests_total 50" in text

    def test_step_monitor_shares_the_renderer(self):
        mon = StepMonitor(items_per_step=4, track_memory=False)
        with mon.step():
            pass
        types = _check_exposition(mon.metrics_text())
        assert types["paddle_tpu_steps_total"] == "gauge"

    def test_summary_percentile_triplets(self):
        met = ServingMetrics()
        met.observe_call(0.25, items=8)
        s = met.summary()
        assert s["completed_total"] == 1 and s["items_total"] == 8
        assert s["tokens_out_total"] == 0          # rows are not tokens
        assert abs(s["e2e_seconds"]["p50"] - 0.25) < 0.05


# --------------------------------------------------- engine test fixtures

CAP, NEW, BATCH = 8, 6, 2


@pytest.fixture(scope="module")
def served_model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
                    max_position_embeddings=64, intermediate_size=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m, cfg


def _engine(m, **kw):
    base = dict(max_batch=BATCH, prompt_cap=CAP, max_new_tokens=NEW,
                decode_chunk=3)
    base.update(kw)
    return ServingEngine(m, ServingConfig(**base))


def _prompts(cfg, lens, seed=1):
    rng = np.random.RandomState(seed)
    ids = rng.randint(1, cfg.vocab_size, (len(lens), CAP)).astype(np.int64)
    for r, ln in enumerate(lens):
        ids[r, ln:] = 0
    return ids


# ------------------------------------------------------------ the engine

def test_engine_greedy_parity_with_ragged(served_model):
    """Acceptance: ServingEngine output == generate_static_ragged
    bit-for-bit on identical prompts."""
    m, cfg = served_model
    lens = [CAP, 5]
    ids = _prompts(cfg, lens)
    eng = _engine(m)
    for i in range(len(lens)):
        eng.submit(ids[i, :lens[i]])
    done = eng.drain()
    assert [r.status for r in done] == ["done", "done"]
    ref = m.generate_static_ragged(paddle.to_tensor(ids), lens,
                                   max_new_tokens=NEW).numpy()[:, CAP:]
    np.testing.assert_array_equal(np.stack([r.tokens for r in done]), ref)
    # spans are complete and ordered for served requests
    for r in done:
        tr = r.trace
        assert tr.t_enqueue <= tr.t_admit <= tr.t_prefill_done \
            <= tr.t_first_token <= tr.t_finish
        assert tr.ttft_s >= 0 and tr.e2e_s >= tr.ttft_s


def test_default_config_is_the_engine_the_cells_run():
    """`ServingConfig()` with no arguments is the paged engine: a pool for
    the worst case of every slot, one decode chunk per budget. The
    `paged` keyword survives as a value that can only be True."""
    c = ServingConfig()
    assert c.paged is True
    assert c.decode_chunk == c.max_new_tokens - 1 == 31
    # a cap prompt decoding its whole budget never writes its last token
    assert c.row_kv_rows == c.prompt_cap + c.max_new_tokens - 1 == 95
    assert c.table_width == -(-95 // c.kv_block) == 6
    assert c.kv_blocks == c.max_batch * c.table_width + 1 == 25
    assert ServingConfig(max_new_tokens=1).decode_chunk == 1
    assert ServingConfig(paged=True).kv_blocks == 25
    with pytest.raises(ValueError, match="padded engine was removed"):
        ServingConfig(paged=False)


def test_default_engine_fits_a_full_batch_of_worst_case_rows(served_model):
    """The default pool admits max_batch cap-length prompts at once, each
    decoding its full budget, with no wait on freed blocks."""
    m, cfg = served_model
    eng = ServingEngine(m, ServingConfig(max_batch=3, prompt_cap=CAP,
                                         max_new_tokens=NEW, kv_block=4))
    ids = _prompts(cfg, [CAP] * 3)
    for row in ids:
        eng.submit(row)
    eng.step()
    assert eng.queue_depth == 0 and len(eng._live()) == 3
    assert eng._pool.free_blocks == 3 * eng.config.table_width \
        - 3 * eng._pool.blocks_needed(CAP + NEW - 1) == 0
    done = eng.drain()
    assert [r.n_out for r in done] == [NEW] * 3
    assert eng.summary()["mem_pressure_episodes_total"] == 0


@pytest.mark.parametrize("prefill_chunk", [None, 4])
@pytest.mark.parametrize("decode_chunk", [1, 3, None])
def test_engine_greedy_parity_over_chunkings(served_model, decode_chunk,
                                             prefill_chunk):
    """How the work is cut (tokens per decode call, prompt tokens per
    prefill window) never changes a token: more requests than slots, so
    rows are spliced in mid-flight at every chunking."""
    m, cfg = served_model
    lens = [CAP, 5, 1, 7, 3]
    ids = _prompts(cfg, lens, seed=11)
    eng = _engine(m, decode_chunk=decode_chunk, prefill_chunk=prefill_chunk,
                  kv_block=4)
    if decode_chunk is None:
        assert eng.config.decode_chunk == NEW - 1
    for i, ln in enumerate(lens):
        eng.submit(ids[i, :ln])
    done = sorted(eng.drain(), key=lambda r: r.id)
    ref = m.generate_static_ragged(paddle.to_tensor(ids), lens,
                                   max_new_tokens=NEW).numpy()[:, CAP:]
    np.testing.assert_array_equal(np.stack([r.tokens for r in done]), ref)
    if prefill_chunk is not None:
        # the cap-length prompt went in as CAP / 4 windows, not one
        names = [e[0] for e in done[0].trace.events]
        assert names.count("prefill_chunk") == CAP // prefill_chunk
    assert eng._pool.free_blocks == eng._pool.capacity_blocks


@pytest.mark.parametrize("sampling", [dict(temperature=0.9),
                                      dict(temperature=0.9, top_k=8),
                                      dict(temperature=0.9, top_p=0.7)])
def test_engine_sampling_is_reproducible_from_its_seed(served_model,
                                                       sampling):
    """Sampled output is a function of (config seed, traffic): the same
    seed replays the same tokens, another seed draws others, and top_k
    keeps every draw inside the k best."""
    m, cfg = served_model
    lens = [CAP, 5, 6]
    ids = _prompts(cfg, lens, seed=5)

    def serve(seed):
        eng = _engine(m, seed=seed, **sampling)
        for i, ln in enumerate(lens):
            eng.submit(ids[i, :ln])
        return np.stack([r.tokens for r in
                         sorted(eng.drain(), key=lambda r: r.id)])

    a, b, c = serve(3), serve(3), serve(4)
    np.testing.assert_array_equal(a, b)
    assert (a != c).any()
    greedy = m.generate_static_ragged(paddle.to_tensor(ids), lens,
                                      max_new_tokens=NEW).numpy()[:, CAP:]
    assert (a != greedy).any() or (c != greedy).any()
    if sampling.get("top_k"):
        # a request's first token is drawn from the prompt's own logits
        for row, ln in enumerate(lens):
            logits = m(paddle.to_tensor(ids[row:row + 1, :ln])).numpy()
            best = np.argsort(logits[0, -1])[-sampling["top_k"]:]
            assert a[row, 0] in best and c[row, 0] in best


def test_n_continuations_of_one_prefix_pay_its_prefill_once(served_model):
    """One shared prefix, N continuations: the trie prefills the prefix
    once, every repeat maps its blocks (`prefill_tokens_saved` grows by
    the prefix per repeat) and each output equals the oracle's."""
    m, cfg = served_model
    kb, n = 4, 4
    rng = np.random.RandomState(9)
    prefix = rng.randint(1, cfg.vocab_size, (kb,)).astype(np.int64)
    lens = [CAP, 6, 7, 6]
    ids = np.zeros((n, CAP), np.int64)
    for i, ln in enumerate(lens):
        ids[i, :kb] = prefix
        ids[i, kb:ln] = rng.randint(1, cfg.vocab_size, (ln - kb,))
    eng = _engine(m, kv_block=kb, prefix_cache=True)
    done = []
    for i, ln in enumerate(lens):      # one at a time: the first caches it
        eng.submit(ids[i, :ln])
        done += eng.drain()
    ref = m.generate_static_ragged(paddle.to_tensor(ids), lens,
                                   max_new_tokens=NEW).numpy()[:, CAP:]
    np.testing.assert_array_equal(np.stack([r.tokens for r in done]), ref)
    s = eng.summary()
    assert s["prefix_miss_total"] == 1 and s["prefix_hit_total"] == n - 1
    assert s["prefill_tokens_saved_total"] == (n - 1) * kb
    # the repeats prefilled their suffix alone
    assert [e[0] for e in done[0].trace.events][0] == "prefill"
    for r in done[1:]:
        assert [e[0] for e in r.trace.events][0] == "suffix_prefill"


def test_engine_zero_recompiles_after_warmup(served_model):
    """Acceptance: a steady-state serving loop adds ZERO jit cache misses
    after the warmup batch — including partial batches (idle slots keep
    every shape pinned)."""
    m, cfg = served_model
    eng = _engine(m)
    ids = _prompts(cfg, [CAP, 5])
    eng.submit(ids[0, :CAP])
    eng.submit(ids[1, :5])
    eng.drain()                        # warmup: compiles prefill + chunks
    miss0 = compile_cache_misses()
    for i in range(3):
        eng.submit(ids[0, :CAP])
        if i != 1:
            eng.submit(ids[1, :5])     # round 2 is partial: an idle slot
        eng.drain()
    assert compile_cache_misses() - miss0 == 0
    assert eng.monitor.recompiles == 0
    assert all(r.get("jit_cache_misses", 0) == 0
               for r in eng.monitor.records[1:])


def test_engine_batch_gauges_and_counters(served_model):
    m, cfg = served_model
    eng = _engine(m)
    ids = _prompts(cfg, [4])
    eng.submit(ids[0, :4])             # 1 of 2 slots used
    eng.drain()
    s = eng.summary()
    assert s["batch_fill_ratio"] == 0.5
    assert 0 < s["kv_occupancy"] <= 1.0
    # the one admitted row reserves its worst-case blocks and no more
    pool = eng._pool
    assert s["kv_slots_occupancy"] == \
        pool.blocks_needed(4 + NEW - 1) * pool.block_size / \
        pool.capacity_tokens
    assert s["tokens_in_total"] == 4 and s["tokens_out_total"] == NEW
    assert s["completed_total"] == 1
    # launch prefill + chunk 1, launch chunk 2 + read, read: every step
    # that ran a model call or read one is a batch record
    assert s["batches_total"] == s["batch_step"]["steps"] - 1 == 2


def test_engine_rejects_overlong_prompt_with_shape_delta(served_model):
    """A prompt beyond the cap would force a new prefill executable: the
    engine refuses and logs the would-be shape delta through
    StepMonitor.record_compile."""
    m, cfg = served_model
    eng = _engine(m)
    req = eng.submit(np.arange(1, CAP + 3))
    assert req.status == "rejected" and req.reason == "prompt_shape"
    assert eng.summary()["rejected_total"] == 1
    ev = eng.monitor.recompile_events[0]
    assert ev["kind"] == "serving_reject"
    assert str(CAP) in ev["delta"] and str(CAP + 2) in ev["delta"]
    # the warning must NOT feed the numeric churn counters: nothing was
    # built (the request was refused precisely so nothing would be)
    assert eng.monitor.recompiles == 0 and eng.monitor.compiles == 0
    assert eng.queue_depth == 0        # never admitted
    # repeat offenders count as rejections but warn only once per shape
    assert eng.submit(np.arange(1, CAP + 3)).status == "rejected"
    assert eng.summary()["rejected_total"] == 2
    assert len(eng.monitor.recompile_events) == 1


def test_engine_queue_full_rejection(served_model):
    m, cfg = served_model
    eng = _engine(m, queue_capacity=2)
    ids = _prompts(cfg, [3, 3, 3])
    assert eng.submit(ids[0, :3]).status == "queued"
    assert eng.submit(ids[1, :3]).status == "queued"
    r = eng.submit(ids[2, :3])
    assert r.status == "rejected" and r.reason == "queue_full"
    assert eng.summary()["rejected_total"] == 1
    assert eng.queue_depth == 2


def test_engine_deadline_timeout(served_model):
    """Requests whose queue wait blows their deadline expire at admission
    (deterministic via the injectable clock)."""
    m, cfg = served_model
    fake = {"t": 0.0}
    eng = ServingEngine(m, ServingConfig(max_batch=BATCH, prompt_cap=CAP,
                                         max_new_tokens=NEW, decode_chunk=3,
                                         deadline_s=0.5),
                        clock=lambda: fake["t"])
    ids = _prompts(cfg, [3, 3])
    eng.submit(ids[0, :3])                        # will expire
    eng.submit(ids[1, :3], deadline_s=10.0)       # per-request override
    fake["t"] = 1.0
    done = eng.drain()
    # expired traffic is a terminal RESULT, not silently dropped
    assert sorted(r.status for r in done) == ["done", "timeout"]
    timed = next(r for r in done if r.status == "timeout")
    assert timed.reason == "queue_deadline" and timed.tokens is None
    s = eng.summary()
    assert s["timeout_total"] == 1 and s["completed_total"] == 1
    # its queue wait (1.0s on the fake clock) lands in the histogram —
    # the longest waits must not vanish from the distribution at expiry
    assert abs(s["queue_seconds"]["p99"] - 1.0) < 0.2


def test_engine_eos_early_exit_and_token_counts(served_model):
    """With a forced-EOS vocabulary walk, finished rows report n_out up to
    and including EOS, and the chunk loop stops once every row is done."""
    m, cfg = served_model
    lens = [CAP, 5]
    ids = _prompts(cfg, lens)
    ref = m.generate_static_ragged(paddle.to_tensor(ids), lens,
                                   max_new_tokens=NEW).numpy()
    eos = int(ref[0, CAP])
    eng = _engine(m, eos_token_id=eos)
    eng.submit(ids[0, :CAP])
    eng.submit(ids[1, :5])
    done = eng.drain()
    by_id = {r.id: r for r in done}
    assert by_id[0].n_out == 1                     # EOS was its 1st token
    assert by_id[0].tokens[0] == eos
    assert by_id[1].n_out >= 1
    s = eng.summary()
    assert s["tokens_out_total"] == sum(r.n_out for r in done)
    # per-row finish is chunk-granular: the EOS-on-token-1 row is stamped
    # at its own chunk, not charged for the batch's remaining chunks
    if by_id[1].n_out > 1:
        assert by_id[0].trace.t_finish < by_id[1].trace.t_finish
        assert by_id[1].trace.tpot_s(by_id[1].n_out) > 0


def test_warmup_depth_extension_is_not_a_recompile(served_model):
    """A request that ends at its first token leaves the decode executable
    uncompiled; its eventual first compile is NOT shape churn and must not
    trip the steady-state recompile guard. Once both are warm, a miss is."""
    m, cfg = served_model
    getattr(m, "_gen_static_cache", {}).clear()   # earlier tests' builds
    eng = _engine(m)
    ids = _prompts(cfg, [CAP, 5])
    eng.submit(ids[0, :CAP], max_new_tokens=1)
    eng.drain()                        # warmup ends at the prefill's token
    assert eng._paged_seen == {"prefill"}
    eng.submit(ids[1, :5])             # decodes: goes deeper than warmup did
    eng.drain()
    assert "decode" in eng._paged_seen
    assert eng.monitor.compiles >= 2 and eng.monitor.recompiles == 0
    # and the guard is live: a build under a warm step counts as churn
    from paddle_tpu.jit.api import _note_cache_miss
    real = m.decode_paged

    def rebuilt(*a, **kw):
        _note_cache_miss()
        return real(*a, **kw)

    m.decode_paged = rebuilt
    try:
        eng.submit(ids[1, :5])
        eng.drain()
    finally:
        del m.decode_paged
    assert eng.monitor.recompiles >= 1


def test_engine_respects_per_request_budget(served_model):
    m, cfg = served_model
    eng = _engine(m)
    ids = _prompts(cfg, [4, 4])
    eng.submit(ids[0, :4], max_new_tokens=2)
    eng.submit(ids[1, :4], max_new_tokens=100)     # clamped to engine max
    done = eng.drain()
    by_id = {r.id: r for r in done}
    assert by_id[0].tokens.shape[0] == 2
    assert by_id[1].tokens.shape[0] == NEW
    # a zero budget is unservable, not "serve 1 anyway"
    r = eng.submit(ids[0, :4], max_new_tokens=0)
    assert r.status == "rejected" and r.reason == "max_new_tokens"


def test_engine_exception_records_inflight_requests(served_model):
    """A call dying mid-flight must not lose the admitted requests from
    the accounting: they land as status='error' before the raise, and the
    engine serves the next request on rebuilt pools."""
    m, cfg = served_model
    eng = _engine(m)
    ids = _prompts(cfg, [4])
    eng.submit(ids[0, :4])

    def boom(*a, **kw):
        raise RuntimeError("injected device failure")

    m.prefill_paged = boom
    try:
        with pytest.raises(RuntimeError, match="injected"):
            eng.step()
    finally:
        del m.prefill_paged
    s = eng.summary()
    assert s["errors_total"] == 1 and s["inflight"] == 0
    assert eng.queue_depth == 0 and not eng.busy
    assert eng._pool.free_blocks == eng._pool.capacity_blocks
    eng.submit(ids[0, :4])
    assert [r.status for r in eng.drain()] == ["done"]


def test_request_jsonl_schema(served_model, tmp_path):
    """One JSONL row per terminal request: nested "request" payload +
    "ts", spans and derived latencies present for served requests."""
    m, cfg = served_model
    jsonl = str(tmp_path / "requests.jsonl")
    eng = ServingEngine(m, ServingConfig(max_batch=BATCH, prompt_cap=CAP,
                                         max_new_tokens=NEW,
                                         decode_chunk=3),
                        metrics=ServingMetrics(jsonl_path=jsonl))
    ids = _prompts(cfg, [CAP, 5])
    eng.submit(ids[0, :CAP])
    eng.submit(ids[1, :5])
    eng.submit(np.arange(1, CAP + 5))              # rejected -> also a row
    eng.drain()
    rows = [json.loads(l) for l in open(jsonl)]
    assert len(rows) == 3
    for row in rows:
        assert set(row) == {"request", "ts"}
        r = row["request"]
        assert {"id", "status", "prompt_tokens", "output_tokens",
                "spans"} <= set(r)
    served = [r["request"] for r in rows if r["request"]["status"] == "done"]
    assert len(served) == 2
    for r in served:
        assert {"queue_s", "ttft_s", "tpot_s", "e2e_s"} <= set(r)
        assert {"t_enqueue", "t_admit", "t_prefill_done", "t_first_token",
                "t_finish", "batch_id"} <= set(r["spans"])
    rej = next(r["request"] for r in rows
               if r["request"]["status"] == "rejected")
    assert rej["reason"] == "prompt_shape" and rej["output_tokens"] == 0


def test_on_record_hook(served_model):
    m, cfg = served_model
    seen = []
    eng = ServingEngine(m, ServingConfig(max_batch=BATCH, prompt_cap=CAP,
                                         max_new_tokens=NEW,
                                         decode_chunk=3),
                        metrics=ServingMetrics(on_record=seen.append))
    eng.submit(_prompts(cfg, [4])[0, :4])
    eng.drain()
    assert len(seen) == 1 and seen[0]["request"]["status"] == "done"


def test_engine_metrics_text_is_valid_exposition(served_model):
    m, cfg = served_model
    eng = _engine(m)
    ids = _prompts(cfg, [CAP, 5])
    eng.submit(ids[0, :CAP])
    eng.submit(ids[1, :5])
    eng.drain()
    text = eng.metrics_text()
    types = _check_exposition(text)
    # request metrics and the batch StepMonitor block share one page
    assert "paddle_tpu_serving_ttft_seconds" in types
    assert "paddle_tpu_serving_batch_steps_total" in types
    _histogram_invariants(text, "paddle_tpu_serving_ttft_seconds")
    # the same page through the unified registry path (ISSUE 12): the
    # promtool-style lint covers everything _check_exposition pins plus
    # family contiguity/collisions — obs tests extend this to merged
    # multi-producer pages
    from paddle_tpu.obs import lint_exposition
    fams = lint_exposition(eng.metrics_registry().render())
    assert set(types) <= set(fams)


def test_programs_launched_is_one_labelled_counter(served_model):
    """Every launch the engine hands to the chip is counted under its
    program's name (`jit.api`'s table): one decode launch a chunk, one
    stage a chunk, one prefill a window, one put-first a final window."""
    from paddle_tpu.jit import api as programs
    m, cfg = served_model
    eng = _engine(m)
    ids = _prompts(cfg, [CAP, 5, 7])
    for r, ln in enumerate([CAP, 5, 7]):
        eng.submit(ids[r, :ln])
    eng.drain()
    launched, mt = eng.metrics.programs_launched, eng.metrics.counters
    assert launched == {programs.PREFILL_PROGRAM: 3,
                        programs.PUT_FIRST_PROGRAM: 3,
                        programs.DECODE_PROGRAM: mt["decode_chunks"],
                        programs.STAGE_PROGRAM: mt["decode_chunks"]}
    assert eng.summary()["programs_launched_total"] == launched
    text = eng.metrics_text()
    assert _check_exposition(text)[
        "paddle_tpu_serving_programs_launched_total"] == "counter"
    for name, n in launched.items():
        assert f'paddle_tpu_serving_programs_launched_total' \
               f'{{program="{name}"}} {n}\n' in text
    # an engine that launched nothing renders no such family
    assert "programs_launched" not in _engine(m).metrics_text()
    eng.close()


def test_the_gc_hook_is_one_a_process_and_close_takes_it_out(served_model):
    import gc
    from paddle_tpu.inference import serving
    m, cfg = served_model
    watch = serving._gc_watch
    for metrics in list(watch._sinks):      # engines other tests left open
        watch.discard(metrics)
    assert watch not in gc.callbacks
    a, b = _engine(m), _engine(m)
    assert gc.callbacks.count(watch) == 1
    gc.collect()
    gc.collect()
    for eng in (a, b):
        mt = eng.metrics.counters
        assert mt["host_gc_pauses"] >= 2 and mt["host_gc_pause_ms"] > 0
    a.close()
    assert gc.callbacks.count(watch) == 1
    seen = a.metrics.counters["host_gc_pauses"]
    gc.collect()
    assert a.metrics.counters["host_gc_pauses"] == seen
    assert b.metrics.counters["host_gc_pauses"] > seen
    b.close()
    b.close()                               # twice is once
    assert watch not in gc.callbacks
    types = _check_exposition(b.metrics_text())
    assert types["paddle_tpu_serving_host_gc_pauses_total"] == "counter"
    assert types["paddle_tpu_serving_host_gc_pause_ms_total"] == "counter"
    # a closed engine still serves, uncounted
    b.submit(_prompts(cfg, [5])[0, :5])
    assert len(b.drain()) == 1


def test_synthetic_traffic_shape():
    tr = synthetic_traffic(16, prompt_cap=8, vocab_size=64, rate=100.0,
                           seed=0)
    assert len(tr) == 16
    ats = [t["at"] for t in tr]
    assert ats == sorted(ats) and ats[0] == 0.0
    assert all(1 <= t["prompt"].shape[0] <= 8 for t in tr)
    assert all(t["prompt"].min() >= 1 and t["prompt"].max() < 64
               for t in tr)


@pytest.mark.slow
def test_engine_under_load_open_loop(served_model):
    """Load generation: open-loop replay of 24 requests; everything
    completes, latency histograms fill, and the steady loop never
    recompiles."""
    m, cfg = served_model
    eng = _engine(m)
    traffic = synthetic_traffic(24, prompt_cap=CAP,
                                vocab_size=cfg.vocab_size, rate=500.0,
                                seed=7)
    eng.submit(traffic[0]["prompt"])
    eng.drain()                        # warmup
    miss0 = compile_cache_misses()
    t0 = eng.clock()
    finished = []
    for item in traffic:
        eng.submit(item["prompt"], enqueue_at=t0 + item["at"])
        while eng.queue_depth >= BATCH:
            finished.extend(eng.step())
    finished.extend(eng.drain())
    assert sum(1 for r in finished if r.status == "done") == 24
    assert compile_cache_misses() - miss0 == 0
    s = eng.summary()
    assert s["ttft_seconds"]["count"] == 25        # incl. warmup request
    assert s["e2e_seconds"]["p99"] > 0


# -------------------------------------- inference.Config.enable_profile()

class TestPredictorProfile:
    def _export(self, tmp_path):
        from paddle_tpu import static
        paddle.enable_static()
        try:
            main = static.Program()
            with static.program_guard(main):
                x = static.data("x", [-1, 8], "float32")
                y = static.nn.fc(x, 4)
            exe = static.Executor()
            prefix = str(tmp_path / "model")
            static.save_inference_model(prefix, [x], [y], exe, program=main)
            return prefix
        finally:
            paddle.disable_static()

    def test_run_latency_lands_in_metrics(self, tmp_path):
        from paddle_tpu import inference
        prefix = self._export(tmp_path)
        config = inference.Config(prefix)
        config.enable_profile()
        assert "profile" in config.summary()
        p = inference.create_predictor(config)
        for _ in range(3):
            p.run([np.random.randn(2, 8).astype(np.float32)])
        s = p.profile_summary()
        assert s["requests_total"] == 3 and s["completed_total"] == 3
        assert s["items_total"] == 6               # batch rows, not tokens
        assert s["e2e_seconds"]["p50"] > 0
        text = p.metrics_text()
        _check_exposition(text)
        assert "paddle_tpu_infer_requests_total 3" in text
        _histogram_invariants(text, "paddle_tpu_infer_e2e_seconds")

    def test_profile_off_by_default(self, tmp_path):
        from paddle_tpu import inference
        prefix = self._export(tmp_path)
        p = inference.create_predictor(inference.Config(prefix))
        p.run([np.random.randn(2, 8).astype(np.float32)])
        assert p.profile_summary() is None and p.metrics_text() == ""

    def test_clone_gets_fresh_metrics(self, tmp_path):
        from paddle_tpu import inference
        prefix = self._export(tmp_path)
        config = inference.Config(prefix)
        config.enable_profile()
        p = inference.create_predictor(config)
        p.run([np.random.randn(2, 8).astype(np.float32)])
        c = p.clone()
        assert c.profile_summary()["requests_total"] == 0
        c.run([np.random.randn(2, 8).astype(np.float32)])
        assert c.profile_summary()["requests_total"] == 1
        assert p.profile_summary()["requests_total"] == 1
