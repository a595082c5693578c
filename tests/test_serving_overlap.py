"""The paged engine's step order: admit, launch, land.

A step enqueues its prefill windows and its decode chunk without reading
anything, and only then reads what the step before launched. Each row's
pending token and done flag are picked on the device from the previous
chunk's outputs, so the host's read is off the way to the next launch.
What that must not change, and what it newly has to get right:

- every request's greedy tokens are bit-identical to the SERIAL order,
  kept here as a plain loop over `prefill_paged` / `decode_paged` with a
  read after every call (one request alone, its own pools);
- a budget's end is known before the read (the row is left out of the
  next chunk), an EOS is not (the row rides one more chunk as a done row,
  its tokens dropped: `eos_late_rows`);
- `Request.n_produced` counts tokens that reached the host, never the
  launched ones;
- `busy` holds while anything is unread, and `drain()` lands it;
- nothing compiles after warm-up;
- a failed step drops what is in flight.
"""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import BlockPool, ServingConfig, ServingEngine
from paddle_tpu.inference import kv_cache
from paddle_tpu.jit.api import compile_cache_misses
from paddle_tpu.models import GPTConfig, GPTForCausalLM

CAP, NEW, CHUNK, KB, WINDOW = 16, 9, 4, 4, 4


@pytest.fixture(scope="module")
def served_model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    intermediate_size=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m, cfg


def serial_reference(m, prompt, budget, eos=None):
    """The request alone, every call read before the next is made: the
    order the engine had before it overlapped. Returns its tokens up to
    and including EOS."""
    plen = len(prompt)
    blocks = -(-(plen + budget - 1) // KB)
    pools = BlockPool.for_model(m, num_blocks=blocks + 1,
                                block_size=KB).make_pools()
    table = 1 + np.arange(blocks, dtype=np.int32)[None]
    ids = np.zeros((1, CAP), np.int64)
    ids[0, :plen] = prompt
    pools, first = m.prefill_paged(ids, [plen], pools, table)
    out = [int(first.numpy()[0])]
    lens, done = plen, out[0] == eos
    while len(out) < budget and not done:
        toks, pools, _, _ = m.decode_paged(
            pools, table, [lens], [out[-1]], [False], CHUNK,
            eos_token_id=eos)
        lens += CHUNK
        fresh = toks.numpy()[0, :budget - len(out)].tolist()
        if eos in fresh:
            fresh, done = fresh[:fresh.index(eos) + 1], True
        out += fresh
    return np.asarray(out, np.int64)


def late_rows(n_out, budget, hit_eos, zero_prefill):
    """Chunk rows the engine spends on a request after its EOS: the
    chunks launched (by budget alone, the host's knowledge at launch)
    behind the one whose landing shows the EOS."""
    if not hit_eos:
        return 0
    j = n_out - 1                         # the EOS's index in the output
    if zero_prefill:                      # chunk m holds (m-1)c .. mc-1
        return int(j // CHUNK + 1 < -(-budget // CHUNK))
    chunks = -(-(budget - 1) // CHUNK)    # chunk m holds 1+(m-1)c .. mc
    if j == 0:
        # the prefill's first token: read with the chunk launched beside
        # it, after the chunk behind that one was launched too
        return min(chunks, 2)
    return int(-(-j // CHUNK) < chunks)


@pytest.fixture(scope="module")
def mix(served_model):
    """(requests, eos): [(prompt, budget)] in submission order, with the
    EOS id planted from the first request's own stream. The engine's
    prompts run several prefill windows (13, 15 and 16 tokens in windows
    of 4), one repeats an earlier block-aligned prompt (a full prefix hit,
    copy-on-write), budgets end on a chunk's edge (9 = 1 + 2 x 4, 5), off
    it (7) and at once (1)."""
    m, cfg = served_model
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, cfg.vocab_size, (n,)).astype(np.int64)
               for n in (13, 8, 5, 16, 3, 9, 12)]
    stream = serial_reference(m, prompts[0], NEW)
    eos = int(stream[2])
    assert eos not in stream[:2], "the toy model's stream changed"
    # the first request's prompt with its first two tokens appended: its
    # prefill's own first token is the EOS
    instant = np.concatenate([prompts[0], stream[:2]])
    reqs = [(prompts[0], NEW), (prompts[1], NEW), (prompts[2], 7),
            (prompts[3], 1), (prompts[1], 8), (prompts[4], 5),
            (instant, NEW), (prompts[5], NEW), (prompts[6], NEW)]
    return reqs, eos


def _engine(m, eos, **kw):
    base = dict(max_batch=2, prompt_cap=CAP, max_new_tokens=NEW,
                decode_chunk=CHUNK, kv_block=KB,
                prefix_cache=True, prefill_chunk=WINDOW, eos_token_id=eos)
    base.update(kw)
    return ServingEngine(m, ServingConfig(**base))


def _step_through(eng, reqs):
    """Submit all, step until idle. Returns (handles, per-step log of
    (slot occupants before, finished, slot occupants after))."""
    handles = [eng.submit(p, max_new_tokens=b) for p, b in reqs]
    log = []
    while eng.busy:
        before = list(eng._slots)
        done = eng.step()
        log.append((before, done, list(eng._slots)))
        for h in handles:
            if h.status != "queued":
                got = sum(len(c) for c in getattr(h, "_chunks", []))
                assert h.n_produced == got <= h._launched, \
                    "n_produced counts what reached the host"
    return handles, log


def test_mix_is_bit_identical_to_the_serial_order(served_model, mix):
    m, _ = served_model
    reqs, eos = mix
    eng = _engine(m, eos)
    calls = {"decode": 0}
    real = m.decode_paged

    def counting(*a, **kw):
        calls["decode"] += 1
        return real(*a, **kw)

    m.decode_paged = counting
    try:
        handles, log = _step_through(eng, reqs)
    finally:
        m.decode_paged = real
    refs = [serial_reference(m, p, b, eos) for p, b in reqs]
    want_late, kinds = 0, set()
    for h, (p, b), ref in zip(handles, reqs, refs):
        assert h.status == "done"
        assert h.n_out == len(ref)
        np.testing.assert_array_equal(h.tokens[:h.n_out], ref)
        assert h.n_produced >= h.n_out
        hit = ref[-1] == eos
        zero = h.trace.t_prefill_done == h.trace.t_admit
        want_late += late_rows(len(ref), b, hit, zero)
        # which of the cases the docstring promises this request is
        if zero:
            kinds.add("zero-prefill")
        if hit and len(ref) == 1:
            kinds.add("eos at the prefill")
        elif hit and not zero and (len(ref) - 1) % CHUNK not in (0, 1) \
                and len(ref) < b:
            kinds.add("eos inside a chunk")
        elif not hit and b == 1:
            kinds.add("budget 1")
        elif not hit and (b - 1) % CHUNK == 0:
            kinds.add("budget on a chunk's edge")
        elif not hit:
            kinds.add("budget off a chunk's edge")
        if len(p) > 2 * WINDOW and not zero:
            kinds.add("several windows")
        assert h.trace.t_first_token <= h.trace.t_finish
    assert kinds == {"zero-prefill", "eos at the prefill",
                     "eos inside a chunk", "budget 1",
                     "budget on a chunk's edge", "budget off a chunk's edge",
                     "several windows"}, kinds
    c = eng.metrics.counters
    assert c["eos_late_rows"] == want_late > 0
    assert c["decode_chunks"] == calls["decode"]
    assert 0 < c["decode_chunks_overlapped"] < c["decode_chunks"]
    s = eng.summary()
    assert s["decode_chunks_total"] == c["decode_chunks"]
    assert "paddle_tpu_serving_eos_late_rows_total" in eng.metrics_text()
    # a slot freed by one step's landing is taken by the next step's
    # admission, while the chunk launched in between is still unread
    assert any(
        b0 is not None and b0 in done and a1 is not None and a1 is not b0
        for (before, done, _), (_, _, after) in zip(log, log[1:])
        for b0, a1 in zip(before, after))
    assert not eng.busy and eng._flight is None
    eng._prefix.clear()
    assert eng._pool.free_blocks == eng._pool.capacity_blocks


def test_nothing_compiles_after_warm_up(served_model, mix):
    """The mix once to warm up, then again: no executable is built and
    JAX compiles nothing, whatever a step holds (a prefill or none, a
    landing with finishes or without, an idle engine's first chunk)."""
    m, _ = served_model
    reqs, eos = mix
    eng = _engine(m, eos)
    _step_through(eng, reqs)
    compiles = []

    def on_compile(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    miss0 = compile_cache_misses()
    handles, _ = _step_through(eng, reqs)
    assert all(h.status == "done" for h in handles)
    assert compile_cache_misses() == miss0
    assert compiles == []
    assert eng.monitor.recompiles == 0


def test_drain_lands_the_chunk_in_flight(served_model, mix):
    m, _ = served_model
    reqs, eos = mix
    eng = _engine(m, eos)
    handles = [eng.submit(p, max_new_tokens=b) for p, b in reqs[:3]]
    while eng._flight is None or eng._flight.toks is None:
        eng.step()
    assert eng.busy and any(h.n_produced < h._launched for h in handles)
    eng.drain()
    assert not eng.busy and eng._flight is None
    for h, (p, b) in zip(handles, reqs):
        ref = serial_reference(m, p, b, eos)
        np.testing.assert_array_equal(h.tokens[:h.n_out], ref)


def test_busy_while_only_a_late_row_is_unread(served_model, mix):
    """One request whose EOS sits in its first chunk: when the landing
    shows it the slot is freed, yet the chunk launched behind it is
    unread: `busy` holds for one more step, which launches nothing."""
    m, _ = served_model
    reqs, eos = mix
    eng = _engine(m, eos)
    h = eng.submit(*reqs[0][:1], max_new_tokens=reqs[0][1])
    while h.status != "done":
        eng.step()
    assert eng._live() == [] and eng.busy
    before = eng.metrics.counters["decode_chunks"]
    assert eng.step() == []
    assert not eng.busy
    assert eng.metrics.counters["decode_chunks"] == before
    assert eng.metrics.counters["eos_late_rows"] == 1


def test_a_late_rows_blocks_can_go_to_the_next_request(served_model, mix):
    """A pool of one row: the request behind an EOS gets the very blocks
    the late chunk still writes. Its prefill is a later call on the same
    pools, so it overwrites what the late row left, and its tokens are
    the serial order's."""
    m, _ = served_model
    reqs, eos = mix
    width = -(-(CAP + NEW - 1) // KB)
    eng = _engine(m, eos, max_batch=1, prefix_cache=False,
                  kv_blocks=width + 1)
    first = eng.submit(reqs[0][0], max_new_tokens=NEW)
    second = eng.submit(reqs[2][0], max_new_tokens=NEW)
    owned = {}
    while eng.busy:
        unread = eng._flight
        eng.step()
        for h in (first, second):
            if h.status == "active" and id(h) not in owned:
                owned[id(h)] = set(eng._pool.owned(h.id))
                if h is second:
                    # admitted while the late chunk was still unread
                    assert first.status == "done" and \
                        unread.rows[0][1] is first
    assert owned[id(first)] & owned[id(second)]
    assert eng.metrics.counters["eos_late_rows"] == 1
    np.testing.assert_array_equal(
        second.tokens[:second.n_out],
        serial_reference(m, reqs[2][0], NEW, eos))


def test_a_failed_step_drops_what_is_in_flight(served_model, mix):
    m, _ = served_model
    reqs, eos = mix
    eng = _engine(m, eos)
    handles = [eng.submit(p, max_new_tokens=b) for p, b in reqs[:2]]
    while eng._flight is None or eng._flight.toks is None:
        eng.step()
    real = m.decode_paged

    def boom(*a, **kw):
        raise RuntimeError("injected device failure")

    m.decode_paged = boom
    try:
        with pytest.raises(RuntimeError, match="injected"):
            eng.step()
    finally:
        m.decode_paged = real
    assert eng._flight is None and not eng.busy
    assert [h.status for h in handles] == ["error", "error"]
    assert all(h.n_produced <= NEW for h in handles)
    assert eng._pool.free_blocks == eng._pool.capacity_blocks
    again = [eng.submit(p, max_new_tokens=b) for p, b in reqs[:3]]
    eng.drain()
    for h, (p, b) in zip(again, reqs):
        np.testing.assert_array_equal(h.tokens[:h.n_out],
                                      serial_reference(m, p, b, eos))


@pytest.mark.parametrize("kw", [
    dict(prefill_chunk=None),
    dict(prefill_chunk=None, prefix_cache=False),
    dict(max_batch=4, decode_chunk=3),
    dict(cache_dtype="int8"),
], ids=["one-shot-prefill", "no-prefix-cache", "batch4-chunk3", "int8-kv"])
def test_other_engine_shapes_keep_their_tokens(served_model, mix, kw):
    """The same requests through engines of other shapes against ONE
    reference: `generate_static_ragged`, which knows nothing of chunks."""
    m, _ = served_model
    reqs, eos = mix
    eng = _engine(m, eos, **kw)
    handles = [eng.submit(p, max_new_tokens=b) for p, b in reqs]
    eng.drain()
    ids = np.zeros((len(reqs), CAP), np.int64)
    for i, (p, _) in enumerate(reqs):
        ids[i, :len(p)] = p
    ref = m.generate_static_ragged(
        paddle.to_tensor(ids), [len(p) for p, _ in reqs],
        max_new_tokens=NEW, eos_token_id=eos,
        cache_dtype=kw.get("cache_dtype")).numpy()[:, CAP:]
    for i, (h, (_, b)) in enumerate(zip(handles, reqs)):
        want = ref[i, :b]
        n = int(np.argmax(want == eos)) + 1 if eos in want else b
        assert h.status == "done" and h.n_out == n
        np.testing.assert_array_equal(h.tokens[:n], want[:n])
    c = eng.metrics.counters
    assert c["decode_chunks_overlapped"] <= c["decode_chunks"]


# ----------------------------------------------- rows that ride neutral
# A slot without a request, a slot in prefill and a row past its EOS are
# `done` rows of the chunk: they attend nothing (length 0), and what they
# compute nobody reads. So a request's tokens cannot depend on how full
# the batch around it is.

def _toy_gpt():
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
        max_position_embeddings=64, intermediate_size=64))
    m.eval()
    kw = dict(prompt_cap=CAP, max_new_tokens=NEW, decode_chunk=CHUNK,
              kv_block=KB, prefill_chunk=WINDOW)

    def check(handles):
        ids = np.zeros((len(handles), CAP), np.int64)
        for i, h in enumerate(handles):
            ids[i, :len(h.prompt)] = h.prompt
        ref = m.generate_static_ragged(
            paddle.to_tensor(ids), [len(h.prompt) for h in handles],
            max_new_tokens=NEW).numpy()[:, CAP:]
        for h, want in zip(handles, ref):
            np.testing.assert_array_equal(h.tokens[:h.n_out],
                                          want[:h.n_out])
    return m, kw, 96, check


def _toy_family(name):
    """(model, engine settings, vocabulary, check of served handles
    against the family's plain reference) at the toy size of the
    family's own tests."""
    import importlib
    import json
    import os
    if name == "gpt":
        return _toy_gpt()
    runner = importlib.import_module(f"benchmarks.runners.serve_{name}")
    ref = importlib.import_module(f"benchmarks.reference_{name}")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    toy = name.replace("_", "-")
    with open(os.path.join(root, f"benchmarks/configs/toy-{toy}.json")) as f:
        config = json.load(f)
    cls = {"pangu_moe": "PanguMoEForCausalLM",
           "minicpm_sala": "MiniCPMSALAForCausalLM",
           "jamba": "JambaForCausalLM"}[name]
    m = getattr(importlib.import_module(f"paddle_tpu.models.{name}"), cls)(
        runner.model_config(config))
    runner.install_weights(m, config, 3)
    m.eval()
    kw = dict(prompt_cap=40, max_new_tokens=NEW, decode_chunk=CHUNK,
              kv_block=8, kv_blocks=64, prefill_chunk=16)
    if name in ("minicpm_sala", "jamba"):
        kw["state_snapshots"] = 4
    logits = ref.logits if name == "pangu_moe" else ref.forward

    def check(handles):
        for h in handles:
            toks = np.asarray(h.tokens)[:h.n_out]
            seq = np.concatenate([np.asarray(h.prompt), toks])
            lg = np.asarray(logits(config, 3, jax.numpy.asarray(
                seq, jax.numpy.int32)))
            at = len(h.prompt) - 1 + np.arange(len(toks))
            assert float((lg[at].max(-1) - lg[at, toks]).max()) <= 1e-4
    return m, kw, 256, check


@pytest.mark.parametrize("family,executables", [
    ("gpt", 4), ("pangu_moe", 4), ("minicpm_sala", 5), ("jamba", 5)])
def test_a_request_alone_in_a_wide_batch_serves_its_own_tokens(family,
                                                               executables):
    """One request in an engine of 8 slots, then the same request among
    seven others: the same tokens, and the family's plain reference's.
    Every chunk of the lone request carries 7 neutral rows
    (`decode_rows_idle`); the engine builds the executables it always
    built (a prefill window, a decode chunk, two helpers, and the move
    of a state row where the model has state), a second engine none."""
    m, kw, vocab, check = _toy_family(family)
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, vocab, (n,)).astype(np.int64)
               for n in (13, 5, 16, 9, 3, 12, 8, 15)]
    # the pool's own programs are kept once a process and pool geometry:
    # a test file this worker ran before may have built this one's
    kv_cache._SPILL_SCATTER_CACHE.clear()
    miss0 = compile_cache_misses()
    alone = ServingEngine(m, ServingConfig(max_batch=8, prefix_cache=True,
                                           **kw))
    first = alone.submit(prompts[0], max_new_tokens=NEW)
    alone.drain()
    built = compile_cache_misses() - miss0
    assert built == executables
    c = alone.metrics.counters
    assert first.status == "done" and first.n_out == NEW
    assert c["decode_chunks"] == -(-(NEW - 1) // CHUNK)
    assert c["decode_rows_idle"] == 7 * c["decode_chunks"]
    assert alone.summary()["decode_rows_idle_total"] == c["decode_rows_idle"]
    assert "paddle_tpu_serving_decode_rows_idle_total" in alone.metrics_text()

    full = ServingEngine(m, ServingConfig(max_batch=8, prefix_cache=False,
                                          **kw))
    handles = [full.submit(p, max_new_tokens=NEW) for p in prompts]
    full.drain()
    assert compile_cache_misses() - miss0 == built
    np.testing.assert_array_equal(handles[0].tokens[:NEW],
                                  first.tokens[:NEW])
    check([first] + handles)
    c = full.metrics.counters
    assert c["decode_rows_idle"] < 7 * c["decode_chunks"]


def test_decode_rows_idle_counts_the_neutral_rows_of_every_chunk(
        served_model, mix):
    """A scripted order of arrivals, an EOS inside a chunk among them:
    the counter is the sum over launched chunks of the rows handed to the
    model as done before the chunk starts, whatever made them neutral
    (no request, a prefill under way, the last tokens launched)."""
    m, _ = served_model
    reqs, eos = mix
    eng = _engine(m, eos, max_batch=4)
    neutral = []
    real = m.decode_paged

    def watching(pools, tables, lens, pending, done, *a, **kw):
        neutral.append(int(np.asarray(lens == 0).sum()))
        assert np.asarray(done)[np.asarray(lens) == 0].all()
        return real(pools, tables, lens, pending, done, *a, **kw)

    m.decode_paged = watching
    try:
        handles = []
        for i, (p, b) in enumerate(reqs):       # two arrive every 2 steps
            handles.append(eng.submit(p, max_new_tokens=b))
            if i % 2:
                eng.step()
                eng.step()
        eng.drain()
    finally:
        m.decode_paged = real
    c = eng.metrics.counters
    assert c["decode_chunks"] == len(neutral)
    assert c["decode_rows_idle"] == sum(neutral) > 0
    assert {0 < n < 4 for n in neutral} == {True}
    for h, (p, b) in zip(handles, reqs):
        np.testing.assert_array_equal(h.tokens[:h.n_out],
                                      serial_reference(m, p, b, eos))
