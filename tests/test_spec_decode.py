"""ISSUE 11: ragged multi-token paged attention + speculative decoding.

Covers (1) interpret-mode parity of the [B, k] Pallas kernels (f32 + q8)
against the gather reference across ragged (k, start, lens) mixes incl.
the k=1 degenerate and exact block-boundary rows; (2) verify_paged's
longest-accepted-prefix rule against a numpy oracle, EOS chain forcing
included; (3) the spec engine's bit-identical-greedy contract vs
generate_static_ragged across mixed accept/reject traffic with zero
post-warmup jit cache misses; (4) chunked prefill: parity + one
executable for every prompt length; (5) trie prompt-lookup drafting and
the spec acceptance metrics.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference import (ServingConfig, ServingEngine,
                                  model_draft_fn, repeated_traffic,
                                  shared_prefix_traffic)
from paddle_tpu.inference.kv_cache import BlockPool
from paddle_tpu.inference.prefix_cache import PrefixCache
from paddle_tpu.jit.api import compile_cache_misses
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.ops.attention import (paged_prefill_write,
                                      paged_prefill_write_q8,
                                      paged_prefix_attention_reference,
                                      paged_prefix_attention_reference_q8,
                                      paged_attention_reference)
from paddle_tpu.ops.pallas.paged_attention import (
    paged_prefix_attention_kernel, paged_prefix_attention_q8_kernel)


# ------------------------------------------------ multi-token kernel parity

def _fp_pool(n_rows=3, bs=4, nh=4, hd=8, mb=4, seed=0):
    """Pool with n_rows block-table rows fully written (mb blocks each)."""
    rng = np.random.RandomState(seed)
    nb = 1 + n_rows * mb
    kp = jnp.zeros((nb, bs, nh, hd), jnp.float32)
    vp = jnp.zeros_like(kp)
    tables = np.arange(1, nb, dtype=np.int32).reshape(n_rows, mb)
    t = jnp.asarray(tables)
    K = rng.randn(n_rows, mb * bs, nh, hd).astype(np.float32) * 0.3
    V = rng.randn(n_rows, mb * bs, nh, hd).astype(np.float32) * 0.3
    for b in range(n_rows):
        kp = paged_prefill_write(kp, jnp.asarray(K[b:b + 1]), t[b:b + 1])
        vp = paged_prefill_write(vp, jnp.asarray(V[b:b + 1]), t[b:b + 1])
    return kp, vp, t


@pytest.mark.parametrize("s,start", [
    (1, (8, 3, 0)),          # k=1 degenerate (the decode case)
    (4, (4, 0, 1)),          # window starting AT a block boundary
    (4, (3, 5, 0)),          # window CROSSING a block boundary
    (5, (11, 2, 7)),         # odd window, mixed offsets
    (8, (8, 0, 0)),          # window = two whole blocks
])
def test_multi_token_kernel_interpret_parity(s, start):
    """Pallas [B, k] kernel (interpret mode) == gather reference across
    ragged (k, start) mixes — block-boundary rows included."""
    kp, vp, t = _fp_pool()
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(3, s, 4, 8).astype(np.float32) * 0.3)
    st = jnp.asarray(start, jnp.int32)
    got = paged_prefix_attention_kernel(q, kp, vp, t, st, interpret=True)
    want = paged_prefix_attention_reference(q, kp, vp, t, st)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_multi_token_kernel_k1_matches_decode_reference():
    """The k=1 window with start = lens-1 IS single-token decode: the
    multi-token kernel subsumes the decode case (same attended set as
    paged_attention_reference at lens attendable rows)."""
    kp, vp, t = _fp_pool(seed=3)
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(3, 1, 4, 8).astype(np.float32) * 0.3)
    lens = jnp.asarray([9, 4, 1], jnp.int32)   # incl. a block boundary
    got = paged_prefix_attention_kernel(q, kp, vp, t, lens - 1,
                                        interpret=True)
    want = paged_attention_reference(q, kp, vp, t, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("s,start", [(1, (8, 3)), (4, (4, 0)),
                                     (6, (10, 2))])
def test_multi_token_q8_kernel_interpret_parity(s, start):
    rng = np.random.RandomState(1)
    bs, nh, hd, mb = 4, 4, 8, 4
    nb = 1 + 2 * mb
    kc = jnp.zeros((nb, bs, nh, hd), jnp.int8)
    ks = jnp.zeros((nb, bs, nh), jnp.float32)
    vc = jnp.zeros_like(kc)
    vs = jnp.zeros_like(ks)
    t = jnp.asarray(np.arange(1, nb, dtype=np.int32).reshape(2, mb))
    K = rng.randn(2, mb * bs, nh, hd).astype(np.float32) * 0.3
    V = rng.randn(2, mb * bs, nh, hd).astype(np.float32) * 0.3
    for b in range(2):
        kc, ks = paged_prefill_write_q8(kc, ks, jnp.asarray(K[b:b + 1]),
                                        t[b:b + 1])
        vc, vs = paged_prefill_write_q8(vc, vs, jnp.asarray(V[b:b + 1]),
                                        t[b:b + 1])
    q = jnp.asarray(rng.randn(2, s, nh, hd).astype(np.float32) * 0.3)
    st = jnp.asarray(start, jnp.int32)
    got = paged_prefix_attention_q8_kernel(q, kc, ks, vc, vs, t, st,
                                           interpret=True)
    want = paged_prefix_attention_reference_q8(q, kc, ks, vc, vs, t, st)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------- trie prompt lookup

class TestLookupContinuation:
    def _pool(self):
        return BlockPool(num_blocks=32, block_size=4, num_layers=1,
                         block_shapes=((4, 1, 2),) * 2, head_axis=1)

    def test_continuation_after_full_blocks(self):
        p = self._pool()
        c = PrefixCache(p)
        toks = np.arange(12, dtype=np.int64) + 1
        c.insert(toks, p.alloc(1, 12))
        # aligned context: the next cached block's key is the draft
        assert c.lookup_continuation(toks[:4], 4) == [5, 6, 7, 8]
        assert c.lookup_continuation(toks[:8], 8) == [9, 10, 11, 12]
        # n caps the draft; walking past the cached path truncates
        assert c.lookup_continuation(toks[:4], 2) == [5, 6]
        assert c.lookup_continuation(toks[:4], 99) == list(range(5, 13))

    def test_partial_tail_matches_inside_a_block(self):
        p = self._pool()
        c = PrefixCache(p)
        toks = np.arange(8, dtype=np.int64) + 1
        c.insert(toks, p.alloc(1, 8))
        # context ends mid-block: the block key's remainder is the draft
        assert c.lookup_continuation(toks[:5], 4) == [6, 7, 8]
        assert c.lookup_continuation(toks[:7], 4) == [8]

    def test_divergence_returns_empty(self):
        p = self._pool()
        c = PrefixCache(p)
        toks = np.arange(8, dtype=np.int64) + 1
        c.insert(toks, p.alloc(1, 8))
        wrong = toks.copy()
        wrong[6] = 77                        # tail diverges from the key
        assert c.lookup_continuation(wrong[:7], 4) == []
        wrong2 = toks.copy()
        wrong2[1] = 77                       # full block diverges
        assert c.lookup_continuation(wrong2[:6], 4) == []
        assert c.lookup_continuation(toks, 4) == []   # path exhausted

    def test_lookup_does_not_stamp_lru(self):
        p = self._pool()
        c = PrefixCache(p)
        a = np.arange(8, dtype=np.int64) + 1
        b = np.arange(8, dtype=np.int64) + 50
        c.insert(a, p.alloc(1, 8))
        c.insert(b, p.alloc(2, 8))
        p.free(1)
        p.free(2)
        c.match(a)                           # a is the recent one
        c.lookup_continuation(b[:4], 4)      # a peek must NOT refresh b
        c.evict(2)
        # b's leaf+root went, a survived
        assert c.lookup_continuation(a[:4], 4) == [5, 6, 7, 8]
        assert c.lookup_continuation(b[:4], 4) == []


# ------------------------------------------------ verify acceptance oracle

@pytest.fixture(scope="module")
def served_model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                    num_heads=4, max_position_embeddings=96,
                    intermediate_size=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m, cfg


CAP, NEW = 8, 6


def _setup_chain(m, seed=1, budget=8):
    pool = BlockPool.for_model(m, num_blocks=16, block_size=4)
    pools = pool.make_pools()
    prompt = np.random.RandomState(seed).randint(
        1, 96, (1, CAP)).astype(np.int64)
    pool.alloc(0, CAP + budget)
    tbl = pool.table_row(0, 4)[None]
    pools, first = m.prefill_paged(prompt, [CAP], pools, tbl)
    return pools, tbl, int(first.numpy()[0])


def test_verify_accept_math_against_plain_chain(served_model):
    """Longest-accepted-prefix rule vs the step-by-step decode chain:
    full accept, full reject, and a mid-window mismatch all emit exactly
    the plain chain's tokens and advance by n_acc + 1."""
    m, cfg = served_model
    pools, tbl, t0 = _setup_chain(m)
    lens = np.asarray([CAP], np.int32)
    pend = np.asarray([t0], np.int32)
    toks, pools, _, _ = m.decode_paged(pools, tbl, lens, pend,
                                       np.zeros((1,), bool), 6)
    ref = np.asarray(toks.numpy())[0]

    cases = [
        (ref[:3].astype(np.int32), 3),                     # full accept
        (np.asarray([95, 94, 93], np.int32), 0),           # full reject
        (np.asarray([ref[0], 93, ref[2]], np.int32), 1),   # mid mismatch
    ]
    for draft, want_acc in cases:
        pools2, tbl2, t0b = _setup_chain(m)
        assert t0b == t0
        e, n_acc, pools2, _ = m.verify_paged(
            pools2, tbl2, lens, pend, draft[None], np.zeros((1,), bool))
        n = int(np.asarray(n_acc)[0])
        e = np.asarray(e.numpy())[0]
        assert n == want_acc
        np.testing.assert_array_equal(e[:n + 1], ref[:n + 1])


def test_verify_chain_continues_bitwise_after_rejects(served_model):
    """Rejected-position KV writes are garbage BELOW the next window's
    start: a plain decode resumed after a partial-accept window matches
    the uninterrupted chain bitwise (the overwrite-before-attendable
    invariant)."""
    m, cfg = served_model
    pools, tbl, t0 = _setup_chain(m)
    lens = np.asarray([CAP], np.int32)
    pend = np.asarray([t0], np.int32)
    toks, pools, _, _ = m.decode_paged(pools, tbl, lens, pend,
                                       np.zeros((1,), bool), 6)
    ref = np.asarray(toks.numpy())[0]

    pools2, tbl2, _ = _setup_chain(m)
    draft = np.asarray([[ref[0], 93, 92]], np.int32)    # accept 1 of 3
    e, n_acc, pools2, _ = m.verify_paged(
        pools2, tbl2, lens, pend, draft, np.zeros((1,), bool))
    n = int(np.asarray(n_acc)[0])
    assert n == 1
    e = np.asarray(e.numpy())
    toks2, pools2, _, _ = m.decode_paged(
        pools2, tbl2, lens + n + 1, e[:, n].astype(np.int32),
        np.zeros((1,), bool), 4)
    np.testing.assert_array_equal(np.asarray(toks2.numpy())[0],
                                  ref[n + 1:n + 5])


def test_verify_eos_chain_forcing(served_model):
    """EOS semantics match decode_paged's sequential rule: once the
    chain emits EOS at a window position, every later emitted position
    is EOS regardless of argmax, and done_out reflects only EMITTED
    positions."""
    m, cfg = served_model
    pools, tbl, t0 = _setup_chain(m)
    lens = np.asarray([CAP], np.int32)
    pend = np.asarray([t0], np.int32)
    toks, pools, _, _ = m.decode_paged(pools, tbl, lens, pend,
                                       np.zeros((1,), bool), 6)
    ref = np.asarray(toks.numpy())[0]
    eos = int(ref[1])          # make the chain's 2nd token "EOS"

    # plain chain with that eos: decode_paged forces post-EOS tokens
    pools2, tbl2, _ = _setup_chain(m)
    toksf, pools2, _, donef = m.decode_paged(
        pools2, tbl2, lens, pend, np.zeros((1,), bool), 4,
        eos_token_id=eos)
    want = np.asarray(toksf.numpy())[0]
    assert np.all(want[1:] == eos)

    # spec window drafting the same chain: emitted tokens match, done set
    pools3, tbl3, _ = _setup_chain(m)
    draft = want[:3].astype(np.int32)[None]
    e, n_acc, pools3, done3 = m.verify_paged(
        pools3, tbl3, lens, pend, draft, np.zeros((1,), bool),
        eos_token_id=eos)
    n = int(np.asarray(n_acc)[0])
    e = np.asarray(e.numpy())[0]
    np.testing.assert_array_equal(e[:n + 1], want[:n + 1])
    assert bool(np.asarray(done3)[0])       # EOS was emitted

    # a row done on ENTRY emits eos everywhere and stays done
    pools4, tbl4, _ = _setup_chain(m)
    e4, _, pools4, done4 = m.verify_paged(
        pools4, tbl4, lens, pend, draft, np.ones((1,), bool),
        eos_token_id=eos)
    assert np.all(np.asarray(e4.numpy()) == eos)
    assert bool(np.asarray(done4)[0])


# ----------------------------------------------------- spec engine oracle

def _ref_chains(m, ids, lens, **kw):
    return m.generate_static_ragged(paddle.to_tensor(ids), lens,
                                    max_new_tokens=NEW,
                                    **kw).numpy()[:, ids.shape[1]:]


def _check_parity(done, ids, lens, ref):
    assert all(r.status == "done" for r in done)
    for r in done:
        row = next(i for i in range(len(lens))
                   if np.array_equal(ids[i, :lens[i]], r.prompt))
        np.testing.assert_array_equal(r.tokens, ref[row])


def test_spec_engine_bit_identical_and_zero_misses(served_model):
    """The headline oracle: speculative greedy output == non-speculative
    generate_static_ragged per row across MIXED accept/reject traffic
    (repeats draft + accept fully; fresh prompts reject or have no
    draft), with zero post-warmup jit cache misses."""
    m, cfg = served_model
    eng = ServingEngine(m, ServingConfig(
        max_batch=2, prompt_cap=CAP, max_new_tokens=NEW, decode_chunk=2,
        kv_block=4, kv_blocks=96, prefix_cache=True,
        spec_decode=True, spec_k=3))
    eng.warmup_prefix_cache(cfg.vocab_size, clear=False)

    # traffic: 2 prompts repeated (full acceptance after first pass) + 3
    # fresh ragged prompts (no draft / rejecting drafts)
    rep = repeated_traffic(6, n_prompts=2, prompt_len=CAP,
                           vocab_size=cfg.vocab_size, rate=1e9, seed=5)
    lens = [CAP, CAP, 7, 3, 5]
    rng = np.random.RandomState(9)
    ids = rng.randint(1, cfg.vocab_size,
                      (len(lens), CAP)).astype(np.int64)
    ids[0] = rep[0]["prompt"] if rep[0]["prompt_id"] == 0 else \
        next(t["prompt"] for t in rep if t["prompt_id"] == 0)
    ids[1] = next(t["prompt"] for t in rep if t["prompt_id"] == 1)
    for r, ln in enumerate(lens):
        ids[r, ln:] = 0
    ref = _ref_chains(m, ids, lens)

    miss0 = compile_cache_misses()
    submitted = []
    for t in rep:
        submitted.append(t["prompt"])
    for i in range(2, len(lens)):
        submitted.append(ids[i, :lens[i]])
    for p in submitted:
        eng.submit(p)
    done = eng.drain()
    assert compile_cache_misses() - miss0 == 0, \
        f"steady spec traffic recompiled: {eng.monitor.recompiles}"
    _check_parity(done, ids, lens, ref)
    s = eng.metrics.counters
    assert s["spec_windows"] > 0 and s["spec_drafts_trie"] > 0
    assert 0 < s["spec_accepted"] <= s["spec_proposed"]
    # repeats accept fully: at least one window emitted spec_k + 1
    assert eng.metrics.hists["spec_accept_len"]._max == 4


def test_spec_engine_parity_with_eos(served_model):
    """Mixed traffic with an EOS token id: spec chains stay bit-identical
    incl. post-EOS forcing and early finish."""
    m, cfg = served_model
    eos = 11
    eng = ServingEngine(m, ServingConfig(
        max_batch=2, prompt_cap=CAP, max_new_tokens=NEW, decode_chunk=2,
        kv_block=4, kv_blocks=96, prefix_cache=True,
        spec_decode=True, spec_k=3, eos_token_id=eos))
    eng.warmup_prefix_cache(cfg.vocab_size, clear=False)
    lens = [CAP, CAP, 6, 2]
    rng = np.random.RandomState(3)
    ids = rng.randint(1, cfg.vocab_size, (len(lens), CAP)).astype(np.int64)
    for r, ln in enumerate(lens):
        ids[r, ln:] = 0
    ref = _ref_chains(m, ids, lens, eos_token_id=eos)
    for rep in range(2):        # second pass drafts the first's chains
        for i in range(len(lens)):
            eng.submit(ids[i, :lens[i]])
        done = eng.drain()
        # engine rows truncate at EOS (n_out); compare the truncated form
        assert all(r.status == "done" for r in done)
        for r in done:
            row = next(i for i in range(len(lens))
                       if np.array_equal(ids[i, :lens[i]], r.prompt))
            want = ref[row]
            np.testing.assert_array_equal(r.tokens[:r.n_out],
                                          want[:r.n_out])
            # beyond n_out the reference chain is EOS-forced padding
            assert np.all(want[r.n_out:] == eos) or \
                r.n_out == want.shape[0]


def test_spec_engine_model_draft_and_source_split(served_model):
    """A draft-model hook (the target itself = oracle drafter) serves
    rows the trie cannot; the metrics split trie vs model windows."""
    m, cfg = served_model
    # budget 1 + spec_k + 1: every request is exactly one full verify
    # window after the prefill token, so no window is budget-truncated
    # and the oracle drafter's acceptance accounting is exact
    new = 5
    eng = ServingEngine(m, ServingConfig(
        max_batch=2, prompt_cap=CAP, max_new_tokens=new, decode_chunk=2,
        kv_block=4, spec_decode=True, spec_k=3,
        spec_draft=model_draft_fn(m, window=16)))
    lens = [CAP, 5, 3]
    rng = np.random.RandomState(2)
    ids = rng.randint(1, cfg.vocab_size, (len(lens), CAP)).astype(np.int64)
    for r, ln in enumerate(lens):
        ids[r, ln:] = 0
    ref = m.generate_static_ragged(paddle.to_tensor(ids), lens,
                                   max_new_tokens=new).numpy()[:, CAP:]
    eng.submit(ids[0, :lens[0]])
    eng.drain()                 # warm: prefill + verify + draft executable
    miss0 = compile_cache_misses()
    for i in range(len(lens)):
        eng.submit(ids[i, :lens[i]])
    done = eng.drain()
    assert compile_cache_misses() - miss0 == 0
    _check_parity(done, ids, lens, ref)
    s = eng.metrics.counters
    assert s["spec_drafts_model"] > 0 and s["spec_drafts_trie"] == 0
    # the oracle drafter's proposals all accept (no truncated windows)
    assert s["spec_accepted"] == s["spec_proposed"]
    # emitted-per-window accounting ties out against real output: every
    # window emitted accepted-drafts + bonus, summed = histogram sum
    assert eng.metrics.hists["spec_accept_len"].sum == \
        s["spec_accepted"] + s["spec_windows"]


def test_spec_request_jsonl_row_carries_acceptance(served_model, tmp_path):
    m, cfg = served_model
    import json
    path = str(tmp_path / "req.jsonl")
    from paddle_tpu.inference import ServingMetrics
    eng = ServingEngine(m, ServingConfig(
        max_batch=2, prompt_cap=CAP, max_new_tokens=NEW, decode_chunk=2,
        kv_block=4, kv_blocks=96, prefix_cache=True,
        spec_decode=True, spec_k=3),
        metrics=ServingMetrics(jsonl_path=path))
    prompt = np.random.RandomState(4).randint(
        1, cfg.vocab_size, (CAP,)).astype(np.int64)
    for _ in range(2):          # second run drafts the first's chain
        eng.submit(prompt)
        eng.drain()
    rows = [json.loads(l) for l in open(path)]
    spec_rows = [r for r in rows
                 if "request" in r and "spec" in r["request"]]
    assert spec_rows, "no request row carried spec acceptance"
    sp = spec_rows[-1]["request"]["spec"]
    assert sp["proposed"] > 0 and 0 <= sp["accepted"] <= sp["proposed"]
    assert sp["accept_rate"] == round(sp["accepted"] / sp["proposed"], 4)


def test_spec_config_validation():
    with pytest.raises(ValueError, match="padded engine was removed"):
        ServingConfig(prefix_cache=True, spec_decode=True, paged=False)
    with pytest.raises(ValueError, match="prefix_cache"):
        ServingConfig(spec_decode=True)            # the default trie draft
    with pytest.raises(ValueError, match="prefix_cache"):
        ServingConfig(spec_decode=True, spec_draft="trie")
    with pytest.raises(ValueError, match="greedy"):
        ServingConfig(prefix_cache=True, spec_decode=True,
                      temperature=0.7)
    with pytest.raises(ValueError, match="spec_k"):
        ServingConfig(prefix_cache=True, spec_decode=True,
                      spec_k=0)
    with pytest.raises(ValueError, match="spec_k"):
        # cap keeps the accept-length histogram's exact integer buckets
        ServingConfig(prefix_cache=True, spec_decode=True,
                      spec_k=32)
    with pytest.raises(ValueError, match="callable"):
        ServingConfig(spec_decode=True, spec_draft="ngram")
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingConfig(prompt_cap=8, prefill_chunk=9)
    with pytest.raises(ValueError, match="padded engine was removed"):
        ServingConfig(prefill_chunk=4, paged=False)
    assert ServingConfig(prefill_chunk=4).prefill_chunk == 4
    # a callable drafter needs no prefix cache
    ServingConfig(spec_decode=True, spec_draft=lambda c, k: [])


def test_spec_int8_paged_parity(served_model):
    """Speculative decode over int8 paged pools: bit-identical to the
    static int8 chain (the q8 multi-token kernel path)."""
    m, cfg = served_model
    eng = ServingEngine(m, ServingConfig(
        max_batch=2, prompt_cap=CAP, max_new_tokens=NEW, decode_chunk=2,
        kv_block=4, kv_blocks=96, prefix_cache=True,
        cache_dtype="int8", spec_decode=True, spec_k=3))
    eng.warmup_prefix_cache(cfg.vocab_size, clear=False)
    lens = [CAP, 5]
    rng = np.random.RandomState(6)
    ids = rng.randint(1, cfg.vocab_size, (len(lens), CAP)).astype(np.int64)
    for r, ln in enumerate(lens):
        ids[r, ln:] = 0
    ref = _ref_chains(m, ids, lens, cache_dtype="int8")
    for _ in range(2):
        for i in range(len(lens)):
            eng.submit(ids[i, :lens[i]])
        done = eng.drain()
        _check_parity(done, ids, lens, ref)
    assert eng.metrics.counters["spec_windows"] > 0


# --------------------------------------------------------- chunked prefill

@pytest.mark.parametrize("pc", [1, 3, 4, 8])
def test_chunked_prefill_parity_and_one_executable(served_model, pc):
    """prefill_chunk=N: greedy output bit-identical to one-shot prefill
    for every prompt length, with ZERO new executables across lengths
    (offsets are data through the single [1, N] start-form program).
    N=1 pins the start-before-width dispatch in the attention branch —
    a [1, 1] window with a start offset is a suffix-prefill chunk, not
    a decode step (it would otherwise write the wrong pool position)."""
    m, cfg = served_model
    lens = [CAP, 7, 3, 1, 5, CAP]
    rng = np.random.RandomState(1)
    ids = rng.randint(1, cfg.vocab_size, (len(lens), CAP)).astype(np.int64)
    for r, ln in enumerate(lens):
        ids[r, ln:] = 0
    ref = _ref_chains(m, ids, lens)
    eng = ServingEngine(m, ServingConfig(
        max_batch=2, prompt_cap=CAP, max_new_tokens=NEW, decode_chunk=2,
        kv_block=4, prefill_chunk=pc))
    eng.submit(ids[0, :lens[0]])
    eng.drain()                                  # warm
    miss0 = compile_cache_misses()
    for i in range(len(lens)):
        eng.submit(ids[i, :lens[i]])
    done = eng.drain()
    assert compile_cache_misses() - miss0 == 0
    _check_parity(done, ids, lens, ref)


def test_chunked_prefill_interleaves_decode(served_model):
    """A long prompt admitted mid-flight must NOT stall the live decode
    batch for its whole prefill: with prefill_chunk set, decode chunks
    keep landing between prefill windows (the monopolization fix), and
    both requests' outputs stay bit-identical to the reference."""
    m, cfg = served_model
    eng = ServingEngine(m, ServingConfig(
        max_batch=2, prompt_cap=CAP, max_new_tokens=NEW, decode_chunk=1,
        kv_block=4, prefill_chunk=2))
    rng = np.random.RandomState(8)
    a = rng.randint(1, cfg.vocab_size, (3,)).astype(np.int64)
    b = rng.randint(1, cfg.vocab_size, (CAP,)).astype(np.int64)
    eng.submit(a)
    eng.step()                 # admit a; prefill window 1 of 2
    eng.step()                 # a's final window: first token sampled
    slot_a = next(i for i, r in enumerate(eng._slots) if r is not None)
    assert eng._prefill_pos[slot_a] < 0     # a is now a decode row
    eng.submit(b)              # cap-length prompt joins mid-flight
    produced_before = eng._slots[slot_a]._produced
    done = eng.step()          # b: window 1 of 4; a: decode chunk runs
    slot_b = next(i for i, r in enumerate(eng._slots)
                  if r is not None and i != slot_a)
    assert eng._prefill_pos[slot_b] >= 0    # b still mid-prefill...
    assert eng._slots[slot_a] is None or \
        eng._slots[slot_a]._produced > produced_before \
        or any(r.prompt.shape[0] == 3 for r in done)
    # ...while a made decode progress in the same step
    done += eng.drain()
    ids = np.stack([np.pad(a, (0, CAP - 3)), b])
    ref = _ref_chains(m, ids, [3, CAP])
    _check_parity(done, ids, [3, CAP], ref)


def test_chunked_prefill_composes_with_prefix_cache_and_spec(served_model):
    """All three together: chunked prefill + prefix cache + speculative
    decode — parity holds and the steady loop stays compile-free."""
    m, cfg = served_model
    eng = ServingEngine(m, ServingConfig(
        max_batch=2, prompt_cap=CAP, max_new_tokens=NEW, decode_chunk=2,
        kv_block=4, kv_blocks=96, prefix_cache=True,
        spec_decode=True, spec_k=3, prefill_chunk=4))
    eng.warmup_prefix_cache(cfg.vocab_size, clear=False)
    lens = [CAP, CAP, 5]
    rng = np.random.RandomState(12)
    ids = rng.randint(1, cfg.vocab_size, (len(lens), CAP)).astype(np.int64)
    for r, ln in enumerate(lens):
        ids[r, ln:] = 0
    ref = _ref_chains(m, ids, lens)
    miss_after_warm = None
    for rep in range(2):
        for i in range(len(lens)):
            eng.submit(ids[i, :lens[i]])
        done = eng.drain()
        _check_parity(done, ids, lens, ref)
        if rep == 0:
            miss_after_warm = compile_cache_misses()
    assert compile_cache_misses() == miss_after_warm
    assert eng.metrics.counters["spec_windows"] > 0


# ------------------------------------------------------ traffic generator

def test_repeated_traffic_profile():
    tr = repeated_traffic(40, n_prompts=3, prompt_len=6, vocab_size=50,
                          rate=100.0, seed=0)
    assert len(tr) == 40
    ids = {t["prompt_id"] for t in tr}
    assert ids <= {0, 1, 2} and len(ids) > 1
    by_id = {}
    for t in tr:
        key = t["prompt_id"]
        if key in by_id:
            np.testing.assert_array_equal(by_id[key], t["prompt"])
        by_id[key] = t["prompt"]
    ats = [t["at"] for t in tr]
    assert ats == sorted(ats) and ats[0] == 0.0
    with pytest.raises(ValueError):
        repeated_traffic(1, n_prompts=0, prompt_len=4, vocab_size=10)


def test_spec_throughput_exceeds_plain_on_repeat_traffic(served_model):
    """The perf claim at toy scale: on repeated-prompt traffic the spec
    engine makes strictly fewer device calls per emitted token than the
    plain paged engine (wall-clock is too noisy for CI; call count is
    the deterministic proxy — each call is one launch+sync)."""
    m, cfg = served_model
    traffic = repeated_traffic(8, n_prompts=2, prompt_len=CAP,
                               vocab_size=cfg.vocab_size, rate=1e9,
                               seed=7)

    def run(spec):
        eng = ServingEngine(m, ServingConfig(
            max_batch=2, prompt_cap=CAP, max_new_tokens=NEW,
            decode_chunk=1, kv_block=4, kv_blocks=96,
            prefix_cache=True, spec_decode=spec, spec_k=3))
        eng.warmup_prefix_cache(cfg.vocab_size)
        eng.metrics = type(eng.metrics)()
        calls0 = eng._calls
        for t in traffic:
            eng.submit(t["prompt"])
        eng.drain()
        toks = eng.metrics.counters["tokens_out"]
        return (eng._calls - calls0) / max(toks, 1), toks

    plain_cpt, toks_p = run(False)
    spec_cpt, toks_s = run(True)
    assert toks_p == toks_s
    assert spec_cpt < plain_cpt, \
        f"spec {spec_cpt:.3f} calls/token !< plain {plain_cpt:.3f}"
