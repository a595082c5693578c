"""Prefix cache subsystem (ISSUE 10): radix-trie matching, refcounted
copy-on-write block sharing, and the int8 paged KV mode.

Covers the refcounted BlockPool (shared alloc, retain/release, free only
at refcount zero, conservation), the PrefixCache trie (insert/match
alignment, LRU eviction, byte budget, reclaim under pool pressure), the
int8 paged ops (gather reference == the static factored-scale math, the
Pallas kernel's interpret path), and the serving engine: zero-prefill
admission on a repeated prefix (TTFT = one decode step, prefill never
called), suffix-only prefill on a partial hit, COW never mutating a
shared block (checksummed), greedy bit-parity with the cache on vs off
and int8-paged vs the static int8 path, pinned shared-occupancy metrics
math, and zero post-warmup recompiles with cache + int8 enabled.
"""
import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference import (BlockPool, PrefixCache, ServingConfig,
                                  ServingEngine, shared_prefix_traffic)
from paddle_tpu.jit.api import compile_cache_misses
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.ops.attention import (attention_q8_cache,
                                      paged_attention_reference,
                                      paged_attention_reference_q8,
                                      paged_cache_write_q8,
                                      paged_prefill_write,
                                      paged_prefix_attention_reference,
                                      quantize_kv)
from paddle_tpu.ops.pallas.paged_attention import paged_attention_q8_kernel


# ------------------------------------------------- refcounted allocator

def _pool(blocks=10, bs=4, **kw):
    return BlockPool(num_blocks=blocks, block_size=bs, num_layers=2,
                     block_shapes=((bs, 2, 4),) * 2, head_axis=1, **kw)


class TestRefcountedPool:
    def test_shared_alloc_free_at_zero(self):
        p = _pool()
        a = p.alloc(1, 8)                       # 2 private blocks
        assert [p.refcount(b) for b in a] == [1, 1]
        b = p.alloc(2, 12, shared=list(a))      # maps both + 1 fresh
        assert len(b) == 3 and list(b[:2]) == list(a)
        assert [p.refcount(x) for x in a] == [2, 2]
        # owner 1 frees: shared blocks stay resident (owner 2 holds them)
        assert p.free(1) == 0
        assert [p.refcount(x) for x in a] == [1, 1]
        assert p.free(2) == 3                   # last refs -> free list
        assert p.free_blocks == p.capacity_blocks

    def test_retain_release_conservation(self):
        """Every alloc path balanced by release: pool drains to full
        capacity whatever the interleaving."""
        p = _pool()
        a = p.alloc(1, 8)
        p.retain(a)                             # the cache's reference
        p.free(1)
        assert p.free_blocks == p.capacity_blocks - 2   # cache holds 2
        c = p.alloc(3, 8, shared=list(a))       # served FROM the cache
        assert list(c) == list(a)
        p.free(3)
        assert p.release(a) == 2
        assert p.free_blocks == p.capacity_blocks
        assert p._refs == {}

    def test_guard_rails(self):
        p = _pool()
        with pytest.raises(ValueError, match="never shared"):
            p.alloc(1, 4, shared=[0])
        with pytest.raises(ValueError, match="not live"):
            p.alloc(1, 4, shared=[3])           # nobody allocated 3
        a = p.alloc(1, 4)
        with pytest.raises(ValueError, match="longer than"):
            p.alloc(2, 2, shared=[int(a[0]), int(a[0])])
        p.free(1)
        with pytest.raises(ValueError, match="underflow"):
            p.release(a)

    def test_int8_pools_and_bytes(self):
        p = _pool(cache_dtype="int8")
        pools = p.make_pools()
        kc, ks, vc, vs = pools[0]
        assert kc.shape == (10, 4, 2, 4) and kc.dtype == jnp.int8
        assert ks.shape == (10, 4, 2) and ks.dtype == jnp.float32
        # 2 layers * (K+V) * (4*2*4 int8 codes + 4*2 f32 scales)
        assert p.bytes_per_block == 2 * 2 * (4 * 2 * 4 + 4 * 2 * 4)
        fp = _pool()
        assert fp.bytes_per_block == 2 * 2 * (4 * 2 * 4 * 4)
        with pytest.raises(ValueError, match="cache_dtype"):
            _pool(cache_dtype="fp8")


# ------------------------------------------------------ the radix trie

class TestPrefixTrie:
    def test_match_is_block_aligned(self):
        p = _pool(blocks=16)
        c = PrefixCache(p)
        toks = np.arange(10, dtype=np.int64) + 1
        blocks = p.alloc(1, 10)                 # 3 blocks, last partial
        assert c.insert(toks, blocks) == 2      # only FULL blocks cached
        assert c.cached_blocks == 2
        got, n = c.match(toks)
        assert n == 8 and got == [int(blocks[0]), int(blocks[1])]
        # divergence inside block 2 -> only block 1 matches
        div = toks.copy()
        div[5] = 99
        got, n = c.match(div)
        assert n == 4 and got == [int(blocks[0])]
        # shorter than one block -> no match
        assert c.match(toks[:3]) == ([], 0)

    def test_insert_dedups_and_shares_nodes(self):
        p = _pool(blocks=16)
        c = PrefixCache(p)
        a = np.arange(8, dtype=np.int64) + 1
        blk_a = p.alloc(1, 8)
        c.insert(a, blk_a)
        # a second chain with the same first block: node dedup'd, the
        # duplicate block is NOT retained (its owner's free releases it)
        b = np.concatenate([a[:4], np.int64([50, 51, 52, 53])])
        blk_b = p.alloc(2, 8)
        assert c.insert(b, blk_b) == 1          # only the divergent block
        assert c.cached_blocks == 3
        assert p.refcount(blk_b[0]) == 1        # not retained by cache
        got, n = c.match(b)
        assert n == 8 and got[0] == int(blk_a[0])

    def test_lru_eviction_refcount_guarded(self):
        p = _pool(blocks=16)
        c = PrefixCache(p)
        a = np.arange(8, dtype=np.int64) + 1
        blk = p.alloc(1, 8)
        c.insert(a, blk)
        p.free(1)                               # cache-only refs now
        b = np.int64([9, 9, 9, 9])
        blk_b = p.alloc(2, 4)
        c.insert(b, blk_b)
        c.match(a)                              # stamp a as recently used
        # owner 2 still live: b's block is NOT evictable; a's chain is,
        # but LRU order inside it is leaf-first (cascade)
        assert c.evict(4) == 2
        assert c.cached_blocks == 1             # b survived via refcount
        assert c.match(a) == ([], 0)
        p.free(2)
        assert c.evict(4) == 1
        assert p.free_blocks == p.capacity_blocks

    def test_byte_budget_evicts_on_insert(self):
        p = _pool(blocks=16)
        c = PrefixCache(p, byte_budget=2 * p.bytes_per_block)
        a = np.arange(8, dtype=np.int64) + 1
        blk = p.alloc(1, 8)
        c.insert(a, blk)
        p.free(1)                               # a's pair is reclaimable
        b = np.int64([7, 7, 7, 7, 8, 8, 8, 8])
        blk_b = p.alloc(2, 8)
        c.insert(b, blk_b)                      # 4 cached > budget of 2:
        # insert evicts a's LRU (reclaimable) pair; b's blocks are
        # refcount-guarded by their live owner
        assert c.cached_blocks == 2
        assert c.match(b)[1] == 8 and c.match(a)[1] == 0
        assert c.cached_bytes <= c.byte_budget
        with pytest.raises(ValueError, match="zero blocks"):
            PrefixCache(p, byte_budget=1)

    def test_reclaim_under_pool_pressure(self):
        p = _pool(blocks=6, bs=4)               # 5 usable blocks
        c = PrefixCache(p)
        a = np.arange(8, dtype=np.int64) + 1
        blk = p.alloc(1, 8)
        c.insert(a, blk)
        p.free(1)                               # 2 blocks cache-resident
        assert p.free_blocks == 3
        assert c.reclaim(5)                     # evicts the cached pair
        assert p.free_blocks == 5
        assert not c.reclaim(6)                 # beyond capacity: honest

    def test_clear_releases(self):
        p = _pool(blocks=16)
        c = PrefixCache(p)
        blk = p.alloc(1, 8)
        c.insert(np.arange(8, dtype=np.int64) + 1, blk)
        p.free(1)
        assert c.clear() == 2
        assert p.free_blocks == p.capacity_blocks and c.cached_blocks == 0


# ----------------------------------------------------- int8 paged ops

def _q8_pool(lens, bs=4, nh=4, hd=8, mb=4, seed=0):
    rng = np.random.RandomState(seed)
    B = len(lens)
    nb = 2 + sum(-(-ln // bs) for ln in lens)
    kc = jnp.zeros((nb, bs, nh, hd), jnp.int8)
    ks = jnp.zeros((nb, bs, nh), jnp.float32)
    vc = jnp.zeros_like(kc)
    vs = jnp.zeros_like(ks)
    tables = np.zeros((B, mb), np.int32)
    nxt = 1
    K = rng.randn(B, mb * bs, nh, hd).astype(np.float32) * 0.3
    V = rng.randn(B, mb * bs, nh, hd).astype(np.float32) * 0.3
    for b, ln in enumerate(lens):
        nblk = -(-ln // bs)
        tables[b, :nblk] = range(nxt, nxt + nblk)
        nxt += nblk
    t = jnp.asarray(tables)
    for b, ln in enumerate(lens):
        for pos in range(ln):
            args = (t[b:b + 1], jnp.asarray([pos], jnp.int32))
            kc, ks = paged_cache_write_q8(
                kc, ks, jnp.asarray(K[b:b + 1, pos:pos + 1]), *args)
            vc, vs = paged_cache_write_q8(
                vc, vs, jnp.asarray(V[b:b + 1, pos:pos + 1]), *args)
    return kc, ks, vc, vs, t, K, V


@pytest.mark.parametrize("lens", [(5, 8, 1), (4, 12, 7)])
def test_paged_q8_reference_matches_static_math(lens):
    """Gathered int8 paged attention == the static factored-scale math
    (attention_q8_cache) on the same rows — the paged pool's per-block
    scales reproduce the static path's per-(pos, head) quantization
    exactly, ragged lengths incl. an exact block boundary."""
    kc, ks, vc, vs, t, K, V = _q8_pool(lens)
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(len(lens), 1, 4, 8).astype(np.float32) * 0.3)
    la = jnp.asarray(lens, jnp.int32)
    got = paged_attention_reference_q8(q, kc, ks, vc, vs, t, la)
    kcod, kscl = quantize_kv(jnp.asarray(K))
    vcod, vscl = quantize_kv(jnp.asarray(V))
    col = jnp.arange(K.shape[1])[None, None, None, :]
    mask = col < la[:, None, None, None]
    want = attention_q8_cache(q, kcod, kscl, vcod, vscl, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_paged_q8_kernel_interpret_matches_reference():
    lens = (5, 8, 1)
    kc, ks, vc, vs, t, _, _ = _q8_pool(lens, seed=2)
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(len(lens), 1, 4, 8).astype(np.float32) * 0.3)
    la = jnp.asarray(lens, jnp.int32)
    got = paged_attention_q8_kernel(q, kc, ks, vc, vs, t, la,
                                    interpret=True)
    want = paged_attention_reference_q8(q, kc, ks, vc, vs, t, la)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_prefill_write_pad_past_table_goes_to_trash():
    """Suffix-prefill padding positions past the TABLE WIDTH must land in
    the trash block — clipping them into the last table entry would let a
    garbage pad column share a destination row with a real suffix column
    (scatter order would then decide who wins)."""
    bs, nh, hd = 4, 2, 4
    pool = jnp.zeros((4, bs, nh, hd), jnp.float32)
    tables = jnp.asarray(np.array([[1, 2]], np.int32))   # width 2, no
    #                                           trailing trash entry
    rng = np.random.RandomState(0)
    new = rng.randn(1, 8, nh, hd).astype(np.float32)     # 4 real + 4 pad
    out = paged_prefill_write(pool, jnp.asarray(new), tables,
                              start=jnp.asarray([4], jnp.int32))
    # real suffix (positions 4..7) lands in block 2 intact
    np.testing.assert_array_equal(np.asarray(out)[2], new[0, :4])
    # pad positions 8..11 went to trash (block 0), not over the suffix
    assert np.abs(np.asarray(out)[0]).sum() > 0
    assert np.abs(np.asarray(out)[3]).sum() == 0


def test_prefix_attention_matches_single_token_reference():
    """Suffix-prefill attention at query row i == single-token paged
    decode attention with lens = start + i + 1 (same pool, same global
    position) — the executable a partial hit runs equals the one the
    plain decode path would have produced token by token."""
    bs, nh, hd, mb = 4, 4, 8, 4
    rng = np.random.RandomState(5)
    nb = 6
    kp = jnp.zeros((nb, bs, nh, hd), jnp.float32)
    vp = jnp.zeros_like(kp)
    tables = jnp.asarray(np.array([[1, 2, 3, 4]], np.int32))
    K = rng.randn(1, 8, nh, hd).astype(np.float32) * 0.3
    V = rng.randn(1, 8, nh, hd).astype(np.float32) * 0.3
    kp = paged_prefill_write(kp, jnp.asarray(K), tables)
    vp = paged_prefill_write(vp, jnp.asarray(V), tables)
    q = jnp.asarray(rng.randn(1, 4, nh, hd).astype(np.float32) * 0.3)
    start = jnp.asarray([4], jnp.int32)
    got = paged_prefix_attention_reference(q, kp, vp, tables, start)
    for i in range(4):
        want = paged_attention_reference(q[:, i:i + 1], kp, vp, tables,
                                         jnp.asarray([4 + i + 1],
                                                     jnp.int32))
        np.testing.assert_allclose(np.asarray(got[:, i]),
                                   np.asarray(want[:, 0]),
                                   rtol=1e-6, atol=1e-6)


# ------------------------------------------------------- engine oracle

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


def _prompts(cfg, lens, seed=1):
    rng = np.random.RandomState(seed)
    ids = rng.randint(1, cfg.vocab_size, (len(lens), CAP)).astype(np.int64)
    for r, ln in enumerate(lens):
        ids[r, ln:] = 0
    return ids


def _engine(m, **kw):
    base = dict(max_batch=2, prompt_cap=CAP, max_new_tokens=NEW,
                decode_chunk=2, kv_block=4, prefix_cache=True)
    base.update(kw)
    return ServingEngine(m, ServingConfig(**base))


def test_config_paged_cache_dtype_validation():
    """int8 pools are a served combination; other narrow dtypes keep
    the structured config-validation finding."""
    from paddle_tpu.analysis.findings import ConfigValidationError
    cfg = ServingConfig(cache_dtype="int8")
    assert cfg.cache_dtype == "int8"
    with pytest.raises(ConfigValidationError) as ei:
        ServingConfig(cache_dtype="float16")
    assert ei.value.finding.code == "paged_cache_dtype"
    with pytest.raises(ValueError, match="padded engine was removed"):
        ServingConfig(prefix_cache=True, paged=False)
    assert ServingConfig(prefix_cache=True).prefix_cache


def test_zero_prefill_admission_repeated_prefix(served_model):
    """Acceptance: a repeated block-aligned prompt admits with ZERO
    prefill tokens — prefill_paged is never called for it, TTFT is one
    decode step (no prefill wall: t_prefill_done == t_admit), prompt
    tokens minus the re-decoded last one count as saved — and greedy
    output is bit-identical to the uncached chain."""
    m, cfg = served_model
    ids = _prompts(cfg, [CAP])
    ref = m.generate_static_ragged(paddle.to_tensor(ids), [CAP],
                                   max_new_tokens=NEW).numpy()[:, CAP:]
    eng = _engine(m)
    eng.submit(ids[0])
    first = eng.drain()
    np.testing.assert_array_equal(first[0].tokens, ref[0])

    calls = {"n": 0}
    real = m.prefill_paged

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    m.prefill_paged = counting
    try:
        req = eng.submit(ids[0])
        done = eng.drain()
    finally:
        m.prefill_paged = real
    assert calls["n"] == 0                      # zero prefill tokens
    assert req.trace.t_prefill_done == req.trace.t_admit
    assert req.trace.t_first_token is not None
    np.testing.assert_array_equal(done[0].tokens, ref[0])
    s = eng.summary()
    assert s["prefill_tokens_saved_total"] == CAP - 1
    assert s["prefix_hit_total"] == 1 and s["prefix_miss_total"] == 1


def test_cow_never_mutates_shared_blocks(served_model):
    """COW invariant: checksums of the SHARED pool regions are identical
    before and after a request that diverges mid-prefix (and after a
    full-hit COW re-decode) — shared blocks are mapped, copied, never
    written."""
    m, cfg = served_model
    ids = _prompts(cfg, [CAP])
    eng = _engine(m, max_batch=1, kv_blocks=33)
    eng.submit(ids[0])
    eng.drain()
    cached, t = eng._prefix.match(ids[0])
    assert t == CAP
    before = [tuple(np.asarray(p)[cached].tobytes() for p in layer)
              for layer in eng._pools]

    # divergent request: shares the first block, new content after
    div = ids[0].copy()
    div[4:] = _prompts(cfg, [CAP], seed=7)[0, 4:]
    eng.submit(div)
    eng.drain()
    # full-hit repeat: exercises the COW copy of the last shared block
    eng.submit(ids[0])
    eng.drain()
    after = [tuple(np.asarray(p)[cached].tobytes() for p in layer)
             for layer in eng._pools]
    assert before == after

    # and the divergent chain was still exact (suffix prefill attended
    # across the shared prefix correctly)
    refd = m.generate_static_ragged(paddle.to_tensor(div[None]), [CAP],
                                    max_new_tokens=NEW).numpy()[0, CAP:]
    eng2 = _engine(m, prefix_cache=False)
    eng2.submit(div)
    np.testing.assert_array_equal(eng2.drain()[0].tokens, refd)


def test_refcount_conservation_through_engine(served_model):
    """Every alloc path the engine takes (miss, suffix hit, COW hit,
    eviction) balances: after drain + cache clear the pool is whole."""
    m, cfg = served_model
    eng = _engine(m)
    lens = [CAP, 5, 3, CAP, 7]
    ids = _prompts(cfg, lens)
    for i in range(len(lens)):
        eng.submit(ids[i, :lens[i]])
    eng.submit(ids[0, :CAP])                    # repeat: COW path
    done = eng.drain()
    assert all(r.status == "done" for r in done)
    assert eng._pool.free_blocks == \
        eng._pool.capacity_blocks - eng._prefix.cached_blocks
    eng._prefix.clear()
    assert eng._pool.free_blocks == eng._pool.capacity_blocks
    assert eng._pool._refs == {}


def test_engine_cache_on_off_parity_and_zero_recompiles(served_model):
    """Acceptance: greedy output bit-identical with the prefix cache on
    vs off across shared-prefix traffic, and ZERO post-warmup jit cache
    misses with the cache enabled (full-prefill, suffix-prefill, COW and
    decode executables all live in the warmup set)."""
    m, cfg = served_model
    traffic = shared_prefix_traffic(12, n_prefixes=2, prefix_len=4,
                                    prompt_cap=CAP,
                                    vocab_size=cfg.vocab_size,
                                    rate=1e9, seed=3)
    eng = _engine(m, kv_blocks=65)
    # warmup: one miss (full prefill + decode), one aligned repeat (COW),
    # one partial hit (suffix prefill)
    warm = _prompts(cfg, [CAP], seed=11)[0]
    eng.submit(warm)
    eng.drain()
    eng.submit(warm)
    eng.drain()
    div = warm.copy()
    div[4:] = _prompts(cfg, [CAP], seed=12)[0, 4:]
    eng.submit(div)
    eng.drain()
    miss0 = compile_cache_misses()
    got = {}
    for item in traffic:
        eng.submit(item["prompt"])
    for r in eng.drain():
        got[r.prompt.tobytes()] = r.tokens
    assert compile_cache_misses() - miss0 == 0
    assert eng.monitor.recompiles == 0
    s = eng.summary()
    assert s["prefix_hit_total"] >= 1           # the traffic repeats

    off = _engine(m, prefix_cache=False)
    for item in traffic:
        off.submit(item["prompt"])
    for r in off.drain():
        np.testing.assert_array_equal(got[r.prompt.tobytes()], r.tokens)


def test_engine_int8_paged_parity(served_model):
    """int8-paged greedy chains track the static int8 path bit-for-bit
    on the f32 CPU reference (the established tolerance is exactness in
    a shared numerics class), with the prefix cache enabled on top."""
    m, cfg = served_model
    lens = [CAP, 5, 3]
    ids = _prompts(cfg, lens)
    ref8 = m.generate_static_ragged(paddle.to_tensor(ids), lens,
                                    max_new_tokens=NEW,
                                    cache_dtype="int8").numpy()[:, CAP:]
    eng = _engine(m, cache_dtype="int8")
    for i in range(len(lens)):
        eng.submit(ids[i, :lens[i]])
    eng.submit(ids[0, :CAP])                    # int8 COW repeat
    done = eng.drain()
    assert len(done) == len(lens) + 1
    for r in done:
        row = next(i for i in range(len(lens))
                   if np.array_equal(ids[i, :lens[i]], r.prompt))
        np.testing.assert_array_equal(r.tokens, ref8[row])
    # int8 pools really are the compact form
    assert eng._pools[0][0].dtype == jnp.int8
    assert len(eng._pools[0]) == 4


def test_shared_occupancy_metrics_pinned(served_model):
    """Physical kv_occupancy counts a shared block ONCE; kv_shared_tokens
    is the logical volume served out of shared blocks — math pinned on a
    concurrent aligned-hit pair."""
    m, cfg = served_model
    ids = _prompts(cfg, [CAP])
    eng = _engine(m)
    eng.submit(ids[0])
    eng.drain()                                 # prefix now cached
    cap_tokens = eng._pool.capacity_tokens
    cached = eng._prefix.cached_blocks          # CAP/4 = 2 blocks
    assert cached == CAP // 4
    # two concurrent requests: A re-admits the cached prompt (COW: 1
    # shared block + 1 private copy, lens starts at CAP-1), B is fresh
    eng.submit(ids[0])
    fresh = _prompts(cfg, [5], seed=21)[0, :5]
    eng.submit(fresh)
    eng.step()                                  # admit both + 1 chunk
    # snapshot at decode entry: A lens=7 over [shared b, cow b] -> 4+3
    # physical but 4 of its 7 logical rows are shared; B lens=5 -> 4+1
    phys = 4 + 3 + 5
    assert eng._kv_snapshot[0] == phys
    assert eng._kv_snapshot[2] == 4
    assert eng.metrics.gauges["kv_occupancy"] == phys / cap_tokens
    assert eng.metrics.gauges["kv_shared_tokens"] == 4
    eng.drain()


def test_engine_pool_pressure_reclaims_cache(served_model):
    """A pool too small to hold live traffic + the cache reclaims cached
    blocks at admission instead of stalling — cached-but-idle prefixes
    are soft capacity."""
    m, cfg = served_model
    # 6 usable blocks: a CAP request pins ceil(13/4)=4 blocks and caches
    # 2 on finish — the second distinct CAP request fits, but the first's
    # REPEAT (1 shared + 3 fresh) only fits after evicting cached blocks
    eng = _engine(m, kv_blocks=7, max_batch=1)
    a = _prompts(cfg, [CAP], seed=31)[0]
    b = _prompts(cfg, [CAP], seed=32)[0]
    ref = {}
    for p in (a, b):
        ref[p.tobytes()] = m.generate_static_ragged(
            paddle.to_tensor(p[None]), [CAP],
            max_new_tokens=NEW).numpy()[0, CAP:]
    for p in (a, b, a, b):
        eng.submit(p)
    done = eng.drain()
    assert [r.status for r in done] == ["done"] * 4
    for r in done:
        np.testing.assert_array_equal(r.tokens, ref[r.prompt.tobytes()])
    assert eng._prefix.evicted_total >= 1
    assert eng.summary()["prefix_hit_total"] >= 1


def test_instant_finish_request_still_populates_cache(served_model):
    """A budget-1 request finishes AT admission — the cache insert must
    land while the request still holds its blocks (retain-after-free
    would raise), and the cached prefix must serve a later repeat."""
    m, cfg = served_model
    eng = _engine(m)
    ids = _prompts(cfg, [CAP])
    r1 = eng.submit(ids[0], max_new_tokens=1)
    eng.drain()
    assert r1.status == "done" and r1.n_out == 1
    assert eng._prefix.cached_blocks == CAP // 4
    ref = m.generate_static_ragged(paddle.to_tensor(ids), [CAP],
                                   max_new_tokens=NEW).numpy()[:, CAP:]
    r2 = eng.submit(ids[0])                     # full hit off the cache
    eng.drain()
    np.testing.assert_array_equal(r2.tokens, ref[0])
    assert eng.summary()["prefill_tokens_saved_total"] == CAP - 1


def test_zero_prefill_insert_never_caches_unwritten_block(served_model):
    """kv_block=1 regression: a zero-prefill hit defers writing position
    plen-1 to its first decode chunk, so the insert at admission must
    not cache that block — a same-step longer prompt would otherwise
    match into all-zero KV and decode garbage."""
    m, cfg = served_model
    eng = _engine(m, kv_block=1)
    p = _prompts(cfg, [3], seed=41)[0, :3]
    q = np.concatenate([p, _prompts(cfg, [1], seed=42)[0, :1]])
    eng.submit(p[:2])                           # caches blocks for p[:2]
    eng.drain()
    eng.submit(p)                               # t=2=plen-1: zero-prefill
    eng.submit(q)                               # same step: extends p
    done = eng.drain()
    ref = {}
    for pr in (p, q):
        ln = len(pr)
        ref[pr.tobytes()] = m.generate_static_ragged(
            paddle.to_tensor(np.pad(pr, (0, CAP - ln))[None]), [ln],
            max_new_tokens=NEW).numpy()[0, CAP:]
    for r in done:
        np.testing.assert_array_equal(r.tokens, ref[r.prompt.tobytes()])


def test_warmup_prefix_cache_covers_every_executable(served_model):
    """engine.warmup_prefix_cache (the shared serve_bench/bench/lint
    choreography) leaves the engine at zero steady-state misses across
    miss + COW + suffix traffic, with its own cached prefixes dropped."""
    m, cfg = served_model
    eng = _engine(m, kv_blocks=65)
    eng.warmup_prefix_cache(cfg.vocab_size)
    assert eng._prefix.cached_blocks == 0       # measured start is cold
    miss0 = compile_cache_misses()
    w = _prompts(cfg, [CAP], seed=51)[0]
    for prompt in (w, w):                       # miss then COW hit
        eng.submit(prompt)
        eng.drain()
    d = w.copy()
    d[4:] = _prompts(cfg, [CAP], seed=52)[0, 4:]
    eng.submit(d)                               # suffix prefill
    eng.drain()
    assert compile_cache_misses() - miss0 == 0
    with pytest.raises(ValueError, match="prefix_cache=True"):
        ServingEngine(m, ServingConfig(max_batch=1, prompt_cap=CAP,
                                       max_new_tokens=2,
                                       kv_block=4)) \
            .warmup_prefix_cache(cfg.vocab_size)


def test_whole_pool_request_never_starves_on_own_prefix(served_model):
    """Starvation edge: a request needing the ENTIRE pool whose cached
    prefix is protected during its own admission would wait forever with
    nothing in flight to free blocks — the engine must drop the hit and
    full-prefill instead (progress beats reuse when they conflict)."""
    m, cfg = served_model
    # 4 usable blocks == exactly one CAP request (ceil(13/4)); its cached
    # prefix (2 blocks) + a COW repeat (1 shared + 3 fresh) cannot coexist
    eng = _engine(m, kv_blocks=5, max_batch=1)
    ids = _prompts(cfg, [CAP])
    ref = m.generate_static_ragged(paddle.to_tensor(ids), [CAP],
                                   max_new_tokens=NEW).numpy()[:, CAP:]
    eng.submit(ids[0])
    eng.drain()
    eng.submit(ids[0])                          # would COW-deadlock
    done = eng.drain(max_batches=20)
    assert [r.status for r in done] == ["done"]
    np.testing.assert_array_equal(done[0].tokens, ref[0])


def test_shared_prefix_traffic_profile():
    tr = shared_prefix_traffic(32, n_prefixes=3, prefix_len=6,
                               prompt_cap=16, vocab_size=64, rate=100.0,
                               seed=0)
    assert len(tr) == 32
    prefixes = {t["prompt"][:6].tobytes() for t in tr}
    assert len(prefixes) == 3
    lens = [t["prompt"].shape[0] for t in tr]
    assert min(lens) >= 7 and max(lens) <= 16
    assert all(0 <= t["prefix_id"] < 3 for t in tr)
    with pytest.raises(ValueError, match="prefix_len"):
        shared_prefix_traffic(2, n_prefixes=1, prefix_len=16,
                              prompt_cap=16, vocab_size=64)


def test_engine_exception_recovers_with_cache(served_model):
    """The mid-flight failure path also resets the prefix cache (the
    pool reset reissued every block) — the engine stays usable and the
    cache repopulates."""
    m, cfg = served_model
    eng = _engine(m)
    ids = _prompts(cfg, [CAP])
    eng.submit(ids[0])
    eng.drain()
    assert eng._prefix.cached_blocks == 2
    eng.submit(ids[0])
    real = m.decode_paged

    def boom(*a, **kw):
        raise RuntimeError("injected device failure")

    m.decode_paged = boom
    try:
        with pytest.raises(RuntimeError, match="injected"):
            eng.step()
    finally:
        m.decode_paged = real
    assert eng._prefix.cached_blocks == 0
    assert eng._pool.free_blocks == eng._pool.capacity_blocks
    eng.submit(ids[0])
    done = eng.drain()
    assert [r.status for r in done] == ["done"]
    assert eng._prefix.cached_blocks == 2


# ---------------------------------------------- host spill tier (ISSUE 14)

def _spill_engine(m, budget_blocks=2, **kw):
    """Engine with a device prefix budget of `budget_blocks` blocks and
    an ample host spill tier — eviction spills instead of dying."""
    from paddle_tpu.inference import BlockPool
    bpb = BlockPool.for_model(m, num_blocks=2, block_size=4).bytes_per_block
    base = dict(prefix_cache_bytes=budget_blocks * bpb,
                spill_host_bytes=1 << 22)
    base.update(kw)
    return _engine(m, **base)


class TestSpillTier:
    def test_pool_block_round_trip_bit_identical(self, served_model):
        """read_block -> write_block moves bytes, never recomputes:
        the round-tripped block equals the source bitwise (f32 AND
        int8 pools), and the write is one donated in-place scatter."""
        m, cfg = served_model
        for cache_dtype in (None, "int8"):
            eng = _engine(m, cache_dtype=cache_dtype)
            ids = _prompts(cfg, [CAP])
            eng.submit(ids[0])
            eng.drain()
            blk = int(eng._prefix.match(ids[0])[0][0])
            src = [tuple(np.asarray(p)[blk].copy() for p in layer)
                   for layer in eng._pools]
            payload = eng._pool.read_block(eng._pools, blk)
            # scatter into a different free block and compare planes
            dst = eng._pool.take(1)[0]
            eng._pools = eng._pool.write_block(eng._pools, dst, payload)
            for li, layer in enumerate(eng._pools):
                for pi, p in enumerate(layer):
                    np.testing.assert_array_equal(
                        np.asarray(p)[dst], src[li][pi])
            eng._pool.release([dst])

    def test_evict_spill_rehydrate_bit_identical_decode(self, served_model):
        """evict-under-budget -> spill -> later hit rehydrates with ONE
        host->device copy per block, decode bit-identical to a
        never-evicted engine AND to the cache-off reference."""
        m, cfg = served_model
        ids = _prompts(cfg, [CAP, CAP, CAP], seed=3)
        eng = _spill_engine(m, budget_blocks=2, kv_blocks=40)
        never = _engine(m, kv_blocks=40)         # ample budget, no spill
        first = {}
        for i in range(3):
            r = eng.submit(ids[i]); eng.drain()
            first[i] = r.tokens
            never.submit(ids[i]); never.drain()
        t = eng._spill
        assert t.spilled_total >= 1              # the 2-block budget
        assert eng._prefix.spilled_blocks == t.spilled_blocks
        # resubmit the LRU-spilled prompt: its blocks rehydrate
        r0 = t.rehydrated_total
        ra = eng.submit(ids[0]); eng.drain()
        rb = never.submit(ids[0]); never.drain()
        assert t.rehydrated_total > r0
        assert t.h2d_copies == t.rehydrated_total   # one copy per block
        np.testing.assert_array_equal(ra.tokens, first[0])
        np.testing.assert_array_equal(ra.tokens, rb.tokens)

    def test_cow_after_rehydrate_checksum_invariance(self, served_model):
        """A full-hit repeat on a REHYDRATED prefix still goes through
        COW: the rehydrated shared blocks' checksums never change."""
        m, cfg = served_model
        ids = _prompts(cfg, [CAP], seed=5)
        eng = _spill_engine(m, budget_blocks=2, kv_blocks=40, max_batch=1)
        eng.submit(ids[0]); eng.drain()
        eng._prefix.evict(eng._prefix.cached_blocks)     # all -> host
        assert eng._prefix.cached_blocks == 0
        eng.submit(ids[0])                               # rehydrates +
        eng.drain()                                      # COW full hit
        assert eng._spill.rehydrated_total >= 2
        cached, t = eng._prefix.match(ids[0])
        assert t == CAP
        before = [tuple(np.asarray(p)[cached].tobytes() for p in layer)
                  for layer in eng._pools]
        eng.submit(ids[0]); eng.drain()                  # another COW hit
        after = [tuple(np.asarray(p)[cached].tobytes() for p in layer)
                 for layer in eng._pools]
        assert before == after

    def test_refcount_conservation_mixed_spill_traffic(self, served_model):
        """Pool conservation through mixed spill/rehydrate/upgrade
        traffic: after drain + clear, every block is back on the free
        list and the refcount table is empty (spilled entries hold NO
        pool reference)."""
        m, cfg = served_model
        eng = _spill_engine(m, budget_blocks=2, kv_blocks=40)
        lens = [CAP, 5, CAP, 3, CAP, 7, CAP]
        ids = _prompts(cfg, lens, seed=9)
        for i, ln in enumerate(lens):
            eng.submit(ids[i, :ln])
            eng.drain()
        eng.submit(ids[0, :CAP]); eng.drain()     # rehydrate + COW
        t = eng._spill
        assert t.spilled_total >= 1
        # device refs == device-cached blocks; spilled hold none
        assert eng._pool.free_blocks == \
            eng._pool.capacity_blocks - eng._prefix.cached_blocks
        eng._prefix.clear()
        assert eng._pool.free_blocks == eng._pool.capacity_blocks
        assert eng._pool._refs == {}
        assert eng._prefix.spilled_blocks == 0
        assert t.spilled_blocks == 0

    def test_tier_budget_drops_lru_spilled(self, served_model):
        """The host tier has its own budget: spilling past it drops the
        LRU spilled leaves for good (dropped_total) and host residency
        never exceeds capacity_blocks."""
        m, cfg = served_model
        from paddle_tpu.inference import BlockPool
        bpb = BlockPool.for_model(m, num_blocks=2,
                                  block_size=4).bytes_per_block
        eng = _engine(m, kv_blocks=40, prefix_cache_bytes=2 * bpb,
                      spill_host_bytes=2 * bpb)    # tier holds 2 blocks
        lens = [CAP, CAP, CAP, CAP]
        ids = _prompts(cfg, lens, seed=11)
        for i, ln in enumerate(lens):
            eng.submit(ids[i, :ln])
            eng.drain()
        t = eng._spill
        assert t.dropped_total >= 1
        assert t.spilled_blocks <= t.capacity_blocks
        assert eng._prefix.spilled_blocks == t.spilled_blocks

    def test_spill_zero_recompiles_after_warmup(self, served_model):
        """warmup_prefix_cache's spill leg lowers the d2h gather and h2d
        scatter too: steady spill/rehydrate traffic adds zero jit cache
        misses and zero logged recompiles."""
        m, cfg = served_model
        eng = _spill_engine(m, budget_blocks=2, kv_blocks=40)
        eng.warmup_prefix_cache(cfg.vocab_size)
        miss0 = compile_cache_misses()
        lens = [CAP, CAP, CAP, 5, CAP]
        ids = _prompts(cfg, lens, seed=13)
        for i, ln in enumerate(lens):
            eng.submit(ids[i, :ln])
            eng.drain()
        eng.submit(ids[0, :CAP]); eng.drain()
        assert eng._spill.rehydrated_total >= 1
        assert compile_cache_misses() - miss0 == 0
        assert eng.monitor.recompiles == 0

    def test_statusz_and_metrics_surface(self, served_model):
        """The tier is scrapeable: statusz carries the spill block and
        metrics_registry renders a lint-clean spill producer."""
        from paddle_tpu.obs import lint_exposition
        m, cfg = served_model
        eng = _spill_engine(m, budget_blocks=2, kv_blocks=40)
        ids = _prompts(cfg, [CAP, CAP, CAP], seed=15)
        for i in range(3):
            eng.submit(ids[i]); eng.drain()
        s = eng.statusz()
        assert s["spill"]["spilled_total"] >= 1
        assert s["prefix_cache"]["spilled_blocks"] == \
            eng._prefix.spilled_blocks
        reg = eng.metrics_registry()
        assert "spill" in reg.producers
        page = reg.render()
        lint_exposition(page)
        assert "paddle_tpu_serving_spill_spilled_total" in page

    def test_rehydrate_survives_tier_trim_under_pool_pressure(self):
        """Found in review: _rehydrate's eviction can spill ANOTHER
        block, whose tier trim scans LRU childless spilled leaves — the
        node being rehydrated is one (stale stamp) and must be
        protected, or its payload is dropped mid-flight and the write
        crashes. Unit-level: tier budget 1 block, pool exhausted."""
        from paddle_tpu.inference import HostSpillTier
        p = _pool(blocks=6, bs=4)
        tier = HostSpillTier(bytes_per_block=p.bytes_per_block,
                             byte_budget=p.bytes_per_block)
        c = PrefixCache(p)
        writes = []
        c.attach_spill(tier,
                       reader=lambda b: (f"payload{b}",),
                       writer=lambda b, pl: writes.append((b, pl)))
        ta = np.arange(4, dtype=np.int64) + 1
        tb = np.arange(4, dtype=np.int64) + 50
        A = p.alloc(1, 4)
        c.insert(ta, A)
        p.free(1)
        B = p.alloc(2, 4)
        c.insert(tb, B)
        p.free(2)
        c.evict(1)                    # spills LRU = A (tier now full)
        assert c.spilled_blocks == 1 and tier.spilled_blocks == 1
        p.alloc(9, p.free_blocks * 4)   # exhaust the free list
        blocks, t = c.match(ta)       # rehydrate A: must evict+trim B,
        assert t == 4                 # NOT drop A's own payload
        assert writes and writes[-1][1] == (f"payload{int(A[0])}",)
        assert tier.rehydrated_total == 1
        assert tier.dropped_total == 1          # B: spilled then dropped
        assert tier.spilled_blocks == 0
        assert c.match(tb) == ([], 0)           # B is gone for good
        # conservation: drop everything, pool whole again
        p.free(9)
        c.clear()
        assert p.free_blocks == p.capacity_blocks and p._refs == {}
