"""Paged KV cache + ragged paged decode attention (ISSUE 5).

Covers the block-pool allocator (alloc/free/reuse, fragmentation, OOM →
reject with reason), paged-vs-reference attention parity across ragged
lengths (including a row at an exact block boundary), the Pallas kernel in
interpret mode, model-level bit-parity of paged prefill/decode with
generate_static_ragged, buffer donation (the paged pools), the
true-token occupancy gauges, and the engine's
slot-level continuous batching: a short request finishes early, frees its
blocks immediately, and a queued request is spliced into the vacated slot
mid-flight with ZERO recompiles.
"""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference import (BlockPool, ServingConfig, ServingEngine,
                                  synthetic_traffic)
from paddle_tpu.jit.api import compile_cache_misses
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.ops.attention import (attention_reference,
                                      paged_attention_reference,
                                      paged_cache_write,
                                      paged_prefill_write, quantize_kv)
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas.paged_attention import paged_attention_kernel
from tools.validate_paged_tpu import (idle_mixes, ragged_case,
                                      ragged_cases)


# ------------------------------------------------------ block allocator

class TestBlockPool:
    def _pool(self, blocks=8, bs=4):
        return BlockPool(num_blocks=blocks, block_size=bs, num_layers=1,
                         block_shapes=((bs, 2, 4),) * 2, head_axis=1)

    def test_alloc_free_reuse(self):
        p = self._pool()
        a = p.alloc(1, 10)                      # 3 blocks of 4
        assert a is not None and len(a) == 3
        assert 0 not in a                       # trash block never issued
        assert p.free_blocks == 4 and p.used_blocks == 3
        b = p.alloc(2, 4)
        assert len(b) == 1 and set(b).isdisjoint(a)
        assert p.free(1) == 3
        c = p.alloc(3, 12)                      # reuses 1's freed blocks
        assert set(c) & set(int(x) for x in a)
        assert p.free_blocks == 3

    def test_fragmented_free_list_still_serves(self):
        """Blocks are unit-granular: interleaved frees can never strand
        capacity — any request whose block count fits the free COUNT is
        servable regardless of which blocks were freed."""
        p = self._pool(blocks=9, bs=4)
        owners = [p.alloc(i, 8) for i in range(4)]      # 8 blocks out
        assert all(o is not None for o in owners)
        p.free(0), p.free(2)                            # non-contiguous
        got = p.alloc(9, 16)                            # 4 blocks
        assert got is not None and len(got) == 4
        assert p.free_blocks == 0

    def test_oom_returns_none_and_fits_ever(self):
        p = self._pool(blocks=4, bs=4)          # 3 usable blocks
        assert p.fits_ever(12) and not p.fits_ever(13)
        assert p.alloc(1, 12) is not None
        assert p.alloc(2, 1) is None            # full now: caller waits
        p.free(1)
        assert p.alloc(2, 1) is not None        # ...and is served after

    def test_double_alloc_raises(self):
        p = self._pool()
        p.alloc(1, 4)
        with pytest.raises(ValueError, match="already holds"):
            p.alloc(1, 4)
        assert p.free(99) == 0                  # unknown owner: no-op

    def test_table_row_padding_and_occupancy(self):
        p = self._pool(blocks=8, bs=4)
        p.alloc(7, 6)
        row = p.table_row(7, 5)
        assert row.dtype == np.int32 and row.shape == (5,)
        assert (row[2:] == 0).all() and (row[:2] > 0).all()
        assert p.capacity_tokens == 28
        assert p.occupancy(6) == 6 / 28
        assert p.slots_occupancy() == 2 / 7
        with pytest.raises(ValueError, match="table width"):
            p.table_row(7, 1)

    def test_validation(self):
        with pytest.raises(ValueError, match="num_blocks"):
            BlockPool(num_blocks=1, block_size=4, num_layers=1,
                      block_shapes=((4, 1, 4),) * 2, head_axis=1)
        p = self._pool()
        pools = p.make_pools()
        assert len(pools) == 1
        assert pools[0][0].shape == (8, 4, 2, 4)


# ------------------------------------------- paged attention op parity

def _build_pool(lens, bs=4, nh=4, hd=8, mb=4, seed=0):
    """Pool + tables holding per-row contiguous K/V; returns the ground
    truth contiguous arrays too."""
    rng = np.random.RandomState(seed)
    B = len(lens)
    nb = 1 + sum(-(-ln // bs) for ln in lens) + 1
    pool_shape = (nb, bs, nh, hd)
    kp = jnp.zeros(pool_shape, jnp.float32)
    vp = jnp.zeros(pool_shape, jnp.float32)
    alloc = BlockPool(num_blocks=nb, block_size=bs, num_layers=1,
                      block_shapes=((bs, nh, hd),) * 2, head_axis=1)
    tables = np.zeros((B, mb), np.int32)
    L = mb * bs
    K = rng.randn(B, L, nh, hd).astype(np.float32) * 0.3
    V = rng.randn(B, L, nh, hd).astype(np.float32) * 0.3
    for b, ln in enumerate(lens):
        if ln:
            alloc.alloc(b, ln)
            tables[b] = alloc.table_row(b, mb)
        for p in range(ln):
            kp = paged_cache_write(kp, jnp.asarray(K[b:b + 1, p:p + 1]),
                                   jnp.asarray(tables[b:b + 1]),
                                   jnp.asarray([p], jnp.int32))
            vp = paged_cache_write(vp, jnp.asarray(V[b:b + 1, p:p + 1]),
                                   jnp.asarray(tables[b:b + 1]),
                                   jnp.asarray([p], jnp.int32))
    return kp, vp, jnp.asarray(tables), K, V


@pytest.mark.parametrize("lens", [(5, 8, 1), (4, 12, 7)])
def test_paged_reference_matches_masked_attention(lens):
    """Gather-reference == dense masked attention on the same K/V — ragged
    lengths including a row at EXACTLY a block boundary (8 and 12 with
    bs=4)."""
    bs, nh, hd, mb = 4, 4, 8, 4
    kp, vp, tables, K, V = _build_pool(lens, bs, nh, hd, mb)
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(len(lens), 1, nh, hd).astype(np.float32) * 0.3)
    la = jnp.asarray(lens, jnp.int32)
    got = paged_attention_reference(q, kp, vp, tables, la)
    col = jnp.arange(mb * bs)[None, None, None, :]
    mask = col < la[:, None, None, None]
    want = attention_reference(q, jnp.asarray(K), jnp.asarray(V), mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_paged_kernel_interpret_matches_reference():
    """The Pallas kernel (interpret mode on CPU; compiled for the chip by
    tests/test_chip_compile.py, compared on the chip by chip_smoke.py's
    kernel phase) against the gather reference — live rows only (the
    kernel zeros dummy lens=0 rows by design)."""
    lens = (5, 8, 1)
    bs, nh, hd, mb = 4, 4, 8, 4
    kp, vp, tables, _, _ = _build_pool(lens, bs, nh, hd, mb, seed=2)
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(len(lens), 1, nh, hd).astype(np.float32) * 0.3)
    la = jnp.asarray(lens, jnp.int32)
    got = paged_attention_kernel(q, kp, vp, tables, la, interpret=True)
    want = paged_attention_reference(q, kp, vp, tables, la)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# The walk (the kernel of heads a DMA can slice: 8k heads of 128k lanes) in
# interpret mode, where no tiling rule applies: the test says so for the
# toy heads. Two tables: the serving cells' 128 slots of 16 tokens, and
# 21 slots, which the 16 pages of a step do not divide.
_walk_interpreted = jax.jit(functools.partial(paged_attention_kernel,
                                              interpret=True))
_WALK_TABLES = {"cell": dict(bs=16, mb=128), "odd": dict(bs=4, mb=21)}
_WALK_CASES = [(t, name) for t, g in _WALK_TABLES.items()
               for name in ragged_cases(g["bs"], 16, g["mb"])]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("table,name", _WALK_CASES)
def test_paged_walk_interpret_matches_reference(monkeypatch, table, name,
                                                dtype):
    """Every edge of the walk against the gather reference: a dummy row,
    exactly one page, exactly one block, one token more, the whole table,
    and a batch mixing them. Pages no row owns, page 0 among them (where
    the table's padding points), hold NaN: nothing past `lens` may reach
    the output."""
    monkeypatch.setattr(pa, "_pages_dma_sliceable", lambda nh, hd: True)
    g = _WALK_TABLES[table]
    nh, hd = 2, 8
    pps = pa._pages_per_step(g["bs"] * nh * hd * jnp.dtype(dtype).itemsize,
                             g["mb"])
    assert pps == 16 and (g["mb"] % pps == 0) == (table == "cell")
    lens = ragged_cases(g["bs"], pps, g["mb"])[name]
    lens = (lens * 8)[:8]               # one shape a table: one compile
    q, kp, vp, tables, la = ragged_case(lens, nh=nh, hd=hd, dtype=dtype,
                                        nb=1 + 4 * (g["mb"] + 1) + 3, **g)
    got = _walk_interpreted(q, kp, vp, tables, la)
    assert got.dtype == q.dtype
    f32 = jnp.float32
    want = paged_attention_reference(
        q.astype(f32), jnp.nan_to_num(kp.astype(f32)),
        jnp.nan_to_num(vp.astype(f32)), tables, la)
    tol = 2e-5 if dtype == "float32" else 1e-2      # one bf16 rounding
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=tol, atol=tol / 4)


# Rows that attend nothing (length 0: a slot without a request, a slot in
# prefill, a row past its EOS) among rows that do. The walk steps over
# them inside its one program; the slot-grid kernels mask by length.
_IDLE_LIVE_LENS = (70, 1, 64, 5, 65, 17, 84)    # blocks of 64 in a table of 84


@pytest.mark.parametrize("mix", list(idle_mixes(8)))
@pytest.mark.parametrize("kernel", ["walk", "slots", "q8"])
def test_rows_of_length_zero_among_live_rows(monkeypatch, kernel, mix):
    """Live rows are bit-equal to the same call without the empty rows,
    whose output is zeros; page 0, where an empty row's table points, and
    every page no row owns hold NaN."""
    if kernel == "walk":
        monkeypatch.setattr(pa, "_pages_dma_sliceable", lambda nh, hd: True)
    live = list(idle_mixes(8)[mix])
    lens = np.zeros(8, np.int64)
    lens[live] = _IDLE_LIVE_LENS[:len(live)]
    q, kp, vp, tables, la = ragged_case(tuple(lens), bs=4, nh=2, hd=8, mb=21,
                                        dtype="float32")
    if kernel == "q8":
        (kc, ks), (vc, vs) = (quantize_kv(jnp.nan_to_num(p))
                              for p in (kp, vp))
        owned = np.unique(np.asarray(tables))
        nobody = np.setdiff1d(np.arange(kp.shape[0]), owned[owned > 0])
        ks, vs = (s.at[nobody].set(jnp.nan) for s in (ks, vs))
        call = lambda q, t, l: pa.paged_attention_q8_kernel(  # noqa: E731
            q, kc, ks, vc, vs, t, l, interpret=True)
    else:
        call = lambda q, t, l: paged_attention_kernel(  # noqa: E731
            q, kp, vp, t, l, interpret=True)
    got = np.asarray(call(q, tables, la))
    idle = np.setdiff1d(np.arange(8), live)
    assert (got[idle] == 0).all()
    assert np.isfinite(got).all()
    if live:
        rows = jnp.asarray(live)
        alone = np.asarray(call(q[rows], tables[rows], la[rows]))
        assert (got[live] == alone).all()
        want = paged_attention_reference(
            q[rows], jnp.nan_to_num(kp), jnp.nan_to_num(vp), tables[rows],
            la[rows])
        np.testing.assert_allclose(alone, np.asarray(want),
                                   atol=2e-2 if kernel == "q8" else 5e-6)


def test_paged_walk_only_where_a_dma_can_slice_a_page():
    """The chip's compiler slices whole tiles: 1.3B's and 13B's heads
    walk, 2.7B's heads of 80 and 125M's 12 of 64 keep the grid over the
    table's slots (tests/test_chip_compile.py compiles both)."""
    assert pa._pages_dma_sliceable(16, 128)
    assert pa._pages_dma_sliceable(40, 128)
    assert pa._pages_dma_sliceable(8, 128)          # 16 heads over mp=2
    assert not pa._pages_dma_sliceable(32, 80)
    assert not pa._pages_dma_sliceable(12, 64)


@pytest.mark.parametrize("page_bytes,mb,want", [
    (64 * 1024, 128, 8),        # the cells: bf16 16 x 16 x 128, 2 MiB
    (128 * 1024, 128, 4),       # the same page in float32
    (32 * 1024, 128, 16),       # 8 heads a shard: the cap
    (64 * 1024, 6, 6),          # never wider than the table
    (4 * 1024 * 1024, 64, 1),   # a page over the budget still walks
])
def test_pages_per_step_comes_from_the_shapes(page_bytes, mb, want):
    assert pa._pages_per_step(page_bytes, mb) == want
    assert 4 * want * page_bytes <= max(pa._WALK_VMEM_BUDGET, 4 * page_bytes)


def test_prefill_write_matches_per_token_writes():
    """Bulk prompt write lands every VALID position exactly where the
    decode-time single-token write would."""
    bs, nh, hd, mb = 4, 2, 4, 4
    lens = (6, 3)
    kp, vp, tables, K, _ = _build_pool(lens, bs, nh, hd, mb, seed=5)
    bulk = jnp.zeros_like(kp)
    bulk = paged_prefill_write(bulk, jnp.asarray(K[:, :8]), tables)
    tb = np.asarray(tables)
    for b, ln in enumerate(lens):
        for p in range(ln):
            blk, off = tb[b, p // bs], p % bs
            np.testing.assert_array_equal(np.asarray(bulk)[blk, off],
                                          np.asarray(kp)[blk, off])
    # padding past a row's reservation landed in the TRASH block (row 1's
    # positions 4..7 hit table entries of 0), never in another row's
    # blocks — the loop above already proves every valid cell of every
    # row survived the other rows' bulk writes
    assert np.abs(np.asarray(bulk)[0]).sum() > 0      # trash got garbage
    assert np.abs(np.asarray(kp)[0]).sum() == 0       # per-token never


# ------------------------------------------------- model-level parity

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


def test_paged_decode_bit_identical_to_static_ragged(served_model):
    """Acceptance: chunked paged greedy decode replays the EXACT token
    chain of generate_static_ragged — ragged lengths incl. a full-cap row
    and one at a block boundary — and a second mixed batch reuses every
    executable (zero new jit cache misses)."""
    m, cfg = served_model
    lens = [CAP, 4, 1]                  # 4 == kv_block: boundary row
    ids = _prompts(cfg, lens)
    ref = m.generate_static_ragged(paddle.to_tensor(ids), lens,
                                   max_new_tokens=NEW).numpy()[:, CAP:]
    pool = BlockPool.for_model(m, num_blocks=32, block_size=4)
    pools = pool.make_pools()
    mb = pool.blocks_needed(CAP + NEW - 1)
    tables = np.zeros((len(lens), mb), np.int32)
    for b, ln in enumerate(lens):
        pool.alloc(b, ln + NEW - 1)
        tables[b] = pool.table_row(b, mb)
    pools, first = m.prefill_paged(ids, np.int32(lens), pools, tables)
    first = first.numpy()
    np.testing.assert_array_equal(first, ref[:, 0])
    pend = first.astype(np.int32)
    done = np.zeros((len(lens),), bool)
    lens_h = np.asarray(lens, np.int32)
    got = [first[:, None]]
    for c in (2, 3):                    # chunked: [1, 2, 3] totals NEW
        toks, pools, _, done_d = m.decode_paged(pools, tables, lens_h,
                                                pend, done, c)
        arr = np.asarray(toks.numpy())
        got.append(arr)
        pend = arr[:, -1].astype(np.int32)
        lens_h = lens_h + c
    np.testing.assert_array_equal(np.concatenate(got, axis=1), ref)
    # steady state: fresh lens/tables, SAME shapes -> zero compiles
    miss0 = compile_cache_misses()
    pools, f2 = m.prefill_paged(ids, np.int32([3, 2, 5]), pools, tables)
    m.decode_paged(pools, tables, np.int32([3, 2, 5]),
                   f2.numpy().astype(np.int32), done, 2)
    assert compile_cache_misses() - miss0 == 0


def test_paged_pools_are_donated(served_model):
    """prefill_paged/decode_paged donate the pool buffers: XLA updates KV
    in place, and the caller's input arrays are consumed."""
    m, cfg = served_model
    pool = BlockPool.for_model(m, num_blocks=16, block_size=4)
    pools = pool.make_pools()
    mb = pool.blocks_needed(CAP + NEW - 1)
    pool.alloc(0, CAP + NEW - 1)
    tables = pool.table_row(0, mb)[None]
    ids = _prompts(cfg, [5])
    buf0 = pools[0][0]
    pools2, first = m.prefill_paged(ids, np.int32([5]), pools, tables)
    assert buf0.is_deleted()
    buf1 = pools2[0][0]
    _, pools3, _, _ = m.decode_paged(pools2, tables, np.int32([5]),
                                     first.numpy().astype(np.int32),
                                     np.zeros((1,), bool), 2)
    assert buf1.is_deleted()
    assert not pools3[0][0].is_deleted()

    # the pool must carry the model dtype — stale pools are rejected
    bad = [(p[0].astype(jnp.bfloat16), p[1].astype(jnp.bfloat16))
           for p in pools3]
    with pytest.raises(ValueError, match="paged KV pools"):
        m.prefill_paged(ids, np.int32([5]), bad, tables)


# ------------------------------------------------------ the paged engine

def _engine(m, **kw):
    base = dict(max_batch=2, prompt_cap=CAP, max_new_tokens=NEW,
                decode_chunk=2, kv_block=4)
    base.update(kw)
    return ServingEngine(m, ServingConfig(**base))


def _row_of(ids, lens, r):
    return next(i for i in range(len(lens))
                if np.array_equal(ids[i, :lens[i]], r.prompt))


def test_engine_paged_parity_and_splice_zero_recompiles(served_model):
    """Acceptance: a short request finishes early, frees its blocks, and a
    QUEUED request is spliced into the vacated slot mid-flight — while the
    longer co-batched row keeps decoding. Every output bit-identical to
    generate_static_ragged; zero jit cache misses after warmup."""
    m, cfg = served_model
    lens = [CAP, 5, 3, 7, 2]
    ids = _prompts(cfg, lens)
    ref = m.generate_static_ragged(paddle.to_tensor(ids), lens,
                                   max_new_tokens=NEW).numpy()[:, CAP:]
    eng = _engine(m)
    eng.submit(ids[0, :lens[0]])
    eng.drain()                         # warmup: prefill + decode compile
    miss0 = compile_cache_misses()
    # 5 requests through 2 slots; request 1 gets a 2-token budget so its
    # slot frees mid-flight and the queue splices into it
    for i in range(len(lens)):
        eng.submit(ids[i, :lens[i]],
                   max_new_tokens=NEW if i != 1 else 2)
    done = eng.drain()
    assert [r.status for r in done] == ["done"] * len(lens)
    for r in done:
        want = ref[_row_of(ids, lens, r)][:r.max_new_tokens]
        np.testing.assert_array_equal(r.tokens, want)
    assert compile_cache_misses() - miss0 == 0
    assert eng.monitor.recompiles == 0
    # the splice actually happened: more admissions than batch capacity
    # finished without ever draining to an empty batch between them
    assert eng.summary()["completed_total"] == len(lens) + 1


def test_engine_paged_eos_early_exit(served_model):
    m, cfg = served_model
    lens = [CAP, 5, 3]
    ids = _prompts(cfg, lens)
    ref = m.generate_static_ragged(paddle.to_tensor(ids), lens,
                                   max_new_tokens=NEW).numpy()
    eos = int(ref[0, CAP])              # row 0 emits EOS as token 1
    refe = m.generate_static_ragged(paddle.to_tensor(ids), lens,
                                    max_new_tokens=NEW,
                                    eos_token_id=eos).numpy()[:, CAP:]
    eng = _engine(m, eos_token_id=eos)
    for i in range(len(lens)):
        eng.submit(ids[i, :lens[i]])
    done = eng.drain()
    by_row = {_row_of(ids, lens, r): r for r in done}
    assert by_row[0].n_out == 1 and by_row[0].tokens[0] == eos
    for i, r in by_row.items():
        np.testing.assert_array_equal(r.tokens[:r.n_out],
                                      refe[i][:r.n_out])
    s = eng.summary()
    assert s["tokens_out_total"] == sum(r.n_out for r in done)


def test_engine_oversubscribed_pool_waits_not_rejects(served_model):
    """A pool smaller than the batch worst case: admission WAITS for freed
    blocks instead of rejecting — anything that fits the pool is served
    (the bucket-mismatch rejection path is gone)."""
    m, cfg = served_model
    lens = [CAP, 5, 7, 3]
    ids = _prompts(cfg, lens)
    ref = m.generate_static_ragged(paddle.to_tensor(ids), lens,
                                   max_new_tokens=NEW).numpy()[:, CAP:]
    # 9 blocks usable = 36 rows; one request needs up to 13 rows (4
    # blocks) — only ~2 fit at once
    eng = _engine(m, kv_blocks=10)
    for i in range(len(lens)):
        eng.submit(ids[i, :lens[i]])
    done = eng.drain()
    assert [r.status for r in done] == ["done"] * len(lens)
    for r in done:
        np.testing.assert_array_equal(r.tokens, ref[_row_of(ids, lens, r)])


def test_engine_kv_oom_reject_reason(served_model):
    m, cfg = served_model
    eng = _engine(m, kv_blocks=3)       # 2 usable blocks = 8 rows
    r = eng.submit(_prompts(cfg, [CAP])[0, :CAP])   # needs 13 rows: never
    assert r.status == "rejected" and r.reason == "kv_oom"
    assert eng.summary()["rejected_total"] == 1
    # a request that fits is still served
    ok = eng.submit(_prompts(cfg, [2])[0, :2], max_new_tokens=3)
    assert ok.status == "queued"
    done = eng.drain()
    assert [x.status for x in done] == ["done"]


def test_occupancy_gauges_pinned_math(served_model):
    """kv_occupancy = live tokens / pooled capacity; kv_slots_occupancy =
    allocation-granular rows / capacity."""
    m, cfg = served_model
    # 1 request (len 5, budget 2) -> snapshot at the decode chunk entry
    # holds 5 live rows over (kv_blocks-1)*kv_block capacity,
    # with ceil((5+2-1)/4)=2 blocks reserved
    eng = _engine(m)
    cap_tokens = (eng.config.kv_blocks - 1) * 4
    eng.submit(_prompts(cfg, [5])[0, :5], max_new_tokens=2)
    eng.drain()
    s = eng.summary()
    assert s["kv_occupancy"] == 5 / cap_tokens
    assert s["kv_slots_occupancy"] == 2 * 4 / cap_tokens


def test_engine_paged_exception_recovers(served_model):
    """A batch dying mid-flight records the in-flight requests as errors
    AND rebuilds the (possibly consumed, donated) pools — the engine stays
    usable."""
    m, cfg = served_model
    eng = _engine(m)
    ids = _prompts(cfg, [5])
    eng.submit(ids[0, :5])
    real = m.decode_paged

    def boom(*a, **kw):
        raise RuntimeError("injected device failure")

    m.decode_paged = boom
    try:
        with pytest.raises(RuntimeError, match="injected"):
            eng.step()
    finally:
        m.decode_paged = real
    s = eng.summary()
    assert s["errors_total"] == 1 and s["inflight"] == 0
    assert eng._pool.free_blocks == eng._pool.capacity_blocks
    eng.submit(ids[0, :5])
    assert [r.status for r in eng.drain()] == ["done"]


def test_longtail_traffic_profile():
    tr = synthetic_traffic(64, prompt_cap=16, vocab_size=64, rate=100.0,
                           seed=0, length_dist="longtail")
    lens = np.asarray([t["prompt"].shape[0] for t in tr])
    assert lens.min() >= 1 and lens.max() <= 16
    # heavy tail: mostly short, some at the cap
    assert np.median(lens) <= 4 and (lens >= 16).any()
    with pytest.raises(ValueError, match="length_dist"):
        synthetic_traffic(2, prompt_cap=4, vocab_size=8,
                          length_dist="zipf")


@pytest.mark.slow
def test_engine_paged_under_load_open_loop(served_model):
    """Open-loop long-tail replay through the paged engine: everything
    completes, outputs stay bit-identical per row, zero steady-state
    recompiles."""
    m, cfg = served_model
    eng = _engine(m)
    traffic = synthetic_traffic(24, prompt_cap=CAP,
                                vocab_size=cfg.vocab_size, rate=500.0,
                                seed=7, length_dist="longtail")
    eng.submit(traffic[0]["prompt"])
    eng.drain()                         # warmup
    miss0 = compile_cache_misses()
    t0 = eng.clock()
    finished = []
    for item in traffic:
        eng.submit(item["prompt"], enqueue_at=t0 + item["at"])
        while eng.queue_depth >= 2:
            finished.extend(eng.step())
    finished.extend(eng.drain())
    assert sum(1 for r in finished if r.status == "done") == 24
    assert compile_cache_misses() - miss0 == 0
    for r in finished:
        ln = r.prompt_len
        ref = m.generate_static_ragged(
            paddle.to_tensor(np.pad(r.prompt, (0, CAP - ln))[None]),
            [ln], max_new_tokens=NEW).numpy()[0, CAP:]
        np.testing.assert_array_equal(r.tokens, ref)
