"""Multi-chip sharded paged serving (ISSUE 16): tensor-parallel decode
over the mesh's `mp` (head) axis with a PROVEN communication plan.

Covers the per-shard invariant suite: greedy output bit-identical across
shard counts 1 vs 2 vs 4 on a CPU host-platform mesh for plain,
prefix-cached, chunked-prefill, and spec-decode traffic; int8 scale
pools sharded WITH their codes (co-sharding, so dequant never crosses
shards); COW copies staying shard-local (zero collectives in the COW
executable); zero post-warmup jit misses at a fixed shard count; the
spill codec's shard-consistency pin (read_block gathers to ONE
full-width host payload whatever the shard count, write_block reshards
it back); and the config/engine validation for the `shards` knob.

The collective-inventory side of the plan (decode = mp-group all-reduce
only, no partitioner-inserted KV gather, pools donated) is gated
statically by `tools/graph_lint.py gpt-paged-sharded` — these tests pin
the numerics the lint cannot see.
"""
import numpy as np
import pytest
import jax

import paddle_tpu as paddle
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.jit.api import compile_cache_misses
from paddle_tpu.models import GPTConfig, GPTForCausalLM

CAP, NEW = 8, 6
SHARDS = (1, 2, 4)


@pytest.fixture(scope="module")
def served_model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    intermediate_size=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m, cfg


def _engine(m, shards, **kw):
    base = dict(max_batch=2, prompt_cap=CAP, max_new_tokens=NEW,
                decode_chunk=2, kv_block=4, shards=shards)
    base.update(kw)
    return ServingEngine(m, ServingConfig(**base))


def _prompts(cfg, lens, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab_size, (n,)).astype(np.int64)
            for n in lens]


def _serve(eng, prompts):
    """{prompt bytes: token list} so cross-engine comparison is
    order-independent."""
    for p in prompts:
        eng.submit(p)
    return {tuple(r.prompt.tolist()): list(r.tokens)
            for r in eng.drain()}


# -------------------------------------------- shard-count bit-identity

def test_plain_traffic_bit_identical_across_shards(served_model):
    """The headline oracle: the SAME greedy tokens at 1, 2 and 4 shards
    for mixed ragged prompts — head-sharding is a layout choice, never a
    numerics choice — and the 1-shard engine already matches the static
    generator, so all shard counts transitively match it too."""
    m, cfg = served_model
    lens = [CAP, 7, 3, 5]
    prompts = _prompts(cfg, lens, seed=3)
    ref = m.generate_static_ragged(
        paddle.to_tensor(np.stack([np.pad(p, (0, CAP - len(p)))
                                   for p in prompts])),
        lens, max_new_tokens=NEW).numpy()
    got = {}
    for s in SHARDS:
        got[s] = _serve(_engine(m, s), prompts)
    assert got[1] == got[2] == got[4]
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(
            got[1][tuple(p.tolist())], ref[i, CAP:])


def test_prefix_cached_traffic_bit_identical_across_shards(served_model):
    """Prefix-cache hit paths (zero-prefill admission, suffix prefill,
    COW) produce shard-count-invariant tokens: both the cold pass and
    the warm (cached) pass agree across 1/2/4 shards."""
    m, cfg = served_model
    prompts = _prompts(cfg, [CAP, CAP, 5], seed=7)
    cold, warm = {}, {}
    for s in SHARDS:
        eng = _engine(m, s, prefix_cache=True, kv_blocks=48)
        cold[s] = _serve(eng, prompts)
        warm[s] = _serve(eng, prompts)       # full hits + suffix hits
    assert cold[1] == cold[2] == cold[4]
    assert warm[1] == warm[2] == warm[4]
    assert cold[1] == warm[1]                # cache itself is invisible


def test_chunked_prefill_bit_identical_across_shards(served_model):
    """prefill_chunk caps per-step prefill work; the chunk boundary must
    not interact with the head sharding (each chunk writes only its own
    shard's H-slice of the pool)."""
    m, cfg = served_model
    prompts = _prompts(cfg, [CAP, 7, CAP], seed=11)
    got = {s: _serve(_engine(m, s, prefill_chunk=3), prompts)
           for s in SHARDS}
    assert got[1] == got[2] == got[4]


def test_spec_decode_bit_identical_across_shards(served_model):
    """Speculative verify windows accept/reject IDENTICALLY at every
    shard count — argmax over replicated logits, so draft acceptance is
    shard-invariant (a vocab-sharded argmax would tie-break per shard
    and silently fork the sequence)."""
    m, cfg = served_model
    prompts = _prompts(cfg, [CAP, CAP], seed=13)
    repeats = prompts + prompts              # second pass drafts + accepts
    got = {}
    for s in SHARDS:
        eng = _engine(m, s, prefix_cache=True, kv_blocks=64,
                      spec_decode=True, spec_k=3)
        first = _serve(eng, prompts)
        second = _serve(eng, prompts)        # trie drafting kicks in
        assert first == second
        got[s] = (first, second)
        assert eng.metrics.counters["spec_windows"] > 0
    assert got[1] == got[2] == got[4]


# ------------------------------------------------- pool sharding layout

def _pool_specs(eng):
    return [[(p.ndim, getattr(p.sharding, "spec", None)) for p in layer]
            for layer in eng._pools]


def test_pools_head_sharded_and_int8_scales_co_sharded(served_model):
    """Device pools carry the declared head sharding: 4D planes
    [num_blocks, bs, H, D] shard H over mp; the int8 scale pools
    [num_blocks, bs, H] shard their H WITH the codes, so a shard
    dequantizes its own heads without ever reading a remote scale."""
    from jax.sharding import PartitionSpec as P
    m, cfg = served_model
    for cache_dtype in (None, "int8"):
        eng = _engine(m, 2, cache_dtype=cache_dtype)
        for layer in _pool_specs(eng):
            for ndim, spec in layer:
                want = P(None, None, "mp", None) if ndim == 4 \
                    else P(None, None, "mp")
                assert spec == want, (ndim, spec)
        if cache_dtype == "int8":
            dts = {str(np.asarray(p).dtype)[:4] for layer in eng._pools
                   for p in layer}
            assert "int8" in dts           # codes really are int8 planes


def test_unsharded_engine_pools_uncommitted(served_model):
    """shards=1 (and the default) never builds a mesh: pools stay plain
    single-device arrays, so the single-chip path is byte-for-byte the
    pre-ISSUE-16 engine."""
    m, cfg = served_model
    eng = _engine(m, 1)
    assert eng._mesh is None
    for layer in _pool_specs(eng):
        for ndim, spec in layer:
            assert spec is None


# ------------------------------------------------- COW shard locality

def test_cow_copy_is_shard_local(served_model):
    """The COW block copy at mp>1 compiles to ZERO collectives: each
    shard copies its own H-slice (gather source and scatter target carry
    the same head sharding), so sharing a prefix never costs a hop."""
    from paddle_tpu.analysis import lint_capture
    m, cfg = served_model
    eng = _engine(m, 2, prefix_cache=True, kv_blocks=48)
    prompts = _prompts(cfg, [CAP], seed=17)
    _serve(eng, prompts)
    with lint_capture() as calls:
        _serve(eng, prompts)                 # full hit -> COW copy
    cow = [c for c in calls
           if isinstance(c[0], tuple) and c[0][0] == "paged_cow"]
    assert cow, "full-hit repeat did not take the COW path"
    kind, fn, (args, kwargs) = cow[0]
    with eng._mesh_scope():
        txt = fn.lower(*args, **kwargs).compile().as_text()
    for coll in ("all-reduce", "all-gather", "all-to-all",
                 "collective-permute", "reduce-scatter"):
        assert coll not in txt, f"COW copy lowered a {coll}"


# -------------------------------------------- steady-state compile cache

def test_zero_post_warmup_misses_sharded(served_model):
    """At a fixed shard count the executable set is closed: after one
    pass of mixed traffic, further traffic (same length profile) causes
    ZERO jit cache misses — resharding never sneaks in a recompile."""
    m, cfg = served_model
    eng = _engine(m, 2, prefix_cache=True, kv_blocks=48)
    _serve(eng, _prompts(cfg, [CAP, 7, 3], seed=19))
    before = compile_cache_misses()
    _serve(eng, _prompts(cfg, [CAP, 7, 3], seed=23))
    assert compile_cache_misses() == before


# -------------------------------------------- spill codec shard pin

def test_spill_payload_shard_consistent_round_trip(served_model):
    """The spill codec's SHARD CONSISTENCY contract: read_block gathers
    ONE full-width host payload whatever the shard count (same shapes,
    same dtypes — the mp axis never leaks into the host format), the
    round trip is BITWISE within an engine (gather → reshard-scatter →
    gather returns the same bytes, and the rehydrated pool keeps its
    head sharding), and a payload read from the 2-shard pool writes
    cleanly into the 1-shard pool and back — one codec, any shard
    count. Across shard counts the VALUES only match to float tolerance:
    the row-parallel all-reduce reorders the partial-sum reduction, so
    layer>0 KV differs in the last ulps (greedy tokens stay
    bit-identical — that oracle is the parity tests above)."""
    m, cfg = served_model
    prompts = _prompts(cfg, [CAP], seed=29)
    for cache_dtype in (None, "int8"):
        engs, payloads = {}, {}
        for s in (1, 2):
            eng = _engine(m, s, prefix_cache=True, kv_blocks=48,
                          cache_dtype=cache_dtype)
            _serve(eng, prompts)
            blk = int(eng._prefix.match(prompts[0])[0][0])
            engs[s] = eng
            payloads[s] = eng._pool.read_block(eng._pools, blk)

        # round trip within the SHARDED engine: bitwise, sharding kept
        eng = engs[2]
        blk = int(eng._prefix.match(prompts[0])[0][0])
        src = [tuple(np.asarray(p)[blk].copy() for p in layer)
               for layer in eng._pools]
        dst = eng._pool.take(1)[0]
        eng._pools = eng._pool.write_block(eng._pools, dst, payloads[2])
        for li, layer in enumerate(eng._pools):
            for pi, p in enumerate(layer):
                np.testing.assert_array_equal(
                    np.asarray(p)[dst], src[li][pi])
                assert getattr(p.sharding, "spec", None) is not None
        eng._pool.release([dst])

        # one host format: same geometry, values within float tolerance
        assert len(payloads[1]) == len(payloads[2])
        for a, b in zip(payloads[1], payloads[2]):
            a, b = np.asarray(a), np.asarray(b)
            assert a.shape == b.shape and a.dtype == b.dtype
            if a.dtype == np.int8:
                assert np.mean(a != b) < 0.01   # quantized: rare ulp flips
            else:
                np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

        # cross-shard-count rehydrate: a block spilled at 2 shards
        # restores BITWISE into the 1-shard pool
        one = engs[1]
        dst = one._pool.take(1)[0]
        one._pools = one._pool.write_block(one._pools, dst, payloads[2])
        back = one._pool.read_block(one._pools, dst)
        for a, b in zip(payloads[2], back):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        one._pool.release([dst])


# ------------------------------------------------------- validation

def test_shards_config_validation(served_model):
    m, cfg = served_model
    with pytest.raises(ValueError, match="shards must be >= 1"):
        ServingConfig(shards=0)
    assert ServingConfig(shards=2).shards == 2     # the one engine shards
    with pytest.raises(ValueError, match="padded engine was removed"):
        ServingConfig(shards=2, paged=False)
    # head divisibility is an ENGINE check (needs the model)
    with pytest.raises(ValueError, match="num_heads"):
        _engine(m, 3)
    # more shards than local devices names the XLA escape hatch
    with pytest.raises(ValueError, match="device"):
        _engine(m, 2 * len(jax.devices()))
