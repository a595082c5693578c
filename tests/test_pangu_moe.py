"""The openPangu-Ultra-MoE decoder at a toy size on the CPU (hidden 64, 4
heads, ranks 32 / 16, 16 experts with 4 held and 4 per token, one dense and
four expert layers), against the plain reference of
benchmarks/reference_pangu_moe.py on the seed's weights. Logits are
compared, not tokens; each tolerance has its reason beside it."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.inference.kv_cache import BlockPool
from paddle_tpu.nn.layers.experts import HeldExperts, experts_forward
from paddle_tpu.ops import latent_attention as LA
from paddle_tpu.ops.pallas import latent_attention as LK

from benchmarks import reference_pangu_moe as R
from benchmarks import weights_pangu_moe as W
from benchmarks.runners import serve_pangu_moe as runner
from tools.validate_paged_tpu import idle_mixes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3
# float32 on both sides; the program's attention is the absorbed form and
# its sums run in another order: differences are a few float32 roundings of
# logits of size ~0.7 (3e-7 seen); 2e-5 is sixty times that and a hundredth
# of what the bfloat16 control moves them by (3e-3)
LOGIT_TOL = 2e-5
# a served token may lie this far under the reference's best logit: nought
# to rounding (an exact tie aside), the limit of the toy cell
GAP_TOL = 1e-4


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmarks/configs/toy-pangu-moe.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model(config):
    from paddle_tpu.models.pangu_moe import PanguMoEForCausalLM
    m = PanguMoEForCausalLM(runner.model_config(config))
    runner.install_weights(m, config, SEED)
    m.eval()
    return m


def _engine(model, **kw):
    cfg = dict(prefix_cache=True, max_batch=3, prompt_cap=40,
               max_new_tokens=8, decode_chunk=3, kv_block=4, kv_blocks=64,
               prefill_chunk=16)
    cfg.update(kw)
    return ServingEngine(model, ServingConfig(**cfg))


def _gap(config, h):
    toks = np.asarray(h.tokens)[:h.n_out]
    seq = np.concatenate([np.asarray(h.prompt), toks])
    ref = np.asarray(R.logits(config, SEED, seq))
    at = len(h.prompt) - 1 + np.arange(len(toks))
    return float((ref[at].max(-1) - ref[at, toks]).max())


def test_plain_forward_gives_the_references_logits(config, model):
    ids = np.random.default_rng(0).integers(1, 256, (2, 24))
    got = model(paddle.to_tensor(ids)).numpy()
    for b in range(2):
        want = np.asarray(R.logits(config, SEED, ids[b]))
        assert np.abs(got[b] - want).max() < LOGIT_TOL


def test_expert_choices_are_the_references(config, model):
    """What each router chose under the plain forward, beside the
    reference's choices: float32 on both sides, so the same experts."""
    ids = np.random.default_rng(2).integers(1, 256, (2, 24))
    got = model.expert_choices(ids)
    assert len(got) == 4 and got[0].shape == (2, 24, 4)
    for b in range(2):
        _, _, want = R.hidden(config, SEED, ids[b])
        for g, w in zip(got, want):
            assert (np.sort(g[b], -1) == np.sort(np.asarray(w), -1)).all()


def test_the_query_projection_is_drawn_wider(config):
    """`w_uq` is QUERY_SPREAD x initializer_range wide, every other matrix
    initializer_range (benchmarks/weights_pangu_moe.py says why: attention
    that tells tokens apart, so that routing is near-uniform)."""
    p = W.make_one_layer(config, SEED, 1)
    std = config["initializer_range"]
    assert abs(float(jnp.std(p["w_uq"])) / (W.QUERY_SPREAD * std) - 1) < 0.05
    for name in ("w_dq", "w_ukv", "w_o", "w_r", "ws_gate", "we_down"):
        assert abs(float(jnp.std(p[name])) / std - 1) < 0.05, name


def test_plain_forward_is_differentiable(model):
    ids = np.random.default_rng(1).integers(1, 256, (1, 8))
    for p in model.parameters():
        p.stop_gradient = False
    loss = model(paddle.to_tensor(ids)).sum()
    loss.backward()
    grads = dict((n, p.grad) for n, p in model.named_parameters())
    assert grads["layers.1.mlp.we_down"] is not None
    assert float(np.abs(grads["layers.0.w_dkv"].numpy()).max()) > 0
    for p in model.parameters():
        p.clear_grad()
        p.stop_gradient = True


def test_prefill_then_decode_through_the_engine(config, model):
    """A ragged batch, a prefix hit, a copy-on-write (a block-aligned
    repeat), a prompt over one prefill window: every served token is the
    reference's choice on its full forward pass over prompt and answer."""
    rng = np.random.default_rng(0)
    eng = _engine(model)
    sysp = rng.integers(1, 256, 16)
    prompts = [np.concatenate([sysp, rng.integers(1, 256, n)])
               for n in (5, 20, 1, 9)] + [sysp.copy(),
                                          np.concatenate([sysp, sysp[:4]])]
    first = [eng.submit(p.astype(np.int64), max_new_tokens=m)
             for p, m in zip(prompts[:4], (8, 5, 8, 3))]
    eng.drain()
    later = [eng.submit(p.astype(np.int64), max_new_tokens=m)
             for p, m in zip(prompts[4:], (6, 8))]
    eng.drain()
    for h in first + later:
        assert h.status == "done" and h.n_out >= 1
        assert _gap(config, h) <= GAP_TOL
    s = eng.summary()
    assert s["prefix_hit_total"] >= 3           # the later two, and more
    assert s["prefill_tokens_saved_total"] >= 16 + 15 + 16
    assert max(len(p) for p in prompts) > 16 * 2     # over one window
    # conservation: every block back once the trie lets go
    eng._prefix.clear()
    assert eng._pool.free_blocks == eng._pool.capacity_blocks


def test_the_engine_reports_the_experts_counters(model):
    eng = _engine(model)
    rng = np.random.default_rng(2)
    eng.submit(rng.integers(1, 256, 9).astype(np.int64), max_new_tokens=4)
    eng.drain()
    s = eng.summary()
    # 9 prompt tokens and 3 decode steps (the chunk's), 4 expert layers,
    # 4 choices a token
    assert s["expert_assignments_made_total"] == (9 + 3) * 4 * 4
    assert 0 < s["expert_assignments_here_total"] \
        <= s["expert_assignments_made_total"]
    assert s["expert_layer_calls_total"] == (1 + 3) * 4
    assert 0 < s["experts_hit_total"] <= 4 * s["expert_layer_calls_total"]
    text = eng.metrics_text()
    for name in ("expert_assignments_here", "expert_assignments_made",
                 "experts_hit", "expert_tokens_max", "expert_layer_calls"):
        assert f"paddle_tpu_serving_{name}_total" in text
    from paddle_tpu import obs
    obs.lint_exposition(text)


@pytest.mark.parametrize("bad", [
    dict(spec_decode=True, spec_k=2), dict(shards=2), dict(cache_dtype="int8"),
    dict(weight_dtype="int8")])
def test_what_the_model_does_not_serve_is_refused(model, bad):
    with pytest.raises(ValueError, match="PanguMoEForCausalLM does not serve"):
        _engine(model, **bad)
    with pytest.raises(ValueError, match="padded engine was removed"):
        _engine(model, paged=False)


def test_gpt_engines_have_no_expert_counters():
    from paddle_tpu.models import GPTForCausalLM, gpt_config
    gpt = GPTForCausalLM(gpt_config("gpt2-tiny") if False else
                         paddle.models.GPTConfig(
                             vocab_size=64, hidden_size=32, num_layers=1,
                             num_heads=2, intermediate_size=64,
                             max_position_embeddings=32))
    eng = ServingEngine(gpt, ServingConfig(max_batch=2,
                                           prompt_cap=8, max_new_tokens=2,
                                           kv_block=4))
    assert "expert_layer_calls" not in eng.metrics.counters
    assert "expert" not in eng.metrics_text()


# ------------------------------------------------------------ expert layer
def _share(config, rank, held, parallel):
    c = dict(config, n_routed_experts=held)
    c["deployment"] = dict(config["deployment"], expert_parallel=parallel,
                           expert_rank=rank)
    return c


def _routed(c, p, h, shared):
    s = W.sizes(c)
    y, stats = experts_forward(
        h, p["w_r"], p["ws_gate"], p["ws_up"], p["ws_down"], p["we_gate"],
        p["we_up"], p["we_down"], first=s["first"], top_k=s["k"],
        scale=c["routed_scaling_factor"], shared=shared)
    return np.asarray(y), np.asarray(stats)


def test_the_four_shares_add_up_to_the_uncut_layer(config):
    """The routed parts of the four chips' shares plus the shared expert
    once are the reference's whole layer of 16 experts: float32, sums in
    another order (1e-6 of outputs of size ~0.05)."""
    h = jnp.asarray(np.random.default_rng(5).normal(size=(24, 64)),
                    jnp.float32)
    whole = _share(config, 0, 16, 1)
    want, _ = R.expert_layer(W.make_one_layer(whole, SEED, 2), h, whole)
    total, made = np.zeros((24, 64), np.float32), 0
    for rank in range(4):
        c = _share(config, rank, 4, 4)
        y, stats = _routed(c, W.make_one_layer(c, SEED, 2), h, rank == 0)
        total += y
        made += stats[0]
    assert made == 24 * 4                     # every choice computed once
    assert np.abs(total - np.asarray(want)).max() < 1e-6
    # and one share alone is the reference's result for that share
    c = _share(config, 1, 4, 4)
    p = W.make_one_layer(c, SEED, 2)
    assert np.abs(_routed(c, p, h, True)[0]
                  - np.asarray(R.expert_layer(p, h, c)[0])).max() < 1e-6


def test_every_token_to_the_same_experts_loses_none(config):
    """No capacity: 40 tokens that all choose the four experts held here
    are all computed, and all weighted."""
    c = _share(config, 1, 4, 4)
    p = dict(W.make_one_layer(c, SEED, 1))
    w_r = np.zeros((64, 16), np.float32)
    w_r[:, 4:8] = 1.0
    h = jnp.asarray(np.abs(np.random.default_rng(6).normal(size=(40, 64))),
                    jnp.float32)
    p["w_r"] = jnp.asarray(w_r)
    y, stats = _routed(c, p, h, False)
    want = sum(2.5 / 4 * np.asarray(R.gated_mlp(
        h, p["we_gate"][j], p["we_up"][j], p["we_down"][j]))
        for j in range(4))
    assert np.abs(y - want).max() < 1e-6
    assert list(stats) == [160.0, 160.0, 4.0, 40.0, 1.0]


def test_held_experts_layer_checks_its_share_and_differentiates():
    with pytest.raises(ValueError, match="not among"):
        HeldExperts(8, 4, num_experts=8, held=4, first=6, top_k=2)
    layer = HeldExperts(8, 4, num_experts=8, held=4, first=4, top_k=2,
                        scale=2.5)
    x = paddle.to_tensor(np.random.default_rng(0).normal(
        size=(2, 3, 8)).astype(np.float32), stop_gradient=False)
    y = layer(x)
    assert tuple(y.shape) == (2, 3, 8)
    y.sum().backward()
    assert layer.ws_down.grad is not None and x.grad is not None


# -------------------------------------------------------------- the cache
def test_block_pool_with_the_latent_geometry(model):
    """Allocate, share, free, copy-on-write and spill move whole blocks of
    the model's own shape: one [W, bs] plane a layer."""
    pool = BlockPool.for_model(model, num_blocks=8, block_size=4)
    assert pool.block_shapes == ((24, 4),) and pool.num_layers == 5
    assert pool.bytes_per_block == 5 * 24 * 4 * 4       # float32, true bytes
    pools = pool.make_pools()
    assert len(pools) == 5 and pools[0][0].shape == (8, 24, 4)
    a = pool.alloc(1, 10)
    assert len(a) == 3 and pool.free_blocks == 4
    pool.retain(a[:2])
    b = pool.alloc(2, 12, shared=list(a[:2]))
    assert list(b[:2]) == list(a[:2]) and pool.refcount(a[0]) == 3
    assert pool.free(1) == 1 and pool.free(2) == 1
    pool.release(a[:2])
    assert pool.free_blocks == pool.capacity_blocks
    # a block's payload out and back (the spill tier's round trip) and a
    # copy of one block into another (copy-on-write), bit for bit
    rng = np.random.default_rng(0)
    pools = [(jnp.asarray(rng.normal(size=(8, 24, 4)), jnp.float32),)
             for _ in range(5)]
    payload = pool.read_block(pools, 3)
    assert payload[0].shape == (5, 24, 4)
    want = [np.asarray(p[0][3]) for p in pools]
    pools = pool.write_block(pools, 6, payload)
    for layer, w in zip(pools, want):
        assert (np.asarray(layer[0][6]) == w).all()
    with pytest.raises(ValueError, match="int8"):
        BlockPool.for_model(model, num_blocks=8, block_size=4,
                            cache_dtype="int8")
    assert pool.head_axis is None and pool.num_heads is None


def test_memz_reports_the_latent_pools_true_bytes(model):
    from paddle_tpu.obs.memz import MemoryLedger
    eng = _engine(model)
    led = eng.attach_memory_ledger(MemoryLedger(capacity_bytes=1 << 30))
    owners = {o["owner"]: o for o in led.census()["owners"]}
    assert owners["kv_pool"]["bytes"] == 64 * 5 * 24 * 4 * 4


@pytest.mark.parametrize("start,lens", [([0, 0], [7, 3]), ([4, 8], [7, 1]),
                                        ([3, 9], [6, 7]), ([0, 5], [0, 7])])
def test_cache_write_by_whole_pages_is_the_write_by_tokens(start, lens):
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(9, 6, 4)), jnp.float32)
    lat = jnp.asarray(rng.normal(size=(2, 7, 6)), jnp.float32)
    tables = np.array([[1, 2, 3, 0], [4, 5, 6, 7]], np.int32)
    got = np.asarray(LA.latent_cache_write(
        pool, lat, jnp.asarray(tables), jnp.asarray(start, jnp.int32),
        jnp.asarray(lens, jnp.int32)))
    want = np.array(pool)
    for b in range(2):
        for i in range(lens[b]):
            pos = start[b] + i
            want[tables[b, pos // 4], :, pos % 4] = np.asarray(lat[b, i])
    live = np.ones(9, bool)
    live[0] = False                        # the trash page may hold anything
    assert (got[live] == want[live]).all()


# -------------------------------------------------------------- the kernel
@pytest.mark.parametrize("lens", [[0, 1, 17, 48, 33], [48, 48, 48, 48, 48],
                                  [1, 0, 0, 2, 9], [8, 16, 24, 32, 40]])
@pytest.mark.parametrize("tokens_per_step", [8, 16, 64])
def test_latent_decode_kernel_in_interpret_mode(monkeypatch, lens,
                                                tokens_per_step):
    """Against jax.numpy on ragged rows: empty rows give zeros, a row's
    last block is part full, the walk fetches ahead across rows. float32,
    one online softmax against one pass: 1e-6."""
    monkeypatch.setattr(LK, "_TOKENS_PER_STEP", tokens_per_step)
    rng = np.random.default_rng(0)
    b, nh, w, rank, bs, mb, nb = 5, 4, 24, 16, 8, 6, 40
    pool = jnp.asarray(rng.normal(size=(nb, w, bs)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, nh, w)), jnp.float32)
    lens = jnp.asarray(lens, jnp.int32)
    tables = jnp.asarray(rng.permutation(nb - 1)[:b * mb].reshape(b, mb) + 1,
                         jnp.int32)
    got = LK.latent_decode_kernel(q, pool, tables, lens, rank=rank,
                                  scale=0.3, interpret=True)
    rows = jnp.moveaxis(pool[tables], 2, 3).reshape(b, mb * bs, w)
    s = jnp.einsum("bhw,btw->bht", q, rows, precision="highest") * 0.3
    s = jnp.where(jnp.arange(mb * bs)[None, None] < lens[:, None, None], s,
                  -1e30)
    p = jnp.where(lens[:, None, None] > 0, jax.nn.softmax(s, -1), 0.0)
    want = jnp.einsum("bht,btr->bhr", p, rows[..., :rank],
                      precision="highest")
    assert float(jnp.abs(got - want).max()) < 1e-6
    xla = LA.latent_paged_attention(q[:, None], pool, tables, lens - 1,
                                    rank=rank, scale=0.3)[:, 0]
    assert float(jnp.abs(xla - want).max()) < 1e-6


@pytest.mark.parametrize("mix", list(idle_mixes(8)))
def test_latent_rows_of_length_zero_among_live_rows(monkeypatch, mix):
    """Rows that attend nothing (a slot without a request, a row past its
    EOS) among live rows: the live rows are bit-equal to the same call
    without them, the empty rows are zeros, and nothing is read of the
    NaN page their tables point at."""
    monkeypatch.setattr(LK, "_TOKENS_PER_STEP", 16)     # blocks of 2 pages
    rng = np.random.default_rng(1)
    b, nh, w, rank, bs, mb, nb = 8, 4, 24, 16, 8, 6, 50
    live = list(idle_mixes(b)[mix])
    lens = np.zeros(b, np.int32)
    lens[live] = (41, 1, 16, 5, 17, 33, 48)[:len(live)]
    pool = jnp.asarray(rng.normal(size=(nb, w, bs)), jnp.float32)
    pool = pool.at[0].set(jnp.nan)
    q = jnp.asarray(rng.normal(size=(b, nh, w)), jnp.float32)
    tables = rng.permutation(nb - 1)[:b * mb].reshape(b, mb) + 1
    tables[lens == 0] = 0
    tables, lens = jnp.asarray(tables, jnp.int32), jnp.asarray(lens)
    call = lambda q, t, l: np.asarray(LK.latent_decode_kernel(  # noqa: E731
        q, pool, t, l, rank=rank, scale=0.3, interpret=True))
    got = call(q, tables, lens)
    idle = np.setdiff1d(np.arange(b), live)
    assert (got[idle] == 0).all() and np.isfinite(got).all()
    if live:
        rows = jnp.asarray(live)
        assert (got[live] == call(q[rows], tables[rows], lens[rows])).all()
        xla = LA.latent_paged_attention(
            q[rows][:, None], jnp.nan_to_num(pool), tables[rows],
            lens[rows] - 1, rank=rank, scale=0.3)[:, 0]
        assert float(jnp.abs(got[live] - xla).max()) < 1e-6


def test_prefill_attention_over_a_cached_prefix_and_the_window():
    """A window of 5 tokens from position 6 on attends the 6 cached
    latents and itself, causally; chunks of the table with an online
    softmax against one pass."""
    rng = np.random.default_rng(1)
    nh, w, rank, bs = 3, 12, 8, 4
    pool = jnp.asarray(rng.normal(size=(12, w, bs)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(2, 5, nh, w)), jnp.float32)
    tables = jnp.asarray([[3, 1, 7, 0], [2, 9, 4, 5]], jnp.int32)
    start = jnp.asarray([6, 2], jnp.int32)
    got = LA.latent_paged_attention(q, pool, tables, start, rank=rank,
                                    scale=0.5)
    rows = jnp.moveaxis(pool[tables], 2, 3).reshape(2, 16, w)
    s = jnp.einsum("bshw,btw->bhst", q, rows, precision="highest") * 0.5
    keep = jnp.arange(16)[None, None] <= (start[:, None]
                                          + jnp.arange(5))[..., None]
    p = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), -1)
    want = jnp.einsum("bhst,btr->bshr", p, rows[..., :rank],
                      precision="highest")
    assert float(jnp.abs(got - want).max()) < 1e-6
