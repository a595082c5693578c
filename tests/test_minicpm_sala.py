"""The MiniCPM-SALA decoder at a toy size on the CPU (hidden 64, 4 query
heads over 2 KV heads, 4 lightning heads, two `minicpm4` and two
`lightning-attn` layers; selection over blocks of 8 tokens past 32 visible
ones), against the plain reference of benchmarks/reference_minicpm_sala.py
on the seed's weights. Logits are compared, not tokens; each tolerance has
its reason beside it."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.inference.kv_cache import (STATE_LOAD, STATE_SAVE,
                                           STATE_ZERO, BlockPool)
from paddle_tpu.inference.prefix_cache import PrefixCache
from paddle_tpu.ops import attention as A
from paddle_tpu.ops import lightning_attention as L
from paddle_tpu.ops import sparse_attention as SA
from paddle_tpu.ops.pallas import paged_attention as pa

from benchmarks import reference_minicpm_sala as R
from benchmarks import weights_minicpm_sala as W
from benchmarks.runners import serve_minicpm_sala as runner
from tools.validate_paged_tpu import idle_mixes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3
# float32 on both sides; the program's sums run in another order (one fused
# projection, an online softmax over chunks of pages, the state in chunks of
# 128): differences are a few float32 roundings of logits of size ~0.15
# (6e-8 seen); 2e-6 is thirty times that and a thousandth of what the
# bfloat16 control moves them by
LOGIT_TOL = 2e-6
# a served token may lie this far under the reference's best logit: nought
# to rounding (an exact tie aside), the limit of the toy cell
GAP_TOL = 1e-4


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT,
                           "benchmarks/configs/toy-minicpm-sala.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model(config):
    from paddle_tpu.models.minicpm_sala import MiniCPMSALAForCausalLM
    m = MiniCPMSALAForCausalLM(runner.model_config(config))
    runner.install_weights(m, config, SEED)
    m.eval()
    return m


def _engine(model, **kw):
    cfg = dict(prefix_cache=True, max_batch=3, prompt_cap=96,
               max_new_tokens=16, decode_chunk=4, kv_block=8, kv_blocks=96,
               prefill_chunk=16, state_snapshots=4)
    cfg.update(kw)
    return ServingEngine(model, ServingConfig(**cfg))


def _ref_logits(config, seq):
    return np.asarray(R.forward(config, SEED, jnp.asarray(seq, jnp.int32)))


def _gap(config, h):
    toks = np.asarray(h.tokens)[:h.n_out]
    ref = _ref_logits(config, np.concatenate([np.asarray(h.prompt), toks]))
    at = len(h.prompt) - 1 + np.arange(len(toks))
    return float((ref[at].max(-1) - ref[at, toks]).max())


def _serve(eng, prompts, budgets):
    hs = [eng.submit(np.asarray(p, np.int64), max_new_tokens=m)
          for p, m in zip(prompts, budgets)]
    eng.drain()
    assert all(h.status == "done" for h in hs)
    return hs


# ------------------------------------------------------- the plain forward
def test_plain_forward_gives_the_references_logits(config, model):
    ids = np.random.default_rng(0).integers(1, 256, (2, 100))
    got = model(paddle.to_tensor(ids)).numpy()
    for b in range(2):
        assert np.abs(got[b] - _ref_logits(config, ids[b])).max() < LOGIT_TOL


def test_the_selected_blocks_are_the_references(config, model):
    """Every (token, KV group) that selects, in both `minicpm4` layers:
    float32 on both sides, so the same pages."""
    ids = np.random.default_rng(2).integers(1, 256, (2, 100))
    got = model.selected_blocks(ids)
    assert len(got) == 2 and got[0][0].shape == (2, 104, 2, 5)
    for b in range(2):
        _, _, want = R.hidden(config, SEED, jnp.asarray(ids[b], jnp.int32))
        for (chosen, sparse), (ref, ref_sparse) in zip(got, want):
            ref_sparse = np.asarray(ref_sparse)
            assert (sparse[b, :100] == ref_sparse).all()
            assert ref_sparse.sum() == 100 - 32       # past dense_len
            assert (chosen[b, :100][ref_sparse]
                    == np.sort(np.asarray(ref), -1)[ref_sparse]).all()


def test_the_references_recurrence_and_its_chunked_form_agree(config):
    ids = jnp.asarray(np.random.default_rng(4).integers(1, 256, 150))
    a = R.forward(config, SEED, ids, recurrence="scan")
    b = R.forward(config, SEED, ids, recurrence="chunked")
    assert float(jnp.abs(a - b).max()) < 1e-6
    rng = np.random.default_rng(0)
    # whole chunks: the chunked form's last state is the padded sequence's
    q, k, v = (jnp.asarray(rng.normal(size=(128, 4, 16)), jnp.float32)
               for _ in range(3))
    (o1, s1), (o2, s2) = (f(q, k, v, R.decay(4)) for f in
                          (R.lightning_scan, R.lightning_chunked))
    assert float(jnp.abs(o1 - o2).max()) < 1e-4 * float(jnp.abs(o1).max())
    assert float(jnp.abs(s1 - s2).max()) < 1e-4 * float(jnp.abs(s1).max())


def test_the_decay_is_lightning_attentions_slopes():
    lam = np.asarray(L.decay(32))
    assert np.allclose(lam, np.asarray(R.decay(32)))
    assert np.allclose(-np.log(lam), 2.0 ** (-8 * np.arange(1, 33) / 32))
    assert lam[0] < 0.44 and lam[-1] > 0.996


def test_plain_forward_is_differentiable(model):
    ids = np.random.default_rng(1).integers(1, 256, (1, 40))
    for p in model.parameters():
        p.stop_gradient = False
    model(paddle.to_tensor(ids)).sum().backward()
    grads = dict((n, p.grad) for n, p in model.named_parameters())
    for name in ("layers.0.w_qkvg", "layers.1.w_qkvg", "layers.3.mlp.w_down"):
        assert float(np.abs(grads[name].numpy()).max()) > 0, name
    for p in model.parameters():
        p.clear_grad()
        p.stop_gradient = True


def test_the_query_norm_of_a_sparse_layer_is_drawn_with_twice_the_gain(config):
    sparse, lightning = (W.make_one_layer(config, SEED, i) for i in (0, 1))
    assert abs(float(jnp.mean(sparse["qn"])) - W.SPARSE_QUERY_GAIN) < 0.2
    for gain in (sparse["kn"], lightning["qn"], lightning["kn"]):
        assert abs(float(jnp.mean(gain)) - 1.0) < 0.1
    c = W.sizes(config)
    q, k, v, g = W.split_qkvg(sparse["w_qkvg"], c, W.SPARSE)
    assert (q.shape[1], k.shape[1], v.shape[1], g.shape[1]) == (64, 32, 32,
                                                                64)


# ------------------------------------------------- through the paged engine
def test_prefill_in_windows_then_decode_through_pages_and_state(config,
                                                                model):
    """A ragged batch over a shared document, prompts of one to six
    prefill windows, a second round that restores the document's state
    from its snapshot: every served token is the reference's choice on its
    full forward pass over prompt and answer."""
    rng = np.random.default_rng(0)
    eng = _engine(model)
    doc = rng.integers(1, 256, 48)
    prompts = [np.concatenate([doc, rng.integers(1, 256, n)])
               for n in (5, 20, 1, 30)] + [rng.integers(1, 256, 9)]
    first = _serve(eng, prompts, (16, 9, 12, 16, 5))
    later = _serve(eng, prompts[:2] + [doc.copy()], (16, 9, 7))
    for h in first + later:
        assert h.n_out >= 1 and _gap(config, h) <= GAP_TOL
    s = eng.summary()
    assert s["state_snapshots_taken_total"] >= 2
    assert s["state_snapshots_restored_total"] >= 3
    assert s["prefix_hit_total"] == s["state_snapshots_restored_total"]
    assert s["sparse_rows_total"] > 0 and s["dense_rows_total"] > 0
    assert s["state_rows_updated_total"] == \
        s["sparse_rows_total"] + s["dense_rows_total"]      # 2 layers each
    # conservation: every block back once the trie lets go, and its rows
    eng._prefix.clear()
    assert eng._pool.free_blocks == eng._pool.capacity_blocks
    assert eng._prefix.snapshots_held == 0


def test_a_prefix_hit_with_a_restored_snapshot_equals_the_cold_request(
        config, model):
    rng = np.random.default_rng(5)
    doc = rng.integers(1, 256, 64)
    ask = np.concatenate([doc, rng.integers(1, 256, 11)])
    warm = _engine(model)
    _serve(warm, [np.concatenate([doc, doc[:3]])], (2,))
    hit, = _serve(warm, [ask], (12,))
    cold, = _serve(_engine(model, prefix_cache=False), [ask], (12,))
    s = warm.summary()
    assert s["state_snapshots_restored_total"] == 1
    assert s["prefill_tokens_saved_total"] == 64
    assert (np.asarray(hit.tokens) == np.asarray(cold.tokens)).all()
    assert _gap(config, hit) <= GAP_TOL


def test_a_match_without_a_snapshot_is_cut_back_and_still_right(config,
                                                                model):
    """The trie holds 64 + 8 tokens of the first prompt's pages but a
    snapshot only at 64 (where its last window began): a prompt that shares
    72 tokens reuses 64, and one that shares 40 reuses none."""
    rng = np.random.default_rng(6)
    base = rng.integers(1, 256, 75)
    eng = _engine(model)
    _serve(eng, [base], (2,))
    assert eng._prefix.snapshots_held == 1
    long_, short = (np.concatenate([base[:n], rng.integers(1, 256, 9)])
                    for n in (72, 40))
    hs = _serve(eng, [long_], (10,)) + _serve(eng, [short], (10,))
    s = eng.summary()
    assert s["prefix_match_cut_tokens_total"] == 8 + 40
    assert s["prefill_tokens_saved_total"] == 64
    assert s["state_snapshots_restored_total"] == 1
    for h in hs:
        assert _gap(config, h) <= GAP_TOL


def test_a_slots_second_tenant_finds_no_stale_state(config, model):
    """One slot, two requests after each other: the second equals the same
    request on a fresh engine."""
    rng = np.random.default_rng(7)
    a, b = rng.integers(1, 256, 50), rng.integers(1, 256, 37)
    eng = _engine(model, max_batch=1, prefix_cache=False)
    _serve(eng, [a], (9,))
    second, = _serve(eng, [b], (9,))
    fresh, = _serve(_engine(model, max_batch=1, prefix_cache=False), [b],
                    (9,))
    assert (np.asarray(second.tokens) == np.asarray(fresh.tokens)).all()
    assert _gap(config, second) <= GAP_TOL


def test_the_new_counters_are_on_the_engines_surface(model):
    eng = _engine(model)
    _serve(eng, [np.arange(1, 60)], (6,))
    text, s = eng.metrics_text(), eng.summary()
    for name in model.step_counter_names + runner.ENGINE_COUNTERS:
        assert f"{name}_total" in s
        assert f"paddle_tpu_serving_{name}_total" in text
    assert 0 < s["state_slots_occupancy"] <= 1
    assert "paddle_tpu_serving_state_slots_occupancy" in text


@pytest.mark.parametrize("kw,why", [
    (dict(spec_decode=True), "spec_decode"),
    (dict(shards=2), "shards"),
    (dict(cache_dtype="int8"), "cache_dtype"),
    (dict(weight_dtype="int8"), "weight_dtype"),
    (dict(spill_host_bytes=1 << 20), "spill_host_bytes"),
    (dict(prefill_chunk=12), "prefill_chunk"),
    (dict(prefill_chunk=None, prompt_cap=90), "prompt_cap"),
    (dict(kv_block=4, prefill_chunk=16), "kv_block")])
def test_what_the_model_does_not_serve_is_refused_at_engine_build(model, kw,
                                                                  why):
    with pytest.raises(ValueError, match=why):
        _engine(model, **kw)


# ------------------------------------------------ the cache manager's planes
def test_the_pool_holds_pages_and_state_rows_in_one_account(model):
    pool = BlockPool.for_model(model, num_blocks=10, block_size=8,
                               state_rows=3, snapshot_rows=2)
    assert pool.has_state and pool.num_layers == 4
    pools = pool.make_pools()
    assert [len(layer) for layer in pools] == [3, 2, 2, 3]
    assert pools[0][0].shape == (10, 2, 8, 16)
    assert pools[0][2].shape == (10, 2 * 4 * 16)
    assert pools[1][0].shape == (3, 4, 16, 16)
    assert pools[1][1].shape == (2, 4, 16, 16)
    assert pools[1][0].dtype == jnp.float32
    # two sparse layers page K, V and compressed keys; two state layers
    assert pool.bytes_per_block == 2 * (2 * 2 * 8 * 16 + 2 * 4 * 16) * 4
    assert pool.state_bytes_per_row == 2 * 4 * 16 * 16 * 4
    assert pool.state_bytes == 5 * pool.state_bytes_per_row
    pools[1] = (pools[1][0].at[1].set(7.0), pools[1][1])
    pools = pool.state_move(pools, STATE_SAVE, 1, 0)
    assert float(pools[1][1][0].min()) == 7.0
    pools = pool.state_move(pools, STATE_ZERO, 1, 0)
    assert float(jnp.abs(pools[1][0]).max()) == 0.0
    pools = pool.state_move(pools, STATE_LOAD, 2, 0)
    assert float(pools[1][0][2].min()) == 7.0
    assert float(jnp.abs(pools[2][0][2]).max()) == 0.0     # its own snapshot
    with pytest.raises(ValueError, match="state planes"):
        BlockPool.for_model(model, num_blocks=10, block_size=8)


def test_a_model_without_state_gets_the_pool_it_always_got():
    pool = BlockPool(num_blocks=4, block_size=2, num_layers=2,
                     block_shapes=((2, 3, 4),) * 2, head_axis=1)
    assert not pool.has_state and pool.state_bytes == 0
    assert pool.bytes_per_block == 2 * 2 * 24 * 4
    assert [tuple(p.shape for p in layer) for layer in pool.make_pools()] \
        == [((4, 2, 3, 4),) * 2] * 2


def test_the_snapshot_longest_unused_makes_room(model):
    pool = BlockPool.for_model(model, num_blocks=40, block_size=8,
                               state_rows=2, snapshot_rows=2)
    trie = PrefixCache(pool)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 256, 16) for _ in range(3)]
    for i, p in enumerate(prompts):
        trie.insert(p, pool.alloc(i, 16))
    more = lambda p: np.concatenate([p, [5]])  # noqa: E731
    assert trie.snapshot(prompts[0], 16) in (0, 1)
    assert trie.snapshot(prompts[0], 16) is None            # holds one
    assert trie.match_state(prompts[0], 15) == ([], 0, None, 16)
    assert trie.snapshot(prompts[1], 8) is not None
    blocks, t, row, cut = trie.match_state(more(prompts[0]), 16)
    assert (t, cut) == (16, 0) and row is not None          # a use
    assert trie.snapshots_held == 2 and trie.snapshot_evictions == 0
    assert trie.snapshot(prompts[2], 16) is not None        # [1]'s goes
    assert trie.snapshot_evictions == 1
    assert trie.match_state(more(prompts[1]), 16)[1] == 0
    assert trie.match_state(more(prompts[0]), 16)[1] == 16
    # a fresh one is not the first to go, however stale the others' uses
    assert trie.snapshot(prompts[1], 16) is not None        # [2]'s goes
    assert trie.match_state(more(prompts[0]), 16)[1] == 16
    assert trie.match_state(more(prompts[2]), 16)[1] == 0
    for i in range(3):
        pool.free(i)
    trie.clear()
    assert trie.snapshots_held == 0 and pool.free_blocks == 39


# ------------------------------------------------------------------ the ops
def _sizes():
    return SA.SparseSizes(kernel=4, stride=2, block=8, topk=5, init_blocks=1,
                          window=8, dense_len=32)


def test_compressed_keys_written_a_token_at_a_time_equal_a_windows():
    rng = np.random.default_rng(0)
    sz, hkv, d, n = _sizes(), 2, 16, 40
    k = jnp.asarray(rng.normal(size=(1, n, hkv, d)), jnp.float32)
    tables = jnp.asarray([[3, 1, 4, 2, 5, 0]], jnp.int32)
    zeros = lambda *s: jnp.zeros(s, jnp.float32)  # noqa: E731
    kp, vp = SA.kv_cache_write(zeros(8, hkv, 8, d), zeros(8, hkv, 8, d), k,
                               k, tables, jnp.zeros((1,), jnp.int32),
                               jnp.asarray([n]))
    win = SA.compressed_write(zeros(8, hkv * 4 * d), kp, tables,
                              jnp.zeros((1,), jnp.int32), jnp.asarray([n]),
                              sz, 40)
    one = zeros(8, hkv * 4 * d)
    for t in range(n):
        one = SA.compressed_write(one, kp, tables, jnp.asarray([t]),
                                  jnp.ones((1,), jnp.int32), sz, 1)
    assert float(jnp.abs(win[1:6] - one[1:6]).max()) < 1e-6
    # compressed key j = mean(k[2 j : 2 j + 4]) sits in page (j + 1) // 4
    want = np.asarray(R.compressed_keys(k[0], {"kernel": 4, "stride": 2}))
    got = np.asarray(win[tables[0, :5]]).reshape(5, hkv, 4, d)
    for j in range(want.shape[0]):
        m = j + 1
        assert np.abs(got[m // 4, :, m % 4] - want[j]).max() < 1e-6


def test_a_window_of_the_state_equals_its_steps_and_stops_at_lens():
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 20, 4, 16)), jnp.float32)
               for _ in range(3))
    s0 = jnp.asarray(rng.normal(size=(2, 4, 16, 16)), jnp.float32)
    lens = jnp.asarray([20, 13])
    o, s = L.lightning_window(q, k, v, s0, lens, chunk=8)
    st, outs = s0, []
    for t in range(20):
        ot, st = L.lightning_decode(q[:, t], k[:, t], v[:, t], st, t < lens)
        outs.append(ot)
    assert float(jnp.abs(s - st).max()) < 1e-4
    want = jnp.stack(outs, 1)
    assert float(jnp.abs(o[0] - want[0]).max()) < 1e-4
    assert float(jnp.abs(o[1, :13] - want[1, :13]).max()) < 1e-4


@pytest.mark.parametrize("tokens", [[5, 5], [48, 48], [17, 40], [0, 1]])
def test_the_page_list_kernel_against_the_gather(tokens):
    """Interpret mode: lists of six pages a (row, KV head), ending in a
    partly filled page, an empty list among them."""
    rng = np.random.default_rng(0)
    b, hkv, g, d, bs, nb, w = 2, 2, 4, 16, 8, 40, 6
    q = jnp.asarray(rng.normal(size=(b, hkv, g, d)), jnp.float32)
    kp, vp = (jnp.asarray(rng.normal(size=(nb, hkv, bs, d)), jnp.float32)
              for _ in range(2))
    ids = jnp.asarray(rng.integers(1, nb, (b, hkv, w)), jnp.int32)
    toks = jnp.broadcast_to(jnp.asarray(tokens, jnp.int32)[:, None], (b, hkv))
    got = pa.grouped_paged_attention_kernel(q, kp, vp, ids, toks, scale=0.25,
                                            interpret=True)
    want = SA.grouped_paged_decode_reference(q, kp, vp, ids, toks, 0.25)
    live = np.asarray(toks) > 0
    assert float(jnp.abs(got - want)[live].max()) < 1e-5
    assert float(jnp.abs(got)[~live].sum()) == 0.0


@pytest.mark.parametrize("mix", list(idle_mixes(8)))
def test_page_lists_of_no_tokens_among_live_rows(mix):
    """Rows that attend nothing (`decode_lists`' tokens zeroed where a row
    is not live) among live rows, both KV heads of a row alike: live rows
    bit-equal to the same call without them, the others zeros, nothing
    read of the NaN page 0."""
    rng = np.random.default_rng(2)
    b, hkv, g, d, bs, nb, w = 8, 2, 4, 16, 8, 60, 40
    live = list(idle_mixes(b)[mix])
    toks = np.zeros(b, np.int32)
    toks[live] = (300, 1, 128, 5, 129, 17, 320)[:len(live)]  # blocks of 16
    q = jnp.asarray(rng.normal(size=(b, hkv, g, d)), jnp.float32)
    kp, vp = (jnp.asarray(rng.normal(size=(nb, hkv, bs, d)),
                          jnp.float32).at[0].set(jnp.nan) for _ in range(2))
    ids = rng.integers(1, nb, (b, hkv, w))
    ids[toks == 0] = 0
    ids = jnp.asarray(ids, jnp.int32)
    toks = jnp.broadcast_to(jnp.asarray(toks)[:, None], (b, hkv))
    call = lambda q, i, t: np.asarray(  # noqa: E731
        pa.grouped_paged_attention_kernel(q, kp, vp, i, t, scale=0.25,
                                          interpret=True))
    got = call(q, ids, toks)
    idle = np.setdiff1d(np.arange(b), live)
    assert (got[idle] == 0).all() and np.isfinite(got).all()
    if live:
        rows = jnp.asarray(live)
        assert (got[live] == call(q[rows], ids[rows], toks[rows])).all()
        want = SA.grouped_paged_decode_reference(
            q[rows], jnp.nan_to_num(kp), jnp.nan_to_num(vp), ids[rows],
            toks[rows], 0.25)
        assert float(jnp.abs(got[live] - want).max()) < 1e-5


def _gqa_case(seed=0, b=3, nh=4, nkv=2, hd=16, bs=4, nb=24, mb=5, s=1):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, s, nh, hd)), jnp.float32)
    kp, vp = (jnp.asarray(rng.normal(size=(nb, bs, nkv, hd)), jnp.float32)
              for _ in range(2))
    tables = jnp.asarray(rng.integers(1, nb, (b, mb)), jnp.int32)
    return q, kp, vp, tables


@pytest.mark.parametrize("walk", [False, True], ids=["slots", "walk"])
def test_grouped_paged_decode_is_the_kernel_over_repeated_heads(monkeypatch,
                                                                walk):
    """2 KV heads under 4 query heads against the same kernel at group
    size 1 over pools whose heads are repeated (what the kernel always
    computed), and against the dense jnp form."""
    if walk:
        monkeypatch.setattr(pa, "_pages_dma_sliceable", lambda nh, hd: True)
    q, kp, vp, tables = _gqa_case()
    lens = jnp.asarray([9, 20, 1], jnp.int32)
    got = pa.paged_attention_kernel(q, kp, vp, tables, lens, interpret=True)
    rep = lambda p: jnp.repeat(p, 2, axis=2)  # noqa: E731
    one = pa.paged_attention_kernel(q, rep(kp), rep(vp), tables, lens,
                                    interpret=True)
    want = A.paged_attention_reference(q, rep(kp), rep(vp), tables, lens)
    if walk:        # the score tiles are half as wide: another sum order
        assert float(jnp.abs(got - one).max()) < 1e-6
    else:
        assert (np.asarray(got) == np.asarray(one)).all()
    assert float(jnp.abs(got - want).max()) < 1e-5


def test_grouped_paged_prefix_is_the_kernel_over_repeated_heads():
    q, kp, vp, tables = _gqa_case(seed=1, s=6)
    start = jnp.asarray([3, 12, 0], jnp.int32)
    got = pa.paged_prefix_attention_kernel(q, kp, vp, tables, start,
                                           interpret=True)
    rep = lambda p: jnp.repeat(p, 2, axis=2)  # noqa: E731
    one = pa.paged_prefix_attention_kernel(q, rep(kp), rep(vp), tables, start,
                                           interpret=True)
    want = A.paged_prefix_attention_reference(q, rep(kp), rep(vp), tables,
                                              start)
    assert (np.asarray(got) == np.asarray(one)).all()
    assert float(jnp.abs(got - want).max()) < 1e-5


def test_group_size_one_traces_the_kernels_as_they_were():
    """At as many KV heads as query heads nothing of the grouping is
    traced: no division of the head's number, the pools' own head count in
    every shape (a static specialisation)."""
    q, kp, vp, tables = _gqa_case(nkv=4)
    lens = jnp.asarray([9, 20, 1], jnp.int32)
    text = str(jax.make_jaxpr(lambda *a: pa.paged_attention_kernel(
        *a, interpret=True))(q, kp, vp, tables, lens))
    assert " div " not in text.split("pallas_call")[1].split("name=")[0]
    with pytest.raises(ValueError, match="groups must be whole"):
        pa.paged_attention_kernel(q, kp[:, :, :3], vp[:, :, :3], tables, lens,
                                  interpret=True)
