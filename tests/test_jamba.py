"""The Jamba decoder at a toy size on the CPU (hidden 64, d_inner 128, 16
states, dt_rank 8, 4 query heads over ONE KV head; layers mamba, attention,
mamba, mamba from period 4 and offset 1), against the plain reference of
benchmarks/reference_jamba.py on the seed's weights. Logits are compared,
not tokens; each tolerance has its reason beside it."""
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
from paddle_tpu.models import decoder_parts as DP
from paddle_tpu.models import jamba as M
from paddle_tpu.ops import selective_scan as SS
from paddle_tpu.ops import sparse_attention as SA
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas import selective_scan as pss

from benchmarks import reference_jamba as R
from benchmarks import weights_jamba as W
from benchmarks.runners import serve_jamba as runner
from benchmarks.tools import calibrate_jamba

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 5
# float32 on both sides; the program's sums run in another order (one
# fused projection, an online softmax over chunks of pages, the state laid
# out [N, d_inner] and not [d_inner, N]): differences are a few float32
# roundings of logits of size ~4 through four layers (3e-6 seen); 2e-5 is
# seven times that and a fortieth of what the scan state kept in bfloat16
# moves them by
LOGIT_TOL = 2e-5
# a served token may lie this far under the reference's best logit: nought
# to rounding (an exact tie aside), the limit of the toy cell
GAP_TOL = 2e-5


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmarks/configs/toy-jamba.json")) as f:
        return json.load(f)


def _model(config):
    m = M.JambaForCausalLM(runner.model_config(config))
    runner.install_weights(m, config, SEED)
    m.eval()
    return m


@pytest.fixture(scope="module")
def model(config):
    return _model(config)


def _engine(model, **kw):
    cfg = dict(prefix_cache=True, max_batch=3, prompt_cap=96,
               max_new_tokens=16, decode_chunk=4, kv_block=8, kv_blocks=96,
               prefill_chunk=16, state_snapshots=4)
    cfg.update(kw)
    return ServingEngine(model, ServingConfig(**cfg))


def _ref_logits(config, seq):
    return np.asarray(R.forward(config, SEED, jnp.asarray(seq, jnp.int32)))


def _gaps(config, h):
    """How far each served token lies under the reference's best logit on
    its full forward pass over prompt and answer."""
    toks = np.asarray(h.tokens)[:h.n_out]
    ref = _ref_logits(config, np.concatenate([np.asarray(h.prompt), toks]))
    at = len(h.prompt) - 1 + np.arange(len(toks))
    return ref[at].max(-1) - ref[at, toks]


def _serve(eng, prompts, budgets):
    hs = [eng.submit(np.asarray(p, np.int64), max_new_tokens=m)
          for p, m in zip(prompts, budgets)]
    eng.drain()
    assert all(h.status == "done" for h in hs)
    return hs


@pytest.fixture()
def tapped(config, monkeypatch):
    """A model whose every sampled logits row is also handed to the host,
    in order: (model, the list they arrive in)."""
    seen, real = [], DP.sample_logits

    def tap(logits, key, **kw):
        jax.debug.callback(lambda lg: seen.append(np.asarray(lg)), logits,
                           ordered=True)
        return real(logits, key, **kw)
    monkeypatch.setattr(DP, "sample_logits", tap)
    return _model(config), seen


# ------------------------------------------------------- the plain forward
def test_plain_forward_gives_the_references_logits(config, model):
    ids = np.random.default_rng(0).integers(1, 256, (2, 100))
    got = model(paddle.to_tensor(ids)).numpy()
    for b in range(2):
        assert np.abs(got[b] - _ref_logits(config, ids[b])).max() < LOGIT_TOL


def test_plain_forward_is_differentiable(config):
    model = _model(config)
    ids = np.random.default_rng(1).integers(1, 256, (1, 40))
    for p in model.parameters():
        p.stop_gradient = False
    model(paddle.to_tensor(ids)).sum().backward()
    grads = dict((n, p.grad) for n, p in model.named_parameters())
    for name in ("layers.0.a_log", "layers.0.conv_w", "layers.0.w_dt",
                 "layers.1.w_qkv", "layers.3.mlp.w_down", "emb"):
        assert float(np.abs(grads[name].numpy()).max()) > 0, name


def test_layer_order_comes_from_period_and_offset(config):
    full = M.JambaConfig()
    assert [i for i, m in enumerate(full.mixers) if m == M.ATTENTION] == \
        [7, 21] and len(full.mixers) == 28 and full.d_inner == 5120
    assert runner.model_config(config).mixers == (
        M.MAMBA, M.ATTENTION, M.MAMBA, M.MAMBA)
    assert W.mixers(config) == runner.model_config(config).mixers


def test_the_time_scales_are_mamba_ones_own(config):
    """A = -(n + 1), dt0 = softplus(b_dt) log-uniform in [0.001, 0.1], a
    depthwise filter of fan-in 4: with b_dt = 0 a state of A = -16 would
    forget within one token and a lost state would not show."""
    p = W.make_one_layer(config, SEED, 0)
    assert np.allclose(np.exp(np.asarray(p["a_log"]))[:, 0],
                       np.arange(1, 17), rtol=1e-6)
    dt0 = np.asarray(jax.nn.softplus(p["b_dt"]))
    assert 0.001 * 0.999 <= dt0.min() and dt0.max() <= 0.1 * 1.001
    assert np.log(dt0).std() > 1.0                      # spread over decades
    assert np.abs(np.asarray(p["conv_w"])).max() <= 0.5
    assert p["conv_w"].shape == (4, 128) and p["a_log"].shape == (16, 128)


# ------------------------------------------------- windows, then decode
@pytest.mark.parametrize("windows", [(1, 2, 3, 5, 8), (8, 2, 1, 1, 16)],
                         ids=["1-2-3-5-page", "page-2-1-1-2pages"])
def test_prefill_in_windows_then_decode_gives_the_references_logits(
        config, tapped, windows):
    """One row prefilled in windows of 1, 2, 3, 5 tokens and a whole page
    (a window shorter than three tokens SHIFTS the conv state), then decoded
    through pages and state: the logits after every window and of every
    decode step are the reference's on its full forward pass."""
    model, seen = tapped
    rng = np.random.default_rng(sum(windows))
    prompt = rng.integers(1, 256, sum(windows))
    pool = BlockPool.for_model(model, num_blocks=12, block_size=8,
                               state_rows=2, snapshot_rows=1)
    pools = pool.make_pools()
    tables = np.asarray([[3, 1, 4, 2, 5, 6]], np.int32)
    off = 0
    for w in windows:
        pools, first = model.prefill_paged(
            prompt[None, off:off + w], np.asarray([w], np.int32), pools,
            tables, start=np.asarray([off], np.int32),
            state_slots=np.asarray([1], np.int32))
        off += w
    n = 6
    # the engine's rows are the state planes' rows: the prompt went to row 1
    toks, pools, lens, _ = model.decode_paged(
        pools, np.concatenate([np.zeros_like(tables), tables]),
        np.asarray([0, off], np.int32),
        np.asarray([0, int(first.numpy()[0])], np.int32),
        np.asarray([True, False]), n)
    jax.effects_barrier()
    toks = np.asarray(toks.numpy())[1]
    seq = np.concatenate([prompt, [int(first.numpy()[0])], toks])
    ref = _ref_logits(config, seq)
    ends = np.cumsum(windows) - 1
    assert len(seen) == len(windows) + n
    for got, at in zip(seen[:len(windows)], ends):
        assert np.abs(got[0] - ref[at]).max() < LOGIT_TOL, at
    for i, got in enumerate(seen[len(windows):]):
        assert np.abs(got[1] - ref[len(prompt) + i]).max() < LOGIT_TOL, i
    assert int(first.numpy()[0]) == ref[len(prompt) - 1].argmax()
    assert (toks == ref[len(prompt):len(prompt) + n].argmax(-1)).all()
    assert int(lens[1]) == off + n


def test_a_done_or_idle_decode_row_leaves_both_states_bit_equal(model):
    rng = np.random.default_rng(3)
    pool = BlockPool.for_model(model, num_blocks=12, block_size=8,
                               state_rows=3, snapshot_rows=1)
    pools = [tuple(jnp.asarray(rng.normal(size=a.shape), a.dtype)
                   for a in layer) for layer in pool.make_pools()]
    before = [[np.asarray(a) for a in layer] for layer in pools]
    tables = np.asarray([[1, 2, 3], [0, 0, 0], [4, 5, 6]], np.int32)
    _, after, _, _ = model.decode_paged(
        pools, tables, np.asarray([9, 0, 11], np.int32),
        np.asarray([7, 0, 9], np.int32), np.asarray([False, True, True]), 3)
    for i, (was, now) in enumerate(zip(before, after)):
        if model.layers[i].kind != M.MAMBA:
            continue
        conv, _, scan, _ = (np.asarray(a) for a in now)
        for plane, old in ((conv, was[0]), (scan, was[2])):
            assert (plane[1:] == old[1:]).all()         # done rows
            assert not (plane[0] == old[0]).all()       # the live one moved


def test_through_the_engine_with_pages_snapshots_and_restores(config,
                                                              model):
    """A ragged batch over a shared system prompt, prompts of one to six
    prefill windows, a second round that restores the system prompt's conv
    and scan state from its snapshot: every served token is the
    reference's choice on its full forward pass over prompt and answer."""
    rng = np.random.default_rng(0)
    eng = _engine(model)
    doc = rng.integers(1, 256, 48)
    prompts = [np.concatenate([doc, rng.integers(1, 256, n)])
               for n in (5, 20, 1, 30)] + [rng.integers(1, 256, 9)]
    first = _serve(eng, prompts, (16, 9, 12, 16, 5))
    later = _serve(eng, prompts[:2] + [doc.copy()], (16, 9, 7))
    for h in first + later:
        assert h.n_out >= 1 and _gaps(config, h).max() <= GAP_TOL
    s = eng.summary()
    assert s["state_snapshots_taken_total"] >= 2
    assert s["state_snapshots_restored_total"] >= 3
    assert s["prefix_hit_total"] == s["state_snapshots_restored_total"]
    # a row rides its last chunk to the end: at least a step a served
    # token after the first, in each of the 3 Mamba layers
    served = sum(h.n_out - 1 for h in first + later)
    assert s["ssm_rows_updated_total"] >= 3 * served
    assert s["ssm_rows_updated_total"] % 3 == 0
    assert s["attn_pages_walked_total"] * 3 >= s["ssm_rows_updated_total"]
    # every prompt token not served from the trie is scanned once a Mamba
    # layer
    assert s["ssm_tokens_scanned_total"] == 3 * (
        sum(len(h.prompt) for h in first + later)
        - s["prefill_tokens_saved_total"])
    eng._prefix.clear()
    assert eng._pool.free_blocks == eng._pool.capacity_blocks
    assert eng._prefix.snapshots_held == 0


def test_a_request_behind_a_cached_system_prompt_equals_the_cold_one(
        config, tapped):
    """Both state arrays of every Mamba layer come back with the snapshot:
    the second request's logits are those of a cold prefill of the whole
    prompt, and the reference's."""
    model, seen = tapped
    rng = np.random.default_rng(5)
    doc = rng.integers(1, 256, 64)
    ask = np.concatenate([doc, rng.integers(1, 256, 11)])
    warm = _engine(model)
    _serve(warm, [np.concatenate([doc, doc[:3]])], (2,))
    jax.effects_barrier()
    del seen[:]
    hit, = _serve(warm, [ask], (8,))
    jax.effects_barrier()
    got = [lg.copy() for lg in seen]
    del seen[:]
    cold, = _serve(_engine(model, prefix_cache=False), [ask], (8,))
    jax.effects_barrier()
    s = warm.summary()
    assert s["state_snapshots_restored_total"] == 1
    assert s["prefill_tokens_saved_total"] == 64
    assert (np.asarray(hit.tokens) == np.asarray(cold.tokens)).all()
    # the hit ran one prefill window, the cold request five; then the
    # same decode chunks, the request in row 0 of both
    hit_decode, cold_decode = got[1:], seen[5:]
    assert len(hit_decode) == len(cold_decode) >= 7
    ref = _ref_logits(config, np.concatenate(
        [ask, np.asarray(hit.tokens)[:hit.n_out]]))
    assert np.abs(got[0][0] - ref[len(ask) - 1]).max() < LOGIT_TOL
    for i, (a, b) in enumerate(zip(hit_decode[:7], cold_decode[:7])):
        assert np.abs(a[0] - b[0]).max() < LOGIT_TOL
        assert np.abs(a[0] - ref[len(ask) + i]).max() < LOGIT_TOL


def test_a_slots_second_tenant_finds_no_stale_state(config, model):
    rng = np.random.default_rng(7)
    a, b = rng.integers(1, 256, 50), rng.integers(1, 256, 37)
    eng = _engine(model, max_batch=1, prefix_cache=False)
    _serve(eng, [a], (9,))
    second, = _serve(eng, [b], (9,))
    fresh, = _serve(_engine(model, max_batch=1, prefix_cache=False), [b],
                    (9,))
    assert (np.asarray(second.tokens) == np.asarray(fresh.tokens)).all()
    assert _gaps(config, second).max() <= GAP_TOL


def test_the_new_counters_are_on_the_engines_surface(model):
    eng = _engine(model)
    _serve(eng, [np.arange(1, 60)], (6,))
    text, s = eng.metrics_text(), eng.summary()
    assert model.step_counter_names == M.STATS
    for name in model.step_counter_names + runner.ENGINE_COUNTERS:
        assert f"{name}_total" in s
        assert f"paddle_tpu_serving_{name}_total" in text
    assert 0 < s["state_slots_occupancy"] <= 1
    assert s["ssm_windows_scanned_total"] == 3 * 4      # 59 tokens, 16 each


@pytest.mark.parametrize("kw,why", [
    (dict(spec_decode=True), "spec_decode"),
    (dict(shards=2), "shards"),
    (dict(cache_dtype="int8"), "cache_dtype"),
    (dict(weight_dtype="int8"), "weight_dtype"),
    (dict(spill_host_bytes=1 << 20), "spill_host_bytes"),
    (dict(prefill_chunk=12), "prefill_chunk"),
    (dict(prefill_chunk=None, prompt_cap=90), "prompt_cap")])
def test_what_the_model_does_not_serve_is_refused_at_engine_build(model, kw,
                                                                  why):
    with pytest.raises(ValueError, match="JambaForCausalLM.*" + why):
        _engine(model, **kw)


# ------------------------------------------------ the cache manager's planes
def test_the_pool_holds_two_state_arrays_a_mamba_layer(model):
    """Conv and scan state side by side in the state planes, zeroed, saved
    and restored together; the attention layer pages one KV head."""
    pool = BlockPool.for_model(model, num_blocks=10, block_size=8,
                               state_rows=3, snapshot_rows=2)
    assert pool.has_state and pool.num_layers == 4
    pools = pool.make_pools()
    assert [len(layer) for layer in pools] == [4, 2, 4, 4]
    assert pools[1][0].shape == (10, 1, 8, 16)          # ONE KV head
    assert [a.shape for a in pools[0]] == [(3, 384), (2, 384),
                                           (3, 16, 128), (2, 16, 128)]
    assert all(a.dtype == jnp.float32 for a in pools[0])
    assert pool.bytes_per_block == 2 * 8 * 16 * 4
    assert pool.state_bytes_per_row == 3 * (384 + 16 * 128) * 4
    assert pool.state_bytes == 5 * pool.state_bytes_per_row
    conv, csnap, scan, ssnap = pools[2]
    pools[2] = (conv.at[1].set(3.0), csnap, scan.at[1].set(7.0), ssnap)
    pools = pool.state_move(pools, STATE_SAVE, 1, 0)
    assert float(pools[2][1][0].min()) == 3.0
    assert float(pools[2][3][0].min()) == 7.0
    pools = pool.state_move(pools, STATE_ZERO, 1, 0)
    assert float(jnp.abs(pools[2][0]).max()) == 0.0
    assert float(jnp.abs(pools[2][2]).max()) == 0.0
    pools = pool.state_move(pools, STATE_LOAD, 2, 0)
    assert float(pools[2][0][2].min()) == 3.0
    assert float(pools[2][2][2].min()) == 7.0
    assert float(jnp.abs(pools[0][2][2]).max()) == 0.0  # its own snapshot


# ------------------------------------------------------------------ the ops
def _scan_case(b, t, din, n=16, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    a_t = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32)[:, None],
                            (n, din))
    return (f(b, t, din), jax.nn.softplus(f(b, t, din) - 3.0), f(b, t, n),
            f(b, t, n), a_t, f(din), f(b, n, din))


def _recurrence(x, dt, bm, cm, a_t, d_skip, state, lens):
    """Steps 5 and 6 of the reference, a token at a time in numpy."""
    x, dt, bm, cm, a, d, s = (np.asarray(v, np.float64) for v in
                              (x, dt, bm, cm, a_t, d_skip, state))
    y = np.zeros_like(x)
    for b in range(x.shape[0]):
        for t in range(int(lens[b])):
            s[b] = np.exp(dt[b, t][None] * a) * s[b] \
                + (dt[b, t] * x[b, t])[None] * bm[b, t][:, None]
            y[b, t] = (s[b] * cm[b, t][:, None]).sum(0) + d * x[b, t]
    return y, s


@pytest.mark.parametrize("b,t,din,lens", [(2, 16, 128, [16, 5]),
                                          (1, 13, 256, [7]),
                                          (2, 8, 1024, [0, 8])])
def test_the_scan_kernel_against_the_recurrence(b, t, din, lens):
    """Interpret mode, from a non-zero state, lens < T (a row of no live
    token keeps its state), T not a multiple of the kernel's eight tokens,
    two blocks of channels: the kernel, the `lax.scan` form and the
    recurrence in float64 agree to float32 rounding."""
    args = _scan_case(b, t, din)
    lens = jnp.asarray(lens, jnp.int32)
    want_y, want_s = _recurrence(*args, lens)
    live = np.arange(t)[None] < np.asarray(lens)[:, None]
    for fn in (SS.scan_window_reference,
               lambda *a: pss.selective_scan_kernel(*a, interpret=True)):
        y, s = fn(*args, lens)
        assert np.abs(np.asarray(y) - want_y)[live].max(initial=0) < 2e-5
        assert np.abs(np.asarray(s) - want_s).max() < 2e-5
        for row in np.flatnonzero(np.asarray(lens) == 0):
            assert (np.asarray(s)[row] == np.asarray(args[6])[row]).all()


def test_a_window_of_the_scan_equals_its_steps():
    x, dt, bm, cm, a_t, d_skip, s0 = _scan_case(3, 10, 128, seed=1)
    lens = jnp.asarray([10, 4, 0])
    y, s = SS.scan_window(x, dt, bm, cm, a_t, d_skip, s0, lens)
    st, outs = s0, []
    for t in range(10):
        yt, st = SS.scan_step(x[:, t], dt[:, t], bm[:, t], cm[:, t], a_t,
                              d_skip, st, t < lens)
        outs.append(yt)
    assert float(jnp.abs(s - st).max()) < 1e-5
    assert (np.asarray(st)[2] == np.asarray(s0)[2]).all()
    want = jnp.stack(outs, 1)
    assert float(jnp.abs(y[0] - want[0]).max()) < 1e-5
    assert float(jnp.abs(y[1, :4] - want[1, :4]).max()) < 1e-5


def test_twenty_query_heads_over_one_kv_head_through_the_page_kernel():
    """Interpret mode at the cell's group: 20 rows a product (not a
    multiple of 8), Hkv = 1, every row's list its whole table, one row
    idle."""
    rng = np.random.default_rng(0)
    b, g, d, bs, nb, w = 3, 20, 16, 8, 30, 6
    q = jnp.asarray(rng.normal(size=(b, 1, g, d)), jnp.float32)
    kp, vp = (jnp.asarray(rng.normal(size=(nb, 1, bs, d)), jnp.float32)
              for _ in range(2))
    ids = jnp.asarray(rng.integers(1, nb, (b, 1, w)), jnp.int32)
    toks = jnp.asarray([[41], [0], [8]], jnp.int32)
    got = pa.grouped_paged_attention_kernel(q, kp, vp, ids, toks, scale=0.25,
                                            interpret=True)
    want = SA.grouped_paged_decode_reference(q, kp, vp, ids, toks, 0.25)
    assert float(jnp.abs(got - want)[jnp.asarray([0, 2])].max()) < 1e-5
    assert float(jnp.abs(got[1]).sum()) == 0.0


# -------------------------------------------------------- planted faults
@pytest.mark.parametrize("fault", ["state_bf16", "snapshot", "snapshot_scan",
                                   "sign", "softplus", "norms"])
def test_a_fault_planted_in_the_program_moves_the_logits(config, tapped,
                                                         fault):
    """Each fault of benchmarks/tools/calibrate_jamba.py, served at the toy
    size in float32 behind a cached system prompt (one slot, so row 0 is
    the request): the logits of the request's last prefill window and of
    its decode steps leave the reference's by more than twice LOGIT_TOL.
    Sound they agree to 3e-6; the scan state rounded to bfloat16 between
    steps moves them by 9e-4 at the window and 2e-3 to 6e-3 in the decode
    steps, a snapshot restored without its conv state by 0.3 to 0.4,
    without its scan state by 0.4 to 2.5, the inner norms left out by 0.8 to
    1.8; A without its sign and dt without
    softplus give no number at all."""
    model, seen = tapped
    eng = _engine(model, max_batch=1)
    take_out = calibrate_jamba.plant(model, eng, fault)
    try:
        rng = np.random.default_rng(11)
        doc = rng.integers(1, 256, 48)
        _serve(eng, [np.concatenate([doc, doc[:3]])], (2,))
        jax.effects_barrier()
        del seen[:]
        ask = np.concatenate([doc, rng.integers(1, 256, 7)])
        h, = _serve(eng, [ask], (12,))
        jax.effects_barrier()
        assert eng.summary()["state_snapshots_restored_total"] == 1
    finally:
        take_out()
    toks = np.asarray(h.tokens)[:h.n_out]
    ref = _ref_logits(config, np.concatenate([ask, toks]))
    moved = [np.abs(lg[0] - ref[len(ask) - 1 + i]).max()
             for i, lg in enumerate(seen[:h.n_out])]
    assert not np.isfinite(moved).all() or max(moved) > 2 * LOGIT_TOL, moved


def test_an_altered_token_shows(config, model):
    h, = _serve(_engine(model), [np.arange(1, 40)], (12,))
    assert _gaps(config, h).max() <= GAP_TOL
    toks = np.asarray(h.tokens)[:h.n_out].copy()
    toks[5] = (toks[5] + 1) % 256
    ref = _ref_logits(config, np.concatenate([np.asarray(h.prompt), toks]))
    at = len(h.prompt) - 1 + np.arange(len(toks))
    assert (ref[at].max(-1) - ref[at, toks]).max() > 100 * GAP_TOL
