"""Flash-attention kernel parity: fwd + blockwise bwd vs XLA reference
(interpret mode on CPU; the driver exercises compiled mode on TPU)."""
import functools
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.attention import attention_reference


def _rand(b, s, h, d, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(b, s, h, d).astype(np.float32)) * 0.3,
            jnp.asarray(rng.randn(b, s, h, d).astype(np.float32)) * 0.3,
            jnp.asarray(rng.randn(b, s, h, d).astype(np.float32)) * 0.3)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("d", [128, 64])
def test_flash_forward_matches_reference(causal, d):
    q, k, v = _rand(2, 256, 2, d)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                          interpret=True)
    want = attention_reference(q, k, v, is_causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("d", [128, 64])
def test_flash_backward_matches_reference(causal, d):
    q, k, v = _rand(1, 256, 2, d, seed=1)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=128,
                                       block_k=128, interpret=True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, is_causal=causal) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"d{name} mismatch")


def test_flash_cross_attention_lengths():
    q, _, _ = _rand(1, 128, 2, 64, seed=2)
    _, k, v = _rand(1, 512, 2, 64, seed=3)
    out = flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)
    want = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_autotune_measured_selection(tmp_path, monkeypatch):
    """PHI-autotune analog (SURVEY §2.1 autotune row): measured tile
    selection, persistent cache hit on the second call."""
    import paddle_tpu as paddle
    from paddle_tpu.ops.pallas import autotune as at
    monkeypatch.setattr(at, "_CACHE_PATH", str(tmp_path / "at.json"))
    at._CACHE = None
    calls = {"n": 0}

    def bench_fn(cand):
        calls["n"] += 1
        import jax.numpy as jnp
        # pretend (512, 512) is fastest, (256,...) infeasible
        if cand[0] == 256:
            raise RuntimeError("vmem oom")
        import time as _t
        # large contrast so the selection is robust on a loaded CI core
        delay = 0.0 if cand == (512, 512) else 0.05

        def run():
            _t.sleep(delay)
            return jnp.zeros(())
        return run

    best = at.tune("k", (8, 512), [(1024, 512), (512, 512), (256, 512)],
                   bench_fn, iters=1)
    assert best == (512, 512)
    n_first = calls["n"]
    assert n_first >= 2                   # measured multiple candidates
    best2 = at.tune("k", (8, 512), [(1024, 512), (512, 512)], bench_fn)
    assert best2 == (512, 512)
    assert calls["n"] == n_first          # cache hit: no re-measure
    # cache file persisted
    at._CACHE = None
    assert at.tune("k", (8, 512), [], bench_fn) == (512, 512)


def test_flash_autotune_flag_wiring():
    """FLAGS_flash_autotune routes flash_attention through the tuner."""
    import paddle_tpu as paddle
    from paddle_tpu.ops.pallas import flash_attention as fa, autotune as at
    seen = {}

    orig = at.tune_flash_blocks
    at.tune_flash_blocks = \
        lambda *a: (seen.setdefault("a", a), (512, 512))[1]
    try:
        paddle.set_flags({"FLAGS_flash_autotune": True})
        q = jnp.zeros((1, 512, 2, 64), jnp.float32)
        fa.flash_attention(q, q, q, causal=True, interpret=True)  # interpret: no tune
        assert "a" not in seen
        try:
            fa.flash_attention(q, q, q, causal=True)
        except Exception:
            pass  # compiled pallas can't run on the CPU test backend;
            #      the tuner consult happens before lowering
        assert seen["a"][1] == 512        # s_q reached the tuner
    finally:
        at.tune_flash_blocks = orig
        paddle.set_flags({"FLAGS_flash_autotune": False})


def test_tune_in_step_measures_full_step_and_caches(tmp_path, monkeypatch):
    """In-context autotune (VERDICT r2 #8): candidates are timed through a
    caller-supplied FULL step under override_blocks, the winner is the
    end-to-end-fastest (not the isolated-kernel-fastest), and it persists
    in the same cache tune() uses."""
    import time
    from paddle_tpu.ops.pallas import autotune as at

    monkeypatch.setattr(at, "_CACHE_PATH", str(tmp_path / "cache.json"))
    at._CACHE = None

    seen = []

    def build_step():
        cand = at._OVERRIDE
        seen.append(cand)

        def run():
            # candidate (512, 512) is fastest END-TO-END; (1024, 1024)
            # would win an isolated benchmark (simulated inversion)
            time.sleep({(1024, 1024): 0.03, (512, 512): 0.005,
                        (256, 256): 0.02}[cand])
            import jax.numpy as jnp
            return jnp.zeros(())

        return run

    got = at.tune_in_step("flash_step_test", (1, 2, 3),
                          [(1024, 1024), (512, 512), (256, 256)], build_step)
    assert got == (512, 512), got
    assert set(seen) == {(1024, 1024), (512, 512), (256, 256)}
    # cached: a second call must NOT rebuild anything
    seen.clear()
    got2 = at.tune_in_step("flash_step_test", (1, 2, 3),
                           [(1024, 1024)], build_step)
    assert got2 == (512, 512) and not seen


def test_override_blocks_reaches_flash(monkeypatch):
    """flash_attention honors the tuner's override at trace time."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import autotune as at
    from paddle_tpu.ops.pallas import flash_attention as fa

    q = jnp.zeros((1, 64, 2, 8), jnp.float32)
    with at.override_blocks(4, 4):
        out = fa.flash_attention(q, q, q, causal=True)
        assert out.shape == q.shape   # reference fallback ran (tiles < 8)


@pytest.mark.parametrize("kv_len", [197, 130, 256])
def test_flash_kv_len_padding_mask(kv_len):
    """kv_len masks zero-padded key rows: fwd AND grads must match the
    reference computed on the UNPADDED arrays (the ViT-197 path)."""
    s_pad = 256
    q, k, v = _rand(2, s_pad, 2, 64, seed=3)

    def f_flash(q, k, v):
        out = flash_attention(q, k, v, block_q=128, block_k=128,
                              interpret=True, kv_len=kv_len)
        return jnp.sum(out[:, :kv_len] ** 2)

    def f_ref(q, k, v):
        out = attention_reference(q[:, :kv_len], k[:, :kv_len], v[:, :kv_len],
                                  scale=1.0 / np.sqrt(64))
        return jnp.sum(out ** 2)

    np.testing.assert_allclose(float(f_flash(q, k, v)), float(f_ref(q, k, v)),
                               rtol=2e-4)
    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        # valid rows match; padded rows of dk/dv are exactly zero
        np.testing.assert_allclose(np.asarray(gf[:, :kv_len]),
                                   np.asarray(gr[:, :kv_len]),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"d{name} valid-row mismatch")
        if name in "kv" and kv_len < s_pad:
            assert float(jnp.abs(gf[:, kv_len:]).max()) == 0.0, \
                f"d{name} padded rows must be zero"


def test_functional_attention_padded_flash_route(monkeypatch):
    """functional_attention at an odd S >= 512 routes through the padded
    flash kernel and matches the reference (interpret-mode check). Shorter
    odd sequences (e.g. ViT's 197) stay on the XLA path — measured faster
    at that scale."""
    import paddle_tpu.ops.attention as A
    q, k, v = _rand(1, 520, 1, 64, seed=4)
    want = attention_reference(q, k, v)
    # force the pallas predicate on, interpret via monkeypatched flash
    monkeypatch.setenv("PADDLE_TPU_FLASH", "1")
    import paddle_tpu.ops.pallas.flash_attention as FA
    orig = FA.flash_attention
    calls = []

    def interp_flash(*a, **kw):
        calls.append(kw.get("kv_len"))
        kw["interpret"] = True
        return orig(*a, **kw)

    monkeypatch.setattr(FA, "flash_attention", interp_flash)
    got = A.functional_attention(q, k, v)
    assert calls == [520], f"padded flash route not taken: {calls}"
    assert got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ----------------------------------------- token-major operands (PR 37)
NH_P = 2        # heads of 128 in the packed projection of the tests below


def _packed(b=2, s=256, nh=NH_P, seed=7):
    """A packed [B, S, 3 nh 128] projection and a cotangent for o."""
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(b, s, 3 * nh * 128).astype(np.float32))
            * 0.3,
            jnp.asarray(rng.randn(b, s, nh * 128).astype(np.float32)))


def _split(qkv, nh=NH_P):
    """q, k, v [B, S, nh, 128] as the model's layout packs them: q heads,
    then k heads, then v heads."""
    b, s, _ = qkv.shape
    return [x.reshape(b, s, nh, 128) for x in jnp.split(qkv, 3, axis=-1)]


def _qkv_value_and_grad(attend, qkv, cot):
    """sum(o * cot) and its gradient in the packed array, of an `attend`
    that maps q, k, v [B, S, nh, 128] (or the packed array) to o."""
    def f(x):
        return jnp.sum(attend(x).reshape(cot.shape) * cot)
    return jax.value_and_grad(f)(qkv)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_qkv_matches_reference(causal):
    """The packed-operand entry: q, k, v are three views of ONE array and
    the gradient comes back as one array, forward and backward as the
    plain reference on the split heads."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_qkv
    qkv, cot = _packed()
    got, g_got = _qkv_value_and_grad(
        lambda x: flash_attention_qkv(x, NH_P, causal=causal, block_q=128,
                                      block_k=128, interpret=True), qkv, cot)
    want, g_want = _qkv_value_and_grad(
        lambda x: attention_reference(*_split(x), is_causal=causal),
        qkv, cot)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-4)
    assert g_got.shape == qkv.shape
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_qkv_bit_equal_to_three_arrays(causal):
    """One walk for both forms of the operands: the packed array read
    through lane-block offsets gives the bits three arrays give."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_qkv
    qkv, cot = _packed(seed=8)
    kw = dict(causal=causal, block_q=128, block_k=128, interpret=True)
    one, g_one = _qkv_value_and_grad(
        lambda x: flash_attention_qkv(x, NH_P, **kw), qkv, cot)
    three, g_three = _qkv_value_and_grad(
        lambda x: flash_attention(*_split(x), **kw), qkv, cot)
    assert float(one) == float(three)
    np.testing.assert_array_equal(np.asarray(g_one), np.asarray(g_three))


def test_flash_qkv_kv_len_inside_the_last_block():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_qkv
    qkv, cot = _packed(seed=9)
    kv_len = 200
    keep = (jnp.arange(256) < kv_len)[None, :, None]
    got, g_got = _qkv_value_and_grad(
        lambda x: flash_attention_qkv(x, NH_P, block_q=128, block_k=128,
                                      interpret=True, kv_len=kv_len)
        * keep, qkv, cot)

    def ref(x):
        q, k, v = (t[:, :kv_len] for t in _split(x))
        out = attention_reference(q, k, v)
        return jnp.pad(out, [(0, 0), (0, 256 - kv_len), (0, 0), (0, 0)])

    want, g_want = _qkv_value_and_grad(ref, qkv, cot)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-4)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want),
                               rtol=2e-3, atol=2e-3)
    # masked keys and values get exactly nothing; their queries do
    dq, dk, dv = jnp.split(g_got, 3, axis=-1)
    assert float(jnp.abs(dk[:, kv_len:]).max()) == 0.0
    assert float(jnp.abs(dv[:, kv_len:]).max()) == 0.0


def test_flash_qkv_refuses_a_width_that_is_not_three_times_the_heads():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_qkv
    qkv, _ = _packed()
    with pytest.raises(ValueError, match="heads"):
        flash_attention_qkv(qkv, NH_P + 1, interpret=True)


def test_flash_qkv_blocks_under_a_tile_take_the_reference():
    """The tuner's override reaches the packed entry too; blocks too small
    for a tile fall back to the plain form on the split heads."""
    from paddle_tpu.ops.pallas import autotune as at
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_qkv
    qkv, _ = _packed(b=1, s=64)
    with at.override_blocks(4, 4):
        out = flash_attention_qkv(qkv, NH_P, causal=True)
    want = attention_reference(*_split(qkv), is_causal=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(want).reshape(out.shape),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("d", [80, 64, 192, 256])
def test_flash_padded_heads_batches_and_gradients(d):
    """Heads under the lanes (cell 3's 80, BERT's 64) or over them (192,
    padded to 256; 256) are padded to whole lanes and laid head-major, one
    head a row of the kernels' batch and as wide as it comes: three heads
    in two batch rows, gradients back in the caller's shape."""
    q, k, v = _rand(2, 256, 3, d, seed=31)
    cot = jnp.asarray(np.random.RandomState(32).randn(*q.shape)
                      .astype(np.float32))

    def loss(attend):
        return lambda *a: jnp.sum(attend(*a) * cot)

    flash = loss(lambda *a: flash_attention(*a, causal=True, block_q=128,
                                            block_k=128, interpret=True))
    ref = loss(lambda *a: attention_reference(*a, is_causal=True))
    np.testing.assert_allclose(float(flash(q, k, v)), float(ref(q, k, v)),
                               rtol=2e-4)
    for gf, gr, name in zip(jax.grad(flash, (0, 1, 2))(q, k, v),
                            jax.grad(ref, (0, 1, 2))(q, k, v), "qkv"):
        assert gf.shape == (2, 256, 3, d)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"d{name} mismatch")


def test_flash_cross_attention_gradients():
    """s_q != s_k, not causal: dq has the queries' length, dk and dv the
    keys'."""
    q, _, _ = _rand(2, 128, 3, 64, seed=33)
    _, k, v = _rand(2, 384, 3, 64, seed=34)
    cot = jnp.asarray(np.random.RandomState(35).randn(*q.shape)
                      .astype(np.float32))
    flash = lambda *a: jnp.sum(flash_attention(
        *a, block_q=128, block_k=128, interpret=True) * cot)
    ref = lambda *a: jnp.sum(attention_reference(*a) * cot)
    for gf, gr, name in zip(jax.grad(flash, (0, 1, 2))(q, k, v),
                            jax.grad(ref, (0, 1, 2))(q, k, v), "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("model", ["layered", "stacked"])
def test_gpt_training_step_reads_heads_from_the_packed_projection(
        monkeypatch, model):
    """Heads of 128 on a TPU with no mp or sp axis: GPT's training
    attention, layered or stacked, hands the projection to the kernels
    whole, and no environment variable chooses it. (The CPU stands in for
    the chip: the gate is told it sees one, the kernels run interpreted.)"""
    import paddle_tpu as paddle
    import paddle_tpu.ops.attention as A
    import paddle_tpu.ops.pallas.flash_attention as FA
    from paddle_tpu.models import GPTForCausalLM, gpt_config
    from paddle_tpu.models.gpt_stacked import GPTStackedForCausalLM
    for name in ("PADDLE_TPU_FLASH", "PADDLE_TPU_FLASH_BQ",
                 "PADDLE_TPU_FLASH_BK"):
        monkeypatch.delenv(name, raising=False)
    monkeypatch.setattr(A, "on_tpu", lambda: True)
    calls = []
    orig = FA.flash_attention_qkv

    def spy(qkv, num_heads, **kw):
        calls.append((tuple(qkv.shape), num_heads))
        return orig(qkv, num_heads, interpret=True, **kw)

    monkeypatch.setattr(FA, "flash_attention_qkv", spy)
    paddle.seed(0)
    cfg = gpt_config("gpt3-125m", hidden_size=256, num_layers=1,
                     num_heads=2, vocab_size=128,
                     max_position_embeddings=128)
    assert cfg.head_dim == 128
    m = GPTForCausalLM(cfg)
    if model == "stacked":
        m = GPTStackedForCausalLM.from_layered(m)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, 128, (2, 128)).astype("int32"))
    lbl = paddle.to_tensor(rng.randint(0, 128, (2, 128)).astype("int64"))
    loss = m.loss(ids, lbl)
    loss.backward()
    assert calls == [((2, 128, 3 * 256), 2)], calls
    assert np.isfinite(float(loss.numpy())) and float(loss.numpy()) > 0
    w = m.qkv_w if model == "stacked" else m.gpt.h[0].attn.qkv.weight
    g = w.grad
    assert g is not None and np.isfinite(np.asarray(g.numpy())).all()
    assert float(np.abs(np.asarray(g.numpy())).max()) > 0


def test_functional_qkv_attention_splits_where_the_kernel_does_not_run(
        monkeypatch):
    """The one gate of the packed route (ops/attention.py): heads off the
    128 lanes, or a host with no TPU, split q, k, v through the caller's
    constraint and take `functional_attention`; where it opens, the same
    numbers come back from the kernels."""
    import paddle_tpu.ops.attention as A
    import paddle_tpu.ops.pallas.flash_attention as FA
    monkeypatch.delenv("PADDLE_TPU_FLASH", raising=False)
    qkv, _ = _packed()
    seen = []

    def constrain(x):
        seen.append(tuple(x.shape))
        return x

    want = A.functional_qkv_attention(qkv, NH_P, 128, is_causal=True,
                                      constrain=constrain)
    assert seen == [(2, 256, NH_P, 128)] * 3 and want.shape == seen[0]
    monkeypatch.setattr(A, "on_tpu", lambda: True)
    monkeypatch.setattr(FA, "flash_attention_qkv", functools.partial(
        FA.flash_attention_qkv, interpret=True))
    got = A.functional_qkv_attention(qkv, NH_P, 128, is_causal=True,
                                     constrain=constrain)
    assert len(seen) == 3, "the packed route splits nothing"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    monkeypatch.setattr(FA, "flash_attention", functools.partial(
        FA.flash_attention, interpret=True))
    halves = A.functional_qkv_attention(qkv, 2 * NH_P, 64, is_causal=True,
                                        constrain=constrain)
    assert len(seen) == 6 and halves.shape == (2, 256, 2 * NH_P, 64)


def test_flash_kv_len_nonpositive_rejected():
    """kv_len <= 0 would mask every key column and silently return a uniform
    average of V (advisor r3 finding) — must raise instead."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_qkv
    q, k, v = _rand(1, 128, 2, 64)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, kv_len=0, interpret=True)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, kv_len=-3, interpret=True)
    with pytest.raises(ValueError):
        flash_attention_qkv(_packed(b=1, s=128)[0], NH_P, kv_len=0,
                            interpret=True)


# ------------------------------------------------- causal tile plan (PR 32)
def _masked_reference(q, k, v, kv_len=None):
    """Causal attention by the kernel's origin rule (query r sees key c
    when r >= c, both counted from 0, whatever s_q and s_k), keys from
    kv_len on masked, as an explicit mask over the plain reference."""
    s_q, s_k = q.shape[1], k.shape[1]
    r, c = np.arange(s_q)[:, None], np.arange(s_k)[None, :]
    keep = (r >= c) & (c < (s_k if kv_len is None else kv_len))
    return attention_reference(q, k, v, mask=jnp.asarray(keep)[None, None])


def _assert_causal_parity(q, k, v, block_q, block_k, kv_len=None):
    """Forward and q/k/v gradients of the causal kernel against the masked
    reference, under a cotangent that weighs every output element."""
    cot = jnp.asarray(np.random.RandomState(11).randn(*q.shape)
                      .astype(np.float32))

    def f_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block_q,
                               block_k=block_k, interpret=True, kv_len=kv_len)

    def f_ref(q, k, v):
        return _masked_reference(q, k, v, kv_len)

    np.testing.assert_allclose(np.asarray(f_flash(q, k, v)),
                               np.asarray(f_ref(q, k, v)),
                               rtol=2e-4, atol=2e-4)
    g_flash = jax.grad(lambda *a: jnp.sum(f_flash(*a) * cot), (0, 1, 2))(
        q, k, v)
    g_ref = jax.grad(lambda *a: jnp.sum(f_ref(*a) * cot), (0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"d{name} mismatch")
    return g_flash


@pytest.mark.parametrize("d", [128, 80])
@pytest.mark.parametrize("s,block", [(512, 256), (1024, 512)])
def test_flash_causal_subtiled_matches_reference(s, block, d):
    """Blocks the diagonal crosses are worked in strips of 128 rows (two a
    block at 256, four at 512; two blocks a side); d = 80 is cell 3's
    head, padded to the lanes. On these shapes the masked reference is the
    plain causal one."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    assert fa.sub_tile(block, block) == 128
    q, k, v = _rand(1, s, 2, d, seed=21)
    _assert_causal_parity(q, k, v, block, block)
    np.testing.assert_array_equal(
        np.asarray(_masked_reference(q, k, v)),
        np.asarray(attention_reference(q, k, v, is_causal=True)))


@pytest.mark.parametrize("block_q,block_k", [(256, 128), (128, 256)])
def test_flash_causal_unequal_blocks_keep_masked_form(block_q, block_k):
    """bq != bk: the diagonal enters a block anywhere, so a crossed block
    is worked whole under its mask, and the numbers are the same."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    assert fa.sub_tile(block_q, block_k) is None
    q, k, v = _rand(1, 512, 2, 64, seed=22)
    _assert_causal_parity(q, k, v, block_q, block_k)


def test_flash_causal_with_kv_len_inside_last_block():
    """causal and a kv_len that cuts the last block: both masks hold, and
    the padded keys get exactly zero dk / dv."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    kv_len = 400
    assert fa.sub_tile(256, 256, kv_len) is None
    q, k, v = _rand(1, 512, 2, 64, seed=23)
    _, dk, dv = _assert_causal_parity(q, k, v, 256, 256, kv_len=kv_len)
    assert float(jnp.abs(dk[:, kv_len:]).max()) == 0.0
    assert float(jnp.abs(dv[:, kv_len:]).max()) == 0.0


@pytest.mark.parametrize("s_q,s_k", [(256, 512), (512, 256)])
def test_flash_causal_unequal_lengths_keep_origin_rule(s_q, s_k):
    """s_q != s_k means what it meant before the tile plan: rows and
    columns both count from 0 (not the reference's bottom-right rule)."""
    q, _, _ = _rand(1, s_q, 2, 64, seed=24)
    _, k, v = _rand(1, s_k, 2, 64, seed=25)
    _assert_causal_parity(q, k, v, 256, 256)


def _plan_pairs(s_q, s_k, bq, bk):
    """The (query, key) pairs the kernels multiply under the plan, as a
    count per pair, and the pairs whose scores go through a causal mask."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    done = np.zeros((s_q, s_k), int)
    masked = np.zeros((s_q, s_k), bool)
    t = fa.sub_tile(bq, bk)
    for qi in range(s_q // bq):
        for ki in range(s_k // bk):
            needed, full = fa._block_class(qi, ki, bq, bk)
            if not needed:
                continue
            strips = [(0, bq, bk)] if full or t is None \
                else fa._crossed_strips(bq, t)
            for r0, rn, cn in strips:
                rows = slice(qi * bq + r0, qi * bq + r0 + rn)
                done[rows, ki * bk:ki * bk + cn] += 1
                if not full:    # whole block, or the strip's square tail
                    first = 0 if t is None else cn - rn
                    masked[rows, ki * bk + first:ki * bk + cn] = True
    return done, masked


@pytest.mark.parametrize("s_q,s_k,bq,bk", [
    (2048, 2048, 1024, 1024), (1024, 1024, 256, 256), (512, 512, 256, 128),
    (512, 1024, 256, 256), (1024, 512, 512, 512), (768, 768, 384, 384)])
def test_causal_plan_covers_the_diagonal_once(s_q, s_k, bq, bk):
    """Every pair under the diagonal is multiplied exactly once, every pair
    left unmasked lies under it, and `causal_work` counts this plan."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    done, masked = _plan_pairs(s_q, s_k, bq, bk)
    under = np.arange(s_q)[:, None] >= np.arange(s_k)[None, :]
    assert done.max() == 1
    assert (done[under] == 1).all()
    assert under[(done == 1) & ~masked].all()
    assert fa.causal_work(s_q, s_k, bq, bk) == (done.sum(), under.sum())


def test_causal_work_at_the_training_shape():
    """S = 2048 at the default blocks: the kernels multiply at most 1.13
    times the pairs under the diagonal (1.5 with whole crossed blocks,
    which is what a kv_len inside the block still takes); a non-causal
    call multiplies what it needs."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    done, needed = fa.causal_work(2048, 2048, fa.DEFAULT_BQ, fa.DEFAULT_BK)
    assert needed == 2048 * 2049 // 2
    assert 1.0 <= done / needed <= 1.13
    whole, _ = fa.causal_work(2048, 2048, fa.DEFAULT_BQ, fa.DEFAULT_BK,
                              kv_len=2047)
    assert whole == 3 * 1024 * 1024
    assert 1.49 < whole / needed < 1.51
    assert fa.causal_work(2048, 2048, 1024, 1024, causal=False) == \
        (2048 * 2048, 2048 * 2048)
    assert fa.causal_work(256, 256, 128, 128, causal=False, kv_len=197) == \
        (256 * 256, 256 * 197)


@pytest.mark.parametrize("bq,bk", [(128, 128), (256, 128), (128, 256),
                                   (384, 128)])
def test_causal_block_classing_matches_a_brute_force_mask(bq, bk):
    """skipped / full / crossed of every (qi, ki) of a 4 x 4 grid."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    under = np.arange(4 * bq)[:, None] >= np.arange(4 * bk)[None, :]
    for qi in range(4):
        for ki in range(4):
            blk = under[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
            needed, full = fa._block_class(qi, ki, bq, bk)
            assert needed == bool(blk.any()), (qi, ki)
            assert full == bool(blk.all()), (qi, ki)
