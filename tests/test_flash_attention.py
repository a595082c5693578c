"""Flash-attention kernel parity: fwd + blockwise bwd vs XLA reference
(interpret mode on CPU; the driver exercises compiled mode on TPU)."""
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


class TestPackedFlash:
    """flash_attention_packed: [B, S, nh*128] layout, in-kernel head loop."""

    def _qkv(self, B=2, S=256, NH=2, HD=128, seed=7):
        rng = np.random.RandomState(seed)
        H = NH * HD
        mk = lambda: jnp.asarray(rng.randn(B, S, H).astype(np.float32) * 0.3)
        return mk(), mk(), mk(), NH, HD

    def _ref(self, q, k, v, nh, hd, causal, kv_len=None):
        B, S, H = q.shape
        q4 = q.reshape(B, S, nh, hd)
        k4 = k.reshape(B, S, nh, hd)
        v4 = v.reshape(B, S, nh, hd)
        if kv_len is not None:
            k4, v4 = k4[:, :kv_len], v4[:, :kv_len]
        return attention_reference(q4, k4, v4, is_causal=causal,
                                   scale=1.0 / np.sqrt(hd)).reshape(B, S, H)

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_and_grads(self, causal):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention_packed
        q, k, v, NH, HD = self._qkv()

        def lf(q, k, v):
            return jnp.sum(flash_attention_packed(
                q, k, v, NH, causal=causal, block_q=128, block_k=128,
                interpret=True) ** 2)

        def lr(q, k, v):
            return jnp.sum(self._ref(q, k, v, NH, HD, causal) ** 2)

        np.testing.assert_allclose(float(lf(q, k, v)), float(lr(q, k, v)),
                                   rtol=2e-4)
        gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, c, nm in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       rtol=2e-3, atol=2e-3,
                                       err_msg=f"d{nm} causal={causal}")

    def test_kv_len(self):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention_packed
        q, k, v, NH, HD = self._qkv()
        out = flash_attention_packed(q, k, v, NH, block_q=128, block_k=128,
                                     interpret=True, kv_len=200)
        want = self._ref(q, k, v, NH, HD, False, kv_len=200)
        np.testing.assert_allclose(np.asarray(out[:, :200]),
                                   np.asarray(want[:, :200]),
                                   rtol=2e-4, atol=2e-4)

    def test_head_dim_fallback(self):
        # hd != 128 falls back to the 4-D kernel path (reference fallback
        # on CPU since tiles degrade) — shape contract holds
        from paddle_tpu.ops.pallas.flash_attention import flash_attention_packed
        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(1, 64, 2 * 64).astype(np.float32))
        out = flash_attention_packed(q, q, q, 2, interpret=True)
        assert out.shape == q.shape

    def test_gpt_routes_through_packed(self, monkeypatch):
        """PADDLE_TPU_FLASH_PACKED=1 routes GPT training attention through
        the packed kernel (interpret-mode, tiny config)."""
        monkeypatch.setenv("PADDLE_TPU_FLASH_PACKED", "1")
        # the platform gate correctly refuses CPU — stub it for the
        # interpret-mode routing check
        import paddle_tpu.models.gpt as G
        monkeypatch.setattr(G, "_use_packed_flash", lambda: True)
        import paddle_tpu.ops.pallas.flash_attention as FA
        calls = []
        orig = FA.flash_attention_packed

        def spy(*a, **kw):
            calls.append(a[3] if len(a) > 3 else kw.get("num_heads"))
            kw["interpret"] = True
            return orig(*a, **kw)

        monkeypatch.setattr(FA, "flash_attention_packed", spy)
        import numpy as np_
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        from paddle_tpu.models import GPTForCausalLM, gpt_config
        paddle.seed(0)
        cfg = gpt_config("gpt3-125m", hidden_size=256, num_layers=1,
                         num_heads=2, vocab_size=128,
                         max_position_embeddings=128)
        assert cfg.head_dim == 128
        m = GPTForCausalLM(cfg)
        ids = paddle.to_tensor(np_.random.randint(0, 128, (1, 128)).astype("int32"))
        lbl = paddle.to_tensor(np_.random.randint(0, 128, (1, 128)).astype("int64"))
        loss = m.loss(ids, lbl)
        loss.backward()
        assert calls, "packed kernel was not routed to"
        assert float(loss.numpy()) > 0 and np_.isfinite(float(loss.numpy()))

def test_flash_save_transposed_grad_parity():
    """PADDLE_TPU_FLASH_SAVE_T residual path (head-major residuals reused in
    bwd) must produce the same gradients as the default recompute-transpose
    path (advisor r3 finding: this opt-in had no coverage)."""
    q, k, v = _rand(2, 256, 2, 64, seed=7)

    def loss(st):
        def f(q, k, v):
            out = flash_attention(q, k, v, causal=True, block_q=128,
                                  block_k=128, interpret=True,
                                  save_transposed=st)
            return jnp.sum(out ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    g_def = loss(False)
    g_st = loss(True)
    for gd, gs, name in zip(g_def, g_st, "qkv"):
        np.testing.assert_allclose(np.asarray(gd), np.asarray(gs),
                                   rtol=1e-6, atol=1e-6,
                                   err_msg=f"d{name} save_transposed mismatch")


def test_flash_kv_len_nonpositive_rejected():
    """kv_len <= 0 would mask every key column and silently return a uniform
    average of V (advisor r3 finding) — must raise instead."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_packed
    q, k, v = _rand(1, 128, 2, 64)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, kv_len=0, interpret=True)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, kv_len=-3, interpret=True)
    qp = jnp.reshape(q, (1, 128, 128))
    with pytest.raises(ValueError):
        flash_attention_packed(qp, qp, qp, num_heads=1, kv_len=0,
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
