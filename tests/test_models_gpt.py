"""GPT flagship model tests (analog of the reference's dygraph_to_static
model tests running real models, SURVEY §4 API/layer level)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import fleet
from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                               GPTPretrainingCriterion, gpt_config)


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    dist.set_mesh(None)
    fleet._fleet_state.update(initialized=False, strategy=None, hcg=None)


def _tiny(**kw):
    base = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                max_position_embeddings=64, intermediate_size=128)
    base.update(kw)
    return GPTConfig(**base)


def test_forward_backward_and_train():
    paddle.seed(0)
    cfg = _tiny()
    m = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion(cfg)
    ids = paddle.to_tensor(np.random.randint(0, 128, (2, 16)).astype("int64"))
    logits = m(ids)
    assert logits.shape == [2, 16, 128]
    loss = crit(logits, ids)
    loss.backward()
    assert np.isfinite(m.gpt.wte.weight.grad.numpy()).all()

    opt = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, opt, lambda a, b: crit(m(a), b))
    l0 = float(step(ids, ids))
    for _ in range(5):
        l = float(step(ids, ids))
    assert l < l0


def test_fused_lm_head_ce_matches_unfused():
    """model.loss (chunked fused linear+CE, no logits materialization) must
    equal forward()+criterion in value AND parameter gradients."""
    paddle.seed(0)
    cfg = _tiny()
    m = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion(cfg)
    ids = paddle.to_tensor(np.random.randint(0, 128, (2, 16)).astype("int64"))

    ref = crit(m(ids), ids)
    ref.backward()
    ref_grad = m.gpt.wte.weight.grad.numpy().copy()
    ref_val = float(ref)
    m.clear_gradients()

    fused = m.loss(ids, ids, chunk_size=8)
    fused.backward()
    np.testing.assert_allclose(float(fused), ref_val, rtol=1e-5)
    np.testing.assert_allclose(m.gpt.wte.weight.grad.numpy(), ref_grad,
                               rtol=2e-4, atol=2e-5)

    # masked variant + non-divisible chunk size falls back to a divisor
    mask = paddle.to_tensor(np.random.randint(0, 2, (2, 16)).astype("float32"))
    lm = m.loss(ids, ids, loss_mask=mask, chunk_size=7)
    assert np.isfinite(float(lm))


def test_adam_bf16_moments_train_and_dtype():
    import jax.numpy as jnp
    paddle.seed(0)
    cfg = _tiny()
    m = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=m.parameters(),
                                 moment_dtype="bfloat16")
    ids = paddle.to_tensor(np.random.randint(0, 128, (2, 16)).astype("int64"))
    step = paddle.jit.TrainStep(m, opt, lambda a, b: m.loss(a, b, chunk_size=8))
    l0 = float(step(ids, ids))
    for _ in range(5):
        l = float(step(ids, ids))
    assert l < l0
    assert step._opt_state[0]["moment1"].dtype == jnp.bfloat16


def test_generate_kv_cache_matches_full_forward():
    """Incremental decode with cache == argmax over full forward logits."""
    paddle.seed(1)
    m = GPTForCausalLM(_tiny())
    m.eval()
    ids = paddle.to_tensor(np.random.randint(0, 128, (1, 8)).astype("int64"))
    out = m.generate(ids, max_new_tokens=4)
    assert out.shape == [1, 12]
    # greedy reference: step the full forward
    cur = ids.numpy()
    for _ in range(4):
        logits = m(paddle.to_tensor(cur)).numpy()
        nxt = logits[:, -1].argmax(-1)[:, None]
        cur = np.concatenate([cur, nxt], axis=1)
    np.testing.assert_array_equal(out.numpy(), cur)


def test_recompute_parity():
    paddle.seed(2)
    ids = np.random.randint(0, 128, (2, 16)).astype("int64")

    def run(use_recompute):
        paddle.seed(3)
        m = GPTForCausalLM(_tiny(use_recompute=use_recompute))
        crit = GPTPretrainingCriterion()
        loss = crit(m(paddle.to_tensor(ids)), paddle.to_tensor(ids))
        loss.backward()
        return float(loss), m.gpt.h[0].attn.qkv.weight.grad.numpy()

    l1, g1 = run(False)
    l2, g2 = run(True)
    assert abs(l1 - l2) < 1e-5
    np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-6)


def test_hybrid_tp_parity_with_single_device():
    ids = np.random.randint(0, 128, (4, 16)).astype("int32")

    def run(mesh):
        paddle.seed(7)
        m = GPTForCausalLM(_tiny())
        crit = GPTPretrainingCriterion()
        opt = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=m.parameters())
        step = paddle.jit.TrainStep(m, opt, lambda a, b: crit(m(a), b),
                                    mesh=mesh, data_axes=("dp",))
        return [float(step(paddle.to_tensor(ids), paddle.to_tensor(ids)))
                for _ in range(3)]

    ref = run(None)
    st = fleet.DistributedStrategy()
    st.hybrid_configs = {"dp_degree": 2, "mp_degree": 4}
    fleet.init(strategy=st)
    got = run(dist.get_mesh())
    np.testing.assert_allclose(ref, got, rtol=3e-4)


def test_gpt_moe_blocks_train_and_aux_loss_flows():
    """GShard-pattern GPT-MoE: every 2nd block routed; router aux loss is
    part of loss() and gradients reach expert AND router weights."""
    paddle.seed(0)
    cfg = _tiny(moe_num_experts=4, moe_every_n_layers=2, moe_gate="gshard")
    m = GPTForCausalLM(cfg)
    moe_blocks = [b for b in m.gpt.h if b.is_moe]
    dense_blocks = [b for b in m.gpt.h if not b.is_moe]
    assert len(moe_blocks) == 1 and len(dense_blocks) == 1

    ids = paddle.to_tensor(np.random.randint(0, 128, (2, 16)).astype("int64"))
    loss = m.loss(ids, ids, chunk_size=8)
    assert m.gpt.last_aux_loss is not None
    # the criterion path carries the aux loss explicitly
    crit_loss = GPTPretrainingCriterion(cfg)(
        m(ids), ids, aux_loss=cfg.moe_aux_weight * m.gpt.last_aux_loss)
    np.testing.assert_allclose(float(crit_loss), float(loss), rtol=1e-4)
    loss.backward()
    mlp = moe_blocks[0].mlp
    assert np.isfinite(mlp.w1.grad.numpy()).all()
    assert np.isfinite(mlp.gate_weight.grad.numpy()).all()
    m.clear_gradients()

    # trains through the fused step too
    opt = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, opt, lambda a, b: m.loss(a, b, chunk_size=8))
    l0 = float(step(ids, ids))
    for _ in range(5):
        l = float(step(ids, ids))
    assert l < l0


def test_gpt_moe_capacity_factor_plumbs():
    """moe_capacity_factor reaches MoELayer and changes the expert-slot
    capacity; cf=1.0 (tight slots) still trains with finite grads."""
    from paddle_tpu.incubate.distributed.models.moe.moe_layer import \
        _capacity
    paddle.seed(0)
    cfg = _tiny(moe_num_experts=4, moe_every_n_layers=2,
                moe_capacity_factor=1.0)
    m = GPTForCausalLM(cfg)
    mlp = [b for b in m.gpt.h if b.is_moe][0].mlp
    assert mlp.capacity_factor == 1.0
    n_tok = 2 * 16
    assert _capacity(n_tok, 4, 2, 1.0) < _capacity(n_tok, 4, 2, 1.25)
    ids = paddle.to_tensor(np.random.randint(0, 128, (2, 16)).astype("int64"))
    loss = m.loss(ids, ids, chunk_size=8)
    loss.backward()
    assert np.isfinite(mlp.w1.grad.numpy()).all()


def test_gpt_moe_dryrun_on_ep_mesh():
    """Expert weights shard over the ep axis; the fused hybrid step
    compiles and runs on a dp x ep virtual mesh."""
    paddle.seed(0)
    mesh = dist.build_mesh({"dp": 2, "ep": 4})
    dist.set_mesh(mesh)
    cfg = _tiny(moe_num_experts=4, moe_every_n_layers=2)
    m = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, opt,
                                lambda a, b: m.loss(a, b, chunk_size=8),
                                mesh=mesh, data_axes=("dp",))
    ids = paddle.to_tensor(np.random.randint(0, 128, (4, 16)).astype("int64"))
    loss = step(ids, ids)
    assert np.isfinite(float(loss))


def test_gpt_moe_with_recompute_aux_flows():
    """Remat + MoE: aux loss is an explicit remat output (a tracer read off
    the layer after jax.checkpoint would leak)."""
    paddle.seed(0)
    cfg = _tiny(moe_num_experts=4, use_recompute=True)
    m = GPTForCausalLM(cfg)
    ids = paddle.to_tensor(np.random.randint(0, 128, (2, 16)).astype("int64"))
    loss = m.loss(ids, ids, chunk_size=8)
    loss.backward()
    moe = [b for b in m.gpt.h if b.is_moe][0]
    assert np.isfinite(moe.mlp.gate_weight.grad.numpy()).all()


def test_adam_int8_moments_train():
    """Blockwise 8-bit Adam state: ~2 bytes/param total moments; must
    still converge through the fused step."""
    import jax.numpy as jnp
    paddle.seed(0)
    m = GPTForCausalLM(_tiny())
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=m.parameters(),
                                 moment_dtype="int8")
    ids = paddle.to_tensor(np.random.randint(0, 128, (2, 16)).astype("int64"))
    step = paddle.jit.TrainStep(m, opt, lambda a, b: m.loss(a, b, chunk_size=8))
    l0 = float(step(ids, ids))
    for _ in range(6):
        l = float(step(ids, ids))
    assert l < l0
    assert step._opt_state[0]["moment1_q"].dtype == jnp.int8


def test_int8_moments_on_sharded_mesh():
    """int8 q/scale state arrays are not param-shaped: spec placement must
    replicate them instead of applying the param PartitionSpec."""
    paddle.seed(0)
    mesh = dist.build_mesh({"dp": 2, "mp": 4})
    dist.set_mesh(mesh)
    m = GPTForCausalLM(_tiny())
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=m.parameters(),
                                 moment_dtype="int8")
    step = paddle.jit.TrainStep(m, opt, lambda a, b: m.loss(a, b, chunk_size=8),
                                mesh=mesh, data_axes=("dp",))
    ids = paddle.to_tensor(np.random.randint(0, 128, (4, 16)).astype("int64"))
    assert np.isfinite(float(step(ids, ids)))


def test_adam_selective_q8_embedding_moments():
    """q8_param_fun: int8 moments for SELECTED params (embedding tables),
    bf16/f32 for the rest — what fits the S=8192 long-context config on one
    chip (bench.py r2 ladder). Mixed state kinds must train together."""
    import jax.numpy as jnp
    paddle.seed(0)
    m = GPTForCausalLM(_tiny())
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-3, parameters=m.parameters(),
        moment_dtype="bfloat16",
        q8_param_fun=lambda n: "wte" in n or "wpe" in n)
    ids = paddle.to_tensor(np.random.randint(0, 128, (2, 16)).astype("int64"))
    step = paddle.jit.TrainStep(m, opt, lambda a, b: m.loss(a, b, chunk_size=8))
    l0 = float(step(ids, ids))
    for _ in range(6):
        l = float(step(ids, ids))
    assert l < l0
    kinds = {}
    for name, st in zip(step._param_names, step._opt_state):
        kinds[name] = "q8" if "moment1_q" in st else str(st["moment1"].dtype)
    embs = [k for k in kinds if "wte" in k or "wpe" in k]
    others = [k for k in kinds if k not in embs]
    assert embs and all(kinds[k] == "q8" for k in embs), kinds
    assert others and all(kinds[k] == "bfloat16" for k in others), kinds


def test_generate_static_matches_growing_cache():
    """generate_static (fixed buffers + one compiled scan) must produce
    exactly the growing-cache generate() sequence for greedy decoding."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_config

    paddle.seed(0)
    cfg = gpt_config("gpt3-125m", hidden_size=128, num_layers=2, num_heads=2,
                     vocab_size=256, max_position_embeddings=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, 256, (2, 8)).astype("int64"))
    a = m.generate(ids, max_new_tokens=6).numpy()
    b = m.generate_static(ids, max_new_tokens=6).numpy()
    assert (a == b).all(), (a, b)
    # second call reuses the compiled runner (no retrace)
    c = m.generate_static(ids, max_new_tokens=6).numpy()
    assert (a == c).all()
    assert len(m._gen_static_cache) == 1


def test_sampling_top_k_top_p():
    """top-k restricts sampled ids to the k best; top-p to the nucleus;
    both paths (eager generate and compiled generate_static) honor them."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_config
    from paddle_tpu.models.gpt import sample_logits

    # unit level: a peaked distribution
    logits = jnp.asarray(np.array([[10.0, 9.0, 1.0, 0.0, -5.0]], np.float32))
    key = jax.random.PRNGKey(0)
    for i in range(5):
        tok = int(sample_logits(logits, jax.random.fold_in(key, i),
                                temperature=1.0, top_k=2)[0])
        assert tok in (0, 1), tok
    # top_p tiny -> only the argmax survives
    for i in range(3):
        tok = int(sample_logits(logits, jax.random.fold_in(key, i),
                                temperature=5.0, top_p=1e-6)[0])
        assert tok == 0, tok
    # greedy path unaffected by the knobs
    assert int(sample_logits(logits, key, temperature=0.0, top_k=1)[0]) == 0

    # model level: both generates run with the knobs and stay in-vocab
    paddle.seed(0)
    cfg = gpt_config("gpt3-125m", hidden_size=64, num_layers=1, num_heads=2,
                     vocab_size=32, max_position_embeddings=32)
    m = GPTForCausalLM(cfg)
    m.eval()
    ids = paddle.to_tensor(np.arange(4, dtype="int64").reshape(1, 4))
    a = m.generate(ids, max_new_tokens=4, temperature=0.9, top_k=5, seed=3)
    b = m.generate_static(ids, max_new_tokens=4, temperature=0.9, top_k=5,
                          top_p=0.9, seed=3)
    for o in (a, b):
        arr = o.numpy()
        assert arr.shape == (1, 8) and (arr >= 0).all() and (arr < 32).all()


def test_generate_eos_early_stop():
    """eos_token_id: eager generate stops early; static generate masks
    finished rows to EOS inside the compiled scan."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_config

    paddle.seed(0)
    cfg = gpt_config("gpt3-125m", hidden_size=64, num_layers=1, num_heads=2,
                     vocab_size=32, max_position_embeddings=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    ids = paddle.to_tensor(np.arange(8, dtype="int64").reshape(2, 4))
    # greedy reference without eos
    ref = m.generate(ids, max_new_tokens=8).numpy()
    # pick the token the model emits FIRST for row 0 as the eos id
    eos = int(ref[0, 4])
    a = m.generate(ids, max_new_tokens=8, eos_token_id=eos).numpy()
    b = m.generate_static(ids, max_new_tokens=8, eos_token_id=eos).numpy()
    # row 0 hits eos immediately: everything after is eos in both paths
    assert (a[0, 4:] == eos).all()
    assert (b[0, 4:] == eos).all()
    # rows that never emit eos match the unconstrained reference prefix
    if not (ref[1] == eos).any():
        n = a.shape[1]
        assert (a[1, :n] == ref[1, :n]).all()

    # single-row batch where the row hits eos immediately: the eager path
    # must actually BREAK (strictly shorter than the unconstrained run)
    one = paddle.to_tensor(ids.numpy()[:1])
    short = m.generate(one, max_new_tokens=8, eos_token_id=eos).numpy()
    assert short.shape[1] < ref.shape[1], short.shape
    assert short[0, -1] == eos


def test_generate_static_ragged_one_program():
    """Ragged serving (VERDICT r3 #7a): one compiled program serves any
    prompt length <= cap — per-row greedy parity with generate_static on
    the unpadded prompts, and a second lengths-pattern must NOT add a new
    executable to the cache."""
    import numpy as np
    paddle.seed(3)
    cfg = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
                    max_position_embeddings=64, intermediate_size=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    rng = np.random.RandomState(0)
    P_cap, new = 10, 6
    lens = [4, 10, 7]
    prompts = np.zeros((3, P_cap), np.int64)
    rows = []
    for i, ln in enumerate(lens):
        row = rng.randint(1, 96, (ln,))
        prompts[i, :ln] = row
        rows.append(row)

    out = m.generate_static_ragged(
        paddle.to_tensor(prompts), lens, max_new_tokens=new).numpy()
    assert out.shape == (3, P_cap + new)

    for i, ln in enumerate(lens):
        single = m.generate_static(
            paddle.to_tensor(rows[i][None]), max_new_tokens=new).numpy()[0]
        np.testing.assert_array_equal(out[i, P_cap:], single[ln:],
                                      err_msg=f"row {i} len {ln}")

    n_exec = len(m._gen_static_cache)
    lens2 = [9, 2, 5]
    prompts2 = np.zeros((3, P_cap), np.int64)
    for i, ln in enumerate(lens2):
        prompts2[i, :ln] = rng.randint(1, 96, (ln,))
    _ = m.generate_static_ragged(paddle.to_tensor(prompts2), lens2,
                                 max_new_tokens=new)
    assert len(m._gen_static_cache) == n_exec  # SAME executable reused


def test_generate_static_ragged_eos_and_sampling():
    import numpy as np
    paddle.seed(4)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1, num_heads=4,
                    max_position_embeddings=48, intermediate_size=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    prompts = np.zeros((2, 6), np.int64)
    prompts[0, :3] = [5, 6, 7]
    prompts[1, :6] = [8, 9, 10, 11, 12, 13]
    out = m.generate_static_ragged(
        paddle.to_tensor(prompts), [3, 6], max_new_tokens=5,
        temperature=0.8, top_k=8, seed=11).numpy()
    assert out.shape == (2, 11)
    assert np.all((out[:, 6:] >= 0) & (out[:, 6:] < 64))


def test_generate_static_int8_weights(monkeypatch):
    """Weight-only int8 decode (VERDICT r3 #7b): quantized payload
    generates near-greedy-parity output on a toy model and never NaNs."""
    import numpy as np
    monkeypatch.setenv("PADDLE_TPU_Q8_DECODE_MIN", "4096")  # toy-size gate
    paddle.seed(5)
    cfg = GPTConfig(vocab_size=96, hidden_size=128, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    intermediate_size=256)
    m = GPTForCausalLM(cfg)
    m.eval()
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(1, 96, (2, 8)).astype(np.int64))
    full = m.generate_static(ids, max_new_tokens=8).numpy()
    q8 = m.generate_static(ids, max_new_tokens=8, weight_dtype="int8").numpy()
    assert q8.shape == full.shape
    # per-channel int8 weights keep greedy decode mostly on-trajectory for
    # a toy model; exact parity is not the contract (weights ARE perturbed)
    agree = (q8[:, 8:] == full[:, 8:]).mean()
    assert agree >= 0.5, f"int8 decode diverged: agreement {agree}"
    # quantized payload is cached: second call must reuse it
    assert m._q8_decode_cache is m._decode_quantized_params()
    # a >=1M-param weight must actually be int8 in the payload
    assert any(q.dtype == np.int8 for q, _ in m._q8_decode_cache.values())


def test_generate_static_int8_kv_cache():
    """cache_dtype="int8" (VERDICT r4 #5 follow-on): the KV cache is stored
    as int8 codes + per-(pos,head) scales — attention reads half the HBM
    bytes per decode step. Greedy output must stay near-parity with the
    bf16 cache on a toy model (the cache IS perturbed by quantization, so
    exact parity is not the contract), and the factored-scale attention
    math must match explicit dequantization."""
    import numpy as np
    paddle.seed(7)
    cfg = GPTConfig(vocab_size=96, hidden_size=128, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    intermediate_size=256)
    m = GPTForCausalLM(cfg)
    m.eval()
    ids = paddle.to_tensor(
        np.random.RandomState(1).randint(1, 96, (2, 8)).astype(np.int64))
    full = m.generate_static(ids, max_new_tokens=8).numpy()
    c8 = m.generate_static(ids, max_new_tokens=8,
                           cache_dtype="int8").numpy()
    assert c8.shape == full.shape
    assert (c8[:, :8] == full[:, :8]).all()          # prompt passthrough
    agree = (c8[:, 8:] == full[:, 8:]).mean()
    assert agree >= 0.5, f"int8-cache decode diverged: agreement {agree}"
    # ragged variant composes with the int8 cache (one program, any len):
    # full-length rows must stay on the non-ragged greedy trajectory
    lens = [3, 8]
    r_full = m.generate_static_ragged(ids, lens, max_new_tokens=6).numpy()
    r_c8 = m.generate_static_ragged(ids, lens, max_new_tokens=6,
                                    cache_dtype="int8").numpy()
    assert r_c8.shape == r_full.shape
    assert (r_c8[1] == r_full[1]).mean() >= 0.75
    import pytest
    with pytest.raises(ValueError):
        m.generate_static(ids, max_new_tokens=2, cache_dtype="float64")


def test_generate_static_int8_weights_and_kv_compose(monkeypatch):
    """weight_dtype="int8" + cache_dtype="int8" together — the exact config
    of the bench ladder's decode-int8-b8 row: int8 GEMM weight streaming
    AND factored-scale int8 cache attention in one compiled program."""
    import numpy as np
    monkeypatch.setenv("PADDLE_TPU_Q8_DECODE_MIN", "4096")  # toy-size gate
    paddle.seed(11)
    cfg = GPTConfig(vocab_size=96, hidden_size=128, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    intermediate_size=256)
    m = GPTForCausalLM(cfg)
    m.eval()
    ids = paddle.to_tensor(
        np.random.RandomState(2).randint(1, 96, (2, 8)).astype(np.int64))
    full = m.generate_static(ids, max_new_tokens=8).numpy()
    both = m.generate_static(ids, max_new_tokens=8, weight_dtype="int8",
                             cache_dtype="int8").numpy()
    assert both.shape == full.shape
    assert (both[:, :8] == full[:, :8]).all()
    agree = (both[:, 8:] == full[:, 8:]).mean()
    assert agree >= 0.5, f"w8+c8 decode diverged: agreement {agree}"
    assert not np.isnan(both.astype(np.float64)).any()


def test_attention_q8_cache_matches_dequant():
    """attention_q8_cache's factored scales (q·cᵀ·s_k; (p·s_v)·c_v) must be
    numerically equivalent to attending over explicitly dequantized K/V."""
    import numpy as np
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import (attention_q8_cache, quantize_kv,
                                          dequantize_kv,
                                          attention_reference,
                                          static_cache_mask)
    rng = np.random.RandomState(3)
    B, L, H, D = 2, 16, 4, 32
    k = jnp.asarray(rng.randn(B, L, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, L, H, D).astype(np.float32))
    q = jnp.asarray(rng.randn(B, 1, H, D).astype(np.float32))
    kc, ks = quantize_kv(k)
    vc, vs = quantize_kv(v)
    # roundtrip error bound: symmetric int8 over head_dim rows
    kd = dequantize_kv(kc, ks, jnp.float32)
    rel = float(jnp.max(jnp.abs(kd - k)) / jnp.max(jnp.abs(k)))
    assert rel < 0.01, rel
    pos = jnp.int32(L - 1)
    mask = static_cache_mask(L, 1, pos)
    got = attention_q8_cache(q, kc, ks, vc, vs, mask)
    want = attention_reference(q, kd, dequantize_kv(vc, vs, jnp.float32),
                               mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-3)


def test_fused_small_param_update_parity(monkeypatch):
    """The fused multi-tensor optimizer apply (TrainStep) must produce
    numerically identical params/moments to the per-param loop — it is the
    same elementwise math on a concatenation."""
    import numpy as np
    from paddle_tpu.jit.train_step import TrainStep

    def build():
        paddle.seed(9)
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, max_position_embeddings=32,
                        intermediate_size=64)
        m = GPTForCausalLM(cfg)
        o = paddle.optimizer.AdamW(learning_rate=1e-3,
                                   parameters=m.parameters(),
                                   weight_decay=0.01)
        s = TrainStep(m, o, lambda a, b: m.loss(a, b, chunk_size=64))
        return m, s

    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 64, (4, 16)).astype("int32"))

    monkeypatch.setenv("PADDLE_TPU_FUSE_SMALL_UPDATES", "0")
    m0, s0 = build()
    l0 = [float(s0(ids, ids)) for _ in range(3)]

    monkeypatch.setenv("PADDLE_TPU_FUSE_SMALL_UPDATES", "262144")
    m1, s1 = build()
    l1 = [float(s1(ids, ids)) for _ in range(3)]

    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    for p0, p1 in zip(m0.parameters(), m1.parameters()):
        np.testing.assert_allclose(np.asarray(p0._data, np.float64),
                                   np.asarray(p1._data, np.float64),
                                   rtol=1e-6, atol=1e-7,
                                   err_msg=p0.name)


def test_fused_small_param_update_parity_momentum(monkeypatch):
    """Momentum joins the fused multi-tensor apply (the big customer is
    ResNet's 628 BN/bias updates): parity vs the per-param loop."""
    import numpy as np
    import paddle_tpu.nn as nn
    from paddle_tpu.jit.train_step import TrainStep

    def build():
        paddle.seed(2)
        m = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 8))
        o = paddle.optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                                      parameters=m.parameters(),
                                      weight_decay=0.001)
        ce = nn.MSELoss()
        s = TrainStep(m, o, lambda a, b: ce(m(a), b))
        return m, s

    x = paddle.to_tensor(np.random.RandomState(0)
                         .randn(8, 16).astype("float32"))
    y = paddle.to_tensor(np.random.RandomState(1)
                         .randn(8, 8).astype("float32"))
    monkeypatch.setenv("PADDLE_TPU_FUSE_SMALL_UPDATES", "0")
    m0, s0 = build()
    l0 = [float(s0(x, y)) for _ in range(3)]
    monkeypatch.setenv("PADDLE_TPU_FUSE_SMALL_UPDATES", "262144")
    m1, s1 = build()
    l1 = [float(s1(x, y)) for _ in range(3)]
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    for p0, p1 in zip(m0.parameters(), m1.parameters()):
        np.testing.assert_allclose(np.asarray(p0._data), np.asarray(p1._data),
                                   rtol=1e-6, atol=1e-7)


def test_generate_static_ragged_int8(monkeypatch):
    """Ragged serving composes with weight-only int8: one executable, any
    prompt length, quantized payload."""
    import numpy as np
    monkeypatch.setenv("PADDLE_TPU_Q8_DECODE_MIN", "4096")
    paddle.seed(7)
    cfg = GPTConfig(vocab_size=96, hidden_size=128, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    intermediate_size=256)
    m = GPTForCausalLM(cfg)
    m.eval()
    P_cap, new = 8, 6
    lens = [8, 3]
    prompts = np.zeros((2, P_cap), np.int64)
    rng = np.random.RandomState(0)
    for i, ln in enumerate(lens):
        prompts[i, :ln] = rng.randint(1, 96, (ln,))
    full = m.generate_static_ragged(paddle.to_tensor(prompts), lens,
                                    max_new_tokens=new).numpy()
    q8 = m.generate_static_ragged(paddle.to_tensor(prompts), lens,
                                  max_new_tokens=new,
                                  weight_dtype="int8").numpy()
    assert q8.shape == full.shape
    agree = (q8[:, P_cap:] == full[:, P_cap:]).mean()
    assert agree >= 0.5, f"int8 ragged diverged: {agree}"
    n_exec = len(m._gen_static_cache)
    lens2 = [5, 7]
    prompts2 = np.zeros((2, P_cap), np.int64)
    for i, ln in enumerate(lens2):
        prompts2[i, :ln] = rng.randint(1, 96, (ln,))
    _ = m.generate_static_ragged(paddle.to_tensor(prompts2), lens2,
                                 max_new_tokens=new, weight_dtype="int8")
    assert len(m._gen_static_cache) == n_exec   # same executable reused
