"""Fused attention ops.

The reference snapshot has only non-flash fused attention with O(S^2) memory
(paddle/fluid/operators/fused/fused_attention_op.cu, SURVEY §5.7) and no
sequence parallelism. Here attention is a first-class fused op: a Pallas
flash-attention kernel on TPU (paddle_tpu/ops/pallas/flash_attention.py) with
an XLA reference path everywhere else, both differentiable. Layout follows
the paddle convention [batch, seq, num_heads, head_dim].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, apply_op
from ..core import random as _random
from ..device import on_tpu
from ..distributed import mesh as _mesh


def _pool_shard(pool):
    """Pin a paged pool (or pool-shaped intermediate) to the serving
    head-sharding: [NB, bs, H, D] with H over mp (int8 scale pools
    [NB, bs, H] shard the same axis). No-op without a mesh or without
    an mp axis — the single-chip path is untouched. Under an mp mesh
    this is what keeps every pool scatter/gather SHARD-LOCAL: block
    index arithmetic only touches axis 0, heads never cross shards."""
    if pool.ndim == 4:
        return _mesh.shard_constraint(pool, None, None, "mp", None)
    if pool.ndim == 3:
        return _mesh.shard_constraint(pool, None, None, "mp")
    return pool


def _gathered_shard(view):
    """Pin a gathered [B, width, H, D] contiguous pool view to the same
    head-sharding as the pool it came from — the axis-0 block gather is
    shard-local by construction; this makes that choice explicit to the
    partitioner instead of hoping propagation picks it."""
    if view.ndim == 4:
        return _mesh.shard_constraint(view, "dp", None, "mp", None)
    if view.ndim == 3:
        return _mesh.shard_constraint(view, "dp", None, "mp")
    return view


def _use_pallas(q_shape, head_dim):
    import os
    force = os.environ.get("PADDLE_TPU_FLASH")  # "1"/"0" override for tuning
    if force == "0":
        return False
    if force != "1":   # unforced: require a TPU-class platform
        if not on_tpu():
            return False
    # MXU-friendly constraints (enforced even when forced — the override
    # opts into the KERNEL on a capable host, never into invalid shapes):
    # seq tiles into 128-row blocks; head_dim pads to the 128-lane boundary
    # inside the kernel wrapper. Measured on v5e: the kernel beats XLA's
    # attention ~1.5x at S=1024 d=64 even with the padding overhead.
    return head_dim % 8 == 0 and q_shape[1] % 128 == 0


_BSHD = ("dp", None, "mp", None)


def _flash(q, k, v, *, causal, scale, kv_len=None):
    """The flash kernel on [B, S, H, D] arrays. Batch rows and heads are
    independent, so under a mesh each (dp, mp) shard runs the kernel on
    its own rows and heads (`shard_kernel`: Mosaic kernels are never
    partitioned automatically)."""
    from .pallas.flash_attention import flash_attention

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               kv_len=kv_len)

    return _mesh.shard_kernel(kernel, (q, k, v), (_BSHD,) * 3, _BSHD)


def _flash_qkv(qkv, num_heads, *, causal, scale):
    """The flash kernel on the PACKED projection [B, S, 3 * heads * 128]:
    q, k, v are read as three views of the one array, o comes back
    [B, S, heads * 128] and the gradient as one packed array. The packed
    lanes are q, k and v heads in turn, no axis a mesh could split, so only
    the batch rows go over dp, each shard running its own as in `_flash`."""
    from .pallas.flash_attention import flash_attention_qkv

    def kernel(qkv):
        return flash_attention_qkv(qkv, num_heads, causal=causal, scale=scale)

    rows = ("dp", None, None)
    return _mesh.shard_kernel(kernel, (qkv,), (rows,), rows)


def attention_reference(q, k, v, mask=None, is_causal=False, scale=None,
                        dropout_p=0.0, dropout_key=None, score_dtype=None):
    """Reference jnp attention on [B, S, H, D]; fp32 softmax accumulation.

    score_dtype: dtype the S×S logit/probability arrays take in HBM.
    Default float32 (exact). Passing the model dtype (bf16) HALVES the
    dominant O(S²) memory traffic of this path — the QK dot still
    accumulates in f32 and the softmax max/sum run in f32; only the stored
    logits/probs round to bf16 (same numerics class as bf16 weights).
    Measured on v5e ViT-L/16 B=32: the f32 score arrays are ~320 MB/layer
    of traffic, the single largest non-matmul cost of the XLA path."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    dt = q.dtype
    import os
    if os.environ.get("PADDLE_TPU_SCORE_F32") == "1":
        # advisor r3: models hard-wire score_dtype=model-dtype for the
        # measured HBM win; this env reverts EVERY attention to exact f32
        # stored scores for convergence-sensitivity checks without code
        # changes (the Pallas kernels are unaffected — their scores are
        # f32-in-VMEM always)
        score_dtype = None
    sdt = jnp.dtype(score_dtype) if score_dtype is not None else jnp.float32
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    logits = (logits * scale).astype(sdt)
    neg = jnp.asarray(-1e30 if sdt == jnp.float32 else -3e38, sdt)
    if is_causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        cmask = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        logits = jnp.where(cmask, logits, neg)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, neg)
        else:
            logits = (logits.astype(jnp.float32)
                      + mask.astype(jnp.float32)).astype(sdt)
    if sdt == jnp.float32:
        probs = jax.nn.softmax(logits, axis=-1)
    else:
        m = jnp.max(logits.astype(jnp.float32), axis=-1, keepdims=True)
        p = jnp.exp(logits.astype(jnp.float32) - m).astype(sdt)
        l = jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True)
        probs = (p.astype(jnp.float32) / l).astype(sdt)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p),
                          jnp.zeros((), probs.dtype))
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(dt), v,
                      preferred_element_type=jnp.float32).astype(dt)


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, scale=None,
                                 score_dtype=None):
    """Eager entry point on Tensors.

    score_dtype (beyond-reference knob): dtype for the stored S×S
    logits/probs on the non-flash path — pass the model dtype (bf16) to
    halve the O(S²) HBM traffic; f32 accumulation is kept either way.
    Measured wins on v5e: ViT-L +5 MFU points, Swin +17% img/s,
    BERT +14% tok/s (those models set it internally)."""
    mask_arr = attn_mask._data if isinstance(attn_mask, Tensor) else attn_mask
    dk = _random.split_key() if (dropout_p > 0.0 and training) else None
    use_flash = (mask_arr is None and (dropout_p == 0.0 or not training)
                 and _use_pallas(tuple(query._data.shape), query._data.shape[-1]))

    if use_flash:
        def fn(q, k, v):
            return _flash(q, k, v, causal=is_causal, scale=scale)
        return apply_op("flash_attention", fn, [query, key, value])

    def fn(q, k, v):
        return attention_reference(q, k, v, mask=mask_arr, is_causal=is_causal,
                                   scale=scale, dropout_p=dropout_p if training else 0.0,
                                   dropout_key=dk, score_dtype=score_dtype)
    return apply_op("sdpa", fn, [query, key, value])


def functional_attention(q, k, v, *, is_causal=False, scale=None, mask=None,
                         score_dtype=None):
    """Pure-array attention for jitted model code: picks flash kernel on TPU,
    reference path elsewhere. Differentiable in both cases. An explicit mask
    (bool keep-mask or additive float, broadcastable to [B,H,Sq,Sk]) forces
    the reference path."""
    if mask is None and _use_pallas(tuple(q.shape), q.shape[-1]):
        return _flash(q, k, v, causal=is_causal, scale=scale)
    # Padded-flash path: self-attention with an odd sequence length
    # zero-pads q/k/v up to the 128-row block boundary and masks padded
    # KEYS inside the kernel (kv_len). Padded q rows compute garbage that
    # is sliced off; their cotangent is zero so dk/dv stay exact.
    # Threshold: measured on v5e, at ViT scale (S=197) the pad/transpose
    # overhead LOSES to XLA's O(S²) path (40% vs 48% MFU end-to-end), so
    # the route only opens where the S² term dominates (S >= 512).
    s = q.shape[1]
    pad = (-s) % 128
    if (mask is None and not is_causal and pad and s >= 512
            and q.shape[1] == k.shape[1]
            and _use_pallas((q.shape[0], s + pad) + tuple(q.shape[2:]),
                            q.shape[-1])):
        cfg = [(0, 0), (0, pad), (0, 0), (0, 0)]
        out = _flash(jnp.pad(q, cfg), jnp.pad(k, cfg), jnp.pad(v, cfg),
                     causal=False, scale=scale, kv_len=s)
        return out[:, :s]
    return attention_reference(q, k, v, mask=mask, is_causal=is_causal,
                               scale=scale, score_dtype=score_dtype)


def functional_qkv_attention(qkv, num_heads, head_dim, *, is_causal=False,
                             scale=None, constrain=lambda x: x):
    """Self-attention on the PACKED projection [B, S, 3 * heads * head_dim]
    (q heads, then k heads, then v heads), for jitted model code; returns
    [B, S, heads, head_dim]. Where the flash kernel runs, heads fill the
    128 lanes and no mp axis splits them, the projection goes to the
    kernels WHOLE and its gradient comes back as one array: no slice, pad
    or head-major copy is made either way (such passes overlap nothing, a
    TensorCore runs one fusion at a time; they were 7% of the GPT-3 1.3B
    step, PERF.md section 6, PR 37). Elsewhere q, k, v are split, each put
    through `constrain` (the caller's sharding constraint for a
    [B, S, heads, head_dim] array), and go to `functional_attention`."""
    b, s = qkv.shape[:2]
    if (head_dim == 128 and _mesh.mesh_axis_size("mp") <= 1
            and _use_pallas((b, s), head_dim)):
        out = _flash_qkv(qkv, num_heads, causal=is_causal, scale=scale)
        return out.reshape(b, s, num_heads, head_dim)
    qkv = qkv.reshape(b, s, 3, num_heads, head_dim)
    q, k, v = (constrain(qkv[:, :, i]) for i in range(3))
    return functional_attention(q, k, v, is_causal=is_causal, scale=scale)


# ----------------------------------------------------- static KV-cache ops
def static_cache_update(buf, new, pos):
    """Write `new` [B, s, H, D] into the fixed buffer [B, L_max, H, D] at
    row cursor `pos` (the CacheKV-workspace write shared by
    GPTForCausalLM.generate_static and incubate FusedMultiHeadAttention).

    Eager calls (concrete pos) raise on overflow; under jit the caller
    owns capacity (lax.dynamic_update_slice would silently clamp).

    Works for any rank >= 2 with the row cursor on axis 1 (the int8 cache
    path stores per-row scales in a rank-3 [B, L_max, H] buffer)."""
    import jax.core as _core
    from jax import lax
    if not isinstance(pos, _core.Tracer):
        p = int(pos)
        if p + new.shape[1] > buf.shape[1]:
            raise ValueError(
                f"static KV cache overflow: pos {p} + {new.shape[1]} new "
                f"rows > L_max {buf.shape[1]}")
    idx = (jnp.int32(0), pos.astype(jnp.int32)) + \
        (jnp.int32(0),) * (buf.ndim - 2)
    return lax.dynamic_update_slice(buf, new.astype(buf.dtype), idx)


# ------------------------------------------------ int8 KV-cache (serving)
def quantize_kv(new):
    """Symmetric per-(batch, position, head) int8 quantization of K/V rows.

    new [B, s, H, D] -> (codes int8 [B, s, H, D], scale f32 [B, s, H]); the
    scale spans the head_dim axis, so dequant is one fused multiply on the
    attention read. Serving analog of the reference's cache-quant path in
    fused_multi_transformer_op.cu (CacheKV int8 rows + per-row scales)."""
    f = new.astype(jnp.float32)
    amax = jnp.max(jnp.abs(f), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    codes = jnp.clip(jnp.round(f / scale[..., None]), -127, 127)
    return codes.astype(jnp.int8), scale


def dequantize_kv(codes, scale, dtype):
    """codes int8 [B, L, H, D] * scale [B, L, H] -> [B, L, H, D] `dtype`."""
    return (codes.astype(jnp.float32)
            * scale[..., None].astype(jnp.float32)).astype(dtype)


def attention_q8_cache(q, k_codes, k_scale, v_codes, v_scale, mask):
    """Decode attention reading an int8 KV cache WITHOUT dequantized
    buffers in HBM.

    The per-(pos,head) scales factor OUT of both contractions:
      q·(c_k·s_k)^T = (q·c_k^T)·s_k        (s_k is constant over head_dim)
      sum_k p_k·(s_v_k·c_v_k) = sum_k (p_k·s_v_k)·c_v_k
    so the big [B, L, H, D] operands enter their dots as bare int8->bf16
    converts (fused into the operand read by XLA — measured: the
    multiply-form dequant instead materializes full-width copies and is
    ~1.4x SLOWER than bf16 caches) and the scale multiplies land on the
    tiny [B, H, s, L] score arrays. Softmax runs in f32 as everywhere
    else. Serving analog of fused_multi_transformer_op.cu's CacheKV-int8
    mode."""
    dt = q.dtype
    att_scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_codes.astype(dt),
                        preferred_element_type=jnp.float32)
    ksT = jnp.transpose(k_scale, (0, 2, 1))[:, :, None, :]   # [B,H,1,L]
    logits = logits * (ksT * att_scale)
    logits = jnp.where(mask, logits, jnp.asarray(-1e30, jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    vsT = jnp.transpose(v_scale, (0, 2, 1))[:, :, None, :]
    probs = (probs * vsT).astype(dt)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v_codes.astype(dt),
                      preferred_element_type=jnp.float32).astype(dt)


def static_cache_update_q8(codes_buf, scale_buf, new, pos):
    """Quantize `new` K/V rows to int8 and write codes+scales at `pos`."""
    codes, scale = quantize_kv(new)
    return (static_cache_update(codes_buf, codes, pos),
            static_cache_update(scale_buf, scale, pos))


# ------------------------------------------------- paged KV cache (serving)
# Block-pool serving path (ISSUE 5; Ragged Paged Attention, arxiv
# 2604.15464): KV lives in a fixed [num_blocks, block, H, D] pool, each
# request owns a list of blocks named by an int32 block table, and ONE
# fixed-shape executable serves any mix of request lengths. Block 0 is the
# reserved TRASH block (inference/kv_cache.py) — table padding entries and
# out-of-budget writes land there, so the scatter updates below never need
# a mask and can never touch another request's blocks.

def paged_cache_write(pool, new, tables, lens):
    """Write one decode-step row per batch entry into its pool block.

    pool [NB, bs, H, D]; new [B, 1, H, D]; tables [B, MB] i32; lens [B]
    i32 = tokens already in each row's cache, so row b's new token lands at
    global position lens[b] → block tables[b, lens[b]//bs], offset
    lens[b]%bs. Rows past their table width clamp into their own last
    block (their outputs are already ignored by then); trash-table rows
    (dummy slots) write block 0."""
    nb, bs = pool.shape[0], pool.shape[1]
    li = lens.astype(jnp.int32)
    bidx = jnp.take_along_axis(tables.astype(jnp.int32),
                               (li // bs)[:, None], axis=1,
                               mode="clip")[:, 0]
    dest = bidx * bs + (li % bs)
    flat = pool.reshape((nb * bs,) + pool.shape[2:])
    flat = flat.at[dest].set(new[:, 0].astype(pool.dtype))
    return _pool_shard(flat.reshape(pool.shape))


def paged_prefill_write(pool, new, tables, start=None):
    """Write a whole (right-padded) prompt's K/V rows into pool blocks.

    new [B, S, H, D] holds the PADDED prompt projection; position p of row
    b goes to block tables[b, p//bs], offset p%bs. Padding columns beyond a
    row's allocated blocks hit table entries of 0 — the trash block — and
    padding columns inside the row's own reservation are plain garbage the
    attention masks exclude until decode overwrites them.

    `start` [B] int32 (prefix-cache suffix prefill, ISSUE 10) offsets row
    b's positions to start[b] + p — the suffix lands after the shared
    cached prefix. Padding positions past the TABLE WIDTH are routed to
    the trash block explicitly (clipping them into the last table entry
    would let a garbage pad column share a destination row with a real
    suffix column and scatter-order would decide who wins); positions
    can never reach the shared prefix blocks (start + p >= start >= the
    prefix end for all written columns)."""
    nb, bs = pool.shape[0], pool.shape[1]
    b, s = new.shape[0], new.shape[1]
    pos = jnp.arange(s, dtype=jnp.int32)[None, :]
    if start is not None:
        pos = pos + start.astype(jnp.int32)[:, None]
    slot = pos // bs
    bidx = jnp.take_along_axis(tables.astype(jnp.int32),
                               jnp.broadcast_to(slot, (b, s)),
                               axis=1, mode="clip")
    bidx = jnp.where(slot >= tables.shape[1], 0, bidx)  # trash, not clip
    dest = (bidx * bs + pos % bs).reshape(-1)
    flat = pool.reshape((nb * bs,) + pool.shape[2:])
    flat = flat.at[dest].set(
        new.reshape((b * s,) + new.shape[2:]).astype(pool.dtype))
    return _pool_shard(flat.reshape(pool.shape))


def paged_prefill_mask(s, lens):
    """[B, 1, S, S] keep-mask for prompt self-attention over a right-padded
    ragged batch: causal AND key column < the row's true length — exactly
    static_cache_mask's ragged form at pos=0 over a buffer the size of the
    prompt itself (one definition of the ragged-causal semantics)."""
    return static_cache_mask(s, s, jnp.int32(0), prompt_lens=lens,
                             prefill_cap=s)


def paged_attention_reference(q, k_pool, v_pool, tables, lens, *,
                              scale=None, score_dtype=None):
    """Pure-jnp ragged paged decode attention — the CPU/tier-1 path and
    the parity oracle for the Pallas kernel.

    q [B, 1, H, D] (single decode token per row); pools [NB, bs, H, D];
    tables [B, MB]; lens [B] = ATTENDABLE rows per batch entry (callers
    pass tokens-in-cache + 1 so the just-written token sees itself).
    Gathers each row's blocks into a contiguous [B, MB*bs, H, D] view and
    defers to `attention_reference` with the ragged keep-mask — same
    softmax/accumulation conventions as the static-cache path. Rows with
    lens == 0 (dummy batch slots) produce garbage, not NaN: the masked
    softmax degrades to uniform, and callers drop those rows."""
    if q.shape[1] != 1:
        raise ValueError(f"paged_attention_reference serves single-token "
                         f"decode; got q seq len {q.shape[1]}")
    nb, bs = k_pool.shape[0], k_pool.shape[1]
    b, mb = tables.shape
    t = tables.astype(jnp.int32)
    k = _gathered_shard(
        jnp.take(k_pool, t, axis=0).reshape((b, mb * bs) + k_pool.shape[2:]))
    v = _gathered_shard(
        jnp.take(v_pool, t, axis=0).reshape((b, mb * bs) + v_pool.shape[2:]))
    col = jnp.arange(mb * bs, dtype=jnp.int32)[None, None, None, :]
    mask = col < lens.astype(jnp.int32)[:, None, None, None]
    return attention_reference(q, k, v, mask=mask, scale=scale,
                               score_dtype=score_dtype)


def _paged_gather(pool, tables):
    """Gather a row's blocks into a contiguous [B, MB*bs, ...] view —
    the XLA-visible reference form shared by every paged attention
    reference below (the Pallas kernels walk the table instead)."""
    nb, bs = pool.shape[0], pool.shape[1]
    b, mb = tables.shape
    t = tables.astype(jnp.int32)
    return _gathered_shard(
        jnp.take(pool, t, axis=0).reshape((b, mb * bs) + pool.shape[2:]))


def paged_prefix_mask(s, width, start):
    """[B, 1, S, width] keep-mask for SUFFIX prefill over a paged pool
    (prefix cache, ISSUE 10): query row i sits at global position
    start[b] + i and sees pool columns <= its own position — causal over
    the shared cached prefix plus the just-written suffix. Columns past
    the causal frontier (garbage padding writes, unwritten decode rows)
    are excluded by the same comparison."""
    col = jnp.arange(width, dtype=jnp.int32)[None, None, None, :]
    row = jnp.arange(s, dtype=jnp.int32)[None, None, :, None]
    return col <= (start.astype(jnp.int32)[:, None, None, None] + row)


def paged_prefix_attention_reference(q, k_pool, v_pool, tables, start, *,
                                     scale=None, score_dtype=None):
    """Suffix-prefill attention over a paged pool: q [B, S, H, D] holds
    the (right-padded) SUFFIX tokens at global positions start[b] + i;
    K/V for both the cached prefix and the suffix live in the pool
    already (prefix from the cache, suffix written by the caller).
    Padded query rows (i >= the row's suffix length) produce garbage the
    caller drops — same contract as paged_prefill_mask prefill."""
    k = _paged_gather(k_pool, tables)
    v = _paged_gather(v_pool, tables)
    mask = paged_prefix_mask(q.shape[1], k.shape[1], start)
    return attention_reference(q, k, v, mask=mask, scale=scale,
                               score_dtype=score_dtype)


# ------------------------------------------ int8 paged KV cache (serving)
# The static int8-KV trick (quantize_kv / attention_q8_cache: int8 codes +
# per-(position, head) f32 scales that FACTOR OUT of both contractions)
# ported to the paged pool (ISSUE 10): code pools are int8
# [NB, bs, H, D], scale pools f32 [NB, bs, H] — per-block factored
# scales, one scale row per pool row. Same pool holds ~2x the resident
# tokens; same write/gather plumbing as the fp paged path.

def paged_cache_write_q8(codes_pool, scale_pool, new, tables, lens):
    """Quantize one decode-step row per batch entry and scatter codes +
    scales into the pools (the int8 form of paged_cache_write)."""
    codes, scale = quantize_kv(new)
    return (paged_cache_write(codes_pool, codes, tables, lens),
            paged_cache_write(scale_pool, scale, tables, lens))


def paged_prefill_write_q8(codes_pool, scale_pool, new, tables,
                           start=None):
    """Quantize a (padded) prompt/suffix projection and bulk-write codes
    + scales into pool blocks (the int8 form of paged_prefill_write)."""
    codes, scale = quantize_kv(new)
    return (paged_prefill_write(codes_pool, codes, tables, start),
            paged_prefill_write(scale_pool, scale, tables, start))


def paged_attention_reference_q8(q, kc_pool, ks_pool, vc_pool, vs_pool,
                                 tables, lens):
    """Single-token decode attention over int8 paged pools — gathers
    codes + scales per row and defers to `attention_q8_cache`, so the
    numerics class is EXACTLY the static int8-KV path's (the parity
    oracle the tests pin). CPU/tier-1 path of paged_attention_q8."""
    if q.shape[1] != 1:
        raise ValueError(f"paged_attention_reference_q8 serves "
                         f"single-token decode; got q seq len {q.shape[1]}")
    kc = _paged_gather(kc_pool, tables)
    ks = _paged_gather(ks_pool, tables)
    vc = _paged_gather(vc_pool, tables)
    vs = _paged_gather(vs_pool, tables)
    col = jnp.arange(kc.shape[1], dtype=jnp.int32)[None, None, None, :]
    mask = col < lens.astype(jnp.int32)[:, None, None, None]
    return attention_q8_cache(q, kc, ks, vc, vs, mask)


def paged_prefix_attention_reference_q8(q, kc_pool, ks_pool, vc_pool,
                                        vs_pool, tables, start):
    """Suffix-prefill attention over int8 paged pools: the q8 form of
    paged_prefix_attention_reference (same causal-over-global-positions
    mask, factored-scale contraction math)."""
    kc = _paged_gather(kc_pool, tables)
    ks = _paged_gather(ks_pool, tables)
    vc = _paged_gather(vc_pool, tables)
    vs = _paged_gather(vs_pool, tables)
    mask = paged_prefix_mask(q.shape[1], kc.shape[1], start)
    return attention_q8_cache(q, kc, ks, vc, vs, mask)


def paged_attention_q8(q, kc_pool, ks_pool, vc_pool, vs_pool, tables,
                       lens):
    """int8 ragged paged decode attention: Pallas kernel on TPU (codes
    stream as int8 bytes, scales multiply the tiny per-block score
    column), jnp gather reference elsewhere — routed exactly like
    paged_attention."""
    if _use_paged_kernel():
        from .pallas.paged_attention import paged_attention_q8_kernel
        return _paged_kernel(paged_attention_q8_kernel, q,
                             (kc_pool, ks_pool, vc_pool, vs_pool), tables,
                             lens)
    return paged_attention_reference_q8(q, kc_pool, ks_pool, vc_pool,
                                        vs_pool, tables, lens)


def _paged_kernel(kernel, q, pools, tables, rows, **kw):
    """One of the four paged kernels on arrays that may live on a mesh:
    batch rows over dp, heads over mp — the head sharding the serving
    pools already carry (`_pool_shard`), so under `ServingConfig(shards=N)`
    every shard walks the block table over its own heads and nothing is
    gathered. `rows` is the per-row lens or start vector."""
    def call(q, tables, rows, *pools):
        return kernel(q, *pools, tables, rows, **kw)

    pool_specs = tuple((None, None, "mp", None)[:p.ndim] for p in pools)
    return _mesh.shard_kernel(
        call, (q, tables, rows) + tuple(pools),
        (_BSHD, ("dp", None), ("dp",)) + pool_specs, _BSHD)


def _use_paged_kernel():
    """Kernel-vs-reference routing, mirroring `_use_pallas`:
    PADDLE_TPU_PAGED=0 forces the jnp reference, =1 forces the Pallas
    kernel (opting a capable host in), unforced requires a TPU-class
    platform. No shape constraints: heads whose pages a DMA cannot slice
    keep the slot-grid kernel (`pallas/paged_attention.py`)."""
    import os
    force = os.environ.get("PADDLE_TPU_PAGED")
    if force == "0":
        return False
    if force == "1":
        return True
    return on_tpu()


def paged_prefix_attention(q, k_pool, v_pool, tables, start, *, scale=None,
                           score_dtype=None):
    """Ragged MULTI-TOKEN paged attention (ISSUE 11; Ragged Paged
    Attention, arxiv 2604.15464): q [B, S, H, D] holds S query tokens per
    row at global positions start[b] + i, each attending every pool
    column <= its own position — causal over the cached prefix plus the
    window itself. One primitive serves suffix prefill after a partial
    prefix hit, chunked prefill, and speculative-decode verification;
    S = 1 with start = lens is the decode case. Pallas kernel on TPU
    (block-table walk, MXU-shaped per-block dots), jnp gather reference
    elsewhere — routed exactly like paged_attention."""
    if _use_paged_kernel():
        from .pallas.paged_attention import paged_prefix_attention_kernel
        return _paged_kernel(paged_prefix_attention_kernel, q,
                             (k_pool, v_pool), tables, start, scale=scale)
    return paged_prefix_attention_reference(q, k_pool, v_pool, tables,
                                            start, scale=scale,
                                            score_dtype=score_dtype)


def paged_prefix_attention_q8(q, kc_pool, ks_pool, vc_pool, vs_pool,
                              tables, start):
    """int8 ragged multi-token paged attention: the q8-pool form of
    paged_prefix_attention (factored-scale contraction math), routed
    kernel-vs-reference like every other paged op."""
    if _use_paged_kernel():
        from .pallas.paged_attention import paged_prefix_attention_q8_kernel
        return _paged_kernel(paged_prefix_attention_q8_kernel, q,
                             (kc_pool, ks_pool, vc_pool, vs_pool), tables,
                             start)
    return paged_prefix_attention_reference_q8(q, kc_pool, ks_pool,
                                               vc_pool, vs_pool, tables,
                                               start)


def paged_attention(q, k_pool, v_pool, tables, lens, *, scale=None,
                    score_dtype=None):
    """Ragged paged decode attention: Pallas kernel on TPU (block-table
    indexed fetches, online softmax, nothing gathered to HBM), jnp gather
    reference elsewhere — selected exactly like flash_attention is."""
    if _use_paged_kernel():
        from .pallas.paged_attention import paged_attention_kernel
        return _paged_kernel(paged_attention_kernel, q, (k_pool, v_pool),
                             tables, lens, scale=scale)
    return paged_attention_reference(q, k_pool, v_pool, tables, lens,
                                     scale=scale, score_dtype=score_dtype)


def static_cache_mask(kv_capacity, s, pos, prompt_lens=None,
                      prefill_cap=None):
    """Bool keep-mask for fixed-buffer decode.

    Base form [1, 1, s, L_max]: query row i (global position pos+i) sees
    buffer columns <= pos+i — causal over the valid prefix, zeroed padding
    beyond the cursor.

    Ragged form (prompt_lens [B], prefill_cap int): prompts were RIGHT-
    padded to prefill_cap before prefill, so buffer rows in
    [prompt_lens[b], prefill_cap) hold garbage k/v — additionally mask
    them per batch row: a column is valid iff col < prompt_lens[b] (real
    prompt) or col >= prefill_cap (decoded tokens). One compiled program
    then serves ANY prompt length <= prefill_cap (VERDICT r3 #7; reference
    CacheKV analog: fused_multi_transformer_op.cu)."""
    col = jnp.arange(kv_capacity)[None, None, None, :]
    row = jnp.arange(s)[None, None, :, None]
    keep = col <= (pos.astype(jnp.int32) + row)
    if prompt_lens is not None:
        valid = ((col < prompt_lens.astype(jnp.int32)[:, None, None, None])
                 | (col >= prefill_cap))
        keep = keep & valid
    return keep
