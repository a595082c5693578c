"""Fused whole-sequence multi-head attention for SHORT sequences (TPU).

Reference anchor: paddle/fluid/operators/fused/fused_attention_op.cu — the
reference fuses QKV-transpose + QK^T + softmax + dropout + PV into one GPU
kernel precisely because at short S the cost is memory traffic and launch
overhead, not FLOPs. This is the TPU-native analog, built for the two model
classes the flash kernel serves poorly:

- ViT/Swin-class (S≈200, many heads): the streaming flash kernel's head-major
  [B*H, S, D] layout costs ~12 ms/step of pure transposes on ViT-L/16 B=32
  (r3 profile), and a (B·H,)-sized grid is 512 near-empty sequential programs.
- BERT-class (S≈512 + attention-probability dropout): XLA generates S² threefry
  bits per layer in HBM — measured ~20% MFU on bert-base MLM, the worst
  transformer number on the r3 board.

Design (differs from flash_attention.py, which streams K/V blocks):
- ONE program holds the ENTIRE sequence for a group of G heads. Grid is
  (B, nh/G); scores/probs (S×S f32) live only in VMEM — no online softmax, no
  logsumexp residual, no delta precompute.
- Layout is the PACKED projection output [B, S, nh·hd] (q, k, v each): the
  same array the qkv matmul produces and the out-projection consumes. Per-head
  lane slices are static offsets. Zero layout transposes in fwd or bwd.
- The backward pass is ONE kernel emitting dq, dk, dv together: with the full
  row resident it recomputes softmax directly (max/sum, not stored lse) and
  the softmax-vjp row term rowsum(dσ⊙σ) exactly, so the only residuals are
  the inputs themselves.
- Attention-probability dropout draws its mask from the Mosaic per-core PRNG
  (pltpu.prng_seed / prng_random_bits), seeded per (batch, head) — the S² of
  random bits never exist in HBM, and the backward regenerates bit-identical
  masks from the same seeds.

Numerics: dots run on bf16 operands with f32 accumulation
(preferred_element_type); softmax max/exp/sum and the probability matrix stay
f32 in VMEM. That is STRICTLY tighter than the XLA fallback path with
score_dtype=bf16 (which rounds stored probs to bf16 in HBM).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...device import on_tpu

_NEG = -1e30

# scoped-VMEM budget to plan head-grouping against (chip limit is 16M;
# leave headroom for Mosaic's own temporaries)
_VMEM_BUDGET = 11 * 1024 * 1024


def _kv_mask_2d(s, kv_len):
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(col < kv_len, s, jnp.asarray(_NEG, s.dtype))


def _causal_mask_2d(s):
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(row >= col, s, jnp.asarray(_NEG, s.dtype))


def _softmax_f32(s):
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    return p / jnp.sum(p, axis=-1, keepdims=True)


def _drop_mask(seed_ref, bi, h, nh, shape, drop_p):
    """Regenerable keep-scale mask: 0 or 1/(1-p), f32.

    Seeded per (batch, global head) so forward and backward draw identical
    bits; uint32 threshold comparison gives P(drop) = drop_p to 2^-32."""
    pltpu.prng_seed(seed_ref[0, 0] + bi * nh + h)
    bits = pltpu.prng_random_bits(shape)
    bits = pltpu.bitcast(bits, jnp.uint32)
    thresh = jnp.uint32(min(int(drop_p * (2.0 ** 32)), 2 ** 32 - 1))
    inv = jnp.float32(1.0 / (1.0 - drop_p))
    return jnp.where(bits >= thresh, inv, jnp.float32(0.0))


def _head(ref, j, hd):
    return ref[0, :, j * hd:(j + 1) * hd]


def _fwd_kernel(seed_ref, *rest, nh, hd, G, scale, kv_len, causal, drop_p,
                per_row_lens=False):
    if per_row_lens:
        lens_ref, q_ref, k_ref, v_ref, o_ref = rest
    else:
        q_ref, k_ref, v_ref, o_ref = rest
    bi, g = pl.program_id(0), pl.program_id(1)
    for j in range(G):
        q = _head(q_ref, j, hd)
        k = _head(k_ref, j, hd)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask_2d(s)
        if per_row_lens:
            # per-batch-row valid length (right-padded batches): the SMEM
            # scalar load by traced bi keeps the mask in-register
            s = _kv_mask_2d(s, lens_ref[bi, 0])
        elif kv_len is not None:
            s = _kv_mask_2d(s, kv_len)
        p = _softmax_f32(s)
        if drop_p > 0.0:
            p = p * _drop_mask(seed_ref, bi, g * G + j, nh, p.shape, drop_p)
        v = _head(v_ref, j, hd)
        o_ref[0, :, j * hd:(j + 1) * hd] = jnp.dot(
            p.astype(v.dtype), v,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _bwd_kernel(seed_ref, *rest, nh, hd, G, scale, kv_len, causal, drop_p,
                per_row_lens=False):
    if per_row_lens:
        lens_ref, q_ref, k_ref, v_ref, do_ref, dqkv_ref = rest
    else:
        q_ref, k_ref, v_ref, do_ref, dqkv_ref = rest
    # dqkv_ref is the FULL (1, S, 3F) packed-gradient block, resident
    # across the head-group grid dim — each group writes its own column
    # span, so d(qkv) leaves the kernel already concatenated (the layout
    # the projection weight-grad consumes) with zero XLA copies. The span
    # start g·(G·hd) is a dynamic offset, so it must be provably 128-
    # aligned (Mosaic lane rule) — _pick_group guarantees G·hd % 128 == 0;
    # per-head writes inside the span assemble in registers first.
    bi, g = pl.program_id(0), pl.program_id(1)
    F = nh * hd
    dqs, dks, dvs = [], [], []
    for j in range(G):
        q = _head(q_ref, j, hd)
        k = _head(k_ref, j, hd)
        v = _head(v_ref, j, hd)
        do = _head(do_ref, j, hd)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask_2d(s)
        if per_row_lens:
            s = _kv_mask_2d(s, lens_ref[bi, 0])
        elif kv_len is not None:
            s = _kv_mask_2d(s, kv_len)
        sigma = _softmax_f32(s)
        dpd = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        if drop_p > 0.0:
            m = _drop_mask(seed_ref, bi, g * G + j, nh, s.shape, drop_p)
            pd = sigma * m           # dropped probabilities (fwd replay)
            dsig = dpd * m           # grad through the same mask
        else:
            pd = sigma
            dsig = dpd
        dvs.append(jnp.dot(pd.astype(do.dtype).T, do,
                           preferred_element_type=jnp.float32))
        # softmax vjp with the row term computed exactly in-register
        r = jnp.sum(dsig * sigma, axis=-1, keepdims=True)
        ds = (sigma * (dsig - r)).astype(q.dtype)
        dqs.append(jnp.dot(ds, k, preferred_element_type=jnp.float32)
                   * scale)
        dks.append(jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
                   * scale)
    span = G * hd
    base = g * span
    dt = dqkv_ref.dtype
    dqkv_ref[0, :, pl.ds(base, span)] = \
        jnp.concatenate(dqs, axis=-1).astype(dt)
    dqkv_ref[0, :, pl.ds(F + base, span)] = \
        jnp.concatenate(dks, axis=-1).astype(dt)
    dqkv_ref[0, :, pl.ds(2 * F + base, span)] = \
        jnp.concatenate(dvs, axis=-1).astype(dt)


def _pick_group(nh, hd, s, itemsize, n_bufs, fixed_bytes=0, batch=None):
    """Largest G dividing nh whose blocks fit the VMEM plan.

    n_bufs: resident (S, G·hd) stream buffers — inputs are double-buffered
    by the pipeline (count 2×), plus ~4 f32 (S,S) ephemerals for the
    score/prob/grad matrices. fixed_bytes: group-size-independent residents
    (the backward's full (S, 3F) dqkv output block, double-buffered)."""
    eph = 4 * s * s * 4 + fixed_bytes
    aligned = [G for G in range(nh, 0, -1)
               if nh % G == 0 and (G * hd) % 128 == 0]
    if not aligned:
        raise ValueError(
            f"fused_mha: no head group of nh={nh} hd={hd} satisfies the "
            f"128-lane alignment rule (use_fused_mha should have gated)")
    best = aligned[-1]   # smallest aligned group as the floor
    for G in aligned:
        blocks = n_bufs * 2 * s * G * hd * itemsize
        if blocks + eph <= _VMEM_BUDGET:
            best = G
            break
    # measured on v5e (S=197 nh=16 hd=64): at B=64 G=8 beats G=16 (two
    # groups per batch item pipeline DMA against compute, full-step 66.2%
    # vs lower); at B=32 the FULL STEP prefers G=16 (56.3% vs 54.2% at
    # G=8 — fewer, fatter programs when the grid is short). The r4 note
    # preferring G=8 universally came from a forward-only microbench.
    while best > 8 and nh % (best // 2) == 0 and (batch is None
                                                  or batch > 32):
        best //= 2
    return best


def _i0():
    # index-map literal must be i32 — a bare python 0 traces as i64 under
    # x64, which Mosaic refuses (same workaround as flash_attention.py)
    return jnp.int32(0)


def _smem_spec():
    # explicit i32 index map: the default map emits python-int literals,
    # which trace as i64 under x64 and Mosaic refuses to return
    return pl.BlockSpec((1, 1), lambda bi, g: (_i0(), _i0()),
                        memory_space=pltpu.SMEM)


def _specs(G, hd, s, n_groups):
    """One (1, S, G·hd) block per (batch, group) over a packed [B,S,F]
    array; q/k/v additionally offset by their third of a fused [B,S,3F]."""
    def at(third):
        return pl.BlockSpec(
            (1, s, G * hd),
            lambda bi, g, _t=third: (bi, _i0(), _t * n_groups + g))
    return at


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _mha(qkv, seed, lensf, nh, scale, kv_len, causal, drop_p, G, interpret,
         use_lens):
    return _mha_fwd(qkv, seed, lensf, nh, scale, kv_len, causal, drop_p, G,
                    interpret, use_lens)


def _lens_spec(b):
    # full [B,1] i32 table in SMEM; every program reads its own row
    return pl.BlockSpec((b, 1), lambda bi, g: (_i0(), _i0()),
                        memory_space=pltpu.SMEM)


FWD_NAME = "pallas_fused_mha_fwd"


def _mha_fwd(qkv, seed, lensf, nh, scale, kv_len, causal, drop_p, G,
             interpret, use_lens):
    b, s, F3 = qkv.shape
    F = F3 // 3
    hd = F // nh
    n_groups = nh // G
    at = _specs(G, hd, s, n_groups)
    extra_specs = [_lens_spec(b)] if use_lens else []
    extra_args = [lensf.astype(jnp.int32)] if use_lens else []
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, nh=nh, hd=hd, G=G, scale=scale,
                          kv_len=kv_len, causal=causal, drop_p=drop_p,
                          per_row_lens=use_lens),
        out_shape=jax.ShapeDtypeStruct((b, s, F), qkv.dtype),
        grid=(b, n_groups),
        in_specs=[
            _smem_spec(), *extra_specs,
            at(0), at(1), at(2),
        ],
        out_specs=pl.BlockSpec((1, s, G * hd), lambda bi, g: (bi, _i0(), g)),
        interpret=interpret,
        name=FWD_NAME,
    )(jax.lax.bitcast_convert_type(seed, jnp.int32),
      *extra_args, qkv, qkv, qkv)
    return out


def _mha_vjp_fwd(qkv, seed, lensf, nh, scale, kv_len, causal, drop_p, G,
                 interpret, use_lens):
    out = _mha_fwd(qkv, seed, lensf, nh, scale, kv_len, causal, drop_p, G,
                   interpret, use_lens)
    return out, (qkv, seed, lensf)


BWD_NAME = "pallas_fused_mha_bwd"


def _mha_vjp_bwd(nh, scale, kv_len, causal, drop_p, G, interpret, use_lens,
                 res, g_out):
    qkv, seed, lensf = res
    b, s, F3 = qkv.shape
    F = F3 // 3
    hd = F // nh
    # the backward streams 4 group-sized buffers (q,k,v,do in) plus the
    # FULL (S, 3F) dqkv output block, which is group-size-independent and
    # double-buffered across the batch grid dim — budget it as fixed
    # note: no batch= here — the measured B=32 configuration (ViT-L 56.3%)
    # is fwd G=16 / bwd G=8: the backward's resident dqkv block already
    # fattens its programs, so the small-batch large-G preference is a
    # forward-only effect
    Gb = min(G, _pick_group(nh, hd, s, qkv.dtype.itemsize, n_bufs=4,
                            fixed_bytes=2 * s * F3 * qkv.dtype.itemsize))
    while Gb > 1 and (nh % Gb or (Gb * hd) % 128):
        Gb -= 1
    n_groups = nh // Gb
    at = _specs(Gb, hd, s, n_groups)
    gspec = pl.BlockSpec((1, s, Gb * hd), lambda bi, gg: (bi, _i0(), gg))
    extra_specs = [_lens_spec(b)] if use_lens else []
    extra_args = [lensf.astype(jnp.int32)] if use_lens else []
    dqkv = pl.pallas_call(
        functools.partial(_bwd_kernel, nh=nh, hd=hd, G=Gb, scale=scale,
                          kv_len=kv_len, causal=causal, drop_p=drop_p,
                          per_row_lens=use_lens),
        out_shape=jax.ShapeDtypeStruct((b, s, F3), qkv.dtype),
        grid=(b, n_groups),
        in_specs=[
            _smem_spec(), *extra_specs,
            at(0), at(1), at(2), gspec,
        ],
        out_specs=pl.BlockSpec((1, s, F3),
                               lambda bi, gg: (bi, _i0(), _i0())),
        interpret=interpret,
        name=BWD_NAME,
    )(jax.lax.bitcast_convert_type(seed, jnp.int32),
      *extra_args, qkv, qkv, qkv, g_out)
    return dqkv, jnp.zeros_like(seed), jnp.zeros_like(lensf)


_mha.defvjp(_mha_vjp_fwd, _mha_vjp_bwd)


def mha_reference_packed(qkv, num_heads, *, scale=None, kv_len=None,
                         causal=False, score_dtype=None):
    """XLA fallback with identical signature (no dropout): unpack, run the
    shared reference softmax-attention, repack."""
    from ..attention import attention_reference
    b, s, F3 = qkv.shape
    F = F3 // 3
    hd = F // num_heads
    a = qkv.reshape(b, s, 3, num_heads, hd)
    mask = None
    if kv_len is not None and kv_len < s:
        mask = (jnp.arange(s) < kv_len)[None, None, None, :]
    out = attention_reference(a[:, :, 0], a[:, :, 1], a[:, :, 2], mask=mask,
                              is_causal=causal, scale=scale,
                              score_dtype=score_dtype)
    return out.reshape(b, s, F)


def use_fused_mha(s, num_heads, head_dim, max_seq=768):
    # max_seq: the per-head (S,S) f32 score/prob ephemerals must fit
    # scoped VMEM alongside the stream buffers — 768 is the measured
    # ceiling class on 16M chips; longer sequences belong to the
    # streaming flash kernel anyway
    """Gate: TPU-class platform, lane-sliceable heads, short sequence."""
    import os
    force = os.environ.get("PADDLE_TPU_FUSED_MHA")
    if force == "0":
        return False
    if force != "1":
        if not on_tpu():
            return False
    return (head_dim % 8 == 0 and head_dim * num_heads % 128 == 0
            and s <= max_seq)


def fused_mha(qkv, num_heads, *, scale=None, kv_len=None, causal=False,
              dropout_p=0.0, dropout_seed=None, heads_per_program=None,
              interpret: bool = False):
    """Fused short-sequence attention on the packed projection output.

    qkv: [B, S, 3·nh·hd] laid out [q heads | k heads | v heads] (the
        reshape-[B,S,3,nh,hd] convention of every encoder block here).
    kv_len: static count of valid key rows (padding mask).
    dropout_p: attention-PROBABILITY dropout rate; needs dropout_seed — a
        float32 scalar (traced ok) whose int32 cast seeds the Mosaic PRNG.
    Returns [B, S, nh·hd] context in the same packed layout.

    S is padded to the 128-lane boundary internally (scores' last dim must
    tile); padded keys are masked via kv_len, padded query rows are sliced
    off and contribute zero gradient.
    """
    b, s, F3 = qkv.shape
    F = F3 // 3
    hd = F // num_heads
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("fused_mha: dropout_p > 0 requires dropout_seed")
    lens_arr = None
    if kv_len is not None and not isinstance(kv_len, int):
        # per-batch-row valid lengths (right-padded batches) — [B] ints
        lens_arr = jnp.asarray(kv_len, jnp.float32).reshape(b, 1)
        kv_len = None
    if kv_len is not None and kv_len <= 0:
        raise ValueError(f"fused_mha: kv_len must be positive, got {kv_len}")
    # No sequence padding: Mosaic masks unaligned block dims natively
    # (measured exact at S=197 on v5e), so ragged lengths cost nothing —
    # the r4 padded variant spent ~1 ms/layer on pad/slice/concat copies.
    if kv_len is not None and kv_len >= s:
        kv_len = None
    if dropout_p > 0.0:
        # float32 carrier for the PRNG seed: custom_vjp requires float
        # primals (int args have no cotangent type). The int seed is packed
        # LOSSLESSLY by bitcast (a value-cast to f32 would round seeds
        # >= 2^24 to multiples of up to 128, shrinking the seed space);
        # the kernel bitcasts back to int32 before SMEM.
        seed = jax.lax.bitcast_convert_type(
            jnp.asarray(dropout_seed).astype(jnp.int32),
            jnp.float32).reshape(1, 1)
    else:
        seed = jnp.zeros((1, 1), jnp.float32)
    if heads_per_program is None:
        # env override rides through the SAME validation as explicit args
        import os
        heads_per_program = (
            int(os.environ.get("PADDLE_TPU_FUSED_MHA_G", "0")) or None)
    if heads_per_program is not None and (
            num_heads % heads_per_program
            or (heads_per_program * hd) % 128):
        # validated HERE so the backward's group-shrink loop can never
        # silently land on an unaligned dqkv span offset (Mosaic lane rule)
        raise ValueError(
            f"fused_mha: heads_per_program={heads_per_program} must divide "
            f"num_heads={num_heads} with heads_per_program*head_dim "
            f"({heads_per_program * hd}) a multiple of 128")
    G = heads_per_program or _pick_group(num_heads, hd, s, qkv.dtype.itemsize,
                                         n_bufs=4, batch=b)
    use_lens = lens_arr is not None
    if lens_arr is None:
        lens_arr = jnp.zeros((b, 1), jnp.float32)   # float carrier (vjp)
    return _mha(qkv, seed, lens_arr, int(num_heads), float(scale),
                None if kv_len is None else int(kv_len), bool(causal),
                float(dropout_p), int(G), bool(interpret), bool(use_lens))
