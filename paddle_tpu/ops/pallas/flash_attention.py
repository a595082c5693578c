"""Pallas flash attention for TPU — streaming forward AND blockwise backward.

Beyond-reference capability (SURVEY §5.7: the reference snapshot has no flash
attention — its fused_attention_op.cu materializes the full S×S probability
matrix). Both passes compute attention blockwise with an online/stored
softmax so HBM traffic is O(S·D) instead of O(S²).

Kernel shape: 3-D sequential grids — (batch·head, q_block, k_block) for the
forward and dQ, (batch·head, k_block, q_block) for dK/dV — with the running
accumulators (m, l, acc / dq / dk,dv) living in VMEM scratch that persists
across the innermost grid dimension. Only one (bq,d) + one (bk,d) tile is
resident per step, so sequence length is bounded by HBM, not VMEM (the
previous full-K/V-block design hit the 16M scoped-vmem limit at S=16k).

Backward follows FlashAttention-2: forward stores per-row logsumexp L
(replicated over 8 sublanes — TPU blocks tile (8,128)); backward recomputes
P = exp(QKᵀ·scale − L) tile by tile with Δ = rowsum(dO ⊙ O) precomputed.

Causal calls work under the diagonal only ("causal tile plan" below). The
HBM blocks stay large (1024 x 1024 at S = 2048: smaller ones re-read K and
V and lose in the step what they win alone, autotune.py), and a block the
diagonal crosses is worked inside VMEM in strips of q rows, each against
the k columns up to its own end, with only the square tile at that end
masked; a block wholly under the diagonal runs with no mask, and a step
above it, which is skipped, names a block already resident and starts no
DMA. `causal_work` counts what a plan multiplies.

Layout: [batch, seq, heads, head_dim] in, same out (paddle convention).
head_dim pads to the 128-lane boundary in the wrapper (zero pads change no
dot product), so 64-dim heads work. Matmuls run on bf16 inputs with f32
accumulation (preferred_element_type) — full MXU rate.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 1024
DEFAULT_BK = 1024
_NEG = -1e30


def _i0():
    # index-map literal: must be i32 — with x64 enabled a bare python 0
    # traces as i64, which Mosaic refuses to return from the index fn
    return jnp.int32(0)


def _causal_mask(s, qi, ki, bq, bk):
    q_idx = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_idx = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(q_idx >= k_idx, s, jnp.asarray(_NEG, s.dtype))


def _kv_mask(s, ki, bk, kv_len):
    """Mask key columns with global index >= kv_len (static padding mask).

    Lets callers with ragged/odd sequence lengths (e.g. ViT's 197 tokens)
    zero-pad K/V up to the 128-row block boundary: padded columns score
    -inf, so exp() gives them zero probability and zero dk/dv."""
    k_idx = ki * bk + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(k_idx < kv_len, s, jnp.asarray(_NEG, s.dtype))


# --------------------------------------------------------- causal tile plan
# A causal block is classed from its grid position alone. One wholly above
# the diagonal is skipped, one wholly under it is FULL and runs with no
# mask, and one the diagonal CROSSES is worked in strips of `sub_tile` q
# rows: strip i meets the k columns up to its own end only, and only its
# last, square tile is masked. HBM blocks and grid are the same for every
# plan; what shrinks is the arithmetic inside a crossed block, from the
# whole block to (n + 1) / 2n of it at n strips. All three kernels take
# the strips by q rows: their products then stream long operands (dkv's
# `p.T @ do` runs over all the strip's columns), and the forward makes one
# softmax update a row. Measured a head on the v5e at S = 2048 (PERF.md
# section 6, PR 32): by k columns, or in square tiles, the same work is
# slower than the whole block.

def _block_class(qi, ki, bq, bk):
    """(needed, full) of causal block (qi, ki); needed and not full is
    crossed. Python ints or traced program ids."""
    needed = ki * bk <= (qi + 1) * bq - 1
    full = ki * bk + bk - 1 <= qi * bq
    return needed, full


def sub_tile(bq, bk, kv_len=None):
    """Rows of a strip of a crossed block: a quarter of the block, lane
    aligned. None where the block is worked whole under its mask: blocks
    that are not square (the diagonal then enters a tile anywhere), too
    small for two lane-aligned strips, or cut by a `kv_len` mask as well."""
    if bq != bk or kv_len is not None:
        return None
    t = max(128, bq // 4)
    return t if t % 128 == 0 and bq % t == 0 and bq > t else None


def _crossed_strips(b, t):
    """Strips of a b x b block on the diagonal, as (first row, rows,
    columns): q rows [r0, r0 + rn) against k columns [0, cn), of which the
    last rn are the tile on the diagonal."""
    return [(r0, t, r0 + t) for r0 in range(0, b, t)]


def causal_work(s_q, s_k, bq, bk, causal=True, kv_len=None):
    """(score pairs the kernels multiply, score pairs the attention needs)
    for one head under the plan the kernels take at these blocks: 1.5 x at
    S = 2048 in blocks of 1024 worked whole, 1.125 x in strips of 256. The
    same for the forward, dq and dkv."""
    cols = s_k if kv_len is None else min(kv_len, s_k)
    if not causal:
        return s_q * s_k, s_q * cols
    t = sub_tile(bq, bk, kv_len)
    crossed = bq * bk if t is None else sum(
        rn * cn for _, rn, cn in _crossed_strips(bq, t))
    done = 0
    for qi in range(s_q // bq):
        for ki in range(s_k // bk):
            needed, full = _block_class(qi, ki, bq, bk)
            done += (bq * bk if full else crossed) if needed else 0
    return done, sum(min(r + 1, cols) for r in range(s_q))


def _diag_tail_mask(s):
    """Causal mask of a strip [rn, cn] whose last rn columns are the square
    tile on the diagonal; the columns before it lie under it."""
    rn, cn = s.shape
    tail = _causal_mask(s[:, cn - rn:], 0, 0, rn, rn)
    return tail if cn == rn else jnp.concatenate([s[:, :cn - rn], tail], 1)


def _block_steps(causal, qi, ki, bq, bk, kv_len):
    """What a kernel does at grid step (qi, ki), as [(when, strips, mask)]:
    under the decorator `when` it works each strip (the form of
    `_crossed_strips`) and puts its scores through `mask`. A non-causal
    block and a full one are one strip with no causal mask; a crossed one
    takes its strips, or the whole block under `_causal_mask` where
    `sub_tile` gives none."""
    whole = [(0, bq, bk)]

    def kv(s):
        return s if kv_len is None else _kv_mask(s, ki, bk, kv_len)

    if not causal:
        return [(lambda body: body(), whole, kv)]
    needed, full = _block_class(qi, ki, bq, bk)
    crossed = jnp.logical_and(needed, jnp.logical_not(full))
    t = sub_tile(bq, bk, kv_len)
    if t is None:
        return [(pl.when(full), whole, kv),
                (pl.when(crossed), whole,
                 lambda s: kv(_causal_mask(s, qi, ki, bq, bk)))]
    return [(pl.when(full), whole, kv),
            (pl.when(crossed), _crossed_strips(bq, t), _diag_tail_mask)]


def _k_block(causal, bq, bk):
    """Block of k / v at step (qi, ki) of the forward's and dq's grids. A
    causal step above the diagonal is skipped: it names the row's last
    needed block, which is resident, so it starts no DMA (at two blocks a
    side k and v are fetched twice a head, not four times)."""
    if not causal:
        return lambda qi, ki: ki
    # lax.div on int32: `//` promotes to i64 under x64, which Mosaic refuses
    return lambda qi, ki: jnp.minimum(
        ki, lax.div((qi + 1) * bq - 1, jnp.int32(bk)))


def _q_block(causal, bq, bk, n_qb):
    """Block of q / dO / lse / delta at step (ki, qi) of dkv's grid: the
    skipped steps come first and name the column's first needed block."""
    if not causal:
        return lambda ki, qi: qi
    return lambda ki, qi: jnp.minimum(
        jnp.maximum(qi, lax.div(ki * bk, jnp.int32(bq))), n_qb - 1)


# ------------------------------------------------------------------ forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc,
                *, scale, causal, n_kb, kv_len=None):
    qi, ki = pl.program_id(1), pl.program_id(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # causal: blocks fully above the diagonal contribute nothing
    for when, strips, mask in _block_steps(causal, qi, ki, bq, bk, kv_len):
        @when
        def _step():
            for r0, rn, cn in strips:
                rows, cols = pl.ds(r0, rn), pl.ds(0, cn)
                q = q_ref[0, rows, :]
                k = k_ref[0, cols, :]
                v = v_ref[0, cols, :]
                s = mask(jnp.dot(q, k.T, preferred_element_type=jnp.float32)
                         * scale)
                m_prev, l_prev = m_sc[rows, :], l_sc[rows, :]
                m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                m_sc[rows, :] = m_new
                l_sc[rows, :] = corr * l_prev + p.sum(axis=-1, keepdims=True)
                acc_sc[rows, :] = corr * acc_sc[rows, :] + jnp.dot(
                    p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(ki == n_kb - 1)
    def _finish():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to((m_sc[...] + jnp.log(l))[:, 0][None, :],
                                      (8, bq))


FWD_NAME = "pallas_flash_fwd"


# Traced once per signature and inlined at every call: a kernel body with
# its strips unrolled takes ~0.1 s to trace, which a 24-layer step would
# otherwise pay 24 times in its set-up. Inlined, the caller's jaxpr is the
# one the plain call gives.
_launcher = functools.partial(
    jax.jit, inline=True,
    static_argnames=("scale", "causal", "bq", "bk", "interpret", "kv_len"))


@_launcher
def _flash_fwd(q, k, v, *, scale, causal, bq, bk, interpret, kv_len=None):
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    qt = jnp.moveaxis(q, 2, 1).reshape(b * h, s_q, d)
    kt = jnp.moveaxis(k, 2, 1).reshape(b * h, s_k, d)
    vt = jnp.moveaxis(v, 2, 1).reshape(b * h, s_k, d)
    n_kb = s_k // bk
    kb = _k_block(causal, bq, bk)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, n_kb=n_kb,
                          kv_len=kv_len),
        out_shape=(jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, 8, s_q), jnp.float32)),
        grid=(b * h, s_q // bq, n_kb),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, _i0())),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, kb(qi, ki), _i0())),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, kb(qi, ki), _i0())),
        ],
        out_specs=(pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, _i0())),
                   pl.BlockSpec((1, 8, bq), lambda bh, qi, ki: (bh, _i0(), qi))),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name=FWD_NAME,
    )(qt, kt, vt)
    return out, lse, (qt, kt, vt)


# ----------------------------------------------------------------- backward
def _strip_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, strip,
                scale, mask):
    """One strip's operands and P = exp(QK^T scale - L), dS = P (dO V^T -
    delta), as (q, k, do, p, ds)."""
    r0, rn, cn = strip
    rows, cols = pl.ds(r0, rn), pl.ds(0, cn)
    q = q_ref[0, rows, :]
    k = k_ref[0, cols, :]
    v = v_ref[0, cols, :]
    do = do_ref[0, rows, :]
    lse = lse_ref[0, 0, rows][:, None]
    delta = delta_ref[0, 0, rows][:, None]
    s = mask(jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale)
    p = jnp.exp(s - lse)
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    return q, k, do, p, p * (dp - delta)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_sc, *, scale, causal, n_kb, kv_len=None):
    qi, ki = pl.program_id(1), pl.program_id(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    for when, strips, mask in _block_steps(causal, qi, ki, bq, bk, kv_len):
        @when
        def _step():
            for strip in strips:
                _, k, _, _, ds = _strip_p_ds(
                    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, strip,
                    scale, mask)
                dq_sc[pl.ds(*strip[:2]), :] += jnp.dot(
                    ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    @pl.when(ki == n_kb - 1)
    def _finish():
        dq_ref[0] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_sc, dv_sc, *, scale, causal, n_qb,
                    kv_len=None):
    ki, qi = pl.program_id(1), pl.program_id(2)
    bk = k_ref.shape[1]
    bq = q_ref.shape[1]

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    for when, strips, mask in _block_steps(causal, qi, ki, bq, bk, kv_len):
        @when
        def _step():
            for strip in strips:
                q, _, do, p, ds = _strip_p_ds(
                    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, strip,
                    scale, mask)
                cols = pl.ds(0, strip[2])
                dv_sc[cols, :] += jnp.dot(p.astype(do.dtype).T, do,
                                          preferred_element_type=jnp.float32)
                dk_sc[cols, :] += jnp.dot(ds.astype(q.dtype).T, q,
                                          preferred_element_type=jnp.float32)

    @pl.when(qi == n_qb - 1)
    def _finish():
        dk_ref[0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


DQ_NAME = "pallas_flash_dq"
DKV_NAME = "pallas_flash_dkv"


@_launcher
def _flash_bwd(res, g, *, scale, causal, bq, bk, interpret, kv_len=None):
    qt, kt, vt, out, lse = res
    bh, s_q, d = qt.shape
    s_k = kt.shape[1]
    dot = jnp.moveaxis(g, 2, 1).reshape(bh, s_q, d)
    delta = jnp.sum(dot.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], (bh, 8, s_q))
    n_kb = s_k // bk
    n_qb = s_q // bq
    kb = _k_block(causal, bq, bk)
    qb = _q_block(causal, bq, bk, n_qb)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          n_kb=n_kb, kv_len=kv_len),
        out_shape=jax.ShapeDtypeStruct((bh, s_q, d), qt.dtype),
        grid=(bh, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, _i0())),
            pl.BlockSpec((1, bk, d), lambda b, qi, ki: (b, kb(qi, ki), _i0())),
            pl.BlockSpec((1, bk, d), lambda b, qi, ki: (b, kb(qi, ki), _i0())),
            pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, _i0())),
            pl.BlockSpec((1, 8, bq), lambda b, qi, ki: (b, _i0(), qi)),
            pl.BlockSpec((1, 8, bq), lambda b, qi, ki: (b, _i0(), qi)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, _i0())),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name=DQ_NAME,
    )(qt, kt, vt, dot, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          n_qb=n_qb, kv_len=kv_len),
        out_shape=(jax.ShapeDtypeStruct((bh, s_k, d), kt.dtype),
                   jax.ShapeDtypeStruct((bh, s_k, d), vt.dtype)),
        grid=(bh, n_kb, n_qb),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, ki, qi: (b, qb(ki, qi), _i0())),
            pl.BlockSpec((1, bk, d), lambda b, ki, qi: (b, ki, _i0())),
            pl.BlockSpec((1, bk, d), lambda b, ki, qi: (b, ki, _i0())),
            pl.BlockSpec((1, bq, d), lambda b, ki, qi: (b, qb(ki, qi), _i0())),
            pl.BlockSpec((1, 8, bq), lambda b, ki, qi: (b, _i0(), qb(ki, qi))),
            pl.BlockSpec((1, 8, bq), lambda b, ki, qi: (b, _i0(), qb(ki, qi))),
        ],
        out_specs=(pl.BlockSpec((1, bk, d), lambda b, ki, qi: (b, ki, _i0())),
                   pl.BlockSpec((1, bk, d), lambda b, ki, qi: (b, ki, _i0()))),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
        name=DKV_NAME,
    )(qt, kt, vt, dot, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------- custom_vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, scale, causal, bq, bk, interpret, kv_len=None,
           save_transposed=False):
    out, _, _ = _flash_fwd(q, k, v, scale=scale, causal=causal, bq=bq, bk=bk,
                           interpret=interpret, kv_len=kv_len)
    b, s_q, h, d = q.shape
    return jnp.moveaxis(out.reshape(b, h, s_q, d), 1, 2)


def _flash_vjp_fwd(q, k, v, scale, causal, bq, bk, interpret, kv_len=None,
                   save_transposed=False):
    out, lse, (qt, kt, vt) = _flash_fwd(q, k, v, scale=scale, causal=causal,
                                        bq=bq, bk=bk, interpret=interpret,
                                        kv_len=kv_len)
    b, s_q, h, d = q.shape
    o = jnp.moveaxis(out.reshape(b, h, s_q, d), 1, 2)
    if save_transposed:
        # residuals: the HEAD-MAJOR [b*h, s, d] copies the forward already
        # built — backward reuses them instead of re-transposing, saving 3
        # layout passes per layer (~20 ms/step on the 1.3B flagship at the
        # measured ~180 GB/s effective HBM bw) at +3·B·S·H·2B residual
        # memory. Right when HBM has headroom; wrong near the remat knee.
        return o, (qt, kt, vt, out, lse, (b, h))
    # default residuals: the ORIGINAL layouts (alias the layer's live
    # tensors) — the transposes are recomputed in bwd, saving 3 head-major
    # copies of q/k/v in HBM across the whole backward (~100MB at 1.3B
    # S=8192; the difference between fitting bf16 moments and OOM)
    return o, (q, k, v, out, lse, (b, h))


def _flash_vjp_bwd(scale, causal, bq, bk, interpret, kv_len, save_transposed,
                   res, g):
    q, k, v, out, lse, (b, h) = res
    d = q.shape[-1]
    if save_transposed:
        qt, kt, vt = q, k, v
    else:
        qt = jnp.moveaxis(q, 2, 1).reshape(b * h, q.shape[1], d)
        kt = jnp.moveaxis(k, 2, 1).reshape(b * h, k.shape[1], d)
        vt = jnp.moveaxis(v, 2, 1).reshape(b * h, v.shape[1], d)
    dq, dk, dv = _flash_bwd((qt, kt, vt, out, lse), g, scale=scale,
                            causal=causal, bq=bq, bk=bk, interpret=interpret,
                            kv_len=kv_len)
    s_q, s_k, d = dq.shape[1], dk.shape[1], dq.shape[2]
    dq = jnp.moveaxis(dq.reshape(b, h, s_q, d), 1, 2)
    dk = jnp.moveaxis(dk.reshape(b, h, s_k, d), 1, 2)
    dv = jnp.moveaxis(dv.reshape(b, h, s_k, d), 1, 2)
    return dq, dk, dv


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _reference(q, k, v, *, scale, causal):
    from ..attention import attention_reference
    return attention_reference(q, k, v, is_causal=causal, scale=scale)


def flash_attention(q, k, v, causal: bool = False, scale=None,
                    block_q: int = None, block_k: int = None,
                    interpret: bool = False, kv_len: int = None,
                    save_transposed: bool = None):
    """Differentiable flash attention on [B, S, H, D] arrays.

    kv_len: static number of VALID key/value rows; rows >= kv_len (zero
    padding up to the block boundary) receive -inf scores in forward and
    backward, so their probability and dk/dv are exactly zero.

    save_transposed: keep the forward's head-major q/k/v copies as
    backward residuals (saves 3 re-transpose passes per layer) at the cost
    of 3·B·S·H·2 bytes of residual memory. Default: env
    PADDLE_TPU_FLASH_SAVE_T ("1"/"0"), else False (memory-lean)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    s_q, s_k = q.shape[1], k.shape[1]
    if kv_len is not None and kv_len <= 0:
        # every key column masked would make exp(s - m) == 1 uniformly and
        # return an average of V rather than erroring — reject up front
        raise ValueError(f"flash_attention: kv_len must be positive, "
                         f"got {kv_len}")
    if kv_len is not None and kv_len >= s_k:
        kv_len = None
    import os
    from . import autotune as _at0
    if block_q is None and block_k is None and _at0._OVERRIDE is not None:
        # in-context tuner (autotune.tune_in_step) forcing this candidate
        block_q, block_k = _at0._OVERRIDE
    env_bq = os.environ.get("PADDLE_TPU_FLASH_BQ")  # tuning knobs
    env_bk = os.environ.get("PADDLE_TPU_FLASH_BK")
    if block_q is None and block_k is None and not env_bq and not env_bk \
            and not interpret:
        from ...core import flags as _flags
        if _flags.get_flags("FLAGS_flash_autotune").get(
                "FLAGS_flash_autotune", False):
            # measured tile selection with a persistent cache (PHI
            # autotune analog; see autotune.py). Measurement only happens
            # on EAGER calls — under an outer jit the benchmark would be
            # staged into the caller's trace, so during tracing we consult
            # the cache and fall back to defaults on a miss.
            import jax.core as _core
            from . import autotune as _at
            sig = (q.shape[0], s_q, s_k, q.shape[2], q.shape[3],
                   int(causal), str(q.dtype))
            cached = _at.cached_blocks("flash_attention", sig)
            if cached is not None:
                block_q, block_k = cached
            elif not isinstance(q, _core.Tracer):
                block_q, block_k = _at.tune_flash_blocks(
                    q.shape[0], s_q, s_k, q.shape[2], q.shape[3], causal,
                    q.dtype)
    bq = block_q or int(env_bq) if (block_q or env_bq) else min(DEFAULT_BQ, s_q)
    bk = block_k or int(env_bk) if (block_k or env_bk) else min(DEFAULT_BK, s_k)
    bq = min(bq, s_q)
    bk = min(bk, s_k)
    while s_q % bq:
        bq //= 2
    while s_k % bk:
        bk //= 2
    if bq < 8 or bk < 8:
        if kv_len is not None:
            from ..attention import attention_reference
            kmask = (jnp.arange(s_k) < kv_len)[None, None, None, :]
            return attention_reference(q, k, v, mask=kmask, is_causal=causal,
                                       scale=scale)
        return _reference(q, k, v, scale=scale, causal=causal)
    d = q.shape[-1]
    pad = (-d) % 128
    if pad:
        cfg = [(0, 0)] * 3 + [(0, pad)]
        q = jnp.pad(q, cfg)
        k = jnp.pad(k, cfg)
        v = jnp.pad(v, cfg)
    if save_transposed is None:
        save_transposed = os.environ.get("PADDLE_TPU_FLASH_SAVE_T") == "1"
    out = _flash(q, k, v, float(scale), bool(causal), int(bq), int(bk),
                 bool(interpret), None if kv_len is None else int(kv_len),
                 bool(save_transposed))
    return out[..., :d] if pad else out


# ----------------------------------------------------- packed-layout kernel
# The [B, S, H, D] kernel above needs head-major [B*H, S, D] copies of
# q/k/v (and of dq/dk/dv/out on the way back) — ~11 layout passes per layer
# that cost ~85 ms/step on the GPT-1.3B flagship at the measured ~180 GB/s
# effective HBM bandwidth (r3 profile). This variant consumes the
# projection output DIRECTLY: q/k/v stay [B, S, H·D] (lane-contiguous),
# the grid is (B, q_block, k_block), and heads are a compile-time loop of
# 128-lane slices inside the kernel — zero transposes in fwd OR bwd.
# Requires head_dim == 128 (lane-tile-aligned slices): true for GPT-1.3B
# and GPT-6.7B (2048/16, 4096/32).

def _p_slice(ref0, h, hd):
    return ref0[:, h * hd:(h + 1) * hd]


def _packed_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc,
                       acc_sc, *, scale, causal, n_kb, nh, hd, kv_len=None):
    qi, ki = pl.program_id(1), pl.program_id(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    needed = True if not causal else (ki * bk <= (qi + 1) * bq - 1)

    @pl.when(needed)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        for h in range(nh):
            s = jnp.dot(_p_slice(q, h, hd), _p_slice(k, h, hd).T,
                        preferred_element_type=jnp.float32) * scale
            if causal:
                s = _causal_mask(s, qi, ki, bq, bk)
            if kv_len is not None:
                s = _kv_mask(s, ki, bk, kv_len)
            m_prev = m_sc[:, h:h + 1]
            l_prev = l_sc[:, h:h + 1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            m_sc[:, h:h + 1] = m_new
            l_sc[:, h:h + 1] = corr * l_prev + p.sum(axis=-1, keepdims=True)
            acc_sc[:, h * hd:(h + 1) * hd] = (
                corr * acc_sc[:, h * hd:(h + 1) * hd]
                + jnp.dot(p.astype(v.dtype), _p_slice(v, h, hd),
                          preferred_element_type=jnp.float32))

    @pl.when(ki == n_kb - 1)
    def _finish():
        l = jnp.maximum(l_sc[...], 1e-30)                    # (bq, nh)
        lhd = jnp.repeat(l, hd, axis=1)                      # (bq, nh*hd)
        o_ref[0] = (acc_sc[...] / lhd).astype(o_ref.dtype)
        lse = m_sc[...] + jnp.log(l)                         # (bq, nh)
        lse_ref[0] = jnp.broadcast_to(
            lse.T[:, None, :], (nh, 8, bq)).reshape(nh * 8, bq)


PACKED_FWD_NAME = "pallas_packed_flash_fwd"


def _packed_flash_fwd(q, k, v, *, scale, causal, bq, bk, interpret, nh,
                      kv_len=None):
    b, s_q, H = q.shape
    s_k = k.shape[1]
    hd = H // nh
    n_kb = s_k // bk

    out, lse = pl.pallas_call(
        functools.partial(_packed_fwd_kernel, scale=scale, causal=causal,
                          n_kb=n_kb, nh=nh, hd=hd, kv_len=kv_len),
        out_shape=(jax.ShapeDtypeStruct((b, s_q, H), q.dtype),
                   jax.ShapeDtypeStruct((b, nh * 8, s_q), jnp.float32)),
        grid=(b, s_q // bq, n_kb),
        in_specs=[
            pl.BlockSpec((1, bq, H), lambda bi, qi, ki: (bi, qi, _i0())),
            pl.BlockSpec((1, bk, H), lambda bi, qi, ki: (bi, ki, _i0())),
            pl.BlockSpec((1, bk, H), lambda bi, qi, ki: (bi, ki, _i0())),
        ],
        out_specs=(
            pl.BlockSpec((1, bq, H), lambda bi, qi, ki: (bi, qi, _i0())),
            pl.BlockSpec((1, nh * 8, bq), lambda bi, qi, ki: (bi, _i0(), qi)),
        ),
        scratch_shapes=[pltpu.VMEM((bq, nh), jnp.float32),
                        pltpu.VMEM((bq, nh), jnp.float32),
                        pltpu.VMEM((bq, H), jnp.float32)],
        interpret=interpret,
        name=PACKED_FWD_NAME,
    )(q, k, v)
    return out, lse


def _packed_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dq_ref, dq_sc, *, scale, causal, n_kb, nh, hd,
                          kv_len=None):
    qi, ki = pl.program_id(1), pl.program_id(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    needed = True if not causal else (ki * bk <= (qi + 1) * bq - 1)

    @pl.when(needed)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse_all = lse_ref[0].reshape(nh, 8, bq)
        delta_all = delta_ref[0].reshape(nh, 8, bq)
        for h in range(nh):
            s = jnp.dot(_p_slice(q, h, hd), _p_slice(k, h, hd).T,
                        preferred_element_type=jnp.float32) * scale
            if causal:
                s = _causal_mask(s, qi, ki, bq, bk)
            if kv_len is not None:
                s = _kv_mask(s, ki, bk, kv_len)
            lse = lse_all[h, 0][:, None]
            delta = delta_all[h, 0][:, None]
            p = jnp.exp(s - lse)
            dp = jnp.dot(_p_slice(do, h, hd), _p_slice(v, h, hd).T,
                         preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)).astype(k.dtype)
            dq_sc[:, h * hd:(h + 1) * hd] += jnp.dot(
                ds, _p_slice(k, h, hd), preferred_element_type=jnp.float32)

    @pl.when(ki == n_kb - 1)
    def _finish():
        dq_ref[0] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _packed_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dk_ref, dv_ref, dk_sc, dv_sc, *, scale, causal,
                           n_qb, nh, hd, kv_len=None):
    ki, qi = pl.program_id(1), pl.program_id(2)
    bk = k_ref.shape[1]
    bq = q_ref.shape[1]

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    needed = True if not causal else ((qi + 1) * bq - 1 >= ki * bk)

    @pl.when(needed)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse_all = lse_ref[0].reshape(nh, 8, bq)
        delta_all = delta_ref[0].reshape(nh, 8, bq)
        for h in range(nh):
            s = jnp.dot(_p_slice(q, h, hd), _p_slice(k, h, hd).T,
                        preferred_element_type=jnp.float32) * scale
            if causal:
                s = _causal_mask(s, qi, ki, bq, bk)
            if kv_len is not None:
                s = _kv_mask(s, ki, bk, kv_len)
            lse = lse_all[h, 0][:, None]
            delta = delta_all[h, 0][:, None]
            p = jnp.exp(s - lse)
            pt = p.astype(do.dtype)
            dv_sc[:, h * hd:(h + 1) * hd] += jnp.dot(
                pt.T, _p_slice(do, h, hd),
                preferred_element_type=jnp.float32)
            dp = jnp.dot(_p_slice(do, h, hd), _p_slice(v, h, hd).T,
                         preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)).astype(q.dtype)
            dk_sc[:, h * hd:(h + 1) * hd] += jnp.dot(
                ds.T, _p_slice(q, h, hd),
                preferred_element_type=jnp.float32)

    @pl.when(qi == n_qb - 1)
    def _finish():
        dk_ref[0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


PACKED_DQ_NAME = "pallas_packed_flash_dq"
PACKED_DKV_NAME = "pallas_packed_flash_dkv"


def _packed_flash_bwd(q, k, v, out, lse, g, *, scale, causal, bq, bk,
                      interpret, nh, kv_len=None):
    b, s_q, H = q.shape
    s_k = k.shape[1]
    hd = H // nh
    # backward kernels hold 2x f32 accumulator panels (bk, H) — clamp their
    # blocks to fit the 16M scoped-VMEM budget independently of the
    # forward's (the fwd carries only ONE panel and can afford 512);
    # re-establish divisibility after the clamp or the grid under-covers
    # the sequence and uncovered gradient rows come back as garbage
    bq = min(bq, 256)
    bk = min(bk, 256)
    while s_q % bq:
        bq //= 2
    while s_k % bk:
        bk //= 2
    n_kb = s_k // bk
    n_qb = s_q // bq
    # delta = rowsum(dO . O) per head: [B, S, nh] -> [B, nh*8, S]
    delta = jnp.sum((g.astype(jnp.float32) * out.astype(jnp.float32))
                    .reshape(b, s_q, nh, hd), axis=-1)       # [B, S, nh]
    delta = jnp.broadcast_to(jnp.moveaxis(delta, 1, 2)[:, :, None, :],
                             (b, nh, 8, s_q)).reshape(b, nh * 8, s_q)

    dq = pl.pallas_call(
        functools.partial(_packed_bwd_dq_kernel, scale=scale, causal=causal,
                          n_kb=n_kb, nh=nh, hd=hd, kv_len=kv_len),
        out_shape=jax.ShapeDtypeStruct((b, s_q, H), q.dtype),
        grid=(b, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((1, bq, H), lambda bi, qi, ki: (bi, qi, _i0())),
            pl.BlockSpec((1, bk, H), lambda bi, qi, ki: (bi, ki, _i0())),
            pl.BlockSpec((1, bk, H), lambda bi, qi, ki: (bi, ki, _i0())),
            pl.BlockSpec((1, bq, H), lambda bi, qi, ki: (bi, qi, _i0())),
            pl.BlockSpec((1, nh * 8, bq), lambda bi, qi, ki: (bi, _i0(), qi)),
            pl.BlockSpec((1, nh * 8, bq), lambda bi, qi, ki: (bi, _i0(), qi)),
        ],
        out_specs=pl.BlockSpec((1, bq, H), lambda bi, qi, ki: (bi, qi, _i0())),
        scratch_shapes=[pltpu.VMEM((bq, H), jnp.float32)],
        interpret=interpret,
        name=PACKED_DQ_NAME,
    )(q, k, v, g, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_packed_bwd_dkv_kernel, scale=scale, causal=causal,
                          n_qb=n_qb, nh=nh, hd=hd, kv_len=kv_len),
        out_shape=(jax.ShapeDtypeStruct((b, s_k, H), k.dtype),
                   jax.ShapeDtypeStruct((b, s_k, H), v.dtype)),
        grid=(b, n_kb, n_qb),
        in_specs=[
            pl.BlockSpec((1, bq, H), lambda bi, ki, qi: (bi, qi, _i0())),
            pl.BlockSpec((1, bk, H), lambda bi, ki, qi: (bi, ki, _i0())),
            pl.BlockSpec((1, bk, H), lambda bi, ki, qi: (bi, ki, _i0())),
            pl.BlockSpec((1, bq, H), lambda bi, ki, qi: (bi, qi, _i0())),
            pl.BlockSpec((1, nh * 8, bq), lambda bi, ki, qi: (bi, _i0(), qi)),
            pl.BlockSpec((1, nh * 8, bq), lambda bi, ki, qi: (bi, _i0(), qi)),
        ],
        out_specs=(
            pl.BlockSpec((1, bk, H), lambda bi, ki, qi: (bi, ki, _i0())),
            pl.BlockSpec((1, bk, H), lambda bi, ki, qi: (bi, ki, _i0())),
        ),
        scratch_shapes=[pltpu.VMEM((bk, H), jnp.float32),
                        pltpu.VMEM((bk, H), jnp.float32)],
        interpret=interpret,
        name=PACKED_DKV_NAME,
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _packed_flash(q, k, v, nh, scale, causal, bq, bk, interpret, kv_len=None):
    out, _ = _packed_flash_fwd(q, k, v, scale=scale, causal=causal, bq=bq,
                               bk=bk, interpret=interpret, nh=nh,
                               kv_len=kv_len)
    return out


def _packed_vjp_fwd(q, k, v, nh, scale, causal, bq, bk, interpret,
                    kv_len=None):
    out, lse = _packed_flash_fwd(q, k, v, scale=scale, causal=causal, bq=bq,
                                 bk=bk, interpret=interpret, nh=nh,
                                 kv_len=kv_len)
    return out, (q, k, v, out, lse)


def _packed_vjp_bwd(nh, scale, causal, bq, bk, interpret, kv_len, res, g):
    q, k, v, out, lse = res
    dq, dk, dv = _packed_flash_bwd(q, k, v, out, lse, g, scale=scale,
                                   causal=causal, bq=bq, bk=bk,
                                   interpret=interpret, nh=nh, kv_len=kv_len)
    return dq, dk, dv


_packed_flash.defvjp(_packed_vjp_fwd, _packed_vjp_bwd)

PACKED_BQ = 256
PACKED_BK = 256


def flash_attention_packed(q, k, v, num_heads: int, causal: bool = False,
                           scale=None, block_q: int = None,
                           block_k: int = None, interpret: bool = False,
                           kv_len: int = None):
    """Flash attention on PACKED [B, S, num_heads*128] arrays.

    Zero layout transposes: inputs are the projection outputs as-is, and
    dq/dk/dv come back in the same layout for the projection weight grads.
    Requires head_dim == 128. Falls back to the [B,S,H,D] kernel via
    reshape when the shape constraints don't hold.

    Measured on v5e (GPT-1.3B B=3 S=2048): parity with the head-major
    kernel at best (73.4% vs 73.3-73.7% MFU across block configs) — the
    ~11 boundary layout passes the packed form eliminates turn out to
    OVERLAP with MXU work in the XLA schedule, while the in-kernel head
    loop (16 lane-sliced dots per block, 16M scoped-VMEM ceiling forcing
    256-row blocks) gives the saving back. Kept as an opt-in
    (PADDLE_TPU_FLASH_PACKED=1 routes GPT through it) for hardware where
    the trade lands differently; the head-major kernel stays the default.
    """
    b, s_q, H = q.shape
    hd = H // num_heads
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    s_k = k.shape[1]
    if kv_len is not None and kv_len <= 0:
        raise ValueError(f"flash_attention_packed: kv_len must be positive, "
                         f"got {kv_len}")
    if kv_len is not None and kv_len >= s_k:
        kv_len = None
    bq = block_q or min(PACKED_BQ, s_q)
    bk = block_k or min(PACKED_BK, s_k)
    bq = min(bq, s_q)
    bk = min(bk, s_k)
    while s_q % bq:
        bq //= 2
    while s_k % bk:
        bk //= 2
    if hd != 128 or bq < 8 or bk < 8:
        q4 = q.reshape(b, s_q, num_heads, hd)
        k4 = k.reshape(b, s_k, num_heads, hd)
        v4 = v.reshape(b, s_k, num_heads, hd)
        out = flash_attention(q4, k4, v4, causal=causal, scale=scale,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret, kv_len=kv_len)
        return out.reshape(b, s_q, H)
    return _packed_flash(q, k, v, int(num_heads), float(scale), bool(causal),
                         int(bq), int(bk), bool(interpret),
                         None if kv_len is None else int(kv_len))
