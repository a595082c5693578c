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

Layout: q, k, v, o and their gradients reach the kernels token-major,
[batch, seq, heads * w], as the projections make and take them, a head w
lanes wide (128, or any multiple of it); a grid step finds its head as a
block of w lanes through the index map ("token-major operands" below), so
heads that are whole lanes have no head-major copy of an operand or a
result, forward or backward. Such copies do not hide under the matmuls: a
TensorCore runs one fusion at a time, and a traced GPT-3 1.3B step showed
them as 7% of its own time; without them the step is 11% faster (PERF.md
section 6, PR 37). `flash_attention_qkv` takes the packed
[batch, seq, 3 * heads * 128] projection whole. `flash_attention` takes
[batch, seq, heads, head_dim] (paddle convention): heads of whole lanes as
a view of the token-major array, other heads zero-padded up to whole lanes
(zero pads change no dot product, so 64-, 80- and 192-dim heads work) and
laid head-major, which is the token-major form of one head a row (see
`_flash`). Matmuls run on bf16 inputs with f32 accumulation
(preferred_element_type) — full MXU rate.
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
LANES = 128     # a head's width in the kernels' operands is whole lanes
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


# ------------------------------------------------- token-major operands
# q, k, v, o and their gradients reach the kernels as the projections make
# and take them: token-major [B, S, n * w], a head a block of w lanes (w a
# multiple of 128). Step g of a grid's first axis (B * nh long) works batch
# g // nh and the lane block off + g % nh, so a head is found by an index
# map and never by a copy. `off` is 0 for an array of nh heads and 0, nh,
# 2 nh for q, k, v as the three groups of ONE packed [B, S, 3 nh w]
# projection. On the chip's HBM tiling a (rows, 128) block of such an
# array is whole 4 KB tiles: the DMA moves what a head-major block's would.

def _head_block(nh, w, off, rows, row):
    """BlockSpec of head g % nh's (1, rows, w) block at grid step
    (g, i, j): batch g // nh, row block `row(i, j)`, lane block off + g % nh
    in units of the head's width w."""
    def index(g, i, j):
        n = jnp.int32(nh)   # i32: a bare python int traces as i64 under x64
        return lax.div(g, n), row(i, j), jnp.int32(off) + lax.rem(g, n)
    return pl.BlockSpec((1, rows, w), index)


def _row_stat(rows, row):
    """BlockSpec of lse / delta, [B * nh, 8, S] float32: one row of
    numbers a head, replicated over 8 sublanes."""
    return pl.BlockSpec((1, 8, rows), lambda g, i, j: (g, _i0(), row(i, j)))


def _first(i, j):
    return i


# Traced once per signature and inlined at every call: a kernel body with
# its strips unrolled takes ~0.1 s to trace, which a 24-layer step would
# otherwise pay 24 times in its set-up. Inlined, the caller's jaxpr is the
# one the plain call gives.
_launcher = functools.partial(
    jax.jit, inline=True,
    static_argnames=("nh", "w", "offs", "scale", "causal", "bq", "bk",
                     "interpret", "kv_len"))


@_launcher
def _flash_fwd(q, k, v, *, nh, w, offs, scale, causal, bq, bk, interpret,
               kv_len=None):
    """o [B, S_q, nh * w] and lse [B * nh, 8, S_q] of token-major q, k, v
    whose heads, w lanes wide, start at lane blocks `offs`."""
    b, s_q = q.shape[:2]
    s_k = k.shape[1]
    n_kb = s_k // bk
    kb = _k_block(causal, bq, bk)
    oq, ok, ov = offs

    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, n_kb=n_kb,
                          kv_len=kv_len),
        out_shape=(jax.ShapeDtypeStruct((b, s_q, nh * w), q.dtype),
                   jax.ShapeDtypeStruct((b * nh, 8, s_q), jnp.float32)),
        grid=(b * nh, s_q // bq, n_kb),
        in_specs=[_head_block(nh, w, oq, bq, _first),
                  _head_block(nh, w, ok, bk, kb),
                  _head_block(nh, w, ov, bk, kb)],
        out_specs=(_head_block(nh, w, 0, bq, _first), _row_stat(bq, _first)),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, w), jnp.float32)],
        interpret=interpret,
        name=FWD_NAME,
    )(q, k, v)


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


def _head_rowsum(a, b, nh):
    """delta = rowsum(dO * O) a head, float32 [B * nh, S], from token-major
    [B, S, nh * w] operands: the lanes are summed a head by a product
    with the heads' 0 / 1 indicator. XLA makes that one fusion that reads
    both operands once and writes the sums with the tokens along the lanes,
    as the kernels take them. (Summed over a [B, S, nh, 128] view instead,
    the float32 products are written out and copied into another tiling
    first: 100 MB a layer at the training shape.) `highest` keeps the
    float32 products whole; the indicator is exact in any precision."""
    if nh == 1:     # head-major rows: the lanes are the one head's
        return jnp.sum(a.astype(jnp.float32) * b.astype(jnp.float32), -1)
    lanes = a.shape[-1]
    heads = (lax.broadcasted_iota(jnp.int32, (nh, lanes), 1) // (lanes // nh)
             == lax.broadcasted_iota(jnp.int32, (nh, lanes), 0))
    sums = jnp.einsum("hl,bsl->bhs", heads.astype(jnp.float32),
                      a.astype(jnp.float32) * b.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST)
    return sums.reshape(a.shape[0] * nh, a.shape[1])


@_launcher
def _flash_bwd(q, k, v, o, lse, do, *, nh, w, offs, scale, causal, bq, bk,
               interpret, kv_len=None):
    """dq [B, S_q, nh * w], dk and dv [B, S_k, nh * w], token-major like
    the operands."""
    b, s_q = q.shape[:2]
    s_k = k.shape[1]
    delta = jnp.broadcast_to(_head_rowsum(do, o, nh)[:, None, :],
                             (b * nh, 8, s_q))
    n_kb = s_k // bk
    n_qb = s_q // bq
    kb = _k_block(causal, bq, bk)
    qb = _q_block(causal, bq, bk, n_qb)
    oq, ok, ov = offs

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          n_kb=n_kb, kv_len=kv_len),
        out_shape=jax.ShapeDtypeStruct((b, s_q, nh * w), q.dtype),
        grid=(b * nh, n_qb, n_kb),
        in_specs=[_head_block(nh, w, oq, bq, _first),
                  _head_block(nh, w, ok, bk, kb),
                  _head_block(nh, w, ov, bk, kb),
                  _head_block(nh, w, 0, bq, _first),
                  _row_stat(bq, _first), _row_stat(bq, _first)],
        out_specs=_head_block(nh, w, 0, bq, _first),
        scratch_shapes=[pltpu.VMEM((bq, w), jnp.float32)],
        interpret=interpret,
        name=DQ_NAME,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          n_qb=n_qb, kv_len=kv_len),
        out_shape=(jax.ShapeDtypeStruct((b, s_k, nh * w), k.dtype),
                   jax.ShapeDtypeStruct((b, s_k, nh * w), v.dtype)),
        grid=(b * nh, n_kb, n_qb),
        in_specs=[_head_block(nh, w, oq, bq, qb),
                  _head_block(nh, w, ok, bk, _first),
                  _head_block(nh, w, ov, bk, _first),
                  _head_block(nh, w, 0, bq, qb),
                  _row_stat(bq, qb), _row_stat(bq, qb)],
        out_specs=(_head_block(nh, w, 0, bk, _first),
                   _head_block(nh, w, 0, bk, _first)),
        scratch_shapes=[pltpu.VMEM((bk, w), jnp.float32),
                        pltpu.VMEM((bk, w), jnp.float32)],
        interpret=interpret,
        name=DKV_NAME,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------- custom_vjp
# `_flash` takes its operands in one of three forms and hands results and
# gradients back in the same one. What tells them apart is what the caller
# has: (packed,) one [B, S, 3 nh w] projection, q, k, v its three groups
# of lane blocks, the gradient one array again; (q, k, v) token-major
# [B, S, nh w]; (q, k, v) as [B, S, nh, w], which is heads PADDED to whole
# lanes. A head's width w is read off the operand: any multiple of 128.
# A padded array is a pass of its own already, and on the chip its reshape
# to [B, S, nh w] is a second one (the 4-D array is tiled 8 heads x 128
# lanes, the token-major one 8 tokens x 128 lanes); laid head-major,
# [B nh, S, w], it costs the same two passes and the kernels run 3-6%
# faster on contiguous blocks (heads of 80 at B 3, S 2048, a layer forward
# and backward on the v5e: 6.62 ms head-major, 6.87 token-major; GPT-3
# 2.7B's step -0.49% token-major). So padded heads go head-major, which to
# the kernels is a token-major array of ONE head a row: nh = 1, B nh batch
# rows. Heads that are whole lanes as they come go token-major: the
# reshape cancels against the projection that made them (three
# projections of one input, a layer: 5.12 ms against 5.27 head-major), and
# where it cannot (slices of a packed array, a [B, S, nh, w] array that
# exists) the two forms tie within 1.3% (PERF.md section 6, PR 37).

def _head_major(x):
    b, s, h, d = x.shape
    return jnp.moveaxis(x, 2, 1).reshape(b * h, s, d)


def _operands(qkv, nh):
    """The kernels' view of a call: (q, k, v) as they take them, and as
    keywords the heads a batch row of theirs holds, a head's width in
    lanes, and the lane blocks q's, k's and v's heads start at."""
    if len(qkv) == 1:
        return qkv * 3, dict(nh=nh, w=qkv[0].shape[-1] // (3 * nh),
                             offs=(0, nh, 2 * nh))
    if qkv[0].ndim == 4:
        return (tuple(map(_head_major, qkv)),
                dict(nh=1, w=qkv[0].shape[-1], offs=(0, 0, 0)))
    return qkv, dict(nh=nh, w=qkv[0].shape[-1] // nh, offs=(0, 0, 0))


def _like(x, operand, nh):
    """A result or gradient of the kernels in its operand's form."""
    if operand.ndim == 4:
        return jnp.moveaxis(x.reshape(-1, nh, *x.shape[1:]), 1, 2)
    return x


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6, 7))
def _flash(qkv, nh, scale, causal, bq, bk, interpret, kv_len=None):
    return _flash_vjp_fwd(qkv, nh, scale, causal, bq, bk, interpret,
                          kv_len)[0]


def _flash_vjp_fwd(qkv, nh, scale, causal, bq, bk, interpret, kv_len=None):
    (q, k, v), heads = _operands(qkv, nh)
    o, lse = _flash_fwd(q, k, v, **heads, scale=scale, causal=causal, bq=bq,
                        bk=bk, interpret=interpret, kv_len=kv_len)
    # residuals: the caller's own arrays, o as the kernel wrote it, lse
    return _like(o, qkv[0], nh), (qkv, o, lse)


def _flash_vjp_bwd(nh, scale, causal, bq, bk, interpret, kv_len, res, g):
    qkv, o, lse = res
    (q, k, v), heads = _operands(qkv, nh)
    do = _head_major(g) if g.ndim == 4 else g
    grads = _flash_bwd(q, k, v, o, lse, do, **heads, scale=scale,
                       causal=causal, bq=bq, bk=bk, interpret=interpret,
                       kv_len=kv_len)
    if len(qkv) == 1:
        # the packed projection's gradient: in a training step XLA reads
        # dq, dk, dv through this concatenate as operands of the weight-
        # and input-gradient products, and no pass of its own is left
        return (jnp.concatenate(grads, axis=-1),),
    return tuple(_like(x, qkv[0], nh) for x in grads),


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _blocks(x, dims, causal, block_q, block_k, interpret, kv_len):
    """(bq, bk, kv_len) a call runs at. `x` is an operand, `dims` its
    call's (B, S_q, S_k, heads, head_dim); a kv_len that masks nothing
    comes back None."""
    b, s_q, s_k, h, d = dims
    if kv_len is not None and kv_len <= 0:
        # every key column masked would make exp(s - m) == 1 uniformly and
        # return an average of V rather than erroring — reject up front
        raise ValueError(f"flash_attention: kv_len must be positive, "
                         f"got {kv_len}")
    if kv_len is not None and kv_len >= s_k:
        kv_len = None
    import os
    from . import autotune as _at
    if block_q is None and block_k is None and _at._OVERRIDE is not None:
        # in-context tuner (autotune.tune_in_step) forcing this candidate
        block_q, block_k = _at._OVERRIDE
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
            sig = (b, s_q, s_k, h, d, int(causal), str(x.dtype))
            cached = _at.cached_blocks("flash_attention", sig)
            if cached is not None:
                block_q, block_k = cached
            elif not isinstance(x, _core.Tracer):
                block_q, block_k = _at.tune_flash_blocks(
                    b, s_q, s_k, h, d, causal, x.dtype)
    bq = block_q or int(env_bq) if (block_q or env_bq) else min(DEFAULT_BQ, s_q)
    bk = block_k or int(env_bk) if (block_k or env_bk) else min(DEFAULT_BK, s_k)
    bq = min(bq, s_q)
    bk = min(bk, s_k)
    while s_q % bq:
        bq //= 2
    while s_k % bk:
        bk //= 2
    return int(bq), int(bk), None if kv_len is None else int(kv_len)


def flash_attention(q, k, v, causal: bool = False, scale=None,
                    block_q: int = None, block_k: int = None,
                    interpret: bool = False, kv_len: int = None):
    """Differentiable flash attention on [B, S, H, D] arrays.

    kv_len: static number of VALID key/value rows; rows >= kv_len (zero
    padding up to the block boundary) receive -inf scores in forward and
    backward, so their probability and dk/dv are exactly zero."""
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    bq, bk, kv_len = _blocks(q, (b, s_q, s_k, h, d), causal, block_q,
                             block_k, interpret, kv_len)
    if bq < 8 or bk < 8:
        from ..attention import attention_reference
        kmask = None if kv_len is None else \
            (jnp.arange(s_k) < kv_len)[None, None, None, :]
        return attention_reference(q, k, v, mask=kmask, is_causal=causal,
                                   scale=scale)
    pad = (-d) % LANES
    if pad:     # heads padded to whole lanes go head-major (see `_flash`)
        cfg = [(0, 0)] * 3 + [(0, pad)]
        qkv = tuple(jnp.pad(x, cfg) for x in (q, k, v))
    else:       # a view the caller's own reshape cancels against
        qkv = tuple(x.reshape(*x.shape[:2], h * d) for x in (q, k, v))
    out = _flash(qkv, h, float(scale), bool(causal), bq, bk, bool(interpret),
                 kv_len)
    return out[..., :d] if pad else out.reshape(b, s_q, h, d)


def flash_attention_qkv(qkv, num_heads: int, causal: bool = False,
                        scale=None, block_q: int = None, block_k: int = None,
                        interpret: bool = False, kv_len: int = None):
    """Flash self-attention on the PACKED projection [B, S, 3 * heads * 128]
    as it leaves the matmul, q heads then k heads then v heads: the
    kernels read q, k, v as three views of the one array and no slice of
    it is made. Returns o [B, S, heads * 128]; the gradient is one
    [B, S, 3 * heads * 128] array."""
    b, s, width = qkv.shape
    if width != 3 * num_heads * LANES:
        raise ValueError(f"flash_attention_qkv: {num_heads} heads of {LANES} "
                         f"packed three times are {3 * num_heads * LANES} "
                         f"lanes, got {width}")
    if scale is None:
        scale = 1.0 / math.sqrt(LANES)
    bq, bk, kv_len = _blocks(qkv, (b, s, s, num_heads, LANES), causal,
                             block_q, block_k, interpret, kv_len)
    if bq < 8 or bk < 8:
        q, k, v = (x.reshape(b, s, num_heads, LANES)
                   for x in jnp.split(qkv, 3, axis=-1))
        return flash_attention(q, k, v, causal, scale, bq, bk, interpret,
                               kv_len).reshape(b, s, num_heads * LANES)
    return _flash((qkv,), int(num_heads), float(scale), bool(causal), bq, bk,
                  bool(interpret), kv_len)
