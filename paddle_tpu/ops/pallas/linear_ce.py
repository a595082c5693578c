"""Fused LM-head + softmax cross-entropy ("linear CE") for TPU.

Reference anchor: paddle/fluid/operators/collective/
c_softmax_with_cross_entropy_op.cu — the reference fuses softmax-CE over
sharded logits but still takes MATERIALIZED logits as input. Here the head
matmul itself lives inside the loss kernel, so the [T, V] logits never exist
in HBM in the forward pass at all.

Why this is the right TPU design (r4 profile): at GPT-1.3B flagship shape
(T = B·S = 6144 tokens, V = 50304, H = 2048) the chunked-XLA path streams
f32 chunk logits through HBM in the forward AND recomputes + re-streams them
under jax.checkpoint in the backward — ~30-37 ms of a 385 ms step, the
largest attackable non-MXU term on the board. The FLOP floor of the three
head matmuls (fwd, dx, dW) is ~19 ms at peak; the gap is pure logits traffic.

Forward (Pallas): grid (token_block, vocab_block), vocab innermost. One
x-tile [Bt, H] and one W-tile [Bv, H] are resident; the [Bt, Bv] f32 logits
tile lives only in registers/VMEM. Running max / sum-exp / gold-logit
accumulators persist in VMEM scratch across the vocab dimension (the same
online-softmax pattern as flash_attention.py). Outputs: per-token loss and
per-token logsumexp (the backward residual).

Backward (XLA matmuls, NO logits recompute chain): with lse saved, the
gradient is closed-form —
    dlogits[t, v] = g[t] * (exp(logits[t, v] - lse[t]) - 1{v == label[t]})
so each token chunk needs ONE bf16 matmul to rebuild the probability tile
fused with its epilogue, then dx = dlogits @ W and dW = dlogitsᵀ @ x run as
plain MXU matmuls. dlogits is materialized in bf16 (half the bytes of the
checkpoint path's f32 logits, with no second recompute pass); chunking keeps
its residency bounded.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...device import on_tpu
from ...distributed import mesh as _mesh

_NEG = -1e30


def _i0():
    # index-map literals must be i32 under x64 (Mosaic refuses i64)
    return jnp.int32(0)


def _fwd_kernel(lab_ref, x_ref, w_ref, lse_ref, gold_ref, m_sc, s_sc, g_sc,
                *, n_v, block_v, vocab, w_layout):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        s_sc[...] = jnp.zeros_like(s_sc)
        g_sc[...] = jnp.zeros_like(g_sc)

    x = x_ref[...]
    w = w_ref[...]
    if w_layout == "vh":
        # logits tile = x [Bt,H] · wᵀ [H,Bv] — contraction on both lasts
        logits = lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    else:  # "hv": w tile is [H, Bv]
        logits = lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    col = vi * block_v + lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    if vocab % block_v:
        # mask the ragged tail tile: out-of-vocab columns score -inf
        logits = jnp.where(col < vocab, logits, jnp.float32(_NEG))
    # gold-logit contribution: exactly one vocab tile contains each label
    lab = lab_ref[...]  # [Bt, 1] i32
    g_sc[...] += jnp.sum(jnp.where(col == lab, logits, jnp.float32(0.0)),
                         axis=1, keepdims=True)
    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    s_sc[...] = s_sc[...] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(logits - m_new), axis=1, keepdims=True)
    m_sc[...] = m_new

    @pl.when(vi == n_v - 1)
    def _finish():
        lse_ref[...] = m_sc[...] + jnp.log(s_sc[...])
        gold_ref[...] = g_sc[...]


# Scoped VMEM the tile plan may take. The v5e compiler refuses a kernel
# above 16 MiB ("exceeded scoped vmem limit"); _plan_bytes reads within
# 1% of the sizes it reports, and the last MiB absorbs that.
_VMEM_BUDGET = 15 * 1024 * 1024


def _plan_bytes(bt, bv, h, itemsize):
    """Scoped VMEM of the forward kernel at tiles (bt, bv): the x and W
    tiles (each double buffered), the f32 logits tile, and twelve [bt, 1]
    columns (labels and the two outputs, double buffered; three scratch
    accumulators; three temporaries) that each pad to 128 f32 lanes.
    Fitted to what libtpu 0.0.34 reports when it refuses a plan at
    H=2048 bf16: (1024, 256) 16.98M, (768, 512) 16.14M, (512, 1024)
    16.97M against 17.0M, 16.0M and 17.0M here."""
    return (2 * bt * h * itemsize + 2 * bv * h * itemsize + bt * bv * 4
            + 12 * bt * 128 * 4)


def _pick_block_t(t, h, itemsize):
    """Largest token block dividing T whose plan fits the budget with a
    256-wide vocab tile: a larger token block re-streams W fewer times,
    and the W stream is the forward's bandwidth term. At the flagship
    shape (T=6144 H=2048 bf16) this gives 768. Chosen for fit, not timed:
    the tile sweep on the chip is a later perf_opt."""
    for bt in (1024, 768, 512, 384, 256, 128, 64, 32, 16, 8):
        if t % bt == 0 and _plan_bytes(bt, 256, h, itemsize) <= _VMEM_BUDGET:
            return bt
    return t


def _pick_block_v(bt, h, itemsize):
    """Widest vocab tile the budget leaves beside token block bt."""
    for bv in (512, 384, 256):
        if _plan_bytes(bt, bv, h, itemsize) <= _VMEM_BUDGET:
            return bv
    return 128


FWD_NAME = "pallas_linear_ce_fwd"


def _fwd(x, w, labels, *, block_t, block_v, w_layout, interpret):
    t, h = x.shape
    vocab = w.shape[0] if w_layout == "vh" else w.shape[1]
    n_t = t // block_t
    n_v = -(-vocab // block_v)
    if w_layout == "vh":
        wspec = pl.BlockSpec((block_v, h), lambda ti, vi: (vi, _i0()))
    else:
        wspec = pl.BlockSpec((h, block_v), lambda ti, vi: (_i0(), vi))
    lse, gold = pl.pallas_call(
        functools.partial(_fwd_kernel, n_v=n_v, block_v=block_v, vocab=vocab,
                          w_layout=w_layout),
        out_shape=(jax.ShapeDtypeStruct((t, 1), jnp.float32),
                   jax.ShapeDtypeStruct((t, 1), jnp.float32)),
        grid=(n_t, n_v),
        in_specs=[
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, _i0())),
            pl.BlockSpec((block_t, h), lambda ti, vi: (ti, _i0())),
            wspec,
        ],
        out_specs=(pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, _i0())),
                   pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, _i0()))),
        scratch_shapes=[pltpu.VMEM((block_t, 1), jnp.float32),
                        pltpu.VMEM((block_t, 1), jnp.float32),
                        pltpu.VMEM((block_t, 1), jnp.float32)],
        interpret=interpret,
        name=FWD_NAME,
    )(labels.reshape(t, 1).astype(jnp.int32), x, w)
    return lse[:, 0], gold[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _linear_ce(x, w, labels, block_t, block_v, w_layout, interpret,
               bwd_chunks):
    """Per-token (logsumexp, gold logit) of logits = x @ W over the vocab
    columns W holds. The loss is lse - gold; the two come out apart so
    that vocab shards can be combined before the subtraction."""
    return _fwd(x, w, labels, block_t=block_t, block_v=block_v,
                w_layout=w_layout, interpret=interpret)


def _linear_ce_fwd(x, w, labels, block_t, block_v, w_layout, interpret,
                   bwd_chunks):
    lse, gold = _fwd(x, w, labels, block_t=block_t, block_v=block_v,
                     w_layout=w_layout, interpret=interpret)
    return (lse, gold), (x, w, labels, lse)


def _linear_ce_bwd(block_t, block_v, w_layout, interpret, bwd_chunks,
                   res, g):
    g_lse, g_gold = g
    x, w, labels, lse = res
    t, h = x.shape
    nc = bwd_chunks
    while t % nc:
        nc -= 1
    ct = t // nc
    dxs = []
    dw = None
    for c in range(nc):
        sl = slice(c * ct, (c + 1) * ct)
        xc, lc, sc = x[sl], labels[sl], lse[sl]
        if w_layout == "vh":
            logits = lax.dot_general(xc, w, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        else:
            logits = jnp.dot(xc, w, preferred_element_type=jnp.float32)
        # d lse / d logits is the softmax over W's columns, d gold / d
        # logits the one-hot of the label (no column when another vocab
        # shard holds it); for loss = lse - gold the cotangents are
        # (g, -g) and this is the familiar (p - onehot) * g
        p = jnp.exp(logits - sc[:, None])
        onehot = (lax.broadcasted_iota(jnp.int32, logits.shape, 1)
                  == lc[:, None].astype(jnp.int32))
        # bf16 dlogits: half the checkpoint path's f32 bytes
        dlog = (p * g_lse[sl][:, None]
                + jnp.where(onehot, g_gold[sl][:, None], jnp.float32(0.0))
                ).astype(x.dtype)
        if w_layout == "vh":
            dxs.append(jnp.dot(dlog, w, preferred_element_type=jnp.float32)
                       .astype(x.dtype))
            dwc = lax.dot_general(dlog, xc, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        else:
            dxs.append(lax.dot_general(dlog, w, (((1,), (1,)), ((), ())),
                                       preferred_element_type=jnp.float32)
                       .astype(x.dtype))
            dwc = lax.dot_general(xc, dlog, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dw = dwc if dw is None else dw + dwc
    dx = jnp.concatenate(dxs, axis=0) if len(dxs) > 1 else dxs[0]
    return dx, dw.astype(w.dtype), None


_linear_ce.defvjp(_linear_ce_fwd, _linear_ce_bwd)


def use_linear_ce(t, h, v):
    """Gate: TPU-class platform, MXU-friendly dims (mirrors use_fused_mha)."""
    import os
    force = os.environ.get("PADDLE_TPU_LINEAR_CE")
    if force == "0":
        return False
    if force != "1":
        if not on_tpu():
            return False
    return h % 128 == 0 and t % 8 == 0 and v >= 1024


def linear_cross_entropy(x, w, labels, *, w_layout="vh", block_t=None,
                         block_v=None, bwd_chunks=None, interpret=False):
    """Per-token softmax-CE of logits = x @ Wᵀ (w_layout="vh", W [V, H]) or
    x @ W (w_layout="hv", W [H, V]), with logits never materialized in the
    forward. x: [T, H]; labels: [T] int. Returns f32 [T] losses.

    Under a mesh the kernel runs per shard (`shard_kernel`): tokens split
    over dp, and W's vocab axis over mp — the layout the vocab-parallel
    embedding and the column-parallel head already keep it in. Each
    vocab shard yields the logsumexp and the gold logit over its own
    columns; the shards combine them (pmax/psum of [T] vectors, the only
    collectives) before the subtraction. Tiles are planned from the
    shard's shapes.
    """
    import os
    vh = w_layout == "vh"
    if bwd_chunks is None:
        bwd_chunks = int(os.environ.get("PADDLE_TPU_LINEAR_CE_BC", "2"))
    vocab = w.shape[0] if vh else w.shape[1]
    w_spec = ("mp", None) if vh else (None, "mp")
    args = (x, w, labels)
    specs = (("dp", None), w_spec, ("dp",))
    n_v = (_mesh.mesh_axis_size("mp")
           if "mp" in _mesh.kernel_axes(args, specs) else 1)

    def local(x, w, labels, first_col):
        # shapes are the shard's own from here on
        t, h = x.shape
        bt = block_t or int(os.environ.get("PADDLE_TPU_LINEAR_CE_BT", "0")) \
            or _pick_block_t(t, h, x.dtype.itemsize)
        bv = block_v or int(os.environ.get("PADDLE_TPU_LINEAR_CE_BV", "0")) \
            or _pick_block_v(bt, h, x.dtype.itemsize)
        if t % bt:
            raise ValueError(f"linear_cross_entropy: T={t} not divisible "
                             f"by block_t={bt}")
        labels = labels.astype(jnp.int32)
        if n_v > 1:
            # a label another shard holds matches no column here
            v_local = w.shape[0] if vh else w.shape[1]
            labels = labels - first_col[0]
            labels = jnp.where((labels >= 0) & (labels < v_local), labels,
                               jnp.int32(-1))
        lse, gold = _linear_ce(x, w, labels, int(bt), int(bv),
                               str(w_layout), bool(interpret),
                               int(bwd_chunks))
        if n_v > 1:
            m = lax.pmax(lax.stop_gradient(lse), "mp")
            lse = m + jnp.log(lax.psum(jnp.exp(lse - m), "mp"))
            gold = lax.psum(gold, "mp")
        return lse - gold

    first_col = jnp.arange(n_v, dtype=jnp.int32) * (vocab // n_v)
    return _mesh.shard_kernel(local, args + (first_col,),
                              specs + (("mp",),), ("dp",))
