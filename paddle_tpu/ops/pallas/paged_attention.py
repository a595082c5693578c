"""Pallas TPU kernels: decode and ragged multi-token attention over a
paged KV pool.

The serving decode problem (ISSUE 5; Ragged Paged Attention, arxiv
2604.15464): each batch row's KV cache is a list of fixed-size blocks
scattered through one [num_blocks, block, H, D] pool, named by an int32
block table. The XLA-visible alternative — gather the blocks into a
contiguous [B, L, H, D] buffer, then attend — materializes the whole
working set in HBM twice per step (`paged_attention_reference`, the
CPU/tier-1 path). This kernel instead walks the block table directly:

  grid (B, MB)   one program per (batch row, table slot), MB innermost so
                 the online-softmax state lives in VMEM scratch across a
                 row's blocks (same accumulator pattern as
                 flash_attention.py);
  block fetch    the K/V BlockSpec index maps read the SCALAR-PREFETCHED
                 block table — Pallas DMAs exactly the pool page the row
                 needs next, so HBM traffic is the true KV bytes, not the
                 padded envelope. Table padding entries are 0 (the trash
                 block), and consecutive same-index fetches collapse in
                 the pipeline, so invalid tail slots cost ~nothing;
  masking        global column j*bs + i is attendable iff < lens[row];
                 blocks entirely past lens skip their accumulate
                 (`pl.when`), partial blocks mask per column.

Compute is deliberately VPU-only (broadcast-multiply-reduce per head, the
q vector is 1 token — there is no MXU shape here worth a relayout); decode
attention is KV-bandwidth-bound, so the fetch pattern IS the optimization.
Numerics: f32 scores/softmax/accumulation whatever the pool dtype (like
the other Pallas kernels here — the XLA static-cache path instead stores
scores in the model dtype, so bf16 models' kernel-vs-reference parity is
approximate; chip_smoke.py's serve phase states what agreement it
requires on the chip instead).

Rows with lens == 0 (dummy batch slots) output zeros (the reference path
outputs masked-uniform garbage instead — both are dropped by callers, and
the parity tests compare live rows).

CPU validation runs this kernel in interpret mode (tests);
tests/test_chip_compile.py compiles every variant for the described chip
at GPT-1.3B widths, and chip_smoke.py's kernel phase compares them with
the references on the chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _i0  # i32 index-map literal (Mosaic x64 rule)

_NEG = -1e30


def _row_map(ndim):
    """Index map of a q / out tile: batch row `bi`, the rest whole."""
    return lambda bi, j, tables, aux: (bi,) + (_i0(),) * (ndim - 1)


def _page_map(ndim):
    """Index map of a pool tile: the page the scalar-prefetched block
    table names for (row, slot)."""
    return lambda bi, j, tables, aux: (tables[bi, j],) + (_i0(),) * (ndim - 1)


def _kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
            m_sc, l_sc, acc_sc, *, scale, nh, bs, n_slots):
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    ln = lens_ref[b]

    @pl.when(j * bs < ln)
    def _step():
        q = q_ref[0].astype(jnp.float32)            # [nh, hd]
        k = k_ref[0].astype(jnp.float32)            # [bs, nh, hd]
        v = v_ref[0].astype(jnp.float32)
        col = j * bs + lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
        keep = col < ln
        # per-head online softmax on the VPU: q is one token, so the
        # "matmul" is a broadcast multiply + lane reduction; nh unrolls
        # statically (serving configs keep nh <= 40)
        for h in range(nh):
            s = jnp.sum(k[:, h, :] * q[h:h + 1, :], axis=-1,
                        keepdims=True) * scale      # [bs, 1]
            s = jnp.where(keep, s, jnp.asarray(_NEG, s.dtype))
            m_prev = m_sc[h:h + 1, :]               # [1, 1]
            l_prev = l_sc[h:h + 1, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)                  # [bs, 1]
            corr = jnp.exp(m_prev - m_new)
            m_sc[h:h + 1, :] = m_new
            l_sc[h:h + 1, :] = corr * l_prev + jnp.sum(p, axis=0,
                                                       keepdims=True)
            acc_sc[h:h + 1, :] = corr * acc_sc[h:h + 1, :] + jnp.sum(
                p * v[:, h, :], axis=0, keepdims=True)

    @pl.when(j == n_slots - 1)
    def _finish():
        l = jnp.maximum(l_sc[...], 1e-30)           # lens==0 rows -> zeros
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)


def _kernel_q8(tables_ref, lens_ref, q_ref, kc_ref, ks_ref, vc_ref,
               vs_ref, o_ref, m_sc, l_sc, acc_sc, *, scale, nh, bs,
               n_slots):
    """int8 paged decode attention (ISSUE 10): the pools carry int8
    codes + per-(row, head) f32 factored scales. Same online-softmax
    skeleton as `_kernel`; the static int8-KV trick applies per block —
    the scale is constant over head_dim, so it factors OUT of both
    contractions: codes stream as bare int8->f32 converts and the scale
    multiplies land on the [bs, 1] score / prob columns."""
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    ln = lens_ref[b]

    @pl.when(j * bs < ln)
    def _step():
        q = q_ref[0].astype(jnp.float32)            # [nh, hd]
        kc = kc_ref[0].astype(jnp.float32)          # [bs, nh, hd] codes
        ks = ks_ref[0]                              # [bs, nh] f32 scales
        vc = vc_ref[0].astype(jnp.float32)
        vs = vs_ref[0]
        col = j * bs + lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
        keep = col < ln
        for h in range(nh):
            s = jnp.sum(kc[:, h, :] * q[h:h + 1, :], axis=-1,
                        keepdims=True) * (ks[:, h:h + 1] * scale)
            s = jnp.where(keep, s, jnp.asarray(_NEG, s.dtype))
            m_prev = m_sc[h:h + 1, :]
            l_prev = l_sc[h:h + 1, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            m_sc[h:h + 1, :] = m_new
            l_sc[h:h + 1, :] = corr * l_prev + jnp.sum(p, axis=0,
                                                       keepdims=True)
            acc_sc[h:h + 1, :] = corr * acc_sc[h:h + 1, :] + jnp.sum(
                (p * vs[:, h:h + 1]) * vc[:, h, :], axis=0, keepdims=True)

    @pl.when(j == n_slots - 1)
    def _finish():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)


DECODE_Q8_NAME = "pallas_paged_q8_decode"


def paged_attention_q8_kernel(q, kc_pool, ks_pool, vc_pool, vs_pool,
                              tables, lens, *, scale=None,
                              interpret=False):
    """q [B, 1, H, D] (or [B, H, D]); code pools int8 [NB, bs, H, D];
    scale pools f32 [NB, bs, H]; tables [B, MB] i32; lens [B]. Returns
    the same layout/dtype as q."""
    squeezed = q.ndim == 4
    if squeezed:
        if q.shape[1] != 1:
            raise ValueError(f"paged decode kernel serves one token per "
                             f"row; got q seq len {q.shape[1]}")
        q3 = q[:, 0]
    else:
        q3 = q
    b, nh, hd = q3.shape
    nb, bs = kc_pool.shape[0], kc_pool.shape[1]
    mb = tables.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    pool_spec = pl.BlockSpec((1, bs, nh, hd), _page_map(4))
    scale_spec = pl.BlockSpec((1, bs, nh), _page_map(3))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=[
            pl.BlockSpec((1, nh, hd), _row_map(3)),
            pool_spec, scale_spec, pool_spec, scale_spec,
        ],
        out_specs=pl.BlockSpec((1, nh, hd), _row_map(3)),
        scratch_shapes=[pltpu.VMEM((nh, 1), jnp.float32),
                        pltpu.VMEM((nh, 1), jnp.float32),
                        pltpu.VMEM((nh, hd), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_q8, scale=scale, nh=nh, bs=bs,
                          n_slots=mb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, hd), q.dtype),
        interpret=interpret,
        name=DECODE_Q8_NAME,
    )(tables.astype(jnp.int32), lens.astype(jnp.int32), q3,
      kc_pool, ks_pool, vc_pool, vs_pool)
    return out[:, None] if squeezed else out


# ------------------------------------------- ragged multi-token kernels
# ISSUE 11 (Ragged Paged Attention, arxiv 2604.15464): one kernel serving
# k >= 1 query tokens per row against that row's block-table KV with a
# per-row START offset — query row i of batch row b sits at global
# position start[b] + i and attends pool columns <= its own position
# (causal within the window, over the cached prefix + the window itself).
# This is the [B, k] primitive behind suffix prefill after a partial
# prefix hit, chunked prefill, and speculative-decode verification; k = 1
# with start = lens degenerates to the decode kernel above (parity
# pinned in tests). Unlike the 1-token kernel the per-block math here IS
# an MXU shape where k permits: scores are a [k, hd] x [hd, bs] dot and
# the value accumulate a [k, bs] x [bs, hd] dot, so wide windows (suffix
# prefill at k = prompt_cap, spec verify at k = spec window) run on the
# MXU while the fetch pattern stays the block-table walk.

def _kernel_multi(tables_ref, start_ref, q_ref, k_ref, v_ref, o_ref,
                  m_sc, l_sc, acc_sc, *, scale, nh, bs, s, n_slots):
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    st = start_ref[b]

    @pl.when(j * bs <= st + s - 1)       # block wholly past the window's
    def _step():                         # causal frontier: skip the fetch
        q = q_ref[0].astype(jnp.float32)            # [s, nh, hd]
        k = k_ref[0].astype(jnp.float32)            # [bs, nh, hd]
        v = v_ref[0].astype(jnp.float32)
        col = j * bs + lax.broadcasted_iota(jnp.int32, (s, bs), 1)
        row = lax.broadcasted_iota(jnp.int32, (s, bs), 0)
        keep = col <= st + row           # causal across prefix + window
        for h in range(nh):
            sc = lax.dot_general(q[:, h, :], k[:, h, :],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            sc = sc * scale                          # [s, bs]
            sc = jnp.where(keep, sc, jnp.asarray(_NEG, sc.dtype))
            m_prev = m_sc[h]                         # [s, 1]
            l_prev = l_sc[h]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            p = jnp.exp(sc - m_new)                  # [s, bs]
            corr = jnp.exp(m_prev - m_new)
            m_sc[h] = m_new
            l_sc[h] = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc_sc[h] = corr * acc_sc[h] + lax.dot_general(
                p, v[:, h, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [s, hd]

    @pl.when(j == n_slots - 1)
    def _finish():
        for h in range(nh):
            l = jnp.maximum(l_sc[h], 1e-30)
            o_ref[0, :, h, :] = (acc_sc[h] / l).astype(o_ref.dtype)


def _kernel_multi_q8(tables_ref, start_ref, q_ref, kc_ref, ks_ref, vc_ref,
                     vs_ref, o_ref, m_sc, l_sc, acc_sc, *, scale, nh, bs,
                     s, n_slots):
    """int8 form of `_kernel_multi`: codes stream as bare int8->f32
    converts into the dots; the per-(row, head) factored scales multiply
    the [s, bs] score / probability tiles (same trick as `_kernel_q8`,
    MXU-shaped like `_kernel_multi`)."""
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    st = start_ref[b]

    @pl.when(j * bs <= st + s - 1)
    def _step():
        q = q_ref[0].astype(jnp.float32)            # [s, nh, hd]
        kc = kc_ref[0].astype(jnp.float32)          # [bs, nh, hd] codes
        ks = ks_ref[0]                              # [bs, nh] f32 scales
        vc = vc_ref[0].astype(jnp.float32)
        vs = vs_ref[0]
        col = j * bs + lax.broadcasted_iota(jnp.int32, (s, bs), 1)
        row = lax.broadcasted_iota(jnp.int32, (s, bs), 0)
        keep = col <= st + row
        for h in range(nh):
            sc = lax.dot_general(q[:, h, :], kc[:, h, :],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            sc = sc * (ks[:, h][None, :] * scale)    # [s, bs]
            sc = jnp.where(keep, sc, jnp.asarray(_NEG, sc.dtype))
            m_prev = m_sc[h]
            l_prev = l_sc[h]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            p = jnp.exp(sc - m_new)
            corr = jnp.exp(m_prev - m_new)
            m_sc[h] = m_new
            l_sc[h] = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc_sc[h] = corr * acc_sc[h] + lax.dot_general(
                p * vs[:, h][None, :], vc[:, h, :],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(j == n_slots - 1)
    def _finish():
        for h in range(nh):
            l = jnp.maximum(l_sc[h], 1e-30)
            o_ref[0, :, h, :] = (acc_sc[h] / l).astype(o_ref.dtype)


PREFIX_NAME = "pallas_paged_prefix"


def paged_prefix_attention_kernel(q, k_pool, v_pool, tables, start, *,
                                  scale=None, interpret=False):
    """Ragged multi-token paged attention: q [B, S, H, D] query tokens at
    global positions start[b] + i; pools [NB, bs, H, D]; tables [B, MB]
    i32; start [B] i32. Each query row attends every pool column <= its
    own position — the kernel form of `paged_prefix_attention_reference`
    (suffix prefill, chunked prefill, spec-decode verify; S = 1 with
    start = lens is exactly the decode case). Returns q's layout."""
    b, s, nh, hd = q.shape
    nb, bs = k_pool.shape[0], k_pool.shape[1]
    mb = tables.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    pool_spec = pl.BlockSpec((1, bs, nh, hd), _page_map(4))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=[
            pl.BlockSpec((1, s, nh, hd), _row_map(4)),
            pool_spec, pool_spec,
        ],
        out_specs=pl.BlockSpec((1, s, nh, hd), _row_map(4)),
        scratch_shapes=[pltpu.VMEM((nh, s, 1), jnp.float32),
                        pltpu.VMEM((nh, s, 1), jnp.float32),
                        pltpu.VMEM((nh, s, hd), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel_multi, scale=scale, nh=nh, bs=bs, s=s,
                          n_slots=mb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, nh, hd), q.dtype),
        interpret=interpret,
        name=PREFIX_NAME,
    )(tables.astype(jnp.int32), start.astype(jnp.int32), q, k_pool, v_pool)


PREFIX_Q8_NAME = "pallas_paged_q8_prefix"


def paged_prefix_attention_q8_kernel(q, kc_pool, ks_pool, vc_pool, vs_pool,
                                     tables, start, *, scale=None,
                                     interpret=False):
    """int8 ragged multi-token paged attention: the q8 pools form of
    `paged_prefix_attention_kernel` (codes int8 [NB, bs, H, D], factored
    scales f32 [NB, bs, H])."""
    b, s, nh, hd = q.shape
    nb, bs = kc_pool.shape[0], kc_pool.shape[1]
    mb = tables.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    pool_spec = pl.BlockSpec((1, bs, nh, hd), _page_map(4))
    scale_spec = pl.BlockSpec((1, bs, nh), _page_map(3))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=[
            pl.BlockSpec((1, s, nh, hd), _row_map(4)),
            pool_spec, scale_spec, pool_spec, scale_spec,
        ],
        out_specs=pl.BlockSpec((1, s, nh, hd), _row_map(4)),
        scratch_shapes=[pltpu.VMEM((nh, s, 1), jnp.float32),
                        pltpu.VMEM((nh, s, 1), jnp.float32),
                        pltpu.VMEM((nh, s, hd), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel_multi_q8, scale=scale, nh=nh, bs=bs, s=s,
                          n_slots=mb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, nh, hd), q.dtype),
        interpret=interpret,
        name=PREFIX_Q8_NAME,
    )(tables.astype(jnp.int32), start.astype(jnp.int32), q,
      kc_pool, ks_pool, vc_pool, vs_pool)


DECODE_NAME = "pallas_paged_decode"


def paged_attention_kernel(q, k_pool, v_pool, tables, lens, *, scale=None,
                           interpret=False):
    """q [B, 1, H, D] (or [B, H, D]); pools [NB, bs, H, D]; tables
    [B, MB] i32; lens [B] = attendable rows per batch entry. Returns the
    same layout as q."""
    squeezed = q.ndim == 4
    if squeezed:
        if q.shape[1] != 1:
            raise ValueError(f"paged decode kernel serves one token per "
                             f"row; got q seq len {q.shape[1]}")
        q3 = q[:, 0]
    else:
        q3 = q
    b, nh, hd = q3.shape
    nb, bs = k_pool.shape[0], k_pool.shape[1]
    mb = tables.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=[
            pl.BlockSpec((1, nh, hd), _row_map(3)),
            pl.BlockSpec((1, bs, nh, hd), _page_map(4)),
            pl.BlockSpec((1, bs, nh, hd), _page_map(4)),
        ],
        out_specs=pl.BlockSpec((1, nh, hd), _row_map(3)),
        scratch_shapes=[pltpu.VMEM((nh, 1), jnp.float32),
                        pltpu.VMEM((nh, 1), jnp.float32),
                        pltpu.VMEM((nh, hd), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, nh=nh, bs=bs, n_slots=mb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, hd), q.dtype),
        interpret=interpret,
        name=DECODE_NAME,
    )(tables.astype(jnp.int32), lens.astype(jnp.int32), q3, k_pool, v_pool)
    return out[:, None] if squeezed else out
