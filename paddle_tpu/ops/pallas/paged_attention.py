"""Pallas TPU kernels: decode and ragged multi-token attention over a
paged KV pool.

The serving decode problem (ISSUE 5; Ragged Paged Attention, arxiv
2604.15464): each batch row's KV cache is a list of fixed-size blocks
scattered through one [num_blocks, block, H, D] pool, named by an int32
block table. The XLA-visible alternative — gather the blocks into a
contiguous [B, L, H, D] buffer, then attend — materializes the whole
working set in HBM twice per step (`paged_attention_reference`, the
CPU/tier-1 path). The kernels here walk the block table instead.

The decode kernel (`paged_attention_kernel`, one query token a row) walks
each row's own pages, several a step (`_kernel_walk`; ISSUE 27):

  one program    the whole batch: q and the output lie whole in VMEM
                 (4 KiB a row), the pools stay in HBM (`pl.ANY`), the
                 block table and `lens` are scalar-prefetched. A loop
                 walks the rows that attend anything, in order
                 (`_PageWalk`); a row of lens 0 (a slot without a
                 request, a row past its EOS: the models hand a done row
                 0) is stepped over by a scalar compare: no DMA, no
                 product, zeros out (0.03 us a row on the chip, PERF.md
                 section 6, PR 35).
  blocks         a row of `lens` tokens costs cdiv(lens, pages_per_step
                 * bs) trips of a `fori_loop`, whatever the table's
                 width. `_pages_per_step` takes the step from the shapes:
                 what `_WALK_VMEM_BUDGET` holds of (K, V) x two slots (8
                 pages = 128 tokens = 1 MiB a step for bf16 pages of
                 16 x 16 x 128).
  fetch          one `make_async_copy` per LIVE page of the block into a
                 double-buffered VMEM slot; the row's next block, or the
                 first of the next row that attends anything, is in
                 flight while this one is computed. Table slots past a
                 row's last page are never read: nothing past `lens`
                 reaches the output (padding slots may point anywhere,
                 page 0 may hold NaN). What a slot holds past the block's
                 live pages is zeros or an earlier row's own page, and
                 meets a zero probability.
  compute        the block as a [T*nh, hd] matrix (row = (token, head)),
                 straight from the slot in the pools' dtype: scores are
                 q [nh, hd] x block^T -> [nh, T*nh] on the MXU, of which
                 row h keeps its own head's lanes (and columns < lens);
                 the online-softmax state is [nh, 1] for all heads at
                 once; the value product is p [nh, T*nh] x block on the
                 MXU. The MXU does nh times the needed multiply-adds and
                 is idle otherwise; K and V never pass through the VPU.

A DMA can slice a page out of a pool only where the page fills whole
(8, 128) tiles of the pool's layout (`_pages_dma_sliceable`: heads % 8,
head_dim % 128; 1.3B, 6.7B, 13B, and their halves under mp). Other heads
(2.7B's 80, 125M's 12 x 64) keep the grid over (row, table slot), one
page a program, VPU-only (`_kernel_slots`): its time follows the table's
width, not the live KV. The int8 decode kernel (`_kernel_q8`) and the
ragged multi-token kernels are that grid too:

  grid (B, MB)   one program per (batch row, table slot), MB innermost so
                 the online-softmax state lives in VMEM scratch across a
                 row's blocks (same accumulator pattern as
                 flash_attention.py);
  block fetch    the K/V BlockSpec index maps read the SCALAR-PREFETCHED
                 block table. Table padding entries are 0 (the trash
                 block); a slot past `lens` skips its arithmetic
                 (`pl.when`), not its grid step (~0.1 us each on v5e,
                 PERF.md section 6, PR 27);
  masking        global column j*bs + i is attendable iff < lens[row];
                 partial blocks mask per column.

Numerics: f32 scores/softmax/accumulation whatever the pool dtype, one
rounding to the output's dtype (the walk hands the MXU a bf16 pool's
probabilities as (hi, lo) bf16 halves, and asks float32 pools for the
float32 contraction). The XLA static-cache path instead stores scores in
the model dtype, so bf16 models' kernel-vs-reference parity is
approximate; chip_smoke.py's serve phase states what agreement it
requires on the chip instead.

Rows with lens == 0 (dummy batch slots) output zeros (the reference path
outputs masked-uniform garbage instead — both are dropped by callers, and
the parity tests compare live rows).

CPU validation runs these kernels in interpret mode (tests; the walk with
`_pages_dma_sliceable` patched, since toy heads fill no tile);
tests/test_chip_compile.py compiles every variant for the described chip
at GPT-1.3B widths and at the serving cells' geometry, and chip_smoke.py's
kernel phase and tools/validate_paged_tpu.py compare them with the
references on the chip.
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


def _kernel_slots(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
            m_sc, l_sc, acc_sc, *, scale, nh, bs, n_slots, group=1):
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
            s = jnp.sum(k[:, h // group, :] * q[h:h + 1, :], axis=-1,
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
                p * v[:, h // group, :], axis=0, keepdims=True)

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
                  m_sc, l_sc, acc_sc, *, scale, nh, bs, s, n_slots, group=1):
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
            sc = lax.dot_general(q[:, h, :], k[:, h // group, :],
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
                p, v[:, h // group, :], (((1,), (0,)), ((), ())),
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
    global positions start[b] + i; pools [NB, bs, Hkv, D], Hkv dividing H
    (query head h reads KV head h // (H / Hkv); Hkv = H is the kernel it
    always was); tables [B, MB] i32; start [B] i32. Each query row
    attends every pool column <= its own position — the kernel form of
    `paged_prefix_attention_reference` (suffix prefill, chunked prefill,
    spec-decode verify; S = 1 with start = lens is exactly the decode
    case). Returns q's layout."""
    b, s, nh, hd = q.shape
    nb, bs = k_pool.shape[0], k_pool.shape[1]
    nkv = _kv_heads(nh, k_pool)
    mb = tables.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    pool_spec = pl.BlockSpec((1, bs, nkv, hd), _page_map(4))
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
                          n_slots=mb, group=nh // nkv),
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

# K and V blocks, two slots each: what the walk keeps in VMEM. Well under
# the 16 MiB scoped limit, beside the [nh, T*nh] f32 score tiles.
_WALK_VMEM_BUDGET = 2 * 1024 * 1024
_MAX_PAGES_PER_STEP = 16


def _pages_per_step(page_bytes, table_width):
    """Pages one step of the walk fetches and computes: as many as the
    VMEM budget holds of (K, V) x two slots, at most the table."""
    fit = _WALK_VMEM_BUDGET // (4 * page_bytes)
    return max(1, min(_MAX_PAGES_PER_STEP, table_width, fit))


def _mxu_rows(x, dtype):
    """x [r, n] as the left operand of a matmul against a block of the
    pools, in the pools' dtype and without losing x's bits: a float32 x
    meets a narrower pool as its (hi, lo) halves stacked into [2r, n] (the
    MXU streams rows; the block, its weights, is the cost). `_sum_rows`
    adds the halves' products."""
    if x.dtype == dtype or jnp.dtype(dtype).itemsize >= 4:
        return x.astype(dtype)
    x = x.astype(jnp.float32)
    hi = x.astype(dtype)
    lo = (x - hi.astype(jnp.float32)).astype(dtype)
    return jnp.concatenate([hi, lo], axis=0)


def _block_dot(x, block, contract):
    """x [r, .] against a [w, hd] block of a pool, float32 out. float32
    pools ask for the float32 contraction (the default rounds both sides
    to bf16); Mosaic refuses that precision on bf16 tiles, which are
    exact in one pass anyway."""
    precision = (lax.Precision.HIGHEST if block.dtype == jnp.float32
                 else None)
    return lax.dot_general(x, block, (contract, ((), ())),
                           precision=precision,
                           preferred_element_type=jnp.float32)


def _sum_rows(y, r):
    """Undo `_mxu_rows` on a product: [k*r, n] -> [r, n]."""
    return sum(y[i:i + r] for i in range(0, y.shape[0], r))


class _PageWalk:
    """The fetch side of a decode walk, shared by the kernels that walk a
    list of pages a row (`_kernel_walk`, `_kernel_grouped_walk`, the
    latent kernel): blocks of `pps` pages into one of two VMEM slots, the
    row's next block, or the next LIVE row's first, in flight while this
    one is computed. A row of no tokens is not part of the walk: it costs
    no DMA and no product, and the row before it looks past it.

    `toks_ref` [rows] the tokens a row attends (scalar-prefetched),
    `width` the slots of a row's list, `copies(row, j, slot, i)` the
    async copies that bring list entry `j` of `row` to place `i` of
    `slot`."""

    def __init__(self, toks_ref, width, *, bs, pps, copies):
        self.toks_ref, self.n_rows = toks_ref, toks_ref.shape[0]
        self.width, self.bs, self.pps, self.copies = width, bs, pps, copies

    @staticmethod
    def cdiv(a, d):                   # i32 throughout (Mosaic x64 rule)
        return lax.div(a + (d - 1), jnp.int32(d))

    def _row(self, row):              # a row number that can be read
        return jnp.minimum(jnp.asarray(row, jnp.int32), self.n_rows - 1)

    def tokens_of(self, row):
        return jnp.minimum(self.toks_ref[self._row(row)],
                           self.width * self.bs)

    def pages_of(self, row):
        return self.cdiv(self.tokens_of(row), self.bs)

    def next_live(self, row):
        """The first row from `row` on that attends anything; `n_rows`
        where none does. A handful of scalar operations a row passed."""
        return lax.while_loop(
            lambda r: (r < self.n_rows) & (self.toks_ref[self._row(r)] <= 0),
            lambda r: r + 1, jnp.asarray(row, jnp.int32))

    def _dmas(self, op, row, blk, slot, n_pages):
        """"start" or "wait" the copies of block `blk` of `row`: its live
        pages only."""
        slot = jnp.asarray(slot, jnp.int32)         # (Mosaic x64 rule)
        for i in range(self.pps):
            @pl.when(blk * self.pps + i < n_pages)
            def _():
                for copy in self.copies(row, blk * self.pps + i, slot,
                                        jnp.int32(i)):
                    getattr(copy, op)()

    def start_first(self, row, slot):
        """Start the first block of `row` (no row: nothing) into `slot`."""
        @pl.when(row < self.n_rows)
        def _():
            self._dmas("start", self._row(row), 0, slot, self.pages_of(row))

    def blocks(self, row, nxt, slot0, step, init):
        """Walk live `row`, whose first block is in flight into `slot0`:
        `step(blk, slot, carry)` sees block `blk` landed in `slot`. Its
        last block starts the first of `nxt`, the next live row. Returns
        (the last carry, the slot `nxt` finds its first block in)."""
        n_pages = self.pages_of(row)
        n_blocks = self.cdiv(n_pages, self.pps)

        def body(blk, carry):
            slot = lax.rem(slot0 + blk, jnp.int32(2))

            @pl.when(blk + 1 < n_blocks)
            def _():
                self._dmas("start", row, blk + 1, 1 - slot, n_pages)

            @pl.when(blk + 1 == n_blocks)
            def _():
                self.start_first(nxt, 1 - slot)

            self._dmas("wait", row, blk, slot, n_pages)
            return step(blk, slot, carry)

        carry = lax.fori_loop(jnp.int32(0), n_blocks, body, init)
        return carry, lax.rem(slot0 + n_blocks, jnp.int32(2))

    def live_rows(self, attend):
        """One program over the whole batch: `attend(row, nxt, slot0)`
        walks a live row (through `blocks`) and returns the next one's
        slot. Rows without tokens are stepped over."""
        first = self.next_live(0)
        self.start_first(first, 0)

        def body(carry):
            row, slot0 = carry
            nxt = self.next_live(row + 1)
            return nxt, attend(row, nxt, slot0)

        lax.while_loop(lambda c: c[0] < self.n_rows, body,
                       (first, jnp.int32(0)))


def _kv_copies(pools, bufs, sems, page, slot, i):
    """The copies of one page (`page` indexes the pools' leading axes) of
    K and of V to place `i` of `slot`: a semaphore each a slot."""
    return [pltpu.make_async_copy(pool.at[page], buf.at[slot, i],
                                  sems.at[jnp.int32(n), slot])
            for n, (pool, buf) in enumerate(zip(pools, bufs))]


def _kernel_walk(tables_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
                 k_buf, v_buf, sems, lane_ref, *, scale, bs, pps):
    nh, hd = q_ref.shape[1], q_ref.shape[2]
    nkv = k_buf.shape[3]                            # KV heads of a page
    t = pps * bs                                    # tokens a block holds
    w = t * nkv                                     # its (token, head) rows

    def copies(row, j, slot, i):
        return _kv_copies((k_hbm, v_hbm), (k_buf, v_buf), sems,
                          tables_ref[row, j], slot, i)

    walk = _PageWalk(lens_ref, tables_ref.shape[1], bs=bs, pps=pps,
                     copies=copies)
    # row h of a score tile keeps the lanes of its own head: lane j is
    # (token j // nh, head j % nh) of the block. Kept for every row as the
    # lane's number where the head is the row's, else past every length:
    # one compare a block then masks head and length
    lane = lax.broadcasted_iota(jnp.int32, (nh, w), 1)
    head = lax.broadcasted_iota(jnp.int32, (nh, w), 0)
    if nh != nkv:           # grouped: row h keeps the lanes of KV head h // G
        head = lax.div(head, jnp.int32(nh // nkv))
    lane_ref[...] = jnp.where(lax.rem(lane, jnp.int32(nkv)) == head,
                              lane, jnp.int32(jnp.iinfo(jnp.int32).max))
    # a block's pages past its row's last are not fetched, and what a slot
    # holds there meets a probability of exactly 0: so it has to be
    # finite. Zero once; after that it is some row's own page
    v_buf[...] = jnp.zeros_like(v_buf)
    o_ref[...] = jnp.zeros_like(o_ref)              # rows of no tokens

    def attend(b, nxt, slot0):
        ln = walk.tokens_of(b)
        q = _mxu_rows(q_ref[b], k_buf.dtype)        # [nh or 2 nh, hd]

        def step(blk, slot, carry):
            m_prev, l_prev, acc = carry
            k = k_buf[slot].reshape(w, hd)
            v = v_buf[slot].reshape(w, hd)
            s = _sum_rows(_block_dot(q, k, ((1,), (1,))), nh) * scale
            keep = lane_ref[...] < (ln - blk * t) * nkv         # [nh, w]
            s = jnp.where(keep, s, jnp.asarray(_NEG, s.dtype))
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)              # exactly 0 off `keep`
            corr = jnp.exp(m_prev - m_new)
            l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
            pv = _block_dot(_mxu_rows(p, v.dtype), v, ((1,), (0,)))
            return m_new, l_new, corr * acc + _sum_rows(pv, nh)

        (_, l, acc), slot = walk.blocks(
            b, nxt, slot0, step,
            (jnp.full((nh, 1), _NEG, jnp.float32),
             jnp.zeros((nh, 1), jnp.float32),
             jnp.zeros((nh, hd), jnp.float32)))
        o_ref[b] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        return slot

    walk.live_rows(attend)


def _kv_heads(nh, k_pool):
    """KV heads of a [NB, bs, Hkv, D] pool under `nh` query heads."""
    nkv = k_pool.shape[2]
    if nh % nkv:
        raise ValueError(f"{nh} query heads over {nkv} KV heads: the "
                         f"groups must be whole")
    return nkv


def _pages_dma_sliceable(nh, hd):
    """Whether the chip's compiler lets a DMA slice one page out of a
    [NB, bs, nh, hd] pool: the pool is tiled over (nh, hd) in HBM, and a
    slice has to cover whole tiles (2.7B's heads of 80, 125M's 12 heads
    of 64 do not)."""
    return nh % 8 == 0 and hd % 128 == 0


def paged_attention_kernel(q, k_pool, v_pool, tables, lens, *, scale=None,
                           interpret=False):
    """q [B, 1, H, D] (or [B, H, D]); pools [NB, bs, Hkv, D], Hkv
    dividing H (query head h reads KV head h // (H / Hkv); Hkv = H is the
    kernel it always was); tables [B, MB] i32; lens [B] = attendable rows
    per batch entry. Returns the same layout as q."""
    squeezed = q.ndim == 4
    if squeezed:
        if q.shape[1] != 1:
            raise ValueError(f"paged decode kernel serves one token per "
                             f"row; got q seq len {q.shape[1]}")
        q3 = q[:, 0]
    else:
        q3 = q
    b, nh, hd = q3.shape
    bs = k_pool.shape[1]
    nkv = _kv_heads(nh, k_pool)
    mb = tables.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    if _pages_dma_sliceable(nkv, hd):
        pps = _pages_per_step(bs * nkv * hd * k_pool.dtype.itemsize, mb)
        # one program: q and the output whole (4 KiB a row), the rows
        # that attend anything walked in order inside it
        rows = pl.BlockSpec((b, nh, hd),
                            lambda i, tables, lens: (_i0(), _i0(), _i0()))
        pool = pl.BlockSpec(memory_space=pl.ANY)
        kernel = functools.partial(_kernel_walk, scale=scale, bs=bs, pps=pps)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[rows, pool, pool],
            out_specs=rows,
            scratch_shapes=[pltpu.VMEM((2, pps, bs, nkv, hd), k_pool.dtype),
                            pltpu.VMEM((2, pps, bs, nkv, hd), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.VMEM((nh, pps * bs * nkv), jnp.int32)],
        )
        semantics = ("arbitrary",)
    else:
        kernel = functools.partial(_kernel_slots, scale=scale, nh=nh, bs=bs,
                                   n_slots=mb, group=nh // nkv)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, mb),
            in_specs=[
                pl.BlockSpec((1, nh, hd), _row_map(3)),
                pl.BlockSpec((1, bs, nkv, hd), _page_map(4)),
                pl.BlockSpec((1, bs, nkv, hd), _page_map(4)),
            ],
            out_specs=pl.BlockSpec((1, nh, hd), _row_map(3)),
            scratch_shapes=[pltpu.VMEM((nh, 1), jnp.float32),
                            pltpu.VMEM((nh, 1), jnp.float32),
                            pltpu.VMEM((nh, hd), jnp.float32)],
        )
        semantics = None
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
        name=DECODE_NAME,
    )(tables.astype(jnp.int32), lens.astype(jnp.int32), q3, k_pool, v_pool)
    return out[:, None] if squeezed else out


# ------------------------------------------------ grouped KV heads, lists
# Fewer KV heads than query heads, and a LIST of pages a (row, KV head)
# instead of the row's table (ops/sparse_attention.py: the pages a
# block-sparse layer selected, or a short row's whole prefix). The pools
# are [NB, Hkv, bs, D]: a KV head's page is a [bs, D] tile of its own, a
# DMA slices it whole, and the G query heads of the group are the rows of
# ONE product against it (no lane of a score tile belongs to another
# head, so nothing of `_kernel_walk`'s head mask is needed). A row of the
# walk is a (row, KV head) pair; one program walks those that attend
# anything, in order, each fetching the next one's first block; only a
# list's last page may be partly filled.

GROUPED_DECODE_NAME = "pallas_paged_grouped_decode"


def grouped_pages_dma_sliceable(bs, hd, dtype):
    """Whether a [bs, hd] page of a [NB, Hkv, bs, hd] pool fills whole
    tiles of the pool's layout, so that a DMA can slice it out."""
    return hd % 128 == 0 and bs % (32 // jnp.dtype(dtype).itemsize) == 0


def _kernel_grouped_walk(ids_ref, toks_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sems, *, scale, bs, pps, hkv):
    g, hd = q_ref.shape[1], q_ref.shape[2]
    t = pps * bs

    def copies(row, j, slot, i):
        return _kv_copies((k_hbm, v_hbm), (k_buf, v_buf), sems,
                          (ids_ref[row, j], lax.rem(row, jnp.int32(hkv))),
                          slot, i)

    walk = _PageWalk(toks_ref, ids_ref.shape[1], bs=bs, pps=pps,
                     copies=copies)
    v_buf[...] = jnp.zeros_like(v_buf)          # see `_kernel_walk`
    o_ref[...] = jnp.zeros_like(o_ref)
    col = lax.broadcasted_iota(jnp.int32, (g, t), 1)

    def attend(r, nxt, slot0):
        ln = walk.tokens_of(r)
        q = _mxu_rows(q_ref[r], k_buf.dtype)        # [g or 2 g, hd]

        def step(blk, slot, carry):
            m_prev, l_prev, acc = carry
            k = k_buf[slot].reshape(t, hd)
            v = v_buf[slot].reshape(t, hd)
            s = _sum_rows(_block_dot(q, k, ((1,), (1,))), g) * scale
            keep = col < ln - blk * t                           # [g, t]
            s = jnp.where(keep, s, jnp.asarray(_NEG, s.dtype))
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            p = jnp.where(keep, p, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
            pv = _block_dot(_mxu_rows(p, v.dtype), v, ((1,), (0,)))
            return m_new, l_new, corr * acc + _sum_rows(pv, g)

        (_, l, acc), slot = walk.blocks(
            r, nxt, slot0, step,
            (jnp.full((g, 1), _NEG, jnp.float32),
             jnp.zeros((g, 1), jnp.float32),
             jnp.zeros((g, hd), jnp.float32)))
        o_ref[r] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        return slot

    walk.live_rows(attend)


def grouped_paged_attention_kernel(q, k_pool, v_pool, ids, tokens, *,
                                   scale=None, interpret=False):
    """q [B, Hkv, G, D]; pools [NB, Hkv, bs, D]; ids [B, Hkv, W] i32 the
    pages each (row, KV head) attends, in order; tokens [B, Hkv] i32 how
    many tokens they hold (only the last page may be partly filled).
    Returns q's layout and dtype."""
    b, hkv, g, hd = q.shape
    bs = k_pool.shape[2]
    w = ids.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    rows = b * hkv
    pps = _pages_per_step(bs * hd * k_pool.dtype.itemsize, w)
    whole = pl.BlockSpec((rows, g, hd),
                         lambda i, ids, toks: (_i0(), _i0(), _i0()))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[whole, pool, pool],
        out_specs=whole,
        scratch_shapes=[pltpu.VMEM((2, pps, bs, hd), k_pool.dtype),
                        pltpu.VMEM((2, pps, bs, hd), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2))],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_grouped_walk, scale=scale, bs=bs, pps=pps,
                          hkv=hkv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=GROUPED_DECODE_NAME,
    )(ids.reshape(rows, w).astype(jnp.int32),
      tokens.reshape(rows).astype(jnp.int32), q.reshape(rows, g, hd),
      k_pool, v_pool)
    return out.reshape(b, hkv, g, hd)
