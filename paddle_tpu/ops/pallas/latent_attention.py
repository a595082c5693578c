"""Pallas decode attention over a paged pool of latents (see
ops/latent_attention.py for the arithmetic): every query head of a row
against the row's own latent pages, walked as `_kernel_walk` of
paged_attention.py walks K and V pages.

  grid (B,)   one program a batch row, in order. The pool stays in HBM
              (`pl.ANY`); the block table and `lens` are scalar-prefetched;
              a row's q [nh, W] and its output [nh, rank] are blocks.
  blocks      a row of `lens` tokens costs cdiv(lens, pps * bs) trips of a
              `fori_loop`, whatever the table's width; a row of lens 0
              costs none and gives zeros.
  fetch       one `make_async_copy` a LIVE page into a double-buffered VMEM
              slot [pps, W, bs]; the row's next block, or the next row's
              first, is in flight while this one is computed. A page is
              [W, bs], tokens along the lanes: with bs a multiple of 128
              it fills whole tiles whatever W is (576 = 4.5 x 128).
  compute     a page at a time: scores q [nh, W] x page on the MXU, the
              block's side by side and masked past `lens`; online softmax
              in float32 with its state in VMEM scratch ([nh, rank]
              float32 is the whole register file); the context is
              p [nh, bs] x page[:rank]^T, p rounded to the pool's dtype.
              One latent feeds all nh heads: 2 nh (W + rank) FLOP for
              W x itemsize bytes, the v5e's own ridge at nh = 128 in
              bfloat16.

What a slot holds past a block's live pages is zeros or an earlier row's
own page, and meets a probability of exactly 0 (the slots are zeroed once,
at the first row).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _i0
from .paged_attention import _block_dot

_NEG = -1e30
LATENT_DECODE_NAME = "pallas_latent_decode"
_TOKENS_PER_STEP = 256


def _kernel(tables_ref, lens_ref, q_ref, pool_hbm, o_ref, buf, sems,
            slot_ref, m_sc, l_sc, acc_sc, *, scale, bs, pps, rank):
    b, n_rows = pl.program_id(0), pl.num_programs(0)
    mb = tables_ref.shape[1]
    t = pps * bs

    def cdiv(a, d):                   # i32 throughout (Mosaic x64 rule)
        return lax.div(a + (d - 1), jnp.int32(d))

    def pages_of(row):
        return jnp.minimum(cdiv(lens_ref[row], bs), mb)

    def block_dmas(op, row, blk, slot, n_pages):
        slot = jnp.asarray(slot, jnp.int32)
        for i in range(pps):
            @pl.when(blk * pps + i < n_pages)
            def _():
                page = tables_ref[row, blk * pps + i]
                getattr(pltpu.make_async_copy(
                    pool_hbm.at[page], buf.at[slot, jnp.int32(i)],
                    sems.at[slot]), op)()

    def fetch_first_of_next_row(slot):
        nxt = jnp.minimum(b + 1, n_rows - 1)

        @pl.when(b + 1 < n_rows)
        def _():
            block_dmas("start", nxt, 0, slot, pages_of(nxt))

    n_pages = pages_of(b)
    n_blocks = cdiv(n_pages, pps)
    ln = jnp.minimum(lens_ref[b], mb * bs)

    @pl.when(b == 0)
    def _():
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        block_dmas("start", 0, 0, 0, n_pages)       # nobody fetched ahead

    slot0 = slot_ref[0]
    q = q_ref[0].astype(buf.dtype)                  # [nh, W]
    m_sc[...] = jnp.full_like(m_sc, _NEG)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)

    def body(blk, carry):
        slot = lax.rem(slot0 + blk, jnp.int32(2))

        @pl.when(blk + 1 < n_blocks)
        def _():
            block_dmas("start", b, blk + 1, 1 - slot, n_pages)

        @pl.when(blk + 1 == n_blocks)
        def _():
            fetch_first_of_next_row(1 - slot)

        block_dmas("wait", b, blk, slot, n_pages)
        pages = [buf[slot, i] for i in range(pps)]              # [W, bs]
        s = jnp.concatenate([_block_dot(q, pg, ((1,), (0,)))
                             for pg in pages], axis=1) * scale  # [nh, T]
        col = blk * t + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < ln, s, jnp.asarray(_NEG, s.dtype))
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                      # exactly 0 past `ln`
        corr = jnp.exp(m_prev - m_new)
        m_sc[...] = m_new
        l_sc[...] = corr * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        p = p.astype(buf.dtype)
        acc_sc[...] = corr * acc_sc[...] + sum(
            _block_dot(p[:, i * bs:(i + 1) * bs], pg[:rank], ((1,), (1,)))
            for i, pg in enumerate(pages))
        return carry

    lax.fori_loop(jnp.int32(0), n_blocks, body, jnp.int32(0))
    o_ref[0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
                ).astype(o_ref.dtype)

    @pl.when(n_blocks == 0)
    def _():                          # an empty row fetches ahead too
        fetch_first_of_next_row(slot0)

    slot_ref[0] = lax.rem(slot0 + n_blocks, jnp.int32(2))


def latent_decode_kernel(q_lat, pool, tables, lens, *, rank: int,
                         scale: float, interpret: bool = False):
    """q_lat [B, nh, W]; pool [NB, W, bs]; tables [B, MB] i32; lens [B] =
    attendable tokens per batch entry. Returns [B, nh, rank]."""
    b, nh, w = q_lat.shape
    bs, mb = pool.shape[2], tables.shape[1]
    pps = max(1, min(mb, _TOKENS_PER_STEP // bs))
    row = lambda width: pl.BlockSpec(  # noqa: E731
        (1, nh, width), lambda bi, tables, lens: (bi, _i0(), _i0()))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[row(w), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row(rank),
        scratch_shapes=[pltpu.VMEM((2, pps, w, bs), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((nh, 1), jnp.float32),
                        pltpu.VMEM((nh, 1), jnp.float32),
                        pltpu.VMEM((nh, rank), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, bs=bs, pps=pps, rank=rank),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, rank), q_lat.dtype),
        # rows in order: each fetches the next one's first block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=LATENT_DECODE_NAME,
    )(tables.astype(jnp.int32), lens.astype(jnp.int32), q_lat, pool)
