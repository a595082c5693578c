"""Pallas decode attention over a paged pool of latents (see
ops/latent_attention.py for the arithmetic): every query head of a row
against the row's own latent pages, walked as `_kernel_walk` of
paged_attention.py walks K and V pages.

  grid (B,)   one program a batch row, in order (a row's q [nh, W] is
              147 KB at the cell's widths: the batch does not lie whole
              in VMEM as `_kernel_walk`'s does). The pool stays in HBM
              (`pl.ANY`); the block table and `lens` are scalar-
              prefetched; a row's q and its output [nh, rank] are blocks.
  blocks      a row of `lens` tokens costs cdiv(lens, pps * bs) trips of a
              `fori_loop`, whatever the table's width; a row of lens 0 (a
              done row: the model hands it 0) costs its grid step and a
              store of zeros, no fetch and no product.
  fetch       one `make_async_copy` a LIVE page into a double-buffered VMEM
              slot [pps, W, bs] (`_PageWalk`); the row's next block, or the
              first of the next row that attends anything, is in flight
              while this one is computed. A page is
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
from .paged_attention import _PageWalk, _block_dot

_NEG = -1e30
LATENT_DECODE_NAME = "pallas_latent_decode"
_TOKENS_PER_STEP = 256


def _kernel(tables_ref, lens_ref, q_ref, pool_hbm, o_ref, buf, sems,
            slot_ref, m_sc, l_sc, acc_sc, *, scale, bs, pps, rank):
    b = pl.program_id(0)
    t = pps * bs

    def copies(row, j, slot, i):
        return [pltpu.make_async_copy(pool_hbm.at[tables_ref[row, j]],
                                      buf.at[slot, i], sems.at[slot])]

    walk = _PageWalk(lens_ref, tables_ref.shape[1], bs=bs, pps=pps,
                     copies=copies)
    live = lens_ref[b] > 0

    @pl.when(b == 0)
    def _():
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        walk.start_first(walk.next_live(0), 0)      # nobody fetched ahead

    @pl.when(jnp.logical_not(live))
    def _():                    # a row of no tokens: no fetch, no product
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(live)
    def _():
        ln = walk.tokens_of(b)
        q = q_ref[0].astype(buf.dtype)              # [nh, W]
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

        def step(blk, slot, carry):
            pages = [buf[slot, i] for i in range(pps)]              # [W, bs]
            s = jnp.concatenate([_block_dot(q, pg, ((1,), (0,)))
                                 for pg in pages], axis=1) * scale  # [nh, T]
            col = blk * t + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col < ln, s, jnp.asarray(_NEG, s.dtype))
            m_prev = m_sc[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)                  # exactly 0 past `ln`
            corr = jnp.exp(m_prev - m_new)
            m_sc[...] = m_new
            l_sc[...] = corr * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
            p = p.astype(buf.dtype)
            acc_sc[...] = corr * acc_sc[...] + sum(
                _block_dot(p[:, i * bs:(i + 1) * bs], pg[:rank],
                           ((1,), (1,)))
                for i, pg in enumerate(pages))
            return carry

        _, slot_ref[0] = walk.blocks(b, walk.next_live(b + 1), slot_ref[0],
                                     step, jnp.int32(0))
        o_ref[0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
                    ).astype(o_ref.dtype)


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
