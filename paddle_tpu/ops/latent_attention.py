"""Attention over a paged pool of latents (multi-head latent attention in
its absorbed form).

The pool holds one latent `[c_kv | k_pe]` of width W = rank + rope a token
and layer and nothing else, a page as `[W, bs]`: the tokens of a page lie
along the lanes, so a page of 128 tokens fills whole (8, 128) tiles
whatever W is (576 = 4.5 x 128 would not, the other way round, and a DMA
slices whole tiles), and a page is the right operand of the score product
as it lies. The pool is `[NB, W, bs]`. A query head arrives already
taken through W_uk and rotated: `q_lat = [W_uk^T q_nope | q_pe]`, width W
too. Then every head attends the same rows,

    scores = q_lat . latent * scale,   context = P . latent[:rank]

and the caller takes the context (width `rank`) through W_uv. That is
attention with one key/value head shared by all query heads, the value
being the first `rank` columns of the key.

`latent_paged_attention` serves a window of S tokens a row at positions
start[b] + i, each attending every pooled column <= its own position: the
cached prefix plus the window itself, which the caller has written
already. It is XLA's: the table in chunks of pages with an online softmax,
as many chunks as the longest row needs (a `fori_loop` whose trip count is
data), so neither its time nor its memory follows the table's width.
`latent_paged_decode` is the S = 1 case and, on the chip, the Pallas
kernel that walks each row's own pages (pallas/latent_attention.py).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .attention import _use_paged_kernel

_NEG = -1e30
_CHUNK_TOKENS = 512


def latent_cache_write(pool, lat, tables, start, lens=None):
    """Write a window of latents lat [B, S, W] at positions start[b] + i
    of each row's pages (tables [B, MB]); tokens i >= lens[b], and
    positions past the table, are not written. Returns the pool.

    Whole pages are read, merged and written back (the pages a window can
    touch: a scatter of single tokens would write along the lanes of the
    [W, bs] pages, and XLA then turns the whole pool round, twice a call).
    A page nothing is written to gets its own content back; padding slots
    go through the trash page 0."""
    b, s, w = lat.shape
    bs, mb = pool.shape[2], tables.shape[1]
    start = start.astype(jnp.int32)
    n_pg = (s + bs - 2) // bs + 1
    col = jnp.arange(n_pg * bs, dtype=jnp.int32)[None]          # the canvas
    src = col - (start % bs)[:, None]                           # its token
    live = (src >= 0) & (src < (s if lens is None else lens[:, None]))
    if s == 1:                      # decode: the one token on every lane
        canvas = jnp.broadcast_to(lat[:, :, :, None], (b, 1, w, bs))
    else:
        canvas = jnp.take_along_axis(
            lat, jnp.clip(src, 0, s - 1)[..., None], axis=1)    # [B, C, W]
        canvas = jnp.moveaxis(canvas.reshape(b, n_pg, bs, w), 2, 3)
    slot = (start // bs)[:, None] + jnp.arange(n_pg, dtype=jnp.int32)[None]
    page = jnp.take_along_axis(tables.astype(jnp.int32), slot, axis=1,
                               mode="clip")
    page = jnp.where(slot >= mb, 0, page).reshape(-1)
    new = jnp.where(live.reshape(b * n_pg, 1, bs),
                    canvas.reshape(b * n_pg, w, bs).astype(pool.dtype),
                    pool[page])
    return pool.at[page].set(new)


def latent_paged_attention(q_lat, pool, tables, start, *, rank: int,
                           scale: float):
    """q_lat [B, S, nh, W]; pool [NB, W, bs]; tables [B, MB] i32; start [B]
    i32. Returns the latent context [B, S, nh, rank] in q_lat's dtype."""
    b, s, nh, w = q_lat.shape
    bs, mb = pool.shape[2], tables.shape[1]
    cp = max(1, min(mb, _CHUNK_TOKENS // bs))        # pages a chunk
    t = cp * bs
    n_chunks = -(-mb // cp)
    tables = jnp.pad(tables.astype(jnp.int32),
                     ((0, 0), (0, n_chunks * cp - mb)))
    start = start.astype(jnp.int32)
    qpos = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None]    # [B, S]
    trips = jnp.minimum((jnp.max(start) + s + t - 1) // t, n_chunks)
    f32 = jnp.float32
    prec = lax.Precision.HIGHEST if pool.dtype == jnp.float32 else None
    q_pool = q_lat.astype(pool.dtype)

    def body(j, carry):
        m, l, acc = carry
        j = jnp.asarray(j, jnp.int32)       # i64 outside a jit, under x64
        pages = lax.dynamic_slice(tables, (jnp.int32(0), j * cp), (b, cp))
        rows = pool[pages]                                  # [B, cp, W, bs]
        sc = jnp.einsum("bshw,bpwt->bhspt", q_pool, rows,
                        preferred_element_type=f32, precision=prec
                        ).reshape(b, nh, s, t) * scale
        tpos = j * t + jnp.arange(t, dtype=jnp.int32)
        keep = tpos[None, None] <= qpos[..., None]                  # [B, S, T]
        sc = jnp.where(keep[:, None], sc, _NEG)
        m_new = jnp.maximum(m, jnp.max(sc, -1))
        p = jnp.where(keep[:, None], jnp.exp(sc - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        pv = jnp.einsum("bhspt,bprt->bhsr",
                        p.astype(pool.dtype).reshape(b, nh, s, cp, bs),
                        rows[:, :, :rank], preferred_element_type=f32,
                        precision=prec)
        return m_new, corr * l + jnp.sum(p, -1), corr[..., None] * acc + pv

    init = (jnp.full((b, nh, s), _NEG, f32), jnp.zeros((b, nh, s), f32),
            jnp.zeros((b, nh, s, rank), f32))
    _, l, acc = lax.fori_loop(jnp.int32(0), trips.astype(jnp.int32), body,
                              init)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 1, 2).astype(q_lat.dtype)


def latent_paged_decode(q_lat, pool, tables, lens, *, rank: int,
                        scale: float):
    """One token a row: q_lat [B, nh, W], attending the `lens[b]` rows the
    row's pages hold (the new token's row written already). Returns
    [B, nh, rank]. The page-walking kernel on the chip, the chunked XLA
    form elsewhere and for pages that fill no lane tile."""
    if _use_paged_kernel() and pool.shape[2] % 128 == 0:
        from .pallas.latent_attention import latent_decode_kernel
        return latent_decode_kernel(q_lat, pool, tables, lens, rank=rank,
                                    scale=scale)
    return latent_paged_attention(q_lat[:, None], pool, tables, lens - 1,
                                  rank=rank, scale=scale)[:, 0]
