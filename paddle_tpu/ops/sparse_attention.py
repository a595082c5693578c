"""Block-sparse attention over a paged KV pool with fewer KV heads than
query heads (InfLLM-V2, the `minicpm4` mixer of models/minicpm_sala.py).

A layer pools three planes, a page of `bs` tokens each:

    k_pool, v_pool  [NB, Hkv, bs, D]    a KV head's page is a [bs, D] tile
    kc_pool         [NB, Hkv * r * D]   the COMPRESSED keys that END in the
                                        page, r = bs / stride of them a head

Compressed key j is the mean of the keys [stride j, stride j + kernel). It
is kept in the page that holds its LAST token, at slot m % r of that page
with m = j + kernel / stride - 1 = (last token + 1) / stride - 1, and is
written when that token is: so every plane of a page is a function of the
tokens up to the page's end and nothing after, and a full page can be
shared through the prefix trie with its compressed keys.

With `bs` = the selection's block, a selected block IS a page. A query at
position t with n = t + 1 visible tokens attends

    n <= dense_len   every page of its row (the table's first pages);
    else             `topk` pages a KV head, chosen by `select_blocks`:
                     scores of the head's G query heads against the row's
                     compressed keys, softmax over the keys, summed over
                     the G heads, max-pooled to pages, the query's own
                     page, the `window` before it and the first `init`
                     forced, ties to the lower page.

Decode gathers the chosen pages' numbers per (row, KV head) and walks
them (`grouped_paged_decode`: the Pallas kernel of pallas/
paged_attention.py on the chip, a gather elsewhere); which of the two
lists a row walks is data. A prefill window masks a chunked dense product
by the selection (`sparse_window_attention`).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from .attention import _use_paged_kernel

_NEG = -1e30
_CHUNK_TOKENS = 2048


@dataclass(frozen=True)
class SparseSizes:
    kernel: int
    stride: int
    block: int
    topk: int
    init_blocks: int
    window: int
    dense_len: int

    @property
    def r(self) -> int:             # compressed keys that end in a page
        return self.block // self.stride

    @property
    def kk(self) -> int:            # strides a compressed key spans
        return self.kernel // self.stride

    def list_width(self, table_width: int) -> int:
        """Pages in the list a decode row walks: the selection's, or a
        dense row's whole prefix."""
        return min(table_width, max(self.topk, self.dense_len // self.block))

    def check(self, kv_block: int) -> None:
        if kv_block != self.block:
            raise ValueError(f"kv_block {kv_block} must be the selection's "
                             f"block_size {self.block}: a selected block "
                             f"is a page")
        if self.block % self.stride or self.kernel % self.stride \
                or self.dense_len % self.block:
            raise ValueError("block_size and kernel_size must be multiples "
                             "of kernel_stride, dense_len of block_size")


def _page_off(tables, pos, bs):
    slot = pos // bs
    page = jnp.take_along_axis(tables, jnp.clip(slot, 0, tables.shape[1] - 1),
                               axis=1)
    page = jnp.where((slot >= tables.shape[1]) | (pos < 0), 0, page)
    return page, pos % bs


def _flat_rows(page, off, hkv, bs):
    """Rows of a pool's flat [NB Hkv bs, D] view holding (page, every KV
    head, offset): [..., Hkv]. Reads and writes go through that view, along
    its leading axis alone, so that XLA leaves the pool's layout as it is
    (indexing [page, :, off] made it copy the whole pool a step)."""
    return (page[..., None] * hkv + jnp.arange(hkv)) * bs + off[..., None]


def kv_cache_write(k_pool, v_pool, k, v, tables, start, lens=None):
    """Write k, v [B, S, Hkv, D] at positions start[b] + i of each row's
    pages; tokens i >= lens[b] and positions past the table go to the
    trash page. Returns the pools."""
    s, hkv = k.shape[1:3]
    bs = k_pool.shape[2]
    pos = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    page, off = _page_off(tables, pos, bs)
    if lens is not None:
        page = jnp.where(jnp.arange(s)[None] < lens[:, None], page, 0)
    dest = _flat_rows(page, off, hkv, bs).reshape(-1)

    def put(pool, new):
        flat = pool.reshape(-1, pool.shape[-1])
        return flat.at[dest].set(
            new.reshape(-1, new.shape[-1]).astype(pool.dtype)
        ).reshape(pool.shape)
    return put(k_pool, k), put(v_pool, v)


def compressed_write(kc_pool, k_pool, tables, start, n_new, sz: SparseSizes,
                     width: int):
    """Write the compressed keys whose last token lies among the `n_new`
    tokens just written at start[b] .. of each row (`width` is the static
    window, 1 for a decode step). Reads the keys back from the pool, so
    the window's keys have to be there already."""
    b = tables.shape[0]
    hkv, bs, d = k_pool.shape[1:]
    r, kk, stride = sz.r, sz.kk, sz.stride
    if width == 1:
        # one token at position t: it ends compressed key m iff
        # (t + 1) % stride == 0
        t = start
        m = (t + 1) // stride - 1
        ok = ((t + 1) % stride == 0) & (m >= kk - 1) & (n_new > 0)
        pos = t[:, None] + 1 - sz.kernel + jnp.arange(sz.kernel)[None]
        page, off = _page_off(tables, jnp.maximum(pos, 0), bs)
        keys = k_pool.reshape(-1, d)[_flat_rows(page, off, hkv, bs)]
        kc = jnp.mean(keys.astype(jnp.float32), axis=1)     # [B, Hkv, D]
        dst, _ = _page_off(tables, (m // r * bs)[:, None], bs)
        dst = jnp.where(ok, dst[:, 0], 0)
        lanes = jnp.arange(r)[None, None, :, None] == (m % r)[:, None, None,
                                                              None]
        new = jnp.broadcast_to(kc[:, :, None, :], (b, hkv, r, d))
        old = kc_pool[dst].reshape(b, hkv, r, d)
        row = jnp.where(lanes & ok[:, None, None, None],
                        new.astype(kc_pool.dtype), old)
        return kc_pool.at[dst].set(row.reshape(b, -1))
    # a window of `width` tokens from start (a multiple of the page): the
    # compressed keys it ends fill whole pages' slots, width / stride of
    # them; their keys reach kernel - stride tokens back
    if width % bs:
        raise ValueError(f"a prefill window ({width}) must be whole pages "
                         f"({bs})")
    back = sz.kernel - stride
    pos = start[:, None] - back + jnp.arange(width + back)[None]
    page, off = _page_off(tables, pos, bs)
    keys = k_pool.reshape(-1, d)[_flat_rows(page, off, hkv, bs)]
    sums = keys.astype(jnp.float32).reshape(b, -1, stride, hkv, d).sum(2)
    n_i = width // stride
    kc = sum(sums[:, a:a + n_i] for a in range(kk)) / sz.kernel
    i = jnp.arange(n_i)[None]
    m = start[:, None] // stride + i
    ok = (m >= kk - 1) & ((i + 1) * stride <= n_new[:, None])       # [B, n_i]
    n_pg = width // bs
    slot = start[:, None] // bs + jnp.arange(n_pg)[None]
    dst, _ = _page_off(tables, slot * bs, bs)
    ok_pg = ok.reshape(b, n_pg, r)
    dst = jnp.where(jnp.any(ok_pg, -1), dst, 0)
    new = jnp.moveaxis(kc.reshape(b, n_pg, r, hkv, d), 2, 3)        # [B,pg,Hkv,r,D]
    old = kc_pool[dst].reshape(b, n_pg, hkv, r, d)
    row = jnp.where(ok_pg[:, :, None, :, None], new.astype(kc_pool.dtype), old)
    return kc_pool.at[dst.reshape(-1)].set(row.reshape(b * n_pg, -1))


def select_blocks(q, kc_pool, tables, pos, sz: SparseSizes):
    """q [B, S, Hkv, G, D] at positions pos [B, S]. Returns (chosen
    [B, S, Hkv, topk] the selected pages' numbers IN THE ROW (table slots),
    ascending; sparse [B, S] whether the query selects at all; keys [B, S]
    the compressed keys it scored)."""
    b, s, hkv, g, d = q.shape
    mb = tables.shape[1]
    r, kk = sz.r, sz.kk
    prec = lax.Precision.HIGHEST if kc_pool.dtype == jnp.float32 else None
    rows = kc_pool[tables]                                  # [B, MB, Hkv r D]
    qc = q.astype(kc_pool.dtype)
    sc = jnp.stack([jnp.stack([
        jnp.einsum("bsid,bmd->bsim", qc[:, :, h],
                   rows[:, :, (h * r + a) * d:(h * r + a + 1) * d],
                   preferred_element_type=jnp.float32, precision=prec)
        for a in range(r)], -1) for h in range(hkv)], 2)    # [B,S,Hkv,G,MB,r]
    sc = sc.reshape(b, s, hkv, g, mb * r) * (d ** -0.5)
    m = jnp.arange(mb * r, dtype=jnp.int32)
    n = pos + 1
    valid = (m >= kk - 1) & (sz.stride * (m + 1) <= n[..., None])   # [B,S,M]
    v5 = valid[:, :, None, None]
    sc = jnp.where(v5, sc, _NEG)
    e = jnp.where(v5, jnp.exp(sc - jnp.max(sc, -1, keepdims=True)), 0.0)
    p = e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30)
    grp = jnp.where(valid[:, :, None], jnp.sum(p, 3), -jnp.inf)     # [B,S,Hkv,M]
    own = grp.reshape(b, s, hkv, mb, r)
    score = jnp.max(own, -1)
    if kk > 1:          # the first kk - 1 slots of the next page overlap too
        nxt = jnp.max(own[..., :kk - 1], -1)
        nxt = jnp.concatenate(
            [nxt[..., 1:], jnp.full(nxt.shape[:-1] + (1,), -jnp.inf)], -1)
        score = jnp.maximum(score, nxt)
    blk = jnp.arange(mb, dtype=jnp.int32)
    cur = (pos // sz.block)[..., None]
    cand = blk <= cur                                               # [B,S,MB]
    forced = cand & ((blk == cur) | (cur - blk <= sz.window // sz.block)
                     | (blk < sz.init_blocks))
    score = jnp.where(forced[:, :, None], jnp.inf, score)
    score = jnp.where(cand[:, :, None], score, -jnp.inf)
    return (_top_pages(score, min(sz.topk, mb)), n > sz.dense_len,
            jnp.sum(valid, -1))


def _top_pages(score, k: int):
    """The k highest of score [..., MB] along the last axis, ties to the
    lower number (`lax.top_k`'s order), as their numbers ASCENDING. By
    rank, not by sorting: a page's rank is how many pages beat it, one
    compare-and-count over [MB, MB] (XLA sorts all MB for a top-k: 0.63 ms
    a layer and decode step at 128 rows of 536 pages, the chip's trace,
    PR 34); the j-th chosen is the page that j chosen pages precede."""
    mb = score.shape[-1]
    blk = jnp.arange(mb, dtype=jnp.int32)
    a, b = score[..., :, None], score[..., None, :]
    beats = (b > a) | ((b == a) & (blk[None, :] < blk[:, None]))
    chosen = jnp.sum(beats, -1, dtype=jnp.int32) < k                # [..., MB]
    before = jnp.cumsum(chosen, -1, dtype=jnp.int32) - 1
    slot = jnp.arange(k, dtype=jnp.int32)[:, None]
    return jnp.sum(jnp.where(chosen[..., None, :]
                             & (before[..., None, :] == slot), blk, 0), -1)


def sparse_window_attention(q, k_pool, v_pool, tables, pos, chosen, sparse,
                            scale: float):
    """A prefill window: q [B, S, Hkv, G, D] at positions pos [B, S], each
    query attending the tokens <= its position of its chosen pages (all
    pages where `sparse` is false). The table in chunks of pages with an
    online softmax, as many chunks as the longest row needs. Returns
    ([B, S, Hkv, G, D] float32, pairs attended [B, S])."""
    b, s, hkv, g, d = q.shape
    bs, mb = k_pool.shape[2], tables.shape[1]
    cp = max(1, min(mb, _CHUNK_TOKENS // bs))
    t = cp * bs
    n_chunks = -(-mb // cp)
    tables = jnp.pad(tables, ((0, 0), (0, n_chunks * cp - mb)))
    mask = jnp.any(chosen[..., None] == jnp.arange(n_chunks * cp), -2) \
        | ~sparse[:, :, None, None]                         # [B,S,Hkv,pages]
    cur = pos // bs
    mask &= jnp.arange(n_chunks * cp) <= cur[..., None, None]
    pairs = jnp.sum(jnp.where(
        jnp.arange(n_chunks * cp) == cur[..., None], pos[..., None] % bs + 1,
        bs) * mask[:, :, 0], -1)
    trips = jnp.minimum((jnp.max(pos) + t) // t, n_chunks)
    f32 = jnp.float32
    prec = lax.Precision.HIGHEST if k_pool.dtype == f32 else None
    qp = q.astype(k_pool.dtype)

    def body(j, carry):
        m, l, acc = carry
        j = jnp.asarray(j, jnp.int32)
        pages = lax.dynamic_slice(tables, (jnp.int32(0), j * cp), (b, cp))
        kk_, vv = k_pool[pages], v_pool[pages]              # [B,cp,Hkv,bs,D]
        sc = jnp.einsum("bshid,bphtd->bshipt", qp, kk_,
                        preferred_element_type=f32, precision=prec
                        ).reshape(b, s, hkv, g, t) * scale
        tpos = j * t + jnp.arange(t, dtype=jnp.int32)
        keep = jnp.repeat(lax.dynamic_slice_in_dim(mask, j * cp, cp, 3), bs,
                          axis=-1) & (tpos <= pos[..., None, None])
        keep = keep[:, :, :, None]                          # [B,S,Hkv,1,T]
        sc = jnp.where(keep, sc, _NEG)
        m_new = jnp.maximum(m, jnp.max(sc, -1))
        p = jnp.where(keep, jnp.exp(sc - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        pv = jnp.einsum("bshipt,bphtd->bshid",
                        p.astype(k_pool.dtype).reshape(b, s, hkv, g, cp, bs),
                        vv, preferred_element_type=f32, precision=prec)
        return m_new, corr * l + jnp.sum(p, -1), corr[..., None] * acc + pv

    init = (jnp.full((b, s, hkv, g), _NEG, f32), jnp.zeros((b, s, hkv, g), f32),
            jnp.zeros((b, s, hkv, g, d), f32))
    _, l, acc = lax.fori_loop(jnp.int32(0), trips.astype(jnp.int32), body,
                              init)
    return acc / jnp.maximum(l, 1e-30)[..., None], pairs


def decode_lists(tables, lens, chosen, sparse, sz: SparseSizes):
    """The pages each (row, KV head) of a decode step walks and how many
    tokens they hold: `lens` [B] tokens a row sees (the new one written),
    chosen [B, Hkv, topk] ascending, sparse [B]. Returns (ids [B, Hkv, W]
    page numbers in the pool, tokens [B, Hkv]). The selection's last page
    is the row's current one, the only one not full."""
    b, hkv, topk = chosen.shape
    w = sz.list_width(tables.shape[1])
    picked = jnp.take_along_axis(tables[:, None], chosen, axis=2)
    picked = jnp.pad(picked, ((0, 0), (0, 0), (0, w - topk)))
    ids = jnp.where(sparse[:, None, None], picked, tables[:, None, :w])
    last = (lens - 1) % sz.block + 1
    toks = jnp.where(sparse, (topk - 1) * sz.block + last, lens)
    return ids, jnp.broadcast_to(toks[:, None], (b, hkv))


def grouped_paged_decode_reference(q, k_pool, v_pool, ids, tokens, scale):
    """q [B, Hkv, G, D]; ids [B, Hkv, W] pages; tokens [B, Hkv] how many
    tokens of the listed pages, in order, are attended. [B, Hkv, G, D]."""
    b, hkv, g, d = q.shape
    w, bs = ids.shape[2], k_pool.shape[2]
    head = jnp.arange(hkv)[None, :, None]
    kk_ = k_pool[ids, head].reshape(b, hkv, w * bs, d)
    vv = v_pool[ids, head].reshape(b, hkv, w * bs, d)
    prec = lax.Precision.HIGHEST if k_pool.dtype == jnp.float32 else None
    sc = jnp.einsum("bhid,bhtd->bhit", q.astype(k_pool.dtype), kk_,
                    preferred_element_type=jnp.float32,
                    precision=prec) * scale
    keep = (jnp.arange(w * bs) < tokens[..., None])[:, :, None]
    p = jax.nn.softmax(jnp.where(keep, sc, _NEG), -1)
    p = jnp.where(keep, p, 0.0)
    return jnp.einsum("bhit,bhtd->bhid", p.astype(k_pool.dtype), vv,
                      preferred_element_type=jnp.float32, precision=prec)


def grouped_paged_decode(q, k_pool, v_pool, ids, tokens, scale):
    """One query token a row, G query heads a KV head, over the listed
    pages: the page-walking kernel on the chip (pages that fill whole
    tiles), the gather elsewhere. float32 [B, Hkv, G, D]."""
    from .pallas.paged_attention import (grouped_pages_dma_sliceable,
                                         grouped_paged_attention_kernel)
    if _use_paged_kernel() and grouped_pages_dma_sliceable(
            k_pool.shape[2], k_pool.shape[3], k_pool.dtype):
        return grouped_paged_attention_kernel(
            q.astype(k_pool.dtype), k_pool, v_pool, ids, tokens,
            scale=scale).astype(jnp.float32)
    return grouped_paged_decode_reference(q, k_pool, v_pool, ids, tokens,
                                          scale)
