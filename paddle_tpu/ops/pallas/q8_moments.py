"""The whole Adam / AdamW update of a leaf with 8-bit moments, one Pallas
pass from codes to codes in the parameter's own layout.

The stored state is fixed (`optimizer.Adam`, and the benchmark reads it
so): codes `[nb, 2048]` and one float32 scale a block, `[nb]`, blocks cut
from the parameter flattened in row-major order. XLA's form of the codec
views a `[R, C]` moment as `[nb, 2048]`; on the chip the two views are
tiled differently (8 rows x 128 lanes of different rows), so each change of
view is a float32 copy of the whole moment, which XLA will not fuse.
Decoding to a float32 root, updating it in XLA's weight-gradient fusion
and encoding it again still carried both roots through HBM three times
(40 bytes a parameter a step against 10 here).

`adamw` reads the gradient, the parameter and both moments' codes and
scales of a row tile, and writes the new parameter, codes and scales over
their inputs. With `C % 128 == 0` no 128-lane chunk of a row straddles two
blocks, so a tile of `m` rows and the blocks it holds are the same
`[m C/128, 128]` chunks in two orders: chunk `l` of tile row `r` is flat
row `r C/128 + l`, and chunk `k` of block `b` is flat row `16 b + k`. The
gradient passes into flat float32 scratch with one strided copy per lane
chunk (Mosaic strides sublanes only in a ref whose last dimension is 128);
the update runs chunk `k` of every block at a time, next to the codes it
decodes, and leaves the step `lr m_hat / (sqrt(v_hat) + eps)` in the
gradient's place and the new roots in scratch of their own; each block's
max over its 16 chunks then scales its new codes; the step passes back
into the tile, where the parameter takes it.

A tile holds whole blocks, a multiple of 32 of them (the int8 codes'
sublanes), and a multiple of 16 rows (bfloat16 sublanes), so for C = 2560
it is 128 rows and a block may straddle two of them (pieces at lanes
0 / 2048 / 1536 / 1024 / 512); for C a multiple of 2048 a block is a row
segment. The arithmetic is the flat form's (`optimizer.Adam.update` over
`optimizer._q8_unblock` / `_q8_block`), op for op, so on the CPU both
forms store the same bits (`tests/test_q8_moments.py`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _i0  # i32 index-map literal (Mosaic x64 rule)

BLOCK = 2048          # optimizer._Q_BLOCK: elements a block, one scale each
CHUNKS = BLOCK // 128  # 128-lane rows of one block
TILE_BYTES = 2 << 20  # float32 bytes of one row tile (aimed at, not exceeded)


def row_tile(shape):
    """Rows a tile for a `[R, C]` parameter, or None where the update keeps
    XLA's flat form: 1-D leaves, lanes not a multiple of 128, a size that
    is no whole number of blocks, or rows that no tile divides."""
    if len(shape) != 2:
        return None
    r, c = (int(s) for s in shape)
    if r <= 0 or c % 128 or (r * c) % BLOCK:
        return None
    # rows a multiple of 16 (bfloat16 sublanes) holding blocks a multiple
    # of 32 (the int8 codes' sublanes); else the whole array as one tile
    unit = math.lcm(16, 512 // math.gcd(c // 128, 512))
    if r % unit or unit * c * 4 > 2 * TILE_BYTES:
        return r if r % 16 == 0 and r * c * 4 <= 2 * TILE_BYTES else None
    m = unit
    while r % (2 * m) == 0 and 2 * m * c * 4 <= TILE_BYTES:
        m *= 2
    return m


def _chunk(flat, k, nbt):
    """Chunk k (lanes 128k..) of every block of the tile: `[nbt, 128]`."""
    return flat[pl.ds(k, nbt, stride=CHUNKS), :]


def _encode(roots, q_ref, s_ref, t, top, second):
    """Codes and scales of the roots, chunk k of every block at rows
    `k nbt..` of `roots`, whose per-lane block max over the 16 chunks is
    `top` (`optimizer._q8_block`, bit for bit)."""
    nbt = q_ref.shape[0]
    amax = jnp.max(top, axis=1, keepdims=True)
    scale = amax * jnp.float32(1 / (255 if second else 127))
    div = jnp.maximum(scale, 1e-30)
    for k in range(CHUNKS):
        q = jnp.round(roots[pl.ds(k * nbt, nbt), :] / div)
        q_ref[:, pl.ds(k * 128, 128)] = q.astype(jnp.int32).astype(q_ref.dtype)
    s_ref[pl.ds(t, 1), :] = scale[:, 0][None, :]


def _adamw_kernel(h_ref, g_ref, p_ref, q1_ref, s1_ref, q2_ref, s2_ref,
                  po_ref, q1o_ref, s1o_ref, q2o_ref, s2o_ref,
                  gf, r1s, r2s, *pf, c, m, b1, b2, eps, decoupled, l2):
    """`gf` holds the gradient in flat order, then the step
    `lr m_hat / (sqrt(v_hat) + eps)` in its place; `r1s` / `r2s` the new
    roots, chunk-major (rows `k nbt..` are chunk k of every block); `pf`
    the parameter in flat order, for coupled L2 only. Strided copies cost
    the kernel most of its time (one TPU v5e, a `[2560, 10240]` leaf:
    0.86 ms with ten strided passes an element, 0.49 with none, the DMAs
    alone 0.44), so only the gradient and the step cross between the
    orders."""
    t = pl.program_id(0)
    nbt = q1_ref.shape[0]
    cl = c // 128
    for lane in range(cl):
        rows = pl.ds(lane, m, stride=cl)
        gf[rows, :] = g_ref[:, pl.ds(lane * 128, 128)].astype(jnp.float32)
        if l2:
            pf[0][rows, :] = \
                p_ref[:, pl.ds(lane * 128, 128)].astype(jnp.float32)
    # h: lr, the decay (wd, or 1 - lr wd when decoupled), 1 - b1^t, 1 - b2^t
    lr, wd, bc1, bc2 = h_ref[0], h_ref[1], h_ref[2], h_ref[3]
    s1 = s1_ref[pl.ds(t, 1), :][0][:, None]
    s2 = s2_ref[pl.ds(t, 1), :][0][:, None]
    top1 = top2 = None
    for k in range(CHUNKS):
        lanes, here = pl.ds(k * 128, 128), pl.ds(k * nbt, nbt)
        g = _chunk(gf, k, nbt)
        # r |r| and r r are sign(r) r^2 and r^2 to the bit
        r = q1_ref[:, lanes].astype(jnp.int32).astype(jnp.float32) * s1
        m0 = r * jnp.abs(r)
        r = (q2_ref[:, lanes].astype(jnp.int32).astype(jnp.float32) + 0.5) \
            * s2
        v0 = r * r
        if l2:
            g = g + wd * _chunk(pf[0], k, nbt)
        mo = b1 * m0 + (1 - b1) * g
        vo = b2 * v0 + (1 - b2) * g * g
        m_hat = mo / bc1
        v_hat = vo / bc2
        gf[pl.ds(k, nbt, stride=CHUNKS), :] = \
            lr * m_hat / (jnp.sqrt(v_hat) + eps)
        # sign(m) sqrt|m| up to the sign of a zero, which codes the same
        a1 = jnp.sqrt(jnp.abs(mo))
        r1s[here, :] = jnp.where(mo < 0, -a1, a1)
        r2 = jnp.sqrt(jnp.maximum(vo, 0.0))
        r2s[here, :] = r2
        top1 = a1 if top1 is None else jnp.maximum(top1, a1)
        top2 = r2 if top2 is None else jnp.maximum(top2, r2)
    _encode(r1s, q1o_ref, s1o_ref, t, top1, False)
    _encode(r2s, q2o_ref, s2o_ref, t, top2, True)
    for lane in range(cl):
        lanes = pl.ds(lane * 128, 128)
        p = p_ref[:, lanes].astype(jnp.float32)
        if decoupled:
            p = p * wd
        po_ref[:, lanes] = \
            (p - gf[pl.ds(lane, m, stride=cl), :]).astype(po_ref.dtype)


# Three or four float32 scratch tiles of at most 2 x TILE_BYTES beside
# the double-buffered gradient, parameter and codes: under 48 MiB for
# every tile and dtype (v5e has 128 MiB of VMEM).
_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                               vmem_limit_bytes=64 << 20)

ADAMW_NAME = "pallas_q8_adamw"


@functools.partial(jax.jit, static_argnames=(
    "b1", "b2", "eps", "decoupled", "l2", "interpret"))
def adamw(grad, param, q1, s1, q2, s2, hyper, *, b1, b2, eps, decoupled,
          l2, interpret=False):
    """One Adam (`decoupled=False`, coupled L2 where `l2`) or AdamW update
    of a `[R, C]` leaf whose moments are codes `[nb, 2048]` (int8 `q1`,
    uint8 `q2`) and scales `[nb]` (`s1`, `s2`). `hyper` is float32
    `[lr, wd or 1 - lr wd, 1 - b1^t, 1 - b2^t]`. Returns the new parameter,
    codes and scales, each written over its input."""
    r, c = (int(s) for s in param.shape)
    m = row_tile(param.shape)
    if m is None:
        raise ValueError(f"no row tile for a leaf of shape {param.shape}")
    nbt, g = m * c // BLOCK, r // m
    n = r * c
    tile = pl.BlockSpec((m, c), lambda i: (i, _i0()))
    codes = pl.BlockSpec((nbt, BLOCK), lambda i: (i, _i0()))
    scales = pl.BlockSpec((g, nbt), lambda i: (_i0(), _i0()))
    kern = functools.partial(_adamw_kernel, c=c, m=m, b1=b1, b2=b2, eps=eps,
                             decoupled=decoupled, l2=l2)
    out = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((r, c), param.dtype),
                   jax.ShapeDtypeStruct((g * nbt, BLOCK), jnp.int8),
                   jax.ShapeDtypeStruct((g, nbt), jnp.float32),
                   jax.ShapeDtypeStruct((g * nbt, BLOCK), jnp.uint8),
                   jax.ShapeDtypeStruct((g, nbt), jnp.float32)),
        grid=(g,),
        in_specs=[pl.BlockSpec((4,), lambda i: (_i0(),),
                               memory_space=pltpu.SMEM),
                  tile, tile, codes, scales, codes, scales],
        out_specs=(tile, codes, scales, codes, scales),
        scratch_shapes=[pltpu.VMEM((nbt * CHUNKS, 128), jnp.float32)]
        * (4 if l2 else 3),
        input_output_aliases={2: 0, 3: 1, 4: 2, 5: 3, 6: 4},
        compiler_params=_PARAMS,
        # a few dozen vector operations and eight square roots or
        # divisions an element; the gradient read, the parameter and the
        # codes read and written
        cost_estimate=pl.CostEstimate(
            flops=40 * n, transcendentals=8 * n,
            bytes_accessed=n * (grad.dtype.itemsize
                                + 2 * param.dtype.itemsize + 4)
            + 16 * (n // BLOCK)),
        interpret=interpret,
        name=ADAMW_NAME,
    )(hyper, grad, param, q1, s1.reshape(g, nbt), q2, s2.reshape(g, nbt))
    new_p, q1, s1, q2, s2 = out
    # Without a barrier on its outputs XLA's scheduler placed every 8-bit
    # update after the whole backward pass, holding each layer's
    # activations to the end (8 layers of the 2.7B step compiled for a
    # v5e: 4.70 GiB of temporaries against 2.14 with it).
    return jax.lax.optimization_barrier(
        (new_p, q1, s1.reshape(-1), q2, s2.reshape(-1)))
