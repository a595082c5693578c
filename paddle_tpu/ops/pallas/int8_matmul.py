"""Weight-only int8 matmul for decode (dequantize IN-REGISTER, not in HBM).

Reference anchor: the weight-only int8 path of the reference's serving
transformer (paddle/fluid/operators/fused/fused_multi_transformer_op.cu) —
int8 weights stream from memory and widen inside the GEMM.

Why a kernel: autoregressive decode is weight-bandwidth-bound (~2.6 GB/step
bf16 at 1.3B). The r4 dequant-at-use path (int8 -> bf16 elementwise, then
the XLA dot) measured 1.31x where the byte ratio promises ~2x: XLA
materializes the widened weight in HBM, so the dot still READS full-width
bytes. Here the int8 tile is DMA'd to VMEM (half the bytes — the whole
win), widened in-register on the VPU, and fed straight to the MXU; the
per-channel scale multiplies the f32 accumulator, which is exact for
per-output-channel quantization ((x @ q) * s == x @ (q * s)).

Layouts: "kn" — q [K, N] with per-output-column scale s [N] (projection
weights [in, out]); "nk" — q [N, K] with per-row scale s [N] (the tied
embedding/LM-head table [V, H]). Forward-only (decode runs under no_grad).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ...device import on_tpu


def _i0():
    return jnp.int32(0)


def _kernel(x_ref, q_ref, s_ref, o_ref, *, w_layout, out_dtype):
    x = x_ref[...]
    q = q_ref[...]
    qw = q.astype(jnp.bfloat16 if x.dtype == jnp.bfloat16 else jnp.float32)
    if w_layout == "kn":
        acc = jnp.dot(x, qw, preferred_element_type=jnp.float32)
    else:  # "nk": contract both last dims
        acc = lax.dot_general(x, qw, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[...] = (acc * s_ref[...]).astype(out_dtype)


def _pick_tiles(m, k, n, itemsize, block_n):
    """(mt, bn) under the scoped-VMEM plan: 2x-buffered x tile (mt, K)
    + 2x-buffered int8 tile (K, bn) + f32 accumulator tile."""
    budget = 11 * 1024 * 1024
    for mt in (256, 128, 64, 32, 16, 8):
        if m % mt:
            continue
        for bn in (block_n, 256, 128):
            if n % bn:
                continue
            need = 2 * mt * k * itemsize + 2 * k * bn + 2 * mt * bn * 4
            if need <= budget:
                return mt, bn
    return 8, 128


KERNEL_NAME = "pallas_int8_matmul"


def int8_matmul(x, q, s, *, w_layout="kn", block_n=512, interpret=False):
    """y = x @ dequant(q, s). x: [M, K]; see module doc for layouts.
    Returns [M, N] in x.dtype. Falls back to an XLA dequant-matmul when the
    platform/shape gate fails (numerics match: scale is per-output)."""
    m, k = x.shape
    n = q.shape[1] if w_layout == "kn" else q.shape[0]
    if not use_int8_matmul(m, k, n):
        # widen to x.dtype (bf16 on TPU), NOT f32: the fallback must not
        # read more weight bytes than the barrier'd bf16 dequant copy
        qw = q.astype(x.dtype)
        if w_layout == "kn":
            acc = jnp.dot(x, qw, preferred_element_type=jnp.float32)
        else:
            acc = lax.dot_general(x, qw, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return (acc * s).astype(x.dtype)
    mt, bn = _pick_tiles(m, k, n, x.dtype.itemsize, block_n)
    grid = (m // mt, n // bn)
    if w_layout == "kn":
        qspec = pl.BlockSpec((k, bn), lambda mi, ni: (_i0(), ni))
    else:
        qspec = pl.BlockSpec((bn, k), lambda mi, ni: (ni, _i0()))
    out = pl.pallas_call(
        functools.partial(_kernel, w_layout=w_layout, out_dtype=x.dtype),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((mt, k), lambda mi, ni: (mi, _i0())),
            qspec,
            pl.BlockSpec((1, bn), lambda mi, ni: (_i0(), ni)),
        ],
        out_specs=pl.BlockSpec((mt, bn), lambda mi, ni: (mi, ni)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(x, q, s.reshape(1, n).astype(jnp.float32))
    return out


def use_int8_matmul(m, k, n, force=None):
    import os
    f = force if force is not None else os.environ.get(
        "PADDLE_TPU_INT8_MATMUL")
    if f in ("0", False):
        return False
    if f not in ("1", True):
        if not on_tpu():
            return False
    # K resident per program (int8 tile (K, bn) must fit VMEM comfortably)
    return m % 8 == 0 and k % 128 == 0 and n % 128 == 0 and k <= 16384


def int8_linear_nd(x, q, s, bias=None, *, w_layout="kn", interpret=False):
    """N-d wrapper: flattens leading dims of x to one matmul M."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    y = int8_matmul(x.reshape(-1, k), q, s, w_layout=w_layout,
                    interpret=interpret)
    y = y.reshape(*lead, y.shape[-1])
    if bias is not None:
        y = y + bias
    return y
