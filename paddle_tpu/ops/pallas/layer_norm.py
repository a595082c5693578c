"""Pallas fused LayerNorm for TPU — single-HBM-pass forward AND backward.

Why this kernel exists (r3 profile, GPT-1.3B B=3 S=2048 on v5e): XLA's
autodiff of the naive mean/var formulation makes 3-4 passes over the
activation per LayerNorm backward (dgamma read, dbeta read, row-stat read,
dx combine) — ~200 MB of HBM traffic per [3,2048,2048] site where ~75 MB
suffices. At the measured ~180 GB/s effective bandwidth of the bench chip,
the 98 LN sites cost ~84 ms of a 387 ms step. This kernel does the textbook
one-pass-per-direction schedule:

  fwd:  read x once per row-block; s1/s2 accumulate in VREGs; write out
        (+ per-row mu, rsig for backward — O(R) extra, negligible)
  bwd:  read dy and x once per row-block; per-row a = Σ dy·γ·x̂ and
        b = Σ dy·γ feed dx in the same pass; dγ/dβ partials accumulate in
        a VMEM scratch across the (sequential) row-block grid and are
        written once at the last block.

The reference snapshot's layer_norm_kernel.cu (phi/kernels/gpu/) is the
capability anchor; the blockwise schedule here is TPU-native (8,128 tiles,
f32 accumulation, lane-dim reductions).

Numerics: statistics use one-pass E[x²]−E[x]² in f32 (same as Flax/Haiku
LN on TPU); outputs round to the input dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...device import on_tpu
from .flash_attention import _i0  # i32 index-map literal (Mosaic x64 rule)

DEFAULT_BLOCK_ROWS = 256


def _fwd_kernel(x_ref, g_ref, b_ref, o_ref, mu_ref, rs_ref, *, eps, n):
    x = x_ref[...].astype(jnp.float32)
    s1 = jnp.sum(x, axis=-1, keepdims=True)
    s2 = jnp.sum(x * x, axis=-1, keepdims=True)
    mu = s1 / n
    var = jnp.maximum(s2 / n - mu * mu, 0.0)
    rs = jax.lax.rsqrt(var + eps)
    xhat = (x - mu) * rs
    out = xhat
    if g_ref is not None:
        out = out * g_ref[...].astype(jnp.float32)
    if b_ref is not None:
        out = out + b_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)
    bq = x.shape[0]
    mu_ref[...] = jnp.broadcast_to(mu[:, 0][None, :], (8, bq))
    rs_ref[...] = jnp.broadcast_to(rs[:, 0][None, :], (8, bq))


def _bwd_kernel(dy_ref, x_ref, mu_ref, rs_ref, g_ref,
                dx_ref, dg_ref, db_ref, dg_sc, db_sc, *, n, n_blocks,
                has_gamma):
    bi = pl.program_id(0)

    @pl.when(bi == 0)
    def _init():
        dg_sc[...] = jnp.zeros_like(dg_sc)
        db_sc[...] = jnp.zeros_like(db_sc)

    dy = dy_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    mu = mu_ref[...][0][:, None]
    rs = rs_ref[...][0][:, None]
    xhat = (x - mu) * rs
    if has_gamma:
        g = g_ref[...].astype(jnp.float32)
        dyg = dy * g
    else:
        dyg = dy
    a = jnp.sum(dyg * xhat, axis=-1, keepdims=True) / n
    b = jnp.sum(dyg, axis=-1, keepdims=True) / n
    dx_ref[...] = (rs * (dyg - xhat * a - b)).astype(dx_ref.dtype)
    dg_sc[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_sc[...] += jnp.sum(dy, axis=0, keepdims=True)

    @pl.when(bi == n_blocks - 1)
    def _finish():
        dg_ref[...] = dg_sc[...].astype(dg_ref.dtype)
        db_ref[...] = db_sc[...].astype(db_ref.dtype)


def _pick_block(r):
    bq = min(DEFAULT_BLOCK_ROWS, r)
    while r % bq:
        bq //= 2
    return bq


FWD_NAME = "pallas_layer_norm_fwd"


def _ln_fwd(x2, gamma, beta, eps, interpret):
    r, h = x2.shape
    bq = _pick_block(r)
    nb = r // bq
    in_specs = [pl.BlockSpec((bq, h), lambda i: (i, _i0()))]
    args = [x2]
    if gamma is not None:
        in_specs.append(pl.BlockSpec((1, h), lambda i: (_i0(), _i0())))
        args.append(gamma.reshape(1, h))
    if beta is not None:
        in_specs.append(pl.BlockSpec((1, h), lambda i: (_i0(), _i0())))
        args.append(beta.reshape(1, h))

    def kern(*refs):
        if gamma is not None and beta is not None:
            x_ref, g_ref, b_ref, o_ref, mu_ref, rs_ref = refs
        elif gamma is not None:
            x_ref, g_ref, o_ref, mu_ref, rs_ref = refs
            b_ref = None
        elif beta is not None:
            x_ref, b_ref, o_ref, mu_ref, rs_ref = refs
            g_ref = None
        else:
            x_ref, o_ref, mu_ref, rs_ref = refs
            g_ref = b_ref = None
        _fwd_kernel(x_ref, g_ref, b_ref, o_ref, mu_ref, rs_ref,
                    eps=eps, n=float(h))

    out, mu, rs = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((r, h), x2.dtype),
                   jax.ShapeDtypeStruct((8, r), jnp.float32),
                   jax.ShapeDtypeStruct((8, r), jnp.float32)),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=(pl.BlockSpec((bq, h), lambda i: (i, _i0())),
                   pl.BlockSpec((8, bq), lambda i: (_i0(), i)),
                   pl.BlockSpec((8, bq), lambda i: (_i0(), i))),
        interpret=interpret,
        name=FWD_NAME,
    )(*args)
    return out, mu, rs


BWD_NAME = "pallas_layer_norm_bwd"


def _ln_bwd(dy2, x2, mu, rs, gamma, interpret):
    r, h = x2.shape
    bq = _pick_block(r)
    nb = r // bq
    has_gamma = gamma is not None
    in_specs = [
        pl.BlockSpec((bq, h), lambda i: (i, _i0())),
        pl.BlockSpec((bq, h), lambda i: (i, _i0())),
        pl.BlockSpec((8, bq), lambda i: (_i0(), i)),
        pl.BlockSpec((8, bq), lambda i: (_i0(), i)),
    ]
    args = [dy2, x2, mu, rs]
    if has_gamma:
        in_specs.append(pl.BlockSpec((1, h), lambda i: (_i0(), _i0())))
        args.append(gamma.reshape(1, h))

    def kern(*refs):
        if has_gamma:
            dy_ref, x_ref, mu_ref, rs_ref, g_ref = refs[:5]
            dx_ref, dg_ref, db_ref, dg_sc, db_sc = refs[5:]
        else:
            dy_ref, x_ref, mu_ref, rs_ref = refs[:4]
            g_ref = None
            dx_ref, dg_ref, db_ref, dg_sc, db_sc = refs[4:]
        _bwd_kernel(dy_ref, x_ref, mu_ref, rs_ref, g_ref,
                    dx_ref, dg_ref, db_ref, dg_sc, db_sc,
                    n=float(h), n_blocks=nb, has_gamma=has_gamma)

    dx, dg, db = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((r, h), dy2.dtype),
                   jax.ShapeDtypeStruct((1, h), jnp.float32),
                   jax.ShapeDtypeStruct((1, h), jnp.float32)),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=(pl.BlockSpec((bq, h), lambda i: (i, _i0())),
                   pl.BlockSpec((1, h), lambda i: (_i0(), _i0())),
                   pl.BlockSpec((1, h), lambda i: (_i0(), _i0()))),
        scratch_shapes=[pltpu.VMEM((1, h), jnp.float32),
                        pltpu.VMEM((1, h), jnp.float32)],
        interpret=interpret,
        name=BWD_NAME,
    )(*args)
    return dx, dg[0], db[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ln(x2, gamma, beta, eps, has_gamma, has_beta, interpret):
    out, _, _ = _ln_fwd(x2, gamma, beta, eps, interpret)
    return out


def _ln_vjp_fwd(x2, gamma, beta, eps, has_gamma, has_beta, interpret):
    out, mu, rs = _ln_fwd(x2, gamma, beta, eps, interpret)
    return out, (x2, mu, rs, gamma, beta)


def _ln_vjp_bwd(eps, has_gamma, has_beta, interpret, res, dy):
    x2, mu, rs, gamma, beta = res
    dx, dg, db = _ln_bwd(dy, x2, mu, rs, gamma, interpret)
    return (dx,
            dg.astype(gamma.dtype) if has_gamma else None,
            db.astype(beta.dtype) if has_beta else None)


_ln.defvjp(_ln_vjp_fwd, _ln_vjp_bwd)


def fused_layer_norm(x, gamma=None, beta=None, eps: float = 1e-5,
                     interpret: bool = False):
    """LayerNorm over the LAST axis of x with optional affine params.

    x: [..., H]; gamma/beta: [H] or None. Returns same shape/dtype as x.
    Requires H % 128 == 0 and a row count divisible down to >=8-row
    blocks; callers fall back to the XLA formulation otherwise."""
    h = x.shape[-1]
    lead = x.shape[:-1]
    r = 1
    for d in lead:
        r *= int(d)
    x2 = x.reshape(r, h)
    out = _ln(x2, gamma, beta, float(eps),
              gamma is not None, beta is not None, bool(interpret))
    return out.reshape(x.shape)


def fused_layer_norm_supported(x_shape):
    """Static routing predicate shared with nn.functional.layer_norm.

    OPT-IN ONLY (PADDLE_TPU_FUSED_LN=1): on the v5e bench chip XLA's
    autodiff LN measured faster than this kernel (2.8 vs 3.4 ms fwd+bwd on
    [3,2048,2048]) — Mosaic's lowering of the f32 cast + two-axis reduce
    chain doesn't beat the fusion XLA already emits. Kept because the
    single-pass schedule is the right shape where relative costs differ.
    The platform gate keeps the env opt-in from routing a CPU host into a
    Mosaic compile that cannot succeed."""
    import os
    if os.environ.get("PADDLE_TPU_FUSED_LN") != "1":
        return False
    if not on_tpu():
        return False
    if x_shape[-1] % 128 != 0:
        return False
    r = 1
    for d in x_shape[:-1]:
        r *= int(d)
    if r < 8 or r % 8 != 0:
        return False
    return True
