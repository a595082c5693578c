"""Fused short-sequence MHA with a cycling additive bias (Swin windows).

Reference anchor: the masked path of the reference's fused attention kernel
(paddle/fluid/operators/fused/fused_attention_op.cu with
operators/fused/fused_softmax_mask.cu:1) — attention logits get an additive
mask before the in-kernel softmax. The TPU shape of that capability here is
built for WINDOW attention: Swin runs thousands of 49-token windows per
image, and a (B·nW)-sized Pallas grid of 49-row programs is dispatch-bound
(measured r4). Instead, W_g windows are BATCHED into one program as a
length-S = W_g·49 sequence whose additive bias carries:

  - block-diagonal structure: -1e9 off the diagonal blocks (windows must
    not attend across each other),
  - the learned relative-position bias, tiled (differentiable — the kernel
    accumulates d(bias) so autodiff reaches the rel-bias table),
  - the static shifted-window masks.

The bias is PERIODIC over the batch: window-groups repeat the same layout
every image, so bias[r] with r = batch_index mod R serves the whole batch.
Grids keep the bias block VMEM-resident: forward (r, g, t) fetches each
(r, g) bias block once; backward (r, t, g) holds the (1, nh, S, S) dbias
output block resident across the inner sweep, accumulating per-program
contributions — Pallas TPU grids are sequential, so read-modify-write on
the resident output block is race-free.

Unlike fused_mha.py (packed [B,S,3F], which needs F % 128 == 0 for its
block slicing), q/k/v ride as SEPARATE arrays here: swin head counts (3,
6, 12, 24 at hd=32) give F = 96/192 that no packed block satisfies, while
a (1, S, G·hd) block over a [B, S, F] array is legal whenever G·hd is
128-aligned OR the full F. The packed<->split boundary is one XLA
slice/concat pair per call — noise at window sizes. Numerics conventions
are shared: bf16 dots, f32 accumulation, f32 softmax.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...device import on_tpu
from .fused_mha import _head, _softmax_f32, _i0


def _fwd_kernel(b_ref, q_ref, k_ref, v_ref, o_ref, *, nh, hd, G, scale):
    for j in range(G):
        q = _head(q_ref, j, hd)
        k = _head(k_ref, j, hd)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = s + b_ref[0, j].astype(jnp.float32)
        p = _softmax_f32(s)
        v = _head(v_ref, j, hd)
        o_ref[0, :, j * hd:(j + 1) * hd] = jnp.dot(
            p.astype(v.dtype), v,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _bwd_kernel(b_ref, q_ref, k_ref, v_ref, do_ref, dq_ref, dk_ref, dv_ref,
                db_ref, *, nh, hd, G, scale):
    t, gg = pl.program_id(1), pl.program_id(2)
    for j in range(G):
        q = _head(q_ref, j, hd)
        k = _head(k_ref, j, hd)
        v = _head(v_ref, j, hd)
        do = _head(do_ref, j, hd)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = s + b_ref[0, j].astype(jnp.float32)
        sigma = _softmax_f32(s)
        dsig = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        dv_ref[0, :, j * hd:(j + 1) * hd] = jnp.dot(
            sigma.astype(do.dtype).T, do,
            preferred_element_type=jnp.float32).astype(dv_ref.dtype)
        r = jnp.sum(dsig * sigma, axis=-1, keepdims=True)
        ds_f32 = sigma * (dsig - r)          # grad wrt (scaled logits+bias)
        hslot = gg * G + j

        @pl.when(t == 0)
        def _init(hslot=hslot, ds_f32=ds_f32):
            db_ref[0, hslot] = ds_f32

        @pl.when(t > 0)
        def _acc(hslot=hslot, ds_f32=ds_f32):
            db_ref[0, hslot] += ds_f32

        ds = ds_f32.astype(q.dtype)
        dq_ref[0, :, j * hd:(j + 1) * hd] = (jnp.dot(
            ds, k, preferred_element_type=jnp.float32)
            * scale).astype(dq_ref.dtype)
        dk_ref[0, :, j * hd:(j + 1) * hd] = (jnp.dot(
            ds.T, q, preferred_element_type=jnp.float32)
            * scale).astype(dk_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _mha_b(q, k, v, bias, nh, scale, G, interpret):
    return _fwd(q, k, v, bias, nh, scale, G, interpret)


FWD_NAME = "pallas_fused_mha_bias_fwd"


def _fwd(q, k, v, bias, nh, scale, G, interpret):
    b, s, F = q.shape
    hd = F // nh
    R = bias.shape[0]
    n_groups = nh // G
    n_t = b // R
    spec = pl.BlockSpec((1, s, G * hd),
                        lambda r, g, t: (t * R + r, _i0(), g))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, nh=nh, hd=hd, G=G, scale=scale),
        out_shape=jax.ShapeDtypeStruct((b, s, F), q.dtype),
        grid=(R, n_groups, n_t),
        in_specs=[
            pl.BlockSpec((1, G, s, s),
                         lambda r, g, t: (r, g, _i0(), _i0())),
            spec, spec, spec,
        ],
        out_specs=spec,
        interpret=interpret,
        name=FWD_NAME,
    )(bias, q, k, v)
    return out


def _vjp_fwd(q, k, v, bias, nh, scale, G, interpret):
    return _fwd(q, k, v, bias, nh, scale, G, interpret), (q, k, v, bias)


BWD_NAME = "pallas_fused_mha_bias_bwd"


def _vjp_bwd(nh, scale, G, interpret, res, g_out):
    q, k, v, bias = res
    b, s, F = q.shape
    hd = F // nh
    R = bias.shape[0]
    n_groups = nh // G
    n_t = b // R
    spec = pl.BlockSpec((1, s, G * hd),
                        lambda r, t, g: (t * R + r, _i0(), g))
    dq, dk, dv, dbias = pl.pallas_call(
        functools.partial(_bwd_kernel, nh=nh, hd=hd, G=G, scale=scale),
        out_shape=(jax.ShapeDtypeStruct((b, s, F), q.dtype),
                   jax.ShapeDtypeStruct((b, s, F), q.dtype),
                   jax.ShapeDtypeStruct((b, s, F), q.dtype),
                   jax.ShapeDtypeStruct((R, nh, s, s), jnp.float32)),
        grid=(R, n_t, n_groups),
        in_specs=[
            pl.BlockSpec((1, G, s, s),
                         lambda r, t, g: (r, g, _i0(), _i0())),
            spec, spec, spec, spec,
        ],
        out_specs=(
            spec, spec, spec,
            pl.BlockSpec((1, nh, s, s), lambda r, t, g: (r, _i0(), _i0(),
                                                         _i0())),
        ),
        interpret=interpret,
        name=BWD_NAME,
    )(bias, q, k, v, g_out)
    return dq, dk, dv, dbias.astype(bias.dtype)


_mha_b.defvjp(_vjp_fwd, _vjp_bwd)


def fused_mha_bias(qkv, num_heads, bias, *, scale=None,
                   heads_per_program=None, interpret=False):
    """Batched-window attention with additive per-head bias.

    qkv: [B, S, 3·nh·hd] packed [q heads | k heads | v heads] (split into
        three arrays at the XLA boundary — one slice, one concat in vjp).
    bias: [R, nh, S, S] additive logits bias; program batch index p uses
        bias[p mod R] (B must be a multiple of R). Differentiable — the
        backward kernel accumulates d(bias) across the batch.
    Returns [B, S, nh·hd] context in the packed layout.
    """
    b, s, F3 = qkv.shape
    F = F3 // 3
    hd = F // num_heads
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    R, bnh, bs1, bs2 = bias.shape
    if bnh != num_heads or bs1 != s or bs2 != s:
        raise ValueError(f"fused_mha_bias: bias {bias.shape} does not match "
                         f"(R, {num_heads}, {s}, {s})")
    if b % R:
        raise ValueError(f"fused_mha_bias: batch {b} not a multiple of "
                         f"bias period {R}")
    G = heads_per_program or _pick_bias_group(num_heads, hd, s,
                                              qkv.dtype.itemsize)
    if num_heads % G or ((G * hd) % 128 and G != num_heads):
        # the (1, S, G·hd) blocks need a 128-aligned last dim unless the
        # block spans the full F (single group)
        raise ValueError(
            f"fused_mha_bias: heads_per_program={G} invalid for nh="
            f"{num_heads} hd={hd} (need nh%G==0 and (G*hd)%128==0, or "
            f"G==nh)")
    q, k, v = qkv[..., :F], qkv[..., F:2 * F], qkv[..., 2 * F:]
    return _mha_b(q, k, v, bias, int(num_heads), float(scale), int(G),
                  bool(interpret))


def _pick_bias_group(nh, hd, s, itemsize):
    """Largest head group fitting the VMEM plan: bias blocks (G,S,S) f32
    dominate — 2x-buffered input plus the resident (nh,S,S) f32 dbias
    output in the backward, plus ~4 (S,S) f32 ephemerals."""
    budget = 10 * 1024 * 1024
    fixed = nh * s * s * 4 + 4 * s * s * 4      # dbias block + ephemerals
    aligned = [G for G in range(nh, 0, -1)
               if nh % G == 0 and ((G * hd) % 128 == 0 or G == nh)]
    for G in aligned:
        need = fixed + 2 * G * s * s * 4 + 8 * 2 * s * G * hd * itemsize
        if need <= budget:
            return G
    return aligned[-1]


def use_fused_mha_bias(s, num_heads, head_dim):
    """Gate: TPU-class platform and a workable VMEM plan."""
    import os
    force = os.environ.get("PADDLE_TPU_FUSED_MHA_BIAS")
    if force == "0":
        return False
    if force != "1":
        if not on_tpu():
            return False
    if head_dim % 8 or s > 512:
        return False
    # bias+dbias resident VMEM must fit even at G=1
    return (num_heads * s * s * 4 + 6 * s * s * 4) <= 10 * 1024 * 1024
