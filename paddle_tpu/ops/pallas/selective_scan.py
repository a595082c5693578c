"""Pallas selective scan (see ops/selective_scan.py for the arithmetic): a
prefill window of T tokens of one row, from the row's state.

  grid (b, Din / bd)   a row and a block of `bd` channels; every block is
              independent (the recurrence couples nothing across d).
  state       the block's [N, bd] float32 (N = 16, bd = 512: eight
              vector registers) is read once, carried through a
              `fori_loop` over the window's tokens and written once. The
              [T, N, Din] decays and states of the associative-scan form
              (84 MB a 256-token window and layer at d_inner 5,120) never
              exist, in VMEM or in HBM.
  tokens      eight at a time: x and dt rows [1, bd] are read with their
              token on the sublanes and broadcast over the N states; B_t
              and C_t are needed as COLUMNS [N, 1] against the lanes, so
              the wrapper hands them over as [T / 8, N, 8] (a chunk's
              eight tokens along the lanes, sliced statically) instead of
              transposing inside the kernel.
  compute     one exp a (token, state, channel), five multiplies and
              adds, a sublane reduction over N for y: all on the vector
              and transcendental units, no matmul.
  padding     a token past `lens` arrives with dt = 0 (the wrapper's
              doing): exp(0 A) = 1 and dt B x = 0 leave the state as it
              was; its y is never read.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _i0

SELECTIVE_SCAN_NAME = "pallas_selective_scan"
_TOKENS = 8             # tokens a trip of the loop: one sublane tile
_CHANNELS = 512         # channels a program: [16, 512] f32 is 8 registers


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, s_ref, y_ref, so_ref):
    a = a_ref[...]                                  # [N, bd]
    d_skip = d_ref[...]                             # [1, bd]

    def chunk(i, s):
        t0 = pl.multiple_of(i * _TOKENS, _TOKENS)
        bc, cc = b_ref[i], c_ref[i]                 # [N, 8]
        for j in range(_TOKENS):
            x_t = x_ref[pl.ds(t0 + j, 1), :]        # [1, bd]
            dt_t = dt_ref[pl.ds(t0 + j, 1), :]
            s = jnp.exp(dt_t * a) * s + (dt_t * x_t) * bc[:, j:j + 1]
            y_ref[pl.ds(t0 + j, 1), :] = jnp.sum(
                s * cc[:, j:j + 1], axis=0, keepdims=True) + d_skip * x_t
        return s

    so_ref[...] = lax.fori_loop(jnp.int32(0),
                                jnp.int32(x_ref.shape[0] // _TOKENS), chunk,
                                s_ref[...])


def _columns(m, t_pad):
    """m [b, T, N] -> [b, T / 8, N, 8]: a chunk's tokens along the lanes."""
    b, t, n = m.shape
    m = jnp.pad(m, ((0, 0), (0, t_pad - t), (0, 0)))
    return jnp.swapaxes(m.reshape(b, t_pad // _TOKENS, _TOKENS, n), 2, 3)


def selective_scan_kernel(x, dt, bm, cm, a_t, d_skip, state, lens, *,
                          interpret: bool = False):
    """x, dt [b, T, Din]; bm, cm [b, T, N]; a_t [N, Din]; d_skip [Din];
    state [b, N, Din]; lens [b]: all float32 but lens. Returns
    (y [b, T, Din], state')."""
    b, t, din = x.shape
    n = a_t.shape[0]
    bd = min(_CHANNELS, din)
    if din % bd:
        raise ValueError(f"d_inner {din} must be a multiple of {bd}")
    t_pad = -(-t // _TOKENS) * _TOKENS
    f32 = jnp.float32
    dt = jnp.where(jnp.arange(t)[None, :, None] < lens[:, None, None],
                   dt.astype(f32), 0.0)
    pad = lambda a: jnp.pad(a.astype(f32),  # noqa: E731
                            ((0, 0), (0, t_pad - t), (0, 0)))
    tokens = pl.BlockSpec((None, t_pad, bd), lambda i, j: (i, _i0(), j))
    cols = pl.BlockSpec((None, t_pad // _TOKENS, n, _TOKENS),
                        lambda i, j: (i, _i0(), _i0(), _i0()))
    row = pl.BlockSpec((None, n, bd), lambda i, j: (i, _i0(), j))
    y, new = pl.pallas_call(
        _kernel,
        grid=(b, din // bd),
        in_specs=[tokens, tokens, cols, cols,
                  pl.BlockSpec((n, bd), lambda i, j: (_i0(), j)),
                  pl.BlockSpec((1, bd), lambda i, j: (_i0(), j)), row],
        out_specs=[tokens, row],
        out_shape=[jax.ShapeDtypeStruct((b, t_pad, din), f32),
                   jax.ShapeDtypeStruct((b, n, din), f32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=SELECTIVE_SCAN_NAME,
    )(pad(x), pad(dt), _columns(bm.astype(f32), t_pad),
      _columns(cm.astype(f32), t_pad), a_t.astype(f32),
      d_skip.astype(f32).reshape(1, din), state.astype(f32))
    return y[:, :t], new
