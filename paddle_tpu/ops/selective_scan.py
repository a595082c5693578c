"""The selective scan of Mamba-1 (Gu and Dao, arXiv:2312.00752), the
recurrence of models/jamba.py's state-space layers:

    S_t[n, d] = exp(dt_t[d] A[n, d]) S_(t-1)[n, d] + dt_t[d] B_t[n] x_t[d]
    y_t[d]    = sum_n S_t[n, d] C_t[n] + D[d] x_t[d]

The decay is per channel d, per state n and per TOKEN: there is no matmul
form, and nothing here builds one. The state is held as [N, d_inner] a
row, d_inner along the lanes (N = 16 fills two sublane tiles; the other
way round 16 of 128 lanes would work and the plane would be padded
eightfold), float32 always.

`scan_window` is a prefill window of T tokens a row from the row's state;
tokens i >= lens[b] leave the state where it is, so the state returned is
the state at the window's last live token. On the chip it is the Pallas
kernel of pallas/selective_scan.py (the state stays in VMEM across the
window; nothing of shape [T, N, d_inner] exists), elsewhere the plain
`lax.scan` over tokens. `scan_step` is one token of every row of a decode
batch; a row that is not live keeps its state bit for bit. It is left to
XLA, which ships it as ONE fusion a layer (y and the new state in one pass
over the plane, in place): measured alone on the chip at 256 rows of
[16, 5120] it moves the plane at 586 GB/s, a Pallas kernel over blocks of
eight rows at 571, and neither is faster with fewer rows live (both move
every row; PERF.md, PR 36).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ..device import on_tpu


def scan_window_reference(x, dt, bm, cm, a_t, d_skip, state, lens):
    """The recurrence token by token. x, dt [b, T, Din]; bm, cm [b, T, N];
    a_t [N, Din]; d_skip [Din]; state [b, N, Din]; lens [b]. Returns
    (y [b, T, Din], state')."""
    t = x.shape[1]
    live = jnp.arange(t)[None] < lens[:, None]                  # [b, T]

    def step(s, tok):
        x_t, dt_t, b_t, c_t, ok = tok
        new = jnp.exp(dt_t[:, None, :] * a_t) * s \
            + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        s = jnp.where(ok[:, None, None], new, s)
        return s, jnp.sum(s * c_t[:, :, None], axis=1) + d_skip * x_t
    mv = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    state, y = lax.scan(step, state, (mv(x), mv(dt), mv(bm), mv(cm),
                                      mv(live)))
    return jnp.moveaxis(y, 0, 1), state


def scan_window(x, dt, bm, cm, a_t, d_skip, state, lens):
    """A prefill window (shapes as `scan_window_reference`, float32): the
    kernel where d_inner and N fill whole tiles on a TPU, else the
    token-by-token recurrence."""
    n, din = a_t.shape
    if on_tpu() and din % 128 == 0 and n % 8 == 0:
        from .pallas.selective_scan import selective_scan_kernel
        return selective_scan_kernel(x, dt, bm, cm, a_t, d_skip, state, lens)
    return scan_window_reference(x, dt, bm, cm, a_t, d_skip, state, lens)


def scan_step(x, dt, bm, cm, a_t, d_skip, state, live):
    """One token of every row: x, dt [B, Din]; bm, cm [B, N]; state
    [B, N, Din]; live [B]. Returns (y [B, Din], state'), the state of a
    row that is not live bit-equal. One pass over the state plane, read
    and written in place where the caller donates it."""
    new = jnp.exp(dt[:, None, :] * a_t) * state \
        + (dt * x)[:, None, :] * bm[:, :, None]
    new = jnp.where(live[:, None, None], new, state)
    return jnp.sum(new * cm[:, :, None], axis=1) + d_skip * x, new
