"""Lightning Attention (Qin et al., arXiv:2401.04658): linear attention
with one fixed decay a head, as a recurrent state beside the KV cache.

    S_t = lambda_h S_(t-1) + k_t v_t^T        S [d, d] a head, float32
    o_t = S_t^T q_t                           (the caller scales)

`lightning_decode` is one step of it over a batch of rows, the state
updated in place where the caller donates it; rows that are not live keep
their state. `lightning_window` is the exact chunked form for a prefill
window that starts from a row's state: within a chunk the masked q k^T
under the decay matrix, across chunks the state; tokens past `lens` leave
the state as it was, so the state after the window is the state at the
window's last live token.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

_CHUNK = 128


def decay(n_heads: int):
    """lambda_h = exp(-s_h), s_h = 2^(-8 (h + 1) / n_heads): [n_heads]."""
    h = jnp.arange(1, n_heads + 1, dtype=jnp.float32)
    return jnp.exp(-(2.0 ** (-8.0 * h / n_heads)))


def lightning_decode(q, k, v, state, live):
    """q, k, v [B, nh, d] float32; state [B, nh, d, d] float32; live [B].
    Returns (o [B, nh, d], state')."""
    lam = decay(q.shape[1])[None, :, None, None]
    new = lam * state + k[..., :, None] * v[..., None, :]
    new = jnp.where(live[:, None, None, None], new, state)
    return jnp.sum(new * q[..., :, None], axis=-2), new


def lightning_window(q, k, v, state, lens, chunk: int = _CHUNK):
    """q, k, v [B, S, nh, d] float32, the window's tokens i < lens[b]
    live; state [B, nh, d, d]. Returns (o [B, S, nh, d], state')."""
    b, s, nh, d = q.shape
    c = min(chunk, s)
    pad = -s % c
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
    n_c = (s + pad) // c
    f32 = jnp.float32
    hi = lax.Precision.HIGHEST
    ln = jnp.log(decay(nh))                                     # [nh]
    i = jnp.arange(c, dtype=f32)
    dmat = jnp.where(i[:, None] >= i[None, :],
                     jnp.exp(ln[:, None, None] * (i[:, None] - i[None, :])),
                     0.0)                                       # [nh, C, C]
    into = jnp.exp(ln[:, None] * (i[None] + 1.0))               # [nh, C]
    rs = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape(b, n_c, c, nh, d), 1, 0)                      # [n_c,B,C,nh,d]

    def step(st, x):
        qc, kc, vc, j = x
        n_live = jnp.clip(lens - j * c, 0, c).astype(f32)       # [B]
        live = i[None] < n_live[:, None]                        # [B, C]
        kc = jnp.where(live[:, :, None, None], kc, 0.0)
        a = jnp.einsum("bihd,bjhd->bhij", qc, kc, precision=hi) * dmat
        o = jnp.einsum("bhij,bjhd->bihd", a, vc, precision=hi) \
            + jnp.einsum("bihd,bhde->bihe",
                         qc * into.T[None, :, :, None], st, precision=hi)
        outof = jnp.exp(ln[None, :, None] * jnp.maximum(
            n_live[:, None, None] - 1.0 - i[None, None], 0.0))  # [B, nh, C]
        whole = jnp.exp(ln[None] * n_live[:, None])             # [B, nh]
        st = whole[..., None, None] * st + jnp.einsum(
            "bjhd,bjhe->bhde", kc * jnp.moveaxis(outof, 1, 2)[..., None], vc,
            precision=hi)
        return st, o
    state, o = lax.scan(step, state.astype(f32),
                        (rs(q), rs(k), rs(v), jnp.arange(n_c)))
    return jnp.moveaxis(o, 0, 1).reshape(b, n_c * c, nh, d)[:, :s], state
