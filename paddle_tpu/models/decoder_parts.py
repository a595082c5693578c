"""What the decoders written for the paged serving engine share
(models/pangu_moe.py, models/minicpm_sala.py): the float32 RMSNorm, the
product in the parameters' dtype, rotary positions with dimension i paired
with i + d/2, and the SiLU-gated MLP's parameters."""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ..core.tensor import Tensor
from ..nn.layer import Layer


def _arr(a, dtype=None):
    """A Tensor's or an array-like's array, in `dtype` if given."""
    return jnp.asarray(a._data if isinstance(a, Tensor) else a, dtype)


def _rms(x, g, eps):
    """RMSNorm in float32, float32 out."""
    x = x.astype(jnp.float32)
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y * g.astype(jnp.float32)


def _mm(a, w):
    """a @ w with a rounded to the weights' dtype, float32 out: the
    residual stream, the norms and the router's input stay float32, every
    product runs in the parameters' dtype."""
    return jnp.matmul(a.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def _rope(x, pos, theta):
    """x [B, S, ..., d] rotated by pos [B, S]; dimension i pairs with
    i + d/2."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freq
    ang = ang.reshape(pos.shape + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


class GatedMLP(Layer):
    def __init__(self, hidden_size, width, init, dtype):
        super().__init__()
        mk = lambda *s: self.create_parameter(  # noqa: E731
            list(s), dtype=dtype, default_initializer=init)
        self.w_gate, self.w_up = mk(hidden_size, width), mk(hidden_size, width)
        self.w_down = mk(width, hidden_size)
