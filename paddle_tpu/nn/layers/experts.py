"""A chip's share of a sparse expert layer (expert parallelism, one rank).

The layer is told which experts of the layer's `num_experts` it holds
(`first` .. `first + held`). It routes every token over ALL the experts
(sigmoid scores in float32, the `top_k` largest, normalised and scaled),
drops nothing, and computes the shared expert plus the part of the result
that its own experts give:

    s = sigmoid(W_r h); w = s_top / (sum s_top + 1e-20) * scale
    y = Shared(h) + sum over chosen experts e held here of w_e E_e(h)
    E(h) = W_down(silu(W_gate h) * W_up h)

What the absent experts would add is left out: on one chip the layer runs
without the exchange that would bring it, and nothing stands in for it.
There is no capacity, no group limit and no bias on the scores (not the
GShard layer of incubate.distributed.models.moe, which pads to a capacity
and drops).

The grouped product is XLA's: every held expert that got a token, over
every token of the call, its output weighted by the token's w_e (0 where
the expert was not chosen); an expert nobody chose is skipped and its
weights are not read. That streams each hit expert's weights once a call
and is bound by that stream up to ~240 tokens a call on a v5e (PERF.md,
PR 28); a product ragged by the tokens each expert got is what a longer
window wants.

`experts_forward` also returns what the call did, as five float32 counts
(`STATS`): assignments computed here, assignments made (top_k a live
token), experts hit, the largest number of tokens one held expert got, and
1 for the call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..layer import Layer
from .. import initializer as I
from ...core.tensor import apply_op

LEAVES = ("w_r", "ws_gate", "ws_up", "ws_down", "we_gate", "we_up", "we_down")
STATS = ("expert_assignments_here", "expert_assignments_made",
         "experts_hit", "expert_tokens_max", "expert_layer_calls")


def route(h, w_r, top_k: int, scale: float):
    """(indices [T, k] among all experts, weights [T, k]), float32 scores."""
    logits = jnp.matmul(h.astype(jnp.float32), w_r.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    top, idx = jax.lax.top_k(jax.nn.sigmoid(logits), top_k)
    return idx, top / (jnp.sum(top, -1, keepdims=True) + 1e-20) * scale


def gated_mlp(h, gate, up, down):
    """W_down(silu(W_gate h) * W_up h), float32 out."""
    a = jax.nn.silu(jnp.matmul(h, gate)) * jnp.matmul(h, up)
    return jnp.matmul(a, down, preferred_element_type=jnp.float32)


def experts_forward(h, w_r, ws_gate, ws_up, ws_down, we_gate, we_up,
                    we_down, *, first: int, top_k: int, scale: float,
                    live=None, shared: bool = True):
    """h [T, H] -> (y [T, H] float32, stats [5] float32). The router reads
    h as it comes (float32 from a float32 residual stream); the products
    run in the experts' dtype. `live` [T] bool marks the tokens that count
    (padding and idle rows still flow through)."""
    held = we_gate.shape[0]
    idx, w = route(h, w_r, top_k, scale)
    h = h.astype(we_gate.dtype)
    local = idx - first
    here = (local >= 0) & (local < held)
    if live is not None:
        here = here & live[:, None]
    # [T, held]: the weight of each held expert for each token, 0 if unchosen
    comb = jnp.sum(jnp.where(
        here[..., None] & (local[..., None] == jnp.arange(held)),
        w[..., None], 0.0), axis=1)
    y = gated_mlp(h, ws_gate, ws_up, ws_down) if shared \
        else jnp.zeros(h.shape, jnp.float32)
    per_expert = jnp.sum(comb > 0, axis=0).astype(jnp.float32)

    def add_expert(e, y):        # plain products, the expert's as stored
        a = jax.nn.silu(jnp.matmul(h, we_gate[e])) * jnp.matmul(h, we_up[e])
        a = (a.astype(jnp.float32) * comb[:, e:e + 1]).astype(h.dtype)
        return y + jnp.matmul(a, we_down[e],
                              preferred_element_type=jnp.float32)
    for e in range(held):        # an expert nobody chose is not streamed
        y = jax.lax.cond(per_expert[e] > 0,
                         functools.partial(add_expert, e), lambda y: y, y)
    n_live = h.shape[0] if live is None else jnp.sum(live)
    stats = jnp.stack([jnp.sum(per_expert),
                       jnp.asarray(n_live * top_k, jnp.float32),
                       jnp.sum(per_expert > 0).astype(jnp.float32),
                       jnp.max(per_expert), jnp.float32(1.0)])
    return y, stats


class HeldExperts(Layer):
    """The experts `first .. first + held` of a layer of `num_experts`,
    its router and its shared expert."""

    def __init__(self, hidden_size: int, expert_width: int,
                 num_experts: int, held: int, first: int, top_k: int,
                 scale: float = 1.0, shared_width: int = 0,
                 initializer_range: float = 0.02, dtype=None):
        super().__init__()
        if not 0 <= first <= num_experts - held:
            raise ValueError(f"experts {first}..{first + held} are not among "
                             f"the layer's {num_experts}")
        if top_k > num_experts:
            raise ValueError(f"top_k {top_k} > {num_experts} experts")
        self.first, self.held, self.top_k = int(first), int(held), int(top_k)
        self.num_experts, self.scale = int(num_experts), float(scale)
        init = I.Normal(0.0, initializer_range)
        mk = lambda *shape: self.create_parameter(  # noqa: E731
            list(shape), dtype=dtype, default_initializer=init)
        h, m, ms = hidden_size, expert_width, shared_width or expert_width
        self.w_r = mk(h, num_experts)
        self.ws_gate, self.ws_up, self.ws_down = mk(h, ms), mk(h, ms), mk(ms, h)
        self.we_gate, self.we_up = mk(held, h, m), mk(held, h, m)
        self.we_down = mk(held, m, h)

    def apply(self, arrays, h, live=None):
        """The pure function over this layer's arrays, in `LEAVES` order."""
        return experts_forward(h, *arrays, first=self.first,
                               top_k=self.top_k, scale=self.scale, live=live)

    def forward(self, x):
        """x [..., H] -> [..., H]; differentiable through apply_op."""
        def fn(x, *arrays):
            y, _ = self.apply(arrays, x.reshape(-1, x.shape[-1]))
            return y.reshape(x.shape).astype(x.dtype)
        return apply_op("held_experts", fn,
                        [x] + [getattr(self, n) for n in LEAVES])
