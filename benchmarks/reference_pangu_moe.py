"""Plain reference of the openPangu-Ultra-MoE block (config.json of
FreedomIntelligence/openPangu-Ultra-MoE-718B, model_type pangu_ultra_moe;
the family's published modelling code for what the config leaves open):
multi-head latent attention with rotary positions on part of each head,
RMSNorm before and after each sub-layer, SiLU-gated MLPs, sigmoid-scored
top-k experts with one shared expert, untied head. Straightforward
`jax.numpy` in float32 at `highest` matmul precision: no cache, no
kernels, no batching, one sequence at a time. It imports nothing of the
program; weights come from benchmarks.weights_pangu_moe and the seed.

    x <- x + N_post_attn(Attn(N_in(x)));  x <- x + N_post_mlp(F(N_pre_mlp(x)))
    Attn: c_q = N_q(W_dq h); [q_nope | q_pe] = W_uq c_q per head;
          [c_kv | k_pe] = W_dkv h, c_kv <- N_kv(c_kv); RoPE on q_pe, k_pe
          (k_pe one vector for all heads); [k_nope | v] = W_ukv c_kv per
          head; softmax((q_nope.k_nope + q_pe.k_pe) / sqrt(dn + dr)) v; W_o
    F:    W_down(silu(W_gate h) * W_up h) in the leading dense layers;
          after them Shared(h) + sum over the chosen experts HELD HERE of
          w_e E_e(h), s = sigmoid(W_r h), the k largest of all Ea scores,
          w = s_top / (sum s_top + 1e-20) x routed_scaling_factor
    logits = W_head N_final(x)

The share is the configuration's: `n_routed_experts` experts of each layer
from number `deployment.expert_rank` x that on, of `deployment.
expert_parallel` times as many; what the absent experts would add is left
out (model-configs guide, section 4). RoPE pairs dimension i with i + dr/2,
angle position x theta^(-2i/dr).

A whole expert layer is 4 GB in float32 at the published widths, so each
layer's weights are made from the seed when the pass reaches it and let
go, and attention goes a block of heads at a time. `mode` is the precision
of the matmul operands (benchmarks.reference: "f32", "bf16", "fp8"), for
the control of `correct`; the router's scores are float32 in every mode,
as in the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks import weights_pangu_moe as W
from benchmarks.reference import F32, HI, mm

HEAD_BLOCK = 8


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g.astype(F32)


def rope(x, pos, theta):
    """x [S, ..., d] rotated by position; pos [S]."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freq[None]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(p, h, config, mode="f32"):
    """h [S, H] (normed) -> [S, H], causal over the sequence."""
    c = W.sizes(config)
    s = h.shape[0]
    nh, dn, dr, dv, rkv = c["nh"], c["dn"], c["dr"], c["dv"], c["Rkv"]
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    pos = jnp.arange(s)
    c_q = rms_norm(mm(h, p["w_dq"], mode), p["n_q"], eps)
    q = mm(c_q, p["w_uq"], mode).reshape(s, nh, dn + dr)
    dkv = mm(h, p["w_dkv"], mode)
    c_kv = rms_norm(dkv[:, :rkv], p["n_kv"], eps)
    k_pe = rope(dkv[:, rkv:], pos, theta)                    # [S, dr]
    kv = mm(c_kv, p["w_ukv"], mode).reshape(s, nh, dn + dv)
    mask = jnp.tril(jnp.ones((s, s), bool))
    hb = min(HEAD_BLOCK, nh)

    def heads(args):
        qb, kvb = args                                       # [S, hb, .]
        q_pe = rope(qb[..., dn:], pos, theta)
        sc = mm(qb[..., :dn], kvb[..., :dn], mode, "qhd,khd->hqk") \
            + mm(q_pe, k_pe, mode, "qhd,kd->hqk")
        sc = jnp.where(mask[None], sc / jnp.sqrt(F32(dn + dr)), -jnp.inf)
        return mm(jax.nn.softmax(sc, -1), kvb[..., dn:], mode,
                  "hqk,khd->qhd")

    split = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape(s, nh // hb, hb, a.shape[-1]), 1, 0)
    ctx = jax.lax.map(heads, (split(q), split(kv)))          # [nb, S, hb, dv]
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(s, nh * dv)
    return mm(ctx, p["w_o"], mode)


def gated_mlp(h, gate, up, down, mode="f32"):
    return mm(jax.nn.silu(mm(h, gate, mode)) * mm(h, up, mode), down, mode)


def route(p, h, config):
    """Indices [S, k] among all Ea experts and weights [S, k], float32."""
    scores = jax.nn.sigmoid(jnp.matmul(h.astype(F32), p["w_r"].astype(F32),
                                       precision=HI))
    top, idx = jax.lax.top_k(scores, config["num_experts_per_tok"])
    w = top / (jnp.sum(top, -1, keepdims=True) + 1e-20) \
        * config["routed_scaling_factor"]
    return idx, w


def expert_layer(p, h, config, mode="f32"):
    """Shared(h) + the held experts' weighted part; also the choices."""
    c = W.sizes(config)
    idx, w = route(p, h, config)
    y = gated_mlp(h, p["ws_gate"], p["ws_up"], p["ws_down"], mode)
    for j in range(c["E"]):
        w_e = jnp.sum(jnp.where(idx == c["first"] + j, w, 0.0), -1)
        y = y + w_e[:, None] * gated_mlp(
            h, p["we_gate"][j], p["we_up"][j], p["we_down"][j], mode)
    return y, idx


def block(p, x, config, dense: bool, mode="f32"):
    eps = config["rms_norm_eps"]
    a = attention(p, rms_norm(x, p["n_in"], eps), config, mode)
    x = x + rms_norm(a, p["n_post_attn"], eps)
    h = rms_norm(x, p["n_pre_mlp"], eps)
    if dense:
        f, idx = gated_mlp(h, p["w_gate"], p["w_up"], p["w_down"], mode), None
    else:
        f, idx = expert_layer(p, h, config, mode)
    return x + rms_norm(f, p["n_post_mlp"], eps), idx


def head_logits(top, x, config, mode="f32"):
    return mm(rms_norm(x, top["n_final"], config["rms_norm_eps"]),
              top["head"].T, mode)


@functools.lru_cache(maxsize=None)
def _fns(key, mode):
    config = W.config_of(key)
    blk = {d: jax.jit(lambda p, x, d=d: block(p, x, config, d, mode))
           for d in (True, False)}
    head = jax.jit(lambda top, x: head_logits(top, x, config, mode))
    return blk, head


def hidden(config, seed, ids, mode="f32"):
    """The residual stream after the last block for one sequence ids [S],
    and each expert layer's choices [S, k]."""
    blk, _ = _fns(W.config_key(config), mode)
    top = W.make_top_only(config, seed)
    x = top["emb"].astype(F32)[jnp.asarray(ids)]
    choices = []
    for i in range(config["num_hidden_layers"]):
        dense = i < config["first_k_dense_replace"]
        x, idx = blk[dense](W.make_one_layer(config, seed, i), x)
        if idx is not None:
            choices.append(idx)
    return top, x, choices


def logits(config, seed, ids, mode="f32"):
    """Logits [S, V] of one sequence: the whole forward pass."""
    top, x, _ = hidden(config, seed, ids, mode)
    _, head = _fns(W.config_key(config), mode)
    return head(top, x)


@jax.jit
def _gaps(lg, tokens, n):
    best = jnp.max(lg, -1)
    got = jnp.take_along_axis(lg, tokens[:, None], -1)[:, 0]
    return jnp.where(jnp.arange(tokens.shape[0]) < n, best - got, 0.0)


def served_gaps(config, seed, ids, first, tokens, n, mode="f32",
                rank_by=None):
    """As benchmarks.reference.served_gaps: one pass over `ids` [S] (a
    prompt, then the tokens the system served, padded), and for each of
    the `n` served tokens from position `first` on, how far its logit lies
    under the reference's best there. With `rank_by` (the float32
    reference's logits at those positions) the gaps are read there for the
    tokens THIS precision puts first: the control. Also returns the
    logits at those positions and the experts' choices."""
    top, x, choices = hidden(config, seed, ids, mode)
    _, head = _fns(W.config_key(config), mode)
    at = jnp.clip(first - 1 + jnp.arange(tokens.shape[0]), 0, x.shape[0] - 1)
    lg = head(top, x[at])
    if rank_by is None:
        return _gaps(lg, tokens, n), lg, choices
    return _gaps(rank_by, jnp.argmax(lg, -1), n), lg, choices
