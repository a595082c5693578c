"""Plain reference of the Jamba decoder (config.json of
ai21labs/AI21-Jamba2-3B, model_type `jamba`; Lieber et al.,
arXiv:2403.19887): Mamba-1 state-space mixers (Gu and Dao,
arXiv:2312.00752) with Jamba's three inner RMSNorms, one attention mixer a
period, a SiLU-gated MLP after every mixer, a tied head. Straightforward
`jax.numpy` in float32 at `highest` matmul precision: no cache, no
kernels, no batching, one sequence at a time, the recurrence token by token
(`lax.scan`). It imports nothing of the program; weights come from
benchmarks.weights_jamba and the seed.

PUBLISHED keys (the catalog row's `config`): 28 layers, hidden 2,560,
vocabulary 65,536, `tie_word_embeddings`, `rms_norm_eps` 1e-6, `hidden_act`
silu, `intermediate_size` 8,192, 20 query heads over 1 KV head,
`attn_layer_period` 14 and `attn_layer_offset` 7, `num_experts` 1 (no
router), `mamba_d_state` 16, `mamba_d_conv` 4, `mamba_expand` 2,
`mamba_dt_rank` 160, `mamba_conv_bias` true, `mamba_proj_bias` false, no
rotary key of any kind.

    x0 = E[ids]
    x <- x + Mixer_i(RMSNorm(x));  x <- x + MLP(RMSNorm(x))
    logits = E^T RMSNorm(x_L)
    MLP(h) = W_down (silu(W_gate h) * W_up h)

    attention (i % 14 == 7): q = W_q h (20 heads of 128), k, v = W_k h,
      W_v h (ONE head of 128 that all query heads share); causal softmax of
      q . k / sqrt(128); W_o of the concatenated heads. No bias, no
      positions.
    mamba (every other layer), token t:
      1. [u_t, z_t] = W_in h_t
      2. c_t = silu(b_conv + sum_(j<4) w_conv[j] * u_(t-3+j)), u before the
         sequence zero
      3. [r_t, B_t, C_t] = W_x c_t (160, 16, 16), each through an RMSNorm
         with a learned weight
      4. dt_t = softplus(W_dt r_t + b_dt)
      5. S_t[d, n] = exp(dt_t[d] A[d, n]) S_(t-1)[d, n]
                     + dt_t[d] B_t[n] c_t[d],   A = -exp(A_log)
      6. y_t[d] = sum_n S_t[d, n] C_t[n] + D[d] c_t[d]
      7. out = W_out (y_t * silu(z_t))

ASSUMED (the configuration's `assumed`; the catalog row does not carry
them): the layer order from period and offset (the `jamba` model type's
rule; the row's `not_given`), `head_dim` 128 = 2,560 / 20, the recurrent
state and steps 4 to 6 in float32 whatever the parameters' dtype, the
weights' distributions (benchmarks/weights_jamba.py).

`mode` is the precision of the matmul operands (benchmarks.reference:
"f32", "bf16", "fp8"), for the control of `correct`; norms, softmax, the
convolution and steps 4 to 6 are float32 in every mode. `forget` = (which,
t) drops a state where a prefix of t tokens ends, as a cache that lost it
would: "scan" starts token t from S = 0, "conv" from a convolution that
saw no input before t. It exists to show that the state reaches the logits
under the seed's weights (`assumed.weights`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks import weights_jamba as W
from benchmarks.reference import F32, HI, mm
# the float32 RMSNorm, the SiLU-gated MLP a block of tokens at a time and
# the gap of served tokens are the other plain reference's, letter for letter
from benchmarks.reference_minicpm_sala import _blocks, _gaps, mlp, rms_norm

QUERY_BLOCK = 512


def attention_mixer(p, h, c, mode):
    s_len = h.shape[0]
    nh, nkv, hd = c["nh"], c["nkv"], c["hd"]
    q, k, v = jnp.split(mm(h, p["w_qkv"], mode),
                        [nh * hd, (nh + nkv) * hd], axis=-1)
    q = q.reshape(s_len, nkv, nh // nkv, hd)
    k, v = k.reshape(s_len, nkv, hd), v.reshape(s_len, nkv, hd)
    u = jnp.arange(s_len)

    def one(qb, tb):
        sc = mm(qb, k, mode, "qgid,ugd->qgiu") / jnp.sqrt(F32(hd))
        seen = u[None, None, None] <= tb[:, None, None, None]
        pr = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
        return mm(pr, v, mode, "qgiu,ugd->qgid")
    o = _blocks(one, (q, u), min(QUERY_BLOCK, s_len))
    return mm(o.reshape(s_len, nh * hd), p["w_o"], mode)


def mamba_mixer(p, h, c, config, mode, forget=None):
    s_len = h.shape[0]
    din, n, r, taps = c["Din"], c["N"], c["R"], c["K"]
    eps = config["rms_norm_eps"]
    u, z = jnp.split(mm(h, p["w_in"], mode), 2, axis=-1)
    w = p["conv_w"].astype(F32)

    def conv(u):
        ext = jnp.concatenate([jnp.zeros((taps - 1, din), F32), u], 0)
        return jax.nn.silu(p["conv_b"].astype(F32) + sum(
            w[j] * ext[j:j + s_len] for j in range(taps)))
    t = jnp.arange(s_len)
    x = conv(u)
    if forget is not None and forget[0] == "conv":
        x = jnp.where((t >= forget[1])[:, None],
                      conv(jnp.where((t < forget[1])[:, None], 0.0, u)), x)
    dt_in, bm, cm = jnp.split(mm(x, p["w_x"], mode), [r, r + n], axis=-1)
    dt_in = rms_norm(dt_in, p["n_dt"], eps)
    bm, cm = rms_norm(bm, p["n_b"], eps), rms_norm(cm, p["n_c"], eps)
    dt = jax.nn.softplus(
        jnp.matmul(dt_in, p["w_dt"].astype(F32), precision=HI)
        + p["b_dt"].astype(F32))
    a = -jnp.exp(p["a_log"].astype(F32)).T                      # [Din, N]
    d_skip = p["d_skip"].astype(F32)
    lost = forget[1] if forget is not None and forget[0] == "scan" else -1

    def step(s, tok):
        x_t, dt_t, b_t, c_t, i = tok
        s = jnp.where(i == lost, 0.0, s)
        s = jnp.exp(dt_t[:, None] * a) * s \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return s, jnp.sum(s * c_t[None, :], -1) + d_skip * x_t
    _, y = jax.lax.scan(step, jnp.zeros((din, n), F32), (x, dt, bm, cm, t))
    return mm(y * jax.nn.silu(z), p["w_out"], mode)


# ------------------------------------------------------------ the model
def block(p, x, kind, config, mode="f32", forget=None):
    """One block on x [S, H]."""
    c = W.sizes(config)
    eps = config["rms_norm_eps"]
    h = rms_norm(x, p["n_in"], eps)
    if kind == W.ATTENTION:
        x = x + attention_mixer(p, h, c, mode)
    else:
        x = x + mamba_mixer(p, h, c, config, mode, forget)
    return x + mlp(p, rms_norm(x, p["n_mlp"], eps), mode)


def logits_of(top, x, config, mode="f32"):
    return mm(rms_norm(x, top["n_final"], config["rms_norm_eps"]),
              top["emb"].T, mode)


@functools.lru_cache(maxsize=None)
def _fns(key, mode, forget):
    config = W.config_of(key)
    blocks = {kind: jax.jit(functools.partial(
        block, kind=kind, config=config, mode=mode, forget=forget))
        for kind in (W.ATTENTION, W.MAMBA)}
    emb = jax.jit(lambda top, ids: top["emb"][ids].astype(F32))
    head = jax.jit(lambda top, x: logits_of(top, x, config, mode))
    return blocks, emb, head


def hidden(config, seed, ids, mode="f32", forget=None):
    """The final stream [S, H] of one sequence ids [S], and the top
    leaves."""
    with jax.default_matmul_precision("highest"):
        blocks, emb, _ = _fns(W.config_key(config), mode, forget)
        top = W.make_top_only(config, seed)
        x = emb(top, ids)
        for i, kind in enumerate(W.mixers(config)):
            x = blocks[kind](W.make_one_layer(config, seed, i), x)
        return top, x


def forward(config, seed, ids, mode="f32", forget=None):
    """Logits [S, V] of one sequence."""
    top, x = hidden(config, seed, ids, mode, forget)
    with jax.default_matmul_precision("highest"):
        return _fns(W.config_key(config), mode, forget)[2](top, x)


def served_gaps(config, seed, ids, first, tokens, n, mode="f32",
                rank_by=None):
    """As benchmarks.reference.served_gaps: one pass over `ids` [S] (a
    prompt, then the tokens the system served, padded), and for each of
    the `n` served tokens from position `first` on, how far its logit lies
    under the reference's best there. With `rank_by` (the float32
    reference's logits at those positions) the gaps are read there for the
    tokens THIS precision puts first: the control. Also returns the logits
    at those positions."""
    top, x = hidden(config, seed, ids, mode)
    at = jnp.clip(first - 1 + jnp.arange(tokens.shape[0]), 0, x.shape[0] - 1)
    with jax.default_matmul_precision("highest"):
        lg = _fns(W.config_key(config), mode, None)[2](top, x[at])
    if rank_by is None:
        return _gaps(lg, tokens, n), lg
    return _gaps(rank_by, jnp.argmax(lg, -1), n), lg
