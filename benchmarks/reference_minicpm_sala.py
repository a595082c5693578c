"""Plain reference of the MiniCPM-SALA block (config.json of
openbmb/MiniCPM-SALA, model_type `minicpm_sala`): a decoder whose mixers
are InfLLM-V2 block-sparse attention (`minicpm4`, the MiniCPM4 family's)
in some layers and Lightning Attention (`lightning-attn`, Qin et al.,
arXiv:2401.04658) in the others, each followed by a SiLU-gated MLP, with
the MiniCPM family's scalings of the stream and of the logits.
Straightforward `jax.numpy` in float32 at `highest` matmul precision: no
cache, no kernels, no batching, one sequence at a time. It imports nothing
of the program; weights come from benchmarks.weights_minicpm_sala and the
seed.

PUBLISHED keys (the catalog row's `config`): the mixer of every layer
(`mixer_types`), hidden 4,096, 32 query heads of 128 over 2 KV heads in a
`minicpm4` layer (`attn_use_rope` false: no positions; `attn_use_output_
gate`), 32 heads of 128 in a `lightning-attn` layer (`lightning_scale`
1/sqrt(d), `lightning_use_rope` with `rope_theta` 10,000, `qk_norm`,
`use_output_gate`, `use_output_norm`), MLP width 16,384 with silu, no
biases, `rms_norm_eps`, an untied head, `scale_emb` 12, `scale_depth` 1.4,
`dim_model_base` 256.

    x0 = scale_emb E[ids]
    x <- x + (scale_depth / sqrt(32)) Mixer(RMSNorm(x))      32: the PUBLISHED
    x <- x + (scale_depth / sqrt(32)) MLP(RMSNorm(x))        depth, whatever the cut
    logits = W_head (RMSNorm(x_L) / (hidden_size / dim_model_base))
    MLP(h) = W_down (silu(W_gate h) * W_up h)

    lightning-attn: q, k, v = W_q h, W_k h, W_v h (32 heads of 128); a
      per-head RMSNorm with a learned weight on q and on k; rotary
      positions on q and k (the whole 128, dimension i paired with i + 64);
      S_t = lambda_h S_(t-1) + k_t v_t^T,  o_t = S_t^T q_t / sqrt(128);
      out = W_o (sigmoid(W_g h) * RMSNorm(o)), the norm over all 4,096.
    minicpm4: q = W_q h (32 heads), k, v = W_k h, W_v h (2 heads); query
      heads 16 g .. 16 g + 15 belong to KV head g; for the query at
      position t with n = t + 1 visible tokens
      1. n <= dense_len: causal attention over all n tokens;
      2. else Kc_j = mean(K[stride j : stride j + kernel]) for every j with
         stride j + kernel <= n; p_(h,j) = softmax_j(q_h . Kc_j / sqrt(128));
         P_(g,j) = sum of p_(h,j) over the heads h of group g;
      3. block b = tokens [block b, block b + block): its score is the max
         of P_(g,j) over the compressed keys whose window overlaps it;
      4. the block holding t, the window / block blocks before it and the
         first init_blocks get +infinity; the topk highest of the blocks
         that start at or before t are selected (ties to the lower b);
      5. o_h = softmax over the tokens u <= t of the selected blocks of
         q_h . k_u / sqrt(128), applied to v_u;
      6. out = W_o (sigmoid(W_g h) * o).

ASSUMED (the configuration's `assumed`; the catalog row does not carry
them): the selection's sizes `kernel_size` 32, `kernel_stride` 16,
`block_size` 64, `topk` 64, `init_blocks` 1, `window_size` 2,048,
`dense_len` 8,192 (MiniCPM4's `sparse_config`); `qk_norm` in the
`minicpm4` mixer too; the decay lambda_h = exp(-2^(-8 (h + 1) / 32)), one a
head, the same in every layer; no activation on q, k, v; `mup_denominator`
unused by the forward pass.

The recurrence is written twice: `lightning_scan` is the recurrence, a
`lax.scan` over tokens; `lightning_chunked` is its exact chunked identity
(within a chunk the masked q k^T under the decay matrix, across chunks the
state), which a 34k-token sequence uses and a test holds to the scan.
Long sequences go a block of queries (attention) or of tokens (MLP) at a
time. `mode` is the precision of the matmul operands (benchmarks.reference:
"f32", "bf16", "fp8"), for the control of `correct`; norms, softmax and
the recurrent state are float32 in every mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks import weights_minicpm_sala as W
from benchmarks.reference import F32, mm

PUBLISHED_DEPTH = 32
QUERY_BLOCK = 128
TOKEN_BLOCK = 2048
CHUNK = 64


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g.astype(F32)


def rope(x, pos, theta):
    """x [S, heads, d] rotated by position; pos [S]."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def decay(n_heads: int):
    """lambda_h = exp(-s_h), s_h = 2^(-8 (h + 1) / n_heads)."""
    h = jnp.arange(1, n_heads + 1, dtype=F32)
    return jnp.exp(-(2.0 ** (-8.0 * h / n_heads)))


def _blocks(fn, xs, block):
    """fn over the leading axis of `xs` (a tuple of arrays), `block` rows
    at a time."""
    n = xs[0].shape[0]
    pad = -n % block
    xs = tuple(jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) for x in xs)
    out = jax.lax.map(lambda a: fn(*a), tuple(
        x.reshape((-1, block) + x.shape[1:]) for x in xs))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((-1,) + o.shape[2:])[:n], out)


def mlp(p, h, mode):
    def one(hb):
        return mm(jax.nn.silu(mm(hb, p["w_gate"], mode))
                  * mm(hb, p["w_up"], mode), p["w_down"], mode)
    return _blocks(one, (h,), min(TOKEN_BLOCK, h.shape[0]))


# ------------------------------------------------------ lightning-attn
def lightning_scan(q, k, v, lam, mode="f32"):
    """The recurrence itself. q, k, v [S, nh, d]; lam [nh]. -> o [S, nh, d]
    (without the 1/sqrt(d)) and the last state [nh, d, d]."""
    nh, d = q.shape[1], q.shape[2]

    def step(s, qkv):
        qt, kt, vt = qkv
        s = lam[:, None, None] * s + mm(kt[:, :, None], vt[:, None, :], mode)
        return s, mm(qt[:, None, :], s, mode)[:, 0]
    s, o = jax.lax.scan(step, jnp.zeros((nh, d, d), F32), (q, k, v))
    return o, s


def lightning_chunked(q, k, v, lam, mode="f32", chunk=CHUNK):
    """The same numbers a chunk at a time: within a chunk
    o_i = sum_(j<=i) lam^(i-j) (q_i . k_j) v_j + lam^(i+1) q_i S, and
    S' = lam^C S + sum_j lam^(C-1-j) k_j v_j^T. (The state returned is
    that after the sequence padded to whole chunks.)"""
    s_len, nh, d = q.shape
    pad = -s_len % chunk
    q, k, v = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (q, k, v))
    i = jnp.arange(chunk, dtype=F32)
    ln = jnp.log(lam)[:, None, None]
    dmat = jnp.where(i[:, None] >= i[None, :],
                     jnp.exp(ln * (i[:, None] - i[None, :])), 0.0)   # [nh,C,C]
    into = jnp.exp(ln[:, :, 0] * (i[None] + 1.0))                    # [nh,C]
    outof = jnp.exp(ln[:, :, 0] * (chunk - 1.0 - i[None]))           # [nh,C]
    whole = jnp.exp(ln * chunk)

    def step(s, qkv):
        qc, kc, vc = (jnp.moveaxis(a, 0, 1) for a in qkv)            # [nh,C,d]
        a = mm(qc, kc, mode, "hid,hjd->hij") * dmat
        o = mm(a, vc, mode, "hij,hjd->hid") \
            + mm(qc * into[..., None], s, mode, "hid,hde->hie")
        s = whole * s + mm(kc * outof[..., None], vc, mode, "hjd,hje->hde")
        return s, jnp.moveaxis(o, 0, 1)
    rs = lambda a: a.reshape(-1, chunk, nh, d)  # noqa: E731
    s, o = jax.lax.scan(step, jnp.zeros((nh, d, d), F32),
                        (rs(q), rs(k), rs(v)))
    return o.reshape(-1, nh, d)[:s_len], s


def lightning_mixer(p, h, c, config, mode, recurrence):
    s_len = h.shape[0]
    nh, d = c["lnh"], c["lhd"]
    eps = config["rms_norm_eps"]
    pos = jnp.arange(s_len)
    w_q, w_k, w_v, w_g = W.split_qkvg(p["w_qkvg"], c, W.LIGHTNING)
    q = mm(h, w_q, mode).reshape(s_len, nh, d)
    k = mm(h, w_k, mode).reshape(s_len, nh, d)
    v = mm(h, w_v, mode).reshape(s_len, nh, d)
    q = rope(rms_norm(q, p["qn"], eps), pos, float(config["rope_theta"]))
    k = rope(rms_norm(k, p["kn"], eps), pos, float(config["rope_theta"]))
    run = lightning_scan if recurrence == "scan" else lightning_chunked
    o, _ = run(q, k, v, decay(nh), mode)
    o = rms_norm((o / jnp.sqrt(F32(d))).reshape(s_len, nh * d), p["n_out"],
                 eps)
    return mm(jax.nn.sigmoid(mm(h, w_g, mode)) * o, p["w_o"], mode)


# ------------------------------------------------------------ minicpm4
def compressed_keys(k, c):
    """k [S, nkv, hd] -> Kc [J, nkv, hd], Kc_j = mean(k[stride j : stride j
    + kernel]); J counts the windows that fit into S."""
    n_j = max((k.shape[0] - c["kernel"]) // c["stride"] + 1, 0)
    idx = c["stride"] * jnp.arange(n_j)[:, None] + jnp.arange(c["kernel"])
    return jnp.mean(k[idx], axis=1)


def select_blocks(q, kc, t, c, mode="f32"):
    """Steps 2 to 4 for queries q [Q, nkv, G, hd] at positions t [Q] over
    the compressed keys kc [J, nkv, hd]. Returns (selected [Q, nkv, NB]
    bool over the NB blocks of the sequence, the chosen blocks' numbers
    [Q, nkv, topk] in the order of `top_k`)."""
    hd = q.shape[-1]
    n_j, r = kc.shape[0], c["block"] // c["stride"]
    kk = c["kernel"] // c["stride"]
    n_b = c["n_blocks"]
    n = t + 1
    if n_j:
        s = mm(q, kc, mode, "qgid,jgd->qgij") / jnp.sqrt(F32(hd))
        valid = (c["stride"] * jnp.arange(n_j)[None] + c["kernel"]
                 <= n[:, None])                                     # [Q, J]
        s = jnp.where(valid[:, None, None], s, -jnp.inf)
        m = jnp.max(s, -1, keepdims=True)
        e = jnp.where(valid[:, None, None],
                      jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
        p = e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30)
        grp = jnp.where(valid[:, None], jnp.sum(p, axis=2), -jnp.inf)
        # compressed key j overlaps block b iff r b - (kk - 1) <= j < r b + r
        j_of = (r * jnp.arange(n_b)[:, None] - (kk - 1)
                + jnp.arange(r + kk - 1)[None])                     # [NB, w]
        inside = (j_of >= 0) & (j_of < n_j)
        score = jnp.max(jnp.where(
            inside, grp[..., jnp.clip(j_of, 0, n_j - 1)], -jnp.inf), -1)
    else:
        score = jnp.full((q.shape[0], q.shape[1], n_b), -jnp.inf, F32)
    b = jnp.arange(n_b)[None]
    cur = (t // c["block"])[:, None]
    cand = b <= cur
    forced = cand & ((b == cur) | (cur - b <= c["window"] // c["block"])
                     | (b < c["init_blocks"]))
    score = jnp.where(forced[:, None], jnp.inf, score)
    score = jnp.where(cand[:, None], score, -jnp.inf)
    _, chosen = jax.lax.top_k(score, min(c["topk"], n_b))
    sel = jnp.any(chosen[..., None] == jnp.arange(n_b), -2) & cand[:, None]
    return sel, chosen


def sparse_mixer(p, h, c, config, mode):
    """-> (out [S, H], the chosen blocks [S, nkv, topk], whether each query
    selected [S])."""
    s_len = h.shape[0]
    nh, nkv, hd = c["nh"], c["nkv"], c["hd"]
    g = nh // nkv
    eps = config["rms_norm_eps"]
    w_q, w_k, w_v, w_g = W.split_qkvg(p["w_qkvg"], c, W.SPARSE)
    q = rms_norm(mm(h, w_q, mode).reshape(s_len, nkv, g, hd), p["qn"], eps)
    k = rms_norm(mm(h, w_k, mode).reshape(s_len, nkv, hd), p["kn"], eps)
    v = mm(h, w_v, mode).reshape(s_len, nkv, hd)
    kc = compressed_keys(k, c)
    c = dict(c, n_blocks=-(-s_len // c["block"]))
    u = jnp.arange(s_len)

    def one(qb, tb):
        sel, chosen = select_blocks(qb, kc, tb, c, mode)
        sparse = tb + 1 > c["dense_len"]
        seen = jnp.repeat(sel, c["block"], axis=-1)[..., :s_len]    # [Q,nkv,S]
        seen = jnp.where(sparse[:, None, None], seen, True) \
            & (u[None, None] <= tb[:, None, None])
        sc = mm(qb, k, mode, "qgid,ugd->qgiu") / jnp.sqrt(F32(hd))
        pr = jax.nn.softmax(jnp.where(seen[:, :, None], sc, -jnp.inf), -1)
        return mm(pr, v, mode, "qgiu,ugd->qgid"), chosen, sparse
    o, chosen, sparse = _blocks(one, (q, u), min(QUERY_BLOCK, s_len))
    gate = jax.nn.sigmoid(mm(h, w_g, mode))
    return (mm(gate * o.reshape(s_len, nh * hd), p["w_o"], mode), chosen,
            sparse)


# ------------------------------------------------------------ the model
def block(p, x, kind, config, mode="f32", recurrence="chunked"):
    """One block on x [S, H]; also what a `minicpm4` mixer chose."""
    c = W.sizes(config)
    eps = config["rms_norm_eps"]
    scale = config["scale_depth"] / jnp.sqrt(F32(PUBLISHED_DEPTH))
    h = rms_norm(x, p["n_in"], eps)
    if kind == W.SPARSE:
        a, chosen, sparse = sparse_mixer(p, h, c, config, mode)
    else:
        a = lightning_mixer(p, h, c, config, mode, recurrence)
        chosen = sparse = None
    x = x + scale * a
    x = x + scale * mlp(p, rms_norm(x, p["n_mlp"], eps), mode)
    return x, chosen, sparse


def embed(top, ids, config):
    return config["scale_emb"] * top["emb"][ids].astype(F32)


def logits_of(top, x, config, mode="f32"):
    x = rms_norm(x, top["n_final"], config["rms_norm_eps"]) \
        / (config["hidden_size"] / config["dim_model_base"])
    return mm(x, top["head"].T, mode)


@functools.lru_cache(maxsize=None)
def _fns(key, mode, recurrence):
    config = W.config_of(key)
    blocks = {kind: jax.jit(functools.partial(
        block, kind=kind, config=config, mode=mode, recurrence=recurrence))
        for kind in (W.SPARSE, W.LIGHTNING)}
    emb = jax.jit(lambda top, ids: embed(top, ids, config))
    head = jax.jit(lambda top, x: logits_of(top, x, config, mode))
    return blocks, emb, head


def hidden(config, seed, ids, mode="f32", recurrence="chunked"):
    """The final stream [S, H] of one sequence ids [S], the top leaves,
    and per `minicpm4` layer (chosen blocks [S, nkv, topk], selected [S])."""
    with jax.default_matmul_precision("highest"):
        blocks, emb, _ = _fns(W.config_key(config), mode, recurrence)
        top = W.make_top_only(config, seed)
        x = emb(top, ids)
        picks = []
        for i, kind in enumerate(config["mixer_types"]):
            x, chosen, sparse = blocks[kind](
                W.make_one_layer(config, seed, i), x)
            if chosen is not None:
                picks.append((chosen, sparse))
        return top, x, picks


def forward(config, seed, ids, mode="f32", recurrence="chunked"):
    """Logits [S, V] of one sequence."""
    top, x, _ = hidden(config, seed, ids, mode, recurrence)
    with jax.default_matmul_precision("highest"):
        return _fns(W.config_key(config), mode, recurrence)[2](top, x)


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
    tokens THIS precision puts first: the control. Also returns the logits
    at those positions."""
    top, x, _ = hidden(config, seed, ids, mode)
    at = jnp.clip(first - 1 + jnp.arange(tokens.shape[0]), 0, x.shape[0] - 1)
    with jax.default_matmul_precision("highest"):
        lg = _fns(W.config_key(config), mode, "chunked")[2](top, x[at])
    if rank_by is None:
        return _gaps(lg, tokens, n), lg
    return _gaps(rank_by, jnp.argmax(lg, -1), n), lg
