"""Plain reference of the GPT-3 block (Brown et al. 2020, after Radford et
al. 2019): learned positions, pre-LayerNorm blocks, causal softmax
attention, a 4x tanh-GELU MLP, tied output head. Straightforward
`jax.numpy` in float32 with `highest` matmul precision: no kernels, no
cache, no batching tricks. It imports nothing of the program and is given
nothing the program made: weights come from benchmarks.weights and the
seed.

Storage follows what the configuration states (`param_dtype` and the
optimizer's `moment_dtype`): a parameter is rounded to its stored type once
per update, as the configuration says it is; every operation between two
such roundings is float32.

`mode` is the precision of the matmul operands and exists for the control
of `correct`: "f32" is the reference; "bf16" rounds operands to bfloat16;
"fp8" rounds them to float8_e4m3 under a per-tensor scale, the step below
bfloat16 that a later PR could be tempted by.

Training goes layer by layer so that it fits beside nothing else on one
chip: a forward pass that keeps each block's input, the head's loss and
gradient, then the blocks in reverse, each updated by AdamW as soon as its
gradient exists. The full gradient is never held.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks import weights as W

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _q(x, mode):
    if mode == "f32":
        return x.astype(F32)
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(F32)
    if mode == "fp8":
        x = x.astype(F32)
        s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        return (x * s).astype(jnp.float8_e4m3fn).astype(F32) / s
    raise ValueError(f"unknown precision mode {mode!r}")


def mm(a, b, mode, spec=None):
    a, b = _q(a, mode), _q(b, mode)
    if spec is None:
        return jnp.matmul(a, b, precision=HI)
    return jnp.einsum(spec, a, b, precision=HI)


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w.astype(F32) + b.astype(F32)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)))


def _attend_row(q, k, v, mode):
    """One sequence: q, k, v [S, nh, hd] -> [S, nh, hd], causal."""
    s, _, hd = q.shape
    scores = mm(q, k, mode, "qhd,khd->hqk") / jnp.sqrt(F32(hd))
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return mm(p, v, mode, "hqk,khd->qhd")


def block(p, x, config, mode="f32"):
    """One pre-LN block on x [B, S, H] (float32)."""
    nh, hd = config["num_heads"], config["head_dim"]
    eps = config["layer_norm_epsilon"]
    b, s, h = x.shape
    y = layer_norm(x, p["ln1_w"], p["ln1_b"], eps)
    qkv = mm(y, p["qkv_w"], mode) + p["qkv_b"].astype(F32)
    qkv = qkv.reshape(b, s, 3, nh, hd)
    row = jax.checkpoint(functools.partial(_attend_row, mode=mode))
    ctx = jax.lax.map(lambda t: row(t[0], t[1], t[2]),
                      (qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]))
    x = x + mm(ctx.reshape(b, s, h), p["out_w"], mode) + p["out_b"].astype(F32)
    y = layer_norm(x, p["ln2_w"], p["ln2_b"], eps)
    u = gelu_tanh(mm(y, p["up_w"], mode) + p["up_b"].astype(F32))
    return x + mm(u, p["down_w"], mode) + p["down_b"].astype(F32)


def embed(top, ids):
    pos = jnp.arange(ids.shape[1])
    return top["wte"].astype(F32)[ids] + top["wpe"].astype(F32)[pos][None]


def head_logits(top, x, config, mode="f32"):
    y = layer_norm(x, top["lnf_w"], top["lnf_b"], config["layer_norm_epsilon"])
    return mm(y, top["wte"].T, mode)


# ------------------------------------------------------------- inference
@functools.lru_cache(maxsize=None)
def _fwd_fns(config_items, mode):
    config = dict(config_items)
    emb = jax.jit(embed)
    blk = jax.jit(lambda p, x: block(p, x, config, mode))

    def gaps(top, x, first, tokens, n):
        """Logits of positions first-1 .. first-1+len(tokens)-1 of one
        sequence, each predicting the served token after it: how far the
        served token's logit lies under the best, and the best's index."""
        at = jnp.clip(first - 1 + jnp.arange(tokens.shape[0]), 0,
                      x.shape[1] - 1)
        rows = x[0][at]
        logits = head_logits(top, rows[None], config, mode)[0]
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
        live = jnp.arange(tokens.shape[0]) < n
        return (jnp.where(live, best - got, 0.0), jnp.argmax(logits, -1),
                logits)
    return emb, blk, jax.jit(gaps)


def served_gaps(config, seed, ids, first, tokens, n, mode="f32",
                rank_by=None):
    """Runs the reference once over `ids` [1, S] (a prompt followed by the
    tokens the system served, padded) and returns, for each of the `n`
    served tokens starting at position `first`, the gap between the
    reference's best logit and the served token's. With `rank_by` given
    (float32 logits of the true reference at the same positions), the gaps
    are read there for the tokens THIS precision puts first: the control."""
    emb, blk, gaps = _fwd_fns(W.hashable(config), mode)
    top = W.make_top_only(config, seed)
    x = emb(top, ids)
    for i in range(config["num_layers"]):
        x = blk(W.make_one_layer(config, seed, i), x)
    g, arg, logits = gaps(top, x, first, tokens, n)
    if rank_by is not None:
        best = jnp.max(rank_by, axis=-1)
        got = jnp.take_along_axis(rank_by, arg[:, None], axis=-1)[:, 0]
        g = jnp.where(jnp.arange(tokens.shape[0]) < n, best - got, 0.0)
    return g, logits


# --------------------------------------------------------------- training
Q_BLOCK = 2048


def _blocks(x):
    n = x.size
    nb = -(-n // Q_BLOCK)
    return jnp.pad(x.reshape(-1), (0, nb * Q_BLOCK - n)).reshape(nb, Q_BLOCK)


def _unblock(b, shape):
    n = 1
    for s in shape:
        n *= s
    return b.reshape(-1)[:n].reshape(shape)


class Q8:
    """The stored type "int8" of a moment, as the configuration states it:
    8-bit codes in blocks of 2,048 with one float32 scale a block (Dettmers
    et al. 2022, 8-bit optimizers). The first moment codes its signed
    square root linearly in int8; the second codes its square root in
    uint8 and decodes at the middle of the code's step."""

    @staticmethod
    def encode_m(x):
        b = _blocks(jnp.sign(x) * jnp.sqrt(jnp.abs(x)))
        scale = jnp.max(jnp.abs(b), axis=1) / 127.0
        q = jnp.round(b / jnp.maximum(scale, 1e-30)[:, None])
        return q.astype(jnp.int8), scale.astype(F32)

    @staticmethod
    def decode_m(qs, shape):
        r = _unblock(qs[0].astype(F32) * qs[1][:, None], shape)
        return jnp.sign(r) * jnp.square(r)

    @staticmethod
    def encode_v(x):
        b = _blocks(jnp.sqrt(jnp.maximum(x, 0.0)))
        scale = jnp.max(b, axis=1) / 255.0
        q = jnp.round(b / jnp.maximum(scale, 1e-30)[:, None])
        return q.astype(jnp.uint8), scale.astype(F32)

    @staticmethod
    def decode_v(qs, shape):
        r = _unblock((qs[0].astype(F32) + 0.5) * qs[1][:, None], shape)
        return jnp.square(r)


def zero_moments(tree: dict, moment_dtype: str) -> dict:
    if moment_dtype == "int8":
        z = lambda a: jnp.zeros(a.shape, F32)  # noqa: E731
        return {"m": {k: Q8.encode_m(z(a)) for k, a in tree.items()},
                "v": {k: Q8.encode_v(z(a)) for k, a in tree.items()}}
    mdt = jnp.dtype(moment_dtype)
    return {"m": {k: jnp.zeros(a.shape, mdt) for k, a in tree.items()},
            "v": {k: jnp.zeros(a.shape, mdt) for k, a in tree.items()}}


def _adamw(p, g, m, v, t, hp, pdt):
    """Decoupled weight decay (Loshchilov & Hutter 2019), bias-corrected."""
    b1, b2 = hp["beta1"], hp["beta2"]
    q8 = hp["moment_dtype"] == "int8"
    p32, g = p.astype(F32), g.astype(F32)
    m0 = Q8.decode_m(m, p.shape) if q8 else m.astype(F32)
    v0 = Q8.decode_v(v, p.shape) if q8 else v.astype(F32)
    m = b1 * m0 + (1 - b1) * g
    v = b2 * v0 + (1 - b2) * g * g
    m_hat = m / (1 - b1 ** t)
    v_hat = v / (1 - b2 ** t)
    p32 = p32 * (1 - hp["learning_rate"] * hp["weight_decay"])
    p32 = p32 - hp["learning_rate"] * m_hat / (jnp.sqrt(v_hat) + hp["epsilon"])
    if q8:
        return p32.astype(pdt), Q8.encode_m(m), Q8.encode_v(v)
    mdt = jnp.dtype(hp["moment_dtype"])
    return p32.astype(pdt), m.astype(mdt), v.astype(mdt)


def compare_leaves(tree: dict, config: dict) -> dict:
    """The leaves as the published architecture has them: the packed qkv
    projection and bias are three tensors each (the key bias has no
    gradient under softmax, and a rule on its gradient, not its name,
    leaves it out of the change)."""
    h = config["hidden_size"]
    out = {}
    for name, a in tree.items():
        if name in ("qkv_w", "qkv_b"):
            for j, part in enumerate("qkv"):
                out[f"{part}{name[3:]}"] = a[..., j * h:(j + 1) * h]
        else:
            out[name] = a
    return out


def norms(tree: dict, config: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32))))
            for k, a in compare_leaves(tree, config).items()}


@functools.lru_cache(maxsize=None)
def _train_fns(config_items, hp_items, mode):
    config, hp = dict(config_items), dict(hp_items)
    pdt = jnp.dtype(config["param_dtype"])
    emb = jax.jit(embed)
    fwd = jax.jit(lambda p, x: block(p, x, config, mode))

    def update(p, g, opt, t):
        new_p, new_m, new_v = {}, {}, {}
        for k in p:
            new_p[k], new_m[k], new_v[k] = _adamw(
                p[k], g[k], opt["m"][k], opt["v"][k], t, hp, pdt)
        return new_p, {"m": new_m, "v": new_v}

    def head(top, x, labels, weight):
        """Loss (mean over the rows given, times `weight`), its gradient to
        x and to the head's leaves; one row at a time so the [S, V] logits
        of one sequence are all that exists."""
        def loss_of(top_h, x):
            def row(args):
                xr, lr = args
                logits = head_logits(top_h, xr[None], config, mode)[0]
                lse = jax.nn.logsumexp(logits, axis=-1)
                gold = jnp.take_along_axis(logits, lr[:, None], -1)[:, 0]
                return jnp.sum(lse - gold)
            tot = jnp.sum(jax.lax.map(jax.checkpoint(row), (x, labels)))
            return weight * tot / (x.shape[0] * x.shape[1])
        head_leaves = {k: top[k] for k in ("wte", "lnf_w", "lnf_b")}
        loss, (g_top, dx) = jax.value_and_grad(loss_of, argnums=(0, 1))(
            head_leaves, x)
        return loss, g_top, dx

    def bwd(p, opt, x_in, dy, t):
        _, vjp = jax.vjp(lambda p_, x_: block(p_, x_, config, mode), p, x_in)
        g, dx = vjp(dy)
        gn = norms(g, config)
        new_p, new_opt = update(p, g, opt, t)
        return dx, new_p, new_opt, gn

    def finish(top, opt, g_head, dx0, ids, t):
        """The embeddings' gradient joins the tied head's, then AdamW."""
        pos = jnp.arange(ids.shape[1])
        g = {"wte": g_head["wte"].astype(F32).at[ids].add(dx0),
             "wpe": jnp.zeros(top["wpe"].shape, F32).at[pos].add(
                 jnp.sum(dx0, axis=0)),
             "lnf_w": g_head["lnf_w"], "lnf_b": g_head["lnf_b"]}
        gn = norms(g, config)
        new_top, new_opt = update(top, g, opt, t)
        return new_top, new_opt, gn

    return (emb, fwd, jax.jit(head), jax.jit(bwd, donate_argnums=(0, 1)),
            jax.jit(finish, donate_argnums=(0, 1)))


class TrainReference:
    """The configuration's training step on the seed's weights, in float32
    between the stored types. `step(ids, labels)` returns the loss and the
    norm of every leaf's gradient as the optimizer got it."""

    def __init__(self, config: dict, seed: int, mode: str = "f32",
                 rows: slice | None = None):
        self.config, self.seed, self.mode = config, seed, mode
        self.hp = dict(config["optimizer"])
        self.fns = _train_fns(W.hashable(config), W.hashable(self.hp), mode)
        self.top = W.make_top_only(config, seed)
        self.layers = [W.make_one_layer(config, seed, i)
                       for i in range(config["num_layers"])]
        md = self.hp["moment_dtype"]
        self.opt_top = zero_moments(self.top, md)
        self.opt_layers = [zero_moments(p, md) for p in self.layers]
        self.t = 0
        # the fault "half of the batch left out": only these rows are seen
        self.rows = rows

    def step(self, ids, labels):
        emb, fwd, head, bwd, finish = self.fns
        if self.rows is not None:
            ids, labels = ids[self.rows], labels[self.rows]
        ids, labels = jnp.asarray(ids), jnp.asarray(labels)
        self.t += 1
        t = jnp.float32(self.t)
        x = emb(self.top, ids)
        inputs = []
        for p in self.layers:
            inputs.append(x)
            x = fwd(p, x)
        loss, g_head, dy = head(self.top, x, labels, jnp.float32(1.0))
        gnorm = {}
        for i in reversed(range(len(self.layers))):
            dy, self.layers[i], self.opt_layers[i], gn = bwd(
                self.layers[i], self.opt_layers[i], inputs.pop(), dy, t)
            gnorm[i] = gn
        self.top, self.opt_top, gn_top = finish(
            self.top, self.opt_top, g_head, dy, ids, t)
        gnorm[-1] = gn_top
        return float(loss), flatten_norms(gnorm)

    def change_norms(self) -> dict:
        """Norm of every leaf's change since the seed's start."""
        out = {-1: delta_norms(self.config, self.seed, -1, self.top)}
        for i, p in enumerate(self.layers):
            out[i] = delta_norms(self.config, self.seed, i, p)
        return flatten_norms(out)


def flatten_norms(by_layer: dict) -> dict:
    flat = {}
    for i, d in by_layer.items():
        for k, val in d.items():
            flat[k if i < 0 else f"h{i}.{k}"] = float(val)
    return flat


@functools.lru_cache(maxsize=None)
def _delta_fn(config_items, top: bool):
    config = dict(config_items)

    def delta(lo, hi, layer, tree):
        start = (W.make_top(config, lo, hi) if top
                 else W.make_layer(config, lo, hi, layer))
        return norms({k: tree[k].astype(F32) - start[k].astype(F32)
                      for k in tree}, config)
    return jax.jit(delta)


def delta_norms(config, seed, layer, tree) -> dict:
    """|leaf - its start|, the start made again from the seed inside the
    same program, so it is never held beside the state on a full chip."""
    lo, hi = W.split_seed(seed)
    fn = _delta_fn(W.hashable(config), layer < 0)
    return fn(lo, hi, jnp.uint32(max(layer, 0)), tree)


@functools.lru_cache(maxsize=None)
def _grad_from_moment_fn(config_items, beta1, q8):
    config = dict(config_items)

    def fn(m, like):
        plain = {k: (Q8.decode_m(a, like[k].shape) if q8 else a.astype(F32))
                 / (1.0 - beta1) for k, a in m.items()}
        return norms(plain, config)
    return jax.jit(fn)


def grad_norms_from_moment(config, first_moment: dict, like: dict) -> dict:
    """After one AdamW step from zero moments, m = (1 - beta1) g: the norm
    of each leaf's gradient as the optimizer got it. `like` gives the
    leaves' shapes (8-bit moments are stored in blocks)."""
    hp = config["optimizer"]
    fn = _grad_from_moment_fn(W.hashable(config), hp["beta1"],
                              hp["moment_dtype"] == "int8")
    return fn(first_moment, like)


# ----------------------------------------------------- what is compared
def leaf_gap(got: dict, want: dict, skip=()) -> tuple[float, str]:
    """Worst leaf by |got - want| over max(want of that leaf, want of the
    median leaf): the gap between two norms, not the norm of a difference."""
    keys = [k for k in want if k not in skip]
    vals = sorted(want[k] for k in keys)
    med = vals[len(vals) // 2]
    worst, where = 0.0, ""
    for k in keys:
        gap = abs(got[k] - want[k]) / max(want[k], med, 1e-30)
        if not gap <= worst:          # a NaN is the worst
            worst, where = (gap if gap == gap else float("inf")), k
    return worst, where


def still_leaves(ref_grad_norms: dict) -> set:
    """Leaves whose gradient is nought to rounding in the reference (under
    a thousandth of the median leaf's): Adam moves them by round-off alone,
    so they are left out of the parameters' change."""
    vals = sorted(ref_grad_norms.values())
    med = vals[len(vals) // 2]
    return {k for k, g in ref_grad_norms.items() if g < 1e-3 * med}
