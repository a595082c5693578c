"""Weights of an openPangu-Ultra-MoE configuration, made on the device from
the seed, a layer at a time (the whole cut is 9.8 GB in bfloat16 and is
never held twice).

Every leaf has a key of its own, and every routed expert one more from its
number among ALL the experts of its layer: the chip that holds experts
80..95 and the uncut layer draw the same expert 83. Values are drawn in
float32 and rounded once to the configuration's `param_dtype`. Norm gains
are 1 + 0.1 n, matrices `initializer_range` x n, n standard normal; the
query up-projection `w_uq` is `QUERY_SPREAD` times as wide, so that routing
is near-uniform, as a router trained with a balance loss gives it. At
`initializer_range` attention over a 2,048-token prefix is near-uniform,
every token attends to the mean of its prefix, the post-attention norm
scales that common vector up to the stream's size (the embedding is a
fiftieth of it), and all tokens behind one system prompt choose the same
experts: 32 tokens hit 78-100 of the 256 experts of a layer where uniform
routing hits 162, and half of a chip's 16 got nothing in a decode call
(`expert_load_max_over_mean.moe` 5.5). Four times as wide a token attends
to a few tokens of its own, the stream is the token's, and 158-170 are
hit (144-162 at three times, 163-172 at twice; the reference on the CPU at
the published widths, 32 tokens at the end of two sequences of 1,088, PR
28). The price: such a network amplifies rounding, so the program in
bfloat16 lies further from the float32 reference (the cell's
`limits_note`).

Shapes (H hidden, nh heads, dn | dr | dv the no-position, rotary and value
head sizes, Rq | Rkv the query and latent ranks, E the experts held here
of Ea in the layer, M an expert's width, I the dense MLP's):

    attention   n_in [H]  w_dq [H, Rq]  n_q [Rq]  w_uq [Rq, nh (dn+dr)]
                w_dkv [H, Rkv+dr]  n_kv [Rkv]  w_ukv [Rkv, nh (dn+dv)]
                w_o [nh dv, H]  n_post_attn [H]  n_pre_mlp [H]
                n_post_mlp [H]
    dense MLP   w_gate, w_up [H, I]  w_down [I, H]
    experts     w_r [H, Ea]  ws_gate, ws_up [H, Ms]  ws_down [Ms, H]
                we_gate, we_up [E, H, M]  we_down [E, M, H]
    top         emb [V, H]  n_final [H]  head [V, H]

The per-head outputs of w_uq are laid out [nh, dn | dr], those of w_ukv
[nh, dn | dv].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.weights import hashable, split_seed, _base_key


def sizes(config: dict) -> dict:
    c = config
    ep = int(c["deployment"]["expert_parallel"])
    held = int(c["n_routed_experts"])
    return {"H": c["hidden_size"], "nh": c["num_attention_heads"],
            "dn": c["qk_nope_head_dim"], "dr": c["qk_rope_head_dim"],
            "dv": c["v_head_dim"], "Rq": c["q_lora_rank"],
            "Rkv": c["kv_lora_rank"], "I": c["intermediate_size"],
            "M": c["moe_intermediate_size"],
            "Ms": c["moe_intermediate_size"] * c["n_shared_experts"],
            "E": held, "Ea": held * ep,
            "first": held * int(c["deployment"]["expert_rank"]),
            "k": c["num_experts_per_tok"], "V": c["vocab_size"],
            "L": c["num_hidden_layers"], "dense": c["first_k_dense_replace"]}


ATTN_LEAVES = {
    "n_in": (lambda c: (c["H"],), "gain"),
    "w_dq": (lambda c: (c["H"], c["Rq"]), "normal"),
    "n_q": (lambda c: (c["Rq"],), "gain"),
    "w_uq": (lambda c: (c["Rq"], c["nh"] * (c["dn"] + c["dr"])), "query"),
    "w_dkv": (lambda c: (c["H"], c["Rkv"] + c["dr"]), "normal"),
    "n_kv": (lambda c: (c["Rkv"],), "gain"),
    "w_ukv": (lambda c: (c["Rkv"], c["nh"] * (c["dn"] + c["dv"])), "normal"),
    "w_o": (lambda c: (c["nh"] * c["dv"], c["H"]), "normal"),
    "n_post_attn": (lambda c: (c["H"],), "gain"),
    "n_pre_mlp": (lambda c: (c["H"],), "gain"),
    "n_post_mlp": (lambda c: (c["H"],), "gain"),
}
DENSE_LEAVES = {
    "w_gate": (lambda c: (c["H"], c["I"]), "normal"),
    "w_up": (lambda c: (c["H"], c["I"]), "normal"),
    "w_down": (lambda c: (c["I"], c["H"]), "normal"),
}
MOE_LEAVES = {
    "w_r": (lambda c: (c["H"], c["Ea"]), "normal"),
    "ws_gate": (lambda c: (c["H"], c["Ms"]), "normal"),
    "ws_up": (lambda c: (c["H"], c["Ms"]), "normal"),
    "ws_down": (lambda c: (c["Ms"], c["H"]), "normal"),
}
EXPERT_LEAVES = {          # one expert's; stacked [E, ...] over those held
    "we_gate": (lambda c: (c["H"], c["M"]), "normal"),
    "we_up": (lambda c: (c["H"], c["M"]), "normal"),
    "we_down": (lambda c: (c["M"], c["H"]), "normal"),
}
TOP_LEAVES = {
    "emb": (lambda c: (c["V"], c["H"]), "normal"),
    "n_final": (lambda c: (c["H"],), "gain"),
    "head": (lambda c: (c["V"], c["H"]), "normal"),
}
_ORDER = (list(TOP_LEAVES) + list(ATTN_LEAVES) + list(DENSE_LEAVES)
          + list(MOE_LEAVES) + list(EXPERT_LEAVES))


def layer_leaves(config: dict, layer: int) -> dict:
    """name -> shape of one block's leaves, as the program holds them."""
    c = sizes(config)
    tables = [ATTN_LEAVES, DENSE_LEAVES if layer < c["dense"] else MOE_LEAVES]
    out = {n: fn(c) for t in tables for n, (fn, _) in t.items()}
    if layer >= c["dense"]:
        out.update({n: (c["E"],) + fn(c)
                    for n, (fn, _) in EXPERT_LEAVES.items()})
    return out


def n_params(config: dict) -> dict:
    """Parameters held here: all, and those outside the routed experts."""
    import math
    c = sizes(config)
    top = sum(math.prod(fn(c)) for fn, _ in TOP_LEAVES.values())
    total = outside = top
    for i in range(c["L"]):
        for n, shape in layer_leaves(config, i).items():
            total += math.prod(shape)
            if n not in EXPERT_LEAVES:
                outside += math.prod(shape)
    return {"total": total, "outside_experts": outside,
            "one_expert": sum(math.prod(fn(c))
                              for fn, _ in EXPERT_LEAVES.values())}


QUERY_SPREAD = 4.0


def _draw(key, shape, kind, std, dtype):
    n = jax.random.normal(key, shape, jnp.float32)
    if kind == "gain":
        return (1.0 + 0.1 * n).astype(dtype)
    return ((QUERY_SPREAD if kind == "query" else 1.0) * std * n).astype(dtype)


def _key(lo, hi, name, layer):
    return jax.random.fold_in(
        jax.random.fold_in(_base_key(lo, hi), _ORDER.index(name)), layer)


def make_layer(config: dict, lo, hi, layer, dense: bool) -> dict:
    """One block's leaves; `layer` may be traced, its kind may not."""
    c = sizes(config)
    std, dt = config["initializer_range"], jnp.dtype(config["param_dtype"])
    li = jnp.asarray(layer, jnp.uint32)
    tables = [ATTN_LEAVES, DENSE_LEAVES if dense else MOE_LEAVES]
    out = {n: _draw(_key(lo, hi, n, li), fn(c), kind, std, dt)
           for t in tables for n, (fn, kind) in t.items()}
    if not dense:
        ids = jnp.uint32(c["first"]) + jnp.arange(c["E"], dtype=jnp.uint32)
        for n, (fn, kind) in EXPERT_LEAVES.items():
            base = _key(lo, hi, n, li)
            out[n] = jax.vmap(lambda e, b=base, s=fn(c), k=kind: _draw(
                jax.random.fold_in(b, e), s, k, std, dt))(ids)
    return out


def make_top(config: dict, lo, hi) -> dict:
    c = sizes(config)
    std, dt = config["initializer_range"], jnp.dtype(config["param_dtype"])
    return {n: _draw(_key(lo, hi, n, jnp.uint32(0xFFFFFFFF)), fn(c), kind,
                     std, dt)
            for n, (fn, kind) in TOP_LEAVES.items()}


def config_key(config: dict):
    """The scalars of a configuration and of its deployment, as a key for
    the caches of jitted functions."""
    return hashable(config) + tuple(
        ("deployment." + k, v) for k, v in hashable(config["deployment"]))


def config_of(key) -> dict:
    config, dep = {}, {}
    for k, v in key:
        if k.startswith("deployment."):
            dep[k[len("deployment."):]] = v
        else:
            config[k] = v
    config["deployment"] = dep
    return config


@functools.lru_cache(maxsize=None)
def _layer_fn(key, dense):
    config = config_of(key)
    return jax.jit(lambda lo, hi, i: make_layer(config, lo, hi, i, dense))


def make_one_layer(config: dict, seed: int, layer: int) -> dict:
    dense = layer < config["first_k_dense_replace"]
    return _layer_fn(config_key(config), dense)(
        *split_seed(seed), jnp.uint32(layer))


@functools.lru_cache(maxsize=None)
def _top_fn(key):
    config = config_of(key)
    return jax.jit(lambda lo, hi: make_top(config, lo, hi))


def make_top_only(config: dict, seed: int) -> dict:
    return _top_fn(config_key(config))(*split_seed(seed))


# names the program gives the same leaves (paddle_tpu.models.pangu_moe)
def program_name(name: str, layer: int = -1) -> str:
    if layer < 0:
        return name
    if name in ATTN_LEAVES:
        return f"layers.{layer}.{name}"
    return f"layers.{layer}.mlp.{name}"
