"""Weights of a MiniCPM-SALA configuration, made on the device from the
seed, a layer at a time (the cut is 10.1 GB in bfloat16 and is never held
twice).

Every leaf has a key of its own. Values are drawn in float32 and rounded
once to the configuration's `param_dtype`. Matrices are
`initializer_range` x n, norm gains 1 + 0.1 n, n standard normal. The gain
of the query norm of a `minicpm4` layer is `SPARSE_QUERY_GAIN` times that:
with `qk_norm` the scores do not follow the width of `w_q` at all (q and k
are unit vectors times their gains), a query against a token's key scores
N(0, 1) and against a compressed key (the mean of 32 keys) N(0, 1/32), so
at gain 1 attention is spread over a third of the visible tokens and the
group scores of the blocks lie within 4.5% of each other. Twice the gain
separates the blocks twice as far (the selection then turns on more than
the last bits of a bfloat16 product) and lets a sparse layer's output
weigh in the stream.

Shapes (H hidden, nh | nkv query and KV heads of hd in a `minicpm4` layer,
lnh heads of lhd in a `lightning-attn` layer, I the MLP's width):

    minicpm4    n_in [H]  w_qkvg [H, nh hd | nkv hd | nkv hd | nh hd]
                qn, kn [hd]  w_o [nh hd, H]
    lightning   n_in [H]  w_qkvg [H, 4 x lnh lhd]  qn, kn [lhd]
                n_out [lnh lhd]  w_o [lnh lhd, H]
    both        n_mlp [H]  w_gate, w_up [H, I]  w_down [I, H]
    top         emb [V, H]  n_final [H]  head [V, H]

`w_qkvg` is W_q, W_k, W_v and the output gate's W_g side by side, its
columns in that order (`split_qkvg`): the four projections of a mixer's
input are one product in the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import _base_key, hashable, split_seed

SPARSE = "minicpm4"
LIGHTNING = "lightning-attn"
SPARSE_QUERY_GAIN = 2.0


def sizes(config: dict) -> dict:
    c = config
    sp = c["assumed"]["sparse_config"]
    return {"H": c["hidden_size"], "nh": c["num_attention_heads"],
            "nkv": c["num_key_value_heads"], "hd": c["head_dim"],
            "lnh": c["lightning_nh"], "lhd": c["lightning_head_dim"],
            "I": c["intermediate_size"], "V": c["vocab_size"],
            "L": c["num_hidden_layers"], "mixers": tuple(c["mixer_types"]),
            "kernel": sp["kernel_size"], "stride": sp["kernel_stride"],
            "block": sp["block_size"], "topk": sp["topk"],
            "init_blocks": sp["init_blocks"], "window": sp["window_size"],
            "dense_len": sp["dense_len"]}


SPARSE_LEAVES = {
    "n_in": (lambda c: (c["H"],), "gain"),
    "w_qkvg": (lambda c: (c["H"], 2 * (c["nh"] + c["nkv"]) * c["hd"]),
               "normal"),
    "qn": (lambda c: (c["hd"],), "query_gain"),
    "kn": (lambda c: (c["hd"],), "gain"),
    "w_o": (lambda c: (c["nh"] * c["hd"], c["H"]), "normal"),
}
LIGHTNING_LEAVES = {
    "n_in": (lambda c: (c["H"],), "gain"),
    "w_qkvg": (lambda c: (c["H"], 4 * c["lnh"] * c["lhd"]), "normal"),
    "qn": (lambda c: (c["lhd"],), "gain"),
    "kn": (lambda c: (c["lhd"],), "gain"),
    "n_out": (lambda c: (c["lnh"] * c["lhd"],), "gain"),
    "w_o": (lambda c: (c["lnh"] * c["lhd"], c["H"]), "normal"),
}
MLP_LEAVES = {
    "n_mlp": (lambda c: (c["H"],), "gain"),
    "w_gate": (lambda c: (c["H"], c["I"]), "normal"),
    "w_up": (lambda c: (c["H"], c["I"]), "normal"),
    "w_down": (lambda c: (c["I"], c["H"]), "normal"),
}
TOP_LEAVES = {
    "emb": (lambda c: (c["V"], c["H"]), "normal"),
    "n_final": (lambda c: (c["H"],), "gain"),
    "head": (lambda c: (c["V"], c["H"]), "normal"),
}
_ORDER = sorted(set(TOP_LEAVES) | set(SPARSE_LEAVES) | set(LIGHTNING_LEAVES)
                | set(MLP_LEAVES))


def split_qkvg(w, c: dict, kind: str):
    """(W_q, W_k, W_v, W_g) of a layer's `w_qkvg`."""
    wide, narrow = (c["nh"] * c["hd"], c["nkv"] * c["hd"]) \
        if kind == SPARSE else (c["lnh"] * c["lhd"],) * 2
    return (w[:, :wide], w[:, wide:wide + narrow],
            w[:, wide + narrow:wide + 2 * narrow], w[:, wide + 2 * narrow:])


def _tables(kind: str):
    return [SPARSE_LEAVES if kind == SPARSE else LIGHTNING_LEAVES, MLP_LEAVES]


def layer_leaves(config: dict, layer: int) -> dict:
    """name -> shape of one block's leaves, as the program holds them."""
    c = sizes(config)
    return {n: fn(c) for t in _tables(c["mixers"][layer])
            for n, (fn, _) in t.items()}


def n_params(config: dict) -> dict:
    """Parameters held here: all, and without the embedding's rows (which
    are looked up, not multiplied)."""
    c = sizes(config)
    total = sum(math.prod(fn(c)) for fn, _ in TOP_LEAVES.values())
    for i in range(c["L"]):
        total += sum(math.prod(s) for s in layer_leaves(config, i).values())
    return {"total": total, "multiplied": total - c["V"] * c["H"]}


def _draw(key, shape, kind, std, dtype):
    n = jax.random.normal(key, shape, jnp.float32)
    if kind == "normal":
        return (std * n).astype(dtype)
    gain = SPARSE_QUERY_GAIN if kind == "query_gain" else 1.0
    return (gain * (1.0 + 0.1 * n)).astype(dtype)


def _key(lo, hi, name, layer):
    return jax.random.fold_in(
        jax.random.fold_in(_base_key(lo, hi), _ORDER.index(name)), layer)


def make_layer(config: dict, lo, hi, layer, kind: str) -> dict:
    """One block's leaves; `layer` may be traced, its kind may not."""
    c = sizes(config)
    std, dt = config["initializer_range"], jnp.dtype(config["param_dtype"])
    li = jnp.asarray(layer, jnp.uint32)
    return {n: _draw(_key(lo, hi, n, li), fn(c), k, std, dt)
            for t in _tables(kind) for n, (fn, k) in t.items()}


def make_top(config: dict, lo, hi) -> dict:
    c = sizes(config)
    std, dt = config["initializer_range"], jnp.dtype(config["param_dtype"])
    return {n: _draw(_key(lo, hi, n, jnp.uint32(0xFFFFFFFF)), fn(c), kind,
                     std, dt)
            for n, (fn, kind) in TOP_LEAVES.items()}


def config_key(config: dict):
    """The scalars of a configuration, its mixers and its assumed sparse
    sizes, as a key for the caches of jitted functions."""
    return hashable(config) + (("mixer_types", tuple(config["mixer_types"])),) \
        + tuple(("sparse." + k, v) for k, v in
                hashable(config["assumed"]["sparse_config"]))


def config_of(key) -> dict:
    config, sparse = {}, {}
    for k, v in key:
        if k.startswith("sparse."):
            sparse[k[len("sparse."):]] = v
        else:
            config[k] = list(v) if k == "mixer_types" else v
    config["assumed"] = {"sparse_config": sparse}
    return config


@functools.lru_cache(maxsize=None)
def _layer_fn(key, kind):
    config = config_of(key)
    return jax.jit(lambda lo, hi, i: make_layer(config, lo, hi, i, kind))


def make_one_layer(config: dict, seed: int, layer: int) -> dict:
    return _layer_fn(config_key(config), config["mixer_types"][layer])(
        *split_seed(seed), jnp.uint32(layer))


@functools.lru_cache(maxsize=None)
def _top_fn(key):
    config = config_of(key)
    return jax.jit(lambda lo, hi: make_top(config, lo, hi))


def make_top_only(config: dict, seed: int) -> dict:
    return _top_fn(config_key(config))(*split_seed(seed))


# names the program gives the same leaves (paddle_tpu.models.minicpm_sala)
def program_name(name: str, layer: int = -1) -> str:
    if layer < 0:
        return name
    if name in MLP_LEAVES and name != "n_mlp":
        return f"layers.{layer}.mlp.{name}"
    return f"layers.{layer}.{name}"
