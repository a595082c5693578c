"""Weights of a Jamba configuration, made on the device from the seed, a
layer at a time (the whole model is 6.06 GB in bfloat16 and is never held
twice).

Every leaf has a key of its own. Values are drawn in float32 and rounded
once to the configuration's `param_dtype`. Matrices are
`initializer_range` x n, norm gains and the skip D 1 + 0.1 n, the
convolution's bias `initializer_range` x n, n standard normal. What sets
the Mamba layers' time scales is Mamba-1's own initialisation (Gu and
Dao, arXiv:2312.00752, section 3.6 and the reference code's defaults):

    A_log[n, d] = log(n + 1)        A = -(n + 1): 16 decays a channel
    b_dt = softplus^-1(dt0)         dt0 log-uniform in [0.001, 0.1]: with
                                    b_dt = 0 every channel has dt 0.69, a
                                    state with A = -16 forgets within one
                                    token and a stale or lost state would
                                    not show in the logits
    conv_w uniform(+-1/sqrt(d_conv))  a depthwise filter of fan-in 4. At
                                    `initializer_range` the convolution's
                                    output, and with it everything the scan
                                    sees and gives, is a fiftieth of the
                                    MLP's contribution to the stream, and
                                    the state does not reach the logits
                                    either (the configuration's
                                    `assumed.weights` has the readings)

Shapes (H hidden, Din = expand H, N states, R dt_rank, K d_conv, nh | nkv
query and KV heads of hd, I the MLP's width):

    mamba       n_in [H]  w_in [H, 2 Din] = [u | z]  conv_w [K, Din] (tap j
                multiplies u_(t-K+1+j))  conv_b [Din]  w_x [Din, R + 2 N] =
                [r | B | C]  n_dt [R]  n_b, n_c [N]  w_dt [R, Din]
                b_dt [Din]  a_log [N, Din]  d_skip [Din]  w_out [Din, H]
    attention   n_in [H]  w_qkv [H, (nh + 2 nkv) hd] = [q | k | v]
                w_o [nh hd, H]
    both        n_mlp [H]  w_gate, w_up [H, I]  w_down [I, H]
    top         emb [V, H] (the head too: tied)  n_final [H]

`a_log`, the scan's state and the conv state hold d_inner LAST, along the
lanes (the published layout is [Din, N] and [Din, 1, K]: the same numbers
transposed).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import _base_key, hashable, split_seed

ATTENTION = "attention"
MAMBA = "mamba"
DT_MIN, DT_MAX = 0.001, 0.1


def mixers(config: dict) -> tuple:
    """Layer i is attention where i % attn_layer_period == attn_layer_offset
    (the `jamba` model type's rule)."""
    return tuple(ATTENTION if i % config["attn_layer_period"]
                 == config["attn_layer_offset"] else MAMBA
                 for i in range(config["num_hidden_layers"]))


def sizes(config: dict) -> dict:
    c = config
    return {"H": c["hidden_size"], "nh": c["num_attention_heads"],
            "nkv": c["num_key_value_heads"], "hd": c["assumed"]["head_dim"],
            "Din": c["mamba_expand"] * c["hidden_size"],
            "N": c["mamba_d_state"], "K": c["mamba_d_conv"],
            "R": c["mamba_dt_rank"], "I": c["intermediate_size"],
            "V": c["vocab_size"], "L": c["num_hidden_layers"],
            "mixers": mixers(c)}


MAMBA_LEAVES = {
    "n_in": (lambda c: (c["H"],), "gain"),
    "w_in": (lambda c: (c["H"], 2 * c["Din"]), "normal"),
    "conv_w": (lambda c: (c["K"], c["Din"]), "conv"),
    "conv_b": (lambda c: (c["Din"],), "bias"),
    "w_x": (lambda c: (c["Din"], c["R"] + 2 * c["N"]), "normal"),
    "n_dt": (lambda c: (c["R"],), "gain"),
    "n_b": (lambda c: (c["N"],), "gain"),
    "n_c": (lambda c: (c["N"],), "gain"),
    "w_dt": (lambda c: (c["R"], c["Din"]), "normal"),
    "b_dt": (lambda c: (c["Din"],), "dt_bias"),
    "a_log": (lambda c: (c["N"], c["Din"]), "a_log"),
    "d_skip": (lambda c: (c["Din"],), "gain"),
    "w_out": (lambda c: (c["Din"], c["H"]), "normal"),
}
ATTENTION_LEAVES = {
    "n_in": (lambda c: (c["H"],), "gain"),
    "w_qkv": (lambda c: (c["H"], (c["nh"] + 2 * c["nkv"]) * c["hd"]),
              "normal"),
    "w_o": (lambda c: (c["nh"] * c["hd"], c["H"]), "normal"),
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
}
_ORDER = sorted(set(TOP_LEAVES) | set(MAMBA_LEAVES) | set(ATTENTION_LEAVES)
                | set(MLP_LEAVES))


def _tables(kind: str):
    return [MAMBA_LEAVES if kind == MAMBA else ATTENTION_LEAVES, MLP_LEAVES]


def layer_leaves(config: dict, layer: int) -> dict:
    """name -> shape of one block's leaves, as the program holds them."""
    c = sizes(config)
    return {n: fn(c) for t in _tables(c["mixers"][layer])
            for n, (fn, _) in t.items()}


def n_params(config: dict) -> dict:
    """Parameters held: all; those a token is multiplied with (all but the
    vectors and the filter, which work elementwise, the embedding counted
    once, as the head); and a Mamba layer's mixer alone."""
    c = sizes(config)
    total = sum(math.prod(fn(c)) for fn, _ in TOP_LEAVES.values())
    multiplied = c["V"] * c["H"]
    for i in range(c["L"]):
        for name, shape in layer_leaves(config, i).items():
            total += math.prod(shape)
            if name.startswith("w_"):
                multiplied += math.prod(shape)
    return {"total": total, "multiplied": multiplied,
            "mamba_mixer": sum(math.prod(fn(c)) for n, (fn, _)
                               in MAMBA_LEAVES.items() if n != "n_in")}


def _draw(key, shape, kind, std, dtype):
    f32 = jnp.float32
    if kind == "a_log":
        x = jnp.broadcast_to(jnp.log(jnp.arange(1, shape[0] + 1, dtype=f32))
                             [:, None], shape)
    elif kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(DT_MIN),
                                        math.log(DT_MAX)))
        x = dt + jnp.log(-jnp.expm1(-dt))           # softplus^-1(dt)
    elif kind == "conv":
        k = shape[0] ** -0.5
        x = jax.random.uniform(key, shape, f32, -k, k)
    else:
        n = jax.random.normal(key, shape, f32)
        x = 1.0 + 0.1 * n if kind == "gain" else std * n
    return x.astype(dtype)


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
    """The scalars of a configuration and its assumed head size, as a key
    for the caches of jitted functions."""
    return hashable(config) + (("assumed.head_dim",
                                config["assumed"]["head_dim"]),)


def config_of(key) -> dict:
    config = {k: v for k, v in key if k != "assumed.head_dim"}
    config["assumed"] = {"head_dim": dict(key)["assumed.head_dim"]}
    return config


@functools.lru_cache(maxsize=None)
def _layer_fn(key, kind):
    config = config_of(key)
    return jax.jit(lambda lo, hi, i: make_layer(config, lo, hi, i, kind))


def make_one_layer(config: dict, seed: int, layer: int) -> dict:
    return _layer_fn(config_key(config), mixers(config)[layer])(
        *split_seed(seed), jnp.uint32(layer))


@functools.lru_cache(maxsize=None)
def _top_fn(key):
    config = config_of(key)
    return jax.jit(lambda lo, hi: make_top(config, lo, hi))


def make_top_only(config: dict, seed: int) -> dict:
    return _top_fn(config_key(config))(*split_seed(seed))


# names the program gives the same leaves (paddle_tpu.models.jamba)
def program_name(name: str, layer: int = -1) -> str:
    if layer < 0:
        return name
    if name in MLP_LEAVES and name != "n_mlp":
        return f"layers.{layer}.mlp.{name}"
    return f"layers.{layer}.{name}"
