"""Weights of a GPT-3 configuration, made on the device from the seed.

The benchmark makes the weights and hands them to the program; the plain
reference makes the same ones again from the same seed and takes nothing
the program has touched. Values are drawn in float32 and rounded once to
the configuration's `param_dtype`, the type they are trained and served in.
Every leaf has a key of its own, so one leaf or one layer can be made
again alone (the parameters' change is measured against a regenerated
start, which is never held twice on a full chip).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# leaf -> (shape as a function of the sizes, kind of draw). The packed qkv
# projection is [H, 3H] with the output laid out as [3, heads, head_dim].
LAYER_LEAVES = {
    "ln1_w": (lambda c: (c["H"],), "one"),
    "ln1_b": (lambda c: (c["H"],), "bias"),
    "qkv_w": (lambda c: (c["H"], 3 * c["H"]), "normal"),
    "qkv_b": (lambda c: (3 * c["H"],), "bias"),
    "out_w": (lambda c: (c["H"], c["H"]), "resid"),
    "out_b": (lambda c: (c["H"],), "bias"),
    "ln2_w": (lambda c: (c["H"],), "one"),
    "ln2_b": (lambda c: (c["H"],), "bias"),
    "up_w": (lambda c: (c["H"], c["M"]), "normal"),
    "up_b": (lambda c: (c["M"],), "bias"),
    "down_w": (lambda c: (c["M"], c["H"]), "resid"),
    "down_b": (lambda c: (c["H"],), "bias"),
}
TOP_LEAVES = {
    "wte": (lambda c: (c["V"], c["H"]), "normal"),
    "wpe": (lambda c: (c["P"], c["H"]), "normal"),
    "lnf_w": (lambda c: (c["H"],), "one"),
    "lnf_b": (lambda c: (c["H"],), "bias"),
}
_ORDER = list(TOP_LEAVES) + list(LAYER_LEAVES)


def sizes(config: dict) -> dict:
    return {"H": config["hidden_size"], "M": config["intermediate_size"],
            "V": config["vocab_size"], "P": config["max_position_embeddings"],
            "L": config["num_layers"], "nh": config["num_heads"],
            "hd": config["head_dim"]}


def n_params(config: dict) -> int:
    c = sizes(config)
    top = sum(math.prod(fn(c)) for fn, _ in TOP_LEAVES.values())
    per_layer = sum(math.prod(fn(c)) for fn, _ in LAYER_LEAVES.values())
    return top + c["L"] * per_layer


def split_seed(seed: int):
    """A whole number of up to 64 bits as two uint32 halves: the driver's
    seeds pass 2**31, and a traced uint32 pair never recompiles."""
    seed = int(seed)
    return jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32((seed >> 32) & 0xFFFFFFFF)


def _base_key(lo, hi):
    return jax.random.fold_in(jax.random.key(lo), hi)


def _draw(key, shape, kind, std, n_layers, dtype):
    if kind == "one":
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif kind == "bias":
        x = std * jax.random.normal(key, shape, jnp.float32)
    elif kind == "resid":
        x = (std / (2.0 * n_layers) ** 0.5) * jax.random.normal(
            key, shape, jnp.float32)
    else:
        x = std * jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def _leaf(config, lo, hi, name, layer, table):
    c = sizes(config)
    fn, kind = table[name]
    key = jax.random.fold_in(
        jax.random.fold_in(_base_key(lo, hi), _ORDER.index(name)), layer)
    return _draw(key, fn(c), kind, config["initializer_range"], c["L"],
                 jnp.dtype(config["param_dtype"]))


def make_layer(config: dict, lo, hi, layer) -> dict:
    """The leaves of one block; `layer` may be traced."""
    return {n: _leaf(config, lo, hi, n, layer, LAYER_LEAVES)
            for n in LAYER_LEAVES}


def make_top(config: dict, lo, hi) -> dict:
    return {n: _leaf(config, lo, hi, n, jnp.uint32(0xFFFFFFFF), TOP_LEAVES)
            for n in TOP_LEAVES}


@functools.lru_cache(maxsize=None)
def _make_all_fn(config_items):
    config = dict(config_items)

    def make(lo, hi):
        layers = jax.lax.map(
            lambda i: make_layer(config, lo, hi, i),
            jnp.arange(config["num_layers"], dtype=jnp.uint32))
        return make_top(config, lo, hi), layers
    return jax.jit(make)


def hashable(config: dict):
    """The scalars of a configuration (or of its optimizer) as a key for
    the caches of jitted functions."""
    return tuple(sorted((k, v) for k, v in config.items()
                        if isinstance(v, (int, float, str))))


def make_all(config: dict, seed: int):
    """(top leaves, layer leaves stacked [L, ...]) in one jitted call."""
    lo, hi = split_seed(seed)
    return _make_all_fn(hashable(config))(lo, hi)


@functools.lru_cache(maxsize=None)
def _make_layer_fn(config_items):
    config = dict(config_items)
    return jax.jit(lambda lo, hi, i: make_layer(config, lo, hi, i))


def make_one_layer(config: dict, seed: int, layer: int) -> dict:
    lo, hi = split_seed(seed)
    return _make_layer_fn(hashable(config))(lo, hi, jnp.uint32(layer))


@functools.lru_cache(maxsize=None)
def _make_top_fn(config_items):
    config = dict(config_items)
    return jax.jit(lambda lo, hi: make_top(config, lo, hi))


def make_top_only(config: dict, seed: int) -> dict:
    lo, hi = split_seed(seed)
    return _make_top_fn(hashable(config))(lo, hi)


# names the program gives the same leaves (paddle_tpu.models.gpt)
PROGRAM_LAYER_NAMES = {
    "ln1_w": "ln_1.weight", "ln1_b": "ln_1.bias",
    "qkv_w": "attn.qkv.weight", "qkv_b": "attn.qkv.bias",
    "out_w": "attn.out.weight", "out_b": "attn.out.bias",
    "ln2_w": "ln_2.weight", "ln2_b": "ln_2.bias",
    "up_w": "mlp.up.weight", "up_b": "mlp.up.bias",
    "down_w": "mlp.down.weight", "down_b": "mlp.down.bias",
}
PROGRAM_TOP_NAMES = {"wte": "gpt.wte.weight", "wpe": "gpt.wpe.weight",
                     "lnf_w": "gpt.ln_f.weight", "lnf_b": "gpt.ln_f.bias"}


def program_name(name: str, layer: int = -1) -> str:
    if layer < 0:
        return PROGRAM_TOP_NAMES[name]
    return f"gpt.h.{layer}.{PROGRAM_LAYER_NAMES[name]}"
