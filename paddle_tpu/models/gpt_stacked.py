"""Stacked-stage GPT — the pipeline-parallel flagship path.

Reference capability: PipelineLayer + 1F1B/interleave scheduling
(fleet/meta_parallel/pp_layers.py:209, pipeline_parallel.py:117-761) makes
pp a first-class hybrid axis next to dp/mp. The TPU-native equivalent is NOT
a per-microbatch p2p driver: block parameters are STACKED on a leading
layer dim (`qkv_w: [L, H, 3H]` etc.), sharded `P("pp", ...)` so each pp
group owns L/pp contiguous layers, and

  * on meshes without pp: one `lax.scan` over the layer dim runs the whole
    depth ("scan-over-layers" — O(1) compile cost in depth);
  * with pp > 1: `distributed.pipeline.pipeline_spmd` rotates microbatch
    activations through the stage shards with a collective-permute each
    tick — steady-state-1F1B utilization, compiled as ONE XLA program that
    composes with dp/mp/sp sharding constraints.

Weight layout/init matches models/gpt.py (same sharding map in the module
docstring there); `from_layered` converts a `GPTForCausalLM` so the two
paths can be checked for loss parity.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor, apply_op
from ..core import ops
from ..nn.layer import Layer
from ..nn import initializer as I
from ..distributed import mesh as _mesh
from ..incubate.nn.functional import fused_linear_cross_entropy_array
from ..ops.attention import functional_qkv_attention
from .gpt import GPTConfig

# (param name, per-layer shape fn, pspec over the stacked [L, ...] tensor,
#  depth-scaled init?) — sharding map mirrors models/gpt.py
_BLOCK_PARAMS = [
    ("ln1_w", lambda c: [c.hidden_size], P("pp", None), "ones"),
    ("ln1_b", lambda c: [c.hidden_size], P("pp", None), "zeros"),
    ("qkv_w", lambda c: [c.hidden_size, 3 * c.hidden_size],
     P("pp", None, "mp"), "normal"),
    ("qkv_b", lambda c: [3 * c.hidden_size], P("pp", "mp"), "zeros"),
    ("out_w", lambda c: [c.hidden_size, c.hidden_size],
     P("pp", "mp", None), "scaled"),
    ("out_b", lambda c: [c.hidden_size], P("pp", None), "zeros"),
    ("ln2_w", lambda c: [c.hidden_size], P("pp", None), "ones"),
    ("ln2_b", lambda c: [c.hidden_size], P("pp", None), "zeros"),
    ("up_w", lambda c: [c.hidden_size, c.intermediate_size],
     P("pp", None, "mp"), "normal"),
    ("up_b", lambda c: [c.intermediate_size], P("pp", "mp"), "zeros"),
    ("down_w", lambda c: [c.intermediate_size, c.hidden_size],
     P("pp", "mp", None), "scaled"),
    ("down_b", lambda c: [c.hidden_size], P("pp", None), "zeros"),
]


def _ln(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _block_batch(p, x, cfg: GPTConfig):
    """One transformer block applied to a stage-batched activation
    [S, mb, s, H] with per-stage params (leaves [S, ...])."""
    nh, hd = cfg.num_heads, cfg.head_dim
    eps = cfg.layer_norm_epsilon
    Sdim, mb, s, H = x.shape

    h = _ln(x, p["ln1_w"][:, None, None], p["ln1_b"][:, None, None], eps)
    qkv = jnp.einsum("smth,shk->smtk", h, p["qkv_w"]) \
        + p["qkv_b"][:, None, None]
    qkv = _mesh.shard_constraint(qkv, "pp", "dp", None, "mp")
    ctx = functional_qkv_attention(
        qkv.reshape(Sdim * mb, s, 3 * nh * hd), nh, hd, is_causal=True,
        constrain=lambda a: _mesh.shard_constraint(
            a, ("pp", "dp"), None, "mp", None))
    ctx = ctx.reshape(Sdim, mb, s, nh * hd)
    a = jnp.einsum("smtk,skh->smth", ctx, p["out_w"]) \
        + p["out_b"][:, None, None]
    a = _mesh.shard_constraint(a, "pp", "dp", None, None)
    x = x + a

    h2 = _ln(x, p["ln2_w"][:, None, None], p["ln2_b"][:, None, None], eps)
    u = jnp.einsum("smth,shk->smtk", h2, p["up_w"]) + p["up_b"][:, None, None]
    u = _mesh.shard_constraint(u, "pp", "dp", None, "mp")
    g = jax.nn.gelu(u, approximate=True)
    d = jnp.einsum("smtk,skh->smth", g, p["down_w"]) \
        + p["down_b"][:, None, None]
    d = _mesh.shard_constraint(d, "pp", "dp", None, None)
    return x + d


def _block_single(p, x, cfg: GPTConfig):
    """One transformer block on a single activation [mb, s, H] with
    per-layer params (no stage dim) — the interleaved-pipeline chunk body.
    Constraints name only auto axes (dp/mp): inside
    `pipeline_scan_interleaved` the pp axis is manual (shard_map
    axis_names={'pp'}) and must not appear in sharding constraints."""
    nh, hd = cfg.num_heads, cfg.head_dim
    eps = cfg.layer_norm_epsilon
    mb, s, H = x.shape

    h = _ln(x, p["ln1_w"], p["ln1_b"], eps)
    qkv = jnp.einsum("mth,hk->mtk", h, p["qkv_w"]) + p["qkv_b"]
    qkv = _mesh.shard_constraint(qkv, "dp", None, "mp")
    ctx = functional_qkv_attention(
        qkv, nh, hd, is_causal=True,
        constrain=lambda a: _mesh.shard_constraint(a, "dp", None, "mp", None))
    a = jnp.einsum("mtk,kh->mth", ctx.reshape(mb, s, nh * hd), p["out_w"]) \
        + p["out_b"]
    a = _mesh.shard_constraint(a, "dp", None, None)
    x = x + a

    h2 = _ln(x, p["ln2_w"], p["ln2_b"], eps)
    u = jnp.einsum("mth,hk->mtk", h2, p["up_w"]) + p["up_b"]
    u = _mesh.shard_constraint(u, "dp", None, "mp")
    g = jax.nn.gelu(u, approximate=True)
    d = jnp.einsum("mtk,kh->mth", g, p["down_w"]) + p["down_b"]
    d = _mesh.shard_constraint(d, "dp", None, None)
    return x + d


def _embed(ids, wte, wpe, cfg):
    x = jnp.take(wte, ids, axis=0) + wpe[None, :ids.shape[1]]
    return _mesh.shard_constraint(x, "dp", None, None)


def _stacked_forward_scan(block_tree, x, cfg):
    """Depth via lax.scan over stacked [L, ...] params (no pp)."""
    def body(a, pl):
        pl1 = jax.tree.map(lambda t: t[None], pl)
        return _block_batch(pl1, a[None], cfg)[0], None

    out, _ = jax.lax.scan(body, x, block_tree)
    return out


def _stacked_loss_array(ids, labels, loss_mask, wte, wpe, lnf_w, lnf_b,
                        *block_leaves, cfg: GPTConfig, num_microbatches=None,
                        chunk_size=128, num_virtual=1):
    """Pure-array stacked-GPT loss; pipelines over pp when the mesh has it.
    num_virtual > 1 routes through the interleaved virtual-stage schedule
    (reference PipelineParallelWithInterleave, pipeline_parallel.py:461)."""
    block_tree = dict(zip([n for n, *_ in _BLOCK_PARAMS], block_leaves))
    x = _embed(ids, wte, wpe, cfg)
    pp = _mesh.mesh_axis_size("pp")
    if pp > 1 and num_virtual > 1:
        from ..distributed.pipeline import pipeline_scan_interleaved
        B, s, H = x.shape
        M = num_microbatches or pp
        V = num_virtual
        Lp = pp * V                       # logical pipeline stages
        L = cfg.num_layers
        assert B % M == 0, f"batch {B} not divisible by microbatches {M}"
        assert L % Lp == 0, \
            f"layers {L} not divisible by pp*num_virtual {Lp}"
        xs = x.reshape(M, B // M, s, H)

        def chunk_fn(ptree, act):
            # ptree leaves [depth_per_chunk, ...] -> scan this chunk's depth
            def body(a, pslice):
                return _block_single(pslice, a, cfg), None

            act, _ = jax.lax.scan(body, act, ptree)
            return act

        # deal logical stages round-robin: sharded row d*V+v must hold
        # logical stage v*pp+d (see pipeline_scan_interleaved contract)
        order = jnp.asarray([v * pp + d for d in range(pp)
                             for v in range(V)], jnp.int32)
        staged = jax.tree.map(
            lambda t: t.reshape((Lp, L // Lp) + t.shape[1:])[order],
            block_tree)
        out = pipeline_scan_interleaved(chunk_fn, staged, xs, axis="pp",
                                        num_virtual=V)
        x = out.reshape(B, s, H)
    elif pp > 1:
        from ..distributed.pipeline import pipeline_spmd
        B, s, H = x.shape
        M = num_microbatches or pp
        assert B % M == 0, f"batch {B} not divisible by microbatches {M}"
        L = cfg.num_layers
        assert L % pp == 0, f"layers {L} not divisible by pp {pp}"
        xs = x.reshape(M, B // M, s, H)

        def stage_fn(ptree, acts):
            # ptree leaves [S, depth, ...] -> scan the local depth
            depth_first = jax.tree.map(lambda t: jnp.moveaxis(t, 1, 0), ptree)

            def body(a, pslice):
                return _block_batch(pslice, a, cfg), None

            acts, _ = jax.lax.scan(body, acts, depth_first)
            return acts

        staged = jax.tree.map(
            lambda t: t.reshape((pp, L // pp) + t.shape[1:]), block_tree)
        out = pipeline_spmd(stage_fn, staged, xs, axis="pp")
        x = out.reshape(B, s, H)
    else:
        x = _stacked_forward_scan(block_tree, x, cfg)
    x = _ln(x, lnf_w, lnf_b, cfg.layer_norm_epsilon)
    per_tok = fused_linear_cross_entropy_array(
        x, wte, labels, chunk_size=chunk_size)
    if loss_mask is not None:
        per_tok = per_tok * loss_mask
        return per_tok.sum() / jnp.maximum(loss_mask.sum(), 1e-8)
    return per_tok.mean()


class GPTStackedForCausalLM(Layer):
    """Scan-over-layers GPT with pp-shardable stacked block params.

    Same math as `GPTForCausalLM` for dense configs (loss parity asserted in
    tests/test_distributed.py); the pp path additionally needs
    `num_layers % pp == 0` and `batch % num_microbatches == 0`.
    MoE/recompute/sequence-parallel configs use the layered model.
    """

    supports_compiled_pp = True  # fleet.distributed_model dispatch marker

    def __init__(self, config: GPTConfig):
        super().__init__()
        assert config.moe_num_experts == 0, \
            "stacked pipeline path is dense-only; use GPTForCausalLM for MoE"
        self.config = config
        c = config
        L = c.num_layers
        self.wte = self.create_parameter(
            [c.vocab_size, c.hidden_size],
            default_initializer=I.Normal(std=c.initializer_range))
        self.wte.pspec = P("mp", None)
        self.wpe = self.create_parameter(
            [c.max_position_embeddings, c.hidden_size],
            default_initializer=I.Normal(std=c.initializer_range))
        self.wpe.pspec = P()
        self.ln_f_w = self.create_parameter(
            [c.hidden_size], default_initializer=I.Constant(1.0))
        self.ln_f_b = self.create_parameter(
            [c.hidden_size], default_initializer=I.Constant(0.0), is_bias=True)
        self.ln_f_w.pspec = P()
        self.ln_f_b.pspec = P()

        scale = 1.0 / math.sqrt(2 * L)
        for name, shape_fn, pspec, kind in _BLOCK_PARAMS:
            shape = [L] + shape_fn(c)
            if kind == "ones":
                init = I.Constant(1.0)
            elif kind == "zeros":
                init = I.Constant(0.0)
            else:
                init = I.Normal(std=c.initializer_range)
            p = self.create_parameter(shape, default_initializer=init)
            if kind == "scaled":
                p.set_value(p._data * scale)
            p.pspec = pspec
            setattr(self, name, p)

    # -- helpers ---------------------------------------------------------
    def _block_tensors(self):
        return [getattr(self, n) for n, *_ in _BLOCK_PARAMS]

    @classmethod
    def from_layered(cls, model) -> "GPTStackedForCausalLM":
        """Stack a GPTForCausalLM's per-block weights (for parity tests and
        for migrating checkpoints into the pipeline layout)."""
        cfg = model.config
        assert cfg.tie_word_embeddings, "stacked path ties embeddings"
        self = cls(cfg)
        gpt = model.gpt
        self.wte.set_value(gpt.wte.weight._data)
        self.wpe.set_value(gpt.wpe.weight._data)
        self.ln_f_w.set_value(gpt.ln_f.weight._data)
        self.ln_f_b.set_value(gpt.ln_f.bias._data)
        pick = {
            "ln1_w": lambda b: b.ln_1.weight, "ln1_b": lambda b: b.ln_1.bias,
            "qkv_w": lambda b: b.attn.qkv.weight,
            "qkv_b": lambda b: b.attn.qkv.bias,
            "out_w": lambda b: b.attn.out.weight,
            "out_b": lambda b: b.attn.out.bias,
            "ln2_w": lambda b: b.ln_2.weight, "ln2_b": lambda b: b.ln_2.bias,
            "up_w": lambda b: b.mlp.up.weight, "up_b": lambda b: b.mlp.up.bias,
            "down_w": lambda b: b.mlp.down.weight,
            "down_b": lambda b: b.mlp.down.bias,
        }
        for name, *_ in _BLOCK_PARAMS:
            stacked = jnp.stack([pick[name](b)._data for b in gpt.h])
            getattr(self, name).set_value(stacked)
        return self

    # -- API -------------------------------------------------------------
    def forward(self, input_ids):
        cfg = self.config

        def fn(ids, wte, wpe, lnf_w, lnf_b, *leaves):
            tree = dict(zip([n for n, *_ in _BLOCK_PARAMS], leaves))
            x = _embed(ids, wte, wpe, cfg)
            x = _stacked_forward_scan(tree, x, cfg)
            x = _ln(x, lnf_w, lnf_b, cfg.layer_norm_epsilon)
            logits = jnp.einsum("bsh,vh->bsv", x, wte)
            return _mesh.shard_constraint(logits, "dp", None, "mp")

        return apply_op("gpt_stacked_forward", fn,
                        [input_ids, self.wte, self.wpe, self.ln_f_w,
                         self.ln_f_b] + self._block_tensors())

    def loss(self, input_ids, labels, loss_mask=None,
             num_microbatches: Optional[int] = None, chunk_size: int = 128,
             num_virtual: int = 1):
        cfg = self.config
        fn = partial(_stacked_loss_array, cfg=cfg,
                     num_microbatches=num_microbatches, chunk_size=chunk_size,
                     num_virtual=num_virtual)
        if loss_mask is None:
            def fn2(ids, labels_, *rest):
                return fn(ids, labels_, None, *rest)
            args = [input_ids, labels]
        else:
            fn2 = fn
            args = [input_ids, labels, loss_mask]
        return apply_op("gpt_stacked_loss", fn2,
                        args + [self.wte, self.wpe, self.ln_f_w, self.ln_f_b]
                        + self._block_tensors())
