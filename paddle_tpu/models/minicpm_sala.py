"""MiniCPM-SALA decoder (model_type `minicpm_sala`) for the paged serving
engine: InfLLM-V2 block-sparse attention with grouped KV heads in the
`minicpm4` layers, Lightning linear attention in the `lightning-attn`
layers, a SiLU-gated MLP after each, the MiniCPM family's scalings.

    x0 = scale_emb E[ids]
    x <- x + (scale_depth / sqrt(published_layers)) f(RMSNorm(x))
    logits = W_head (RMSNorm(x_L) / (hidden_size / dim_model_base))

A `minicpm4` layer pools pages of keys, values and compressed keys
(ops/sparse_attention.py) and selects, per query and KV head, the pages it
attends once the query sees more than `dense_len` tokens. A
`lightning-attn` layer holds no pages: its cached state is one
[heads, d, d] float32 matrix a ROW (ops/lightning_attention.py), kept by
the cache manager in a plane of its own beside the pages
(`kv_pool_geometry`: `layer_block_shapes` and `state_shapes`;
inference/kv_cache.py), updated in place by decode, advanced by the
chunked form in a prefill window, and snapshot by the engine where a
prefix ends so that the prefix trie can restore it.

One code path: the plain `forward` (whole sequences, differentiable) runs
the prefill window's functions over a private pool laid out in order, so
what the tests hold against the reference is what the engine runs. The
model implements what `inference.ServingEngine` calls: `config`,
`prefill_paged` (with `state_slots`), `decode_paged`, `_gen_cache_get`,
`kv_pool_geometry`, the per-call counters (`detach_step_counters`).
Speculative decoding, shards, an int8 cache or weights and the host spill
tier are refused by `check_serving_config`.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import apply_op
from ..jit.api import SELECTED_BLOCKS_PROGRAM, named_program
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.layers.experts import gated_mlp
from ..ops.lightning_attention import lightning_decode, lightning_window
from ..ops.sparse_attention import (SparseSizes, compressed_write,
                                    decode_lists, grouped_paged_decode,
                                    kv_cache_write, select_blocks,
                                    sparse_window_attention)
from .decoder_parts import (GatedMLP, PagedStateDecoder, StepCall, _arr, _mm,
                            _rms, _rope)

SPARSE = "minicpm4"
LIGHTNING = "lightning-attn"

# per-call device counters: decode row-steps a `minicpm4` layer that
# selected / attended densely, the pages each walked (all KV heads), the
# compressed keys selecting queries scored (prefill too), decode row-steps
# a `lightning-attn` layer updated, the (query, token) pairs attended a
# `minicpm4` layer (prefill too)
STATS = ("sparse_rows", "dense_rows", "sparse_blocks_attended",
         "dense_blocks_attended", "sparse_keys_scored", "state_rows_updated",
         "attn_pairs")


@dataclass
class MiniCPMSALAConfig:
    vocab_size: int = 73448
    hidden_size: int = 4096
    mixer_types: tuple = (SPARSE,) + (LIGHTNING,) * 3
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    lightning_heads: int = 32
    lightning_head_dim: int = 128
    intermediate_size: int = 16384
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    published_layers: int = 32          # the depth under scale_depth's root
    kernel_size: int = 32               # the selection (assumed sizes)
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192
    initializer_range: float = 0.02
    dtype: str = "float32"

    @property
    def num_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def sparse(self) -> SparseSizes:
        return SparseSizes(self.kernel_size, self.kernel_stride,
                           self.block_size, self.topk, self.init_blocks,
                           self.window_size, self.dense_len)


class MiniCPMSALABlock(Layer):
    def __init__(self, c: MiniCPMSALAConfig, kind: str):
        super().__init__()
        if kind not in (SPARSE, LIGHTNING):
            raise ValueError(f"unknown mixer {kind!r}")
        init = I.Normal(0.0, c.initializer_range)
        one = I.Constant(1.0)
        mk = lambda shape, i=init: self.create_parameter(  # noqa: E731
            list(shape), dtype=c.dtype, default_initializer=i)
        h = c.hidden_size
        self.kind = kind
        self.n_in = mk((h,), one)
        if kind == SPARSE:
            wide, hd = c.num_heads * c.head_dim, c.head_dim
            narrow = c.num_kv_heads * hd
        else:
            wide, hd = c.lightning_heads * c.lightning_head_dim, \
                c.lightning_head_dim
            narrow = wide
        # the four projections of the mixer's input as one matrix, its
        # columns [q | k | v | gate]: one product a token
        self.w_qkvg = mk((h, 2 * wide + 2 * narrow))
        self.qn, self.kn = mk((hd,), one), mk((hd,), one)
        if kind == LIGHTNING:
            self.n_out = mk((wide,), one)
        self.w_o = mk((wide, h))
        self.n_mlp = mk((h,), one)
        self.mlp = GatedMLP(h, c.intermediate_size, init, c.dtype)


class MiniCPMSALAForCausalLM(PagedStateDecoder):
    def __init__(self, config: MiniCPMSALAConfig):
        super().__init__()
        c = self.config = config
        init = I.Normal(0.0, c.initializer_range)
        mk = lambda shape, i=init: self.create_parameter(  # noqa: E731
            list(shape), dtype=c.dtype, default_initializer=i)
        self.emb = mk((c.vocab_size, c.hidden_size))
        self.layers = [MiniCPMSALABlock(c, kind) for kind in c.mixer_types]
        for i, blk in enumerate(self.layers):
            self.add_sublayer(f"layers.{i}", blk)
        self.n_final = mk((c.hidden_size,), I.Constant(1.0))
        self.head = mk((c.vocab_size, c.hidden_size))
        self._init_serving()

    STATS = STATS

    # ------------------------------------------------ the pure functions
    def _sparse_mixer(self, p, pre, h, call: StepCall, i: int):
        """h [B, S, H] (normed) -> the mixer's output [B, S, H]; writes the
        window's (or the step's) keys, values and compressed keys into the
        layer's pages first. S = 1 with no `lens` is a decode step."""
        c, sz = self.config, self.config.sparse
        b, s, _ = h.shape
        hkv, hd = c.num_kv_heads, c.head_dim
        g = c.num_heads // hkv
        eps, scale = c.rms_norm_eps, hd ** -0.5
        q, k, v, gate = jnp.split(
            _mm(h, p[pre + "w_qkvg"]),
            np.cumsum([hkv * g * hd, hkv * hd, hkv * hd]), axis=-1)
        q = _rms(q.reshape(b, s, hkv, g, hd), p[pre + "qn"], eps)
        k = _rms(k.reshape(b, s, hkv, hd), p[pre + "kn"], eps)
        v = v.reshape(b, s, hkv, hd)
        k_pool, v_pool, kc_pool = call.pools[i]
        start = call.pos[:, 0]
        k_pool, v_pool = kv_cache_write(k_pool, v_pool, k, v, call.tables,
                                        start, call.lens)
        n_new = jnp.ones_like(start) if call.lens is None else call.lens
        kc_pool = compressed_write(kc_pool, k_pool, call.tables, start,
                                   n_new, sz, call.width)
        call.pools[i] = (k_pool, v_pool, kc_pool)
        chosen, sparse, keys = select_blocks(q, kc_pool, call.tables,
                                             call.pos, sz)
        call.chosen.append((chosen, sparse))
        live = call.live
        call.count("sparse_keys_scored", jnp.where(live & sparse, keys, 0))
        if call.lens is None:                       # a decode step
            seen = call.pos[:, 0] + 1
            ids, toks = decode_lists(call.tables, seen, chosen[:, 0],
                                     sparse[:, 0], sz)
            # a row that is not live attends nothing: nobody reads it
            toks = jnp.where(live, toks, 0)
            o = grouped_paged_decode(q[:, 0], k_pool, v_pool, ids, toks,
                                     scale)[:, None]
            sp, lv = sparse[:, 0], live[:, 0]
            call.count("sparse_rows", lv & sp)
            call.count("dense_rows", lv & ~sp)
            call.count("sparse_blocks_attended",
                       jnp.where(lv & sp, hkv * chosen.shape[-1], 0))
            call.count("dense_blocks_attended", jnp.where(
                lv & ~sp, hkv * ((seen + sz.block - 1) // sz.block), 0))
            call.count("attn_pairs", jnp.where(lv, toks[:, 0], 0))
        else:
            o, pairs = sparse_window_attention(
                q, k_pool, v_pool, call.tables, call.pos, chosen, sparse,
                scale)
            call.count("attn_pairs", jnp.where(live, pairs, 0))
        return _mm(jax.nn.sigmoid(gate) * o.reshape(b, s, -1),
                   p[pre + "w_o"])

    def _lightning_mixer(self, p, pre, h, call: StepCall, i: int):
        c = self.config
        b, s, _ = h.shape
        nh, d = c.lightning_heads, c.lightning_head_dim
        eps = c.rms_norm_eps
        q, k, v, gate = jnp.split(_mm(h, p[pre + "w_qkvg"]), 4, axis=-1)
        q, k, v = (a.reshape(b, s, nh, d) for a in (q, k, v))
        q = _rope(_rms(q, p[pre + "qn"], eps), call.pos, c.rope_theta)
        k = _rope(_rms(k, p[pre + "kn"], eps), call.pos, c.rope_theta)
        state, snaps = call.pools[i]
        if call.lens is None:                       # a decode step
            o, state = lightning_decode(q[:, 0], k[:, 0], v[:, 0], state,
                                        call.live[:, 0])
            o = o[:, None]
            call.count("state_rows_updated", call.live)
        else:
            o, rows = lightning_window(q, k, v, state[call.slots], call.lens)
            state = state.at[call.slots].set(rows)
        call.pools[i] = (state, snaps)
        o = _rms((o * d ** -0.5).reshape(b, s, nh * d), p[pre + "n_out"], eps)
        return _mm(jax.nn.sigmoid(gate) * o, p[pre + "w_o"])

    def _stream(self, p, ids, call: StepCall):
        """The final residual stream [B, S, H] of the call's tokens."""
        c = self.config
        eps = c.rms_norm_eps
        depth = c.scale_depth / np.sqrt(c.published_layers)
        x = c.scale_emb * p["emb"][ids].astype(jnp.float32)
        for i, blk in enumerate(self.layers):
            pre = f"layers.{i}."
            mixer = self._sparse_mixer if blk.kind == SPARSE \
                else self._lightning_mixer
            x = x + depth * mixer(p, pre, _rms(x, p[pre + "n_in"], eps),
                                  call, i)
            h = _rms(x, p[pre + "n_mlp"], eps)
            x = x + depth * gated_mlp(
                h.astype(p[pre + "mlp.w_gate"].dtype), p[pre + "mlp.w_gate"],
                p[pre + "mlp.w_up"], p[pre + "mlp.w_down"])
        return x

    def _logits(self, p, x):
        c = self.config
        x = _rms(x, p["n_final"], c.rms_norm_eps) \
            / (c.hidden_size / c.dim_model_base)
        return _mm(x, p["head"].T)

    # --------------------------------------------------- plain forward
    def _plain(self, ids):
        """fn(*arrays) -> (logits [B, S, V], what each `minicpm4` layer
        chose) of whole sequences ids [B, S]: one prefill window over a
        private pool whose pages lie in order, from a zero state."""
        c = self.config
        b, s = ids.shape
        bs = c.block_size
        mb = -(-s // bs)
        ids = jnp.pad(ids, ((0, 0), (0, mb * bs - s)))
        tables = 1 + jnp.arange(b * mb, dtype=jnp.int32).reshape(b, mb)
        pos = jnp.broadcast_to(jnp.arange(mb * bs, dtype=jnp.int32),
                               (b, mb * bs))
        lens = jnp.full((b,), s, jnp.int32)
        geo = self.kv_pool_geometry(bs)

        def fn(*arrays):
            p = self._tree(arrays)
            dt = p["emb"].dtype
            pools = [tuple(jnp.zeros((b * mb + 1,) + shp, dt)
                           for shp in paged)
                     + tuple(jnp.zeros((n,) + shp, jnp.float32)
                             for shp in state for n in (b, 1))
                     for paged, state in zip(geo["layer_block_shapes"],
                                             geo["state_shapes"])]
            call = StepCall(STATS, pools, tables, pos, lens, pos < s,
                            jnp.arange(b), mb * bs)
            x = self._stream(p, ids, call)
            return self._logits(p, x[:, :s]), call.chosen
        return fn

    def forward(self, input_ids):
        """Logits [B, S, V] of whole sequences, no cache. Differentiable."""
        fn = self._plain(_arr(input_ids))
        return apply_op("minicpm_sala_forward", lambda *a: fn(*a)[0],
                        list(self.parameters()))

    def selected_blocks(self, input_ids) -> list:
        """What each `minicpm4` layer selected under the plain forward: a
        list of (pages [B, S, Hkv, topk] ascending, whether the query
        selected [B, S]); the sequence padded to whole pages."""
        fn = self._plain(_arr(input_ids))
        out = named_program(lambda *a: fn(*a)[1], SELECTED_BLOCKS_PROGRAM)(
            *(q._data for q in self.parameters()))
        return [(np.asarray(a), np.asarray(b)) for a, b in out]  # lint: allow(tracer-asarray)

    # ------------------------------------------------ the paged engine
    # (`prefill_paged`, `decode_paged`, the counters: PagedStateDecoder)
    def check_serving_config(self, cfg) -> None:
        """Refuses what this model does not implement, at engine build."""
        bad = self._refusals(cfg, "neither the state planes nor 2 KV heads "
                                  "are sharded")
        if bad:
            raise ValueError("MiniCPMSALAForCausalLM does not serve under "
                             + "; ".join(bad))
        self.config.sparse.check(cfg.kv_block)

    def kv_pool_geometry(self, block_size: int) -> dict:
        """What `BlockPool.for_model` builds. A `minicpm4` layer pools
        pages of keys and values [Hkv, block_size, D] and of compressed
        keys [Hkv r D]; a `lightning-attn` layer pools none and holds one
        [heads, d, d] float32 state a row (and a snapshot)."""
        c = self.config
        c.sparse.check(block_size)
        kv = (c.num_kv_heads, block_size, c.head_dim)
        kc = (c.num_kv_heads * c.sparse.r * c.head_dim,)
        st = (c.lightning_heads, c.lightning_head_dim, c.lightning_head_dim)
        sparse = [blk.kind == SPARSE for blk in self.layers]
        return {"num_layers": c.num_layers,
                "layer_block_shapes": [(kv, kv, kc) if s else ()
                                       for s in sparse],
                "state_shapes": [() if s else (st,) for s in sparse],
                "dtype": self.emb._data.dtype}
