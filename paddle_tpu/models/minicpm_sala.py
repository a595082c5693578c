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
from jax import lax

from ..core.tensor import Tensor, apply_op
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.layers.experts import gated_mlp
from ..ops.lightning_attention import lightning_decode, lightning_window
from ..ops.sparse_attention import (SparseSizes, compressed_write,
                                    decode_lists, grouped_paged_decode,
                                    kv_cache_write, select_blocks,
                                    sparse_window_attention)
from .decoder_parts import GatedMLP, _arr, _mm, _rms, _rope
from .gpt import GPTForCausalLM, sample_logits

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


class _Call:
    """What one traced call (a prefill window or a decode step) hands its
    layers: where the tokens sit, the planes of each layer as they are
    consumed and replaced, and the counters."""

    def __init__(self, pools, tables, pos, lens, live, slots, width):
        self.pools = list(pools)
        self.tables, self.pos, self.lens = tables, pos, lens
        self.live, self.slots, self.width = live, slots, width
        self.stats = dict.fromkeys(STATS, jnp.float32(0))
        self.chosen = []

    def count(self, name, x):
        self.stats[name] = self.stats[name] + jnp.sum(x).astype(jnp.float32)

    def stats_array(self):
        return jnp.stack([self.stats[k] for k in STATS])


class MiniCPMSALAForCausalLM(Layer):
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
        self._names = [n for n, _ in self.named_parameters()]
        self._stats = np.zeros((len(STATS),), np.float32)

    # ------------------------------------------------ the pure functions
    def _tree(self, arrays):
        return dict(zip(self._names, arrays))

    def _sparse_mixer(self, p, pre, h, call: _Call, i: int):
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

    def _lightning_mixer(self, p, pre, h, call: _Call, i: int):
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

    def _stream(self, p, ids, call: _Call):
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
            call = _Call(pools, tables, pos, lens, pos < s,
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
        out = jax.jit(lambda *a: fn(*a)[1])(
            *(q._data for q in self.parameters()))
        return [(np.asarray(a), np.asarray(b)) for a, b in out]  # lint: allow(tracer-asarray)

    # ------------------------------------------------ the paged engine
    _gen_cache_get = GPTForCausalLM._gen_cache_get

    def check_serving_config(self, cfg) -> None:
        """Refuses what this model does not implement, at engine build."""
        bad = [why for cond, why in (
            (cfg.spec_decode, "spec_decode=True (no verify_paged; a "
                              "rejected draft would have to roll the "
                              "recurrent state back)"),
            ((cfg.shards or 1) > 1, "shards > 1 (neither the state planes "
                                    "nor 2 KV heads are sharded)"),
            (cfg.cache_dtype is not None, f"cache_dtype={cfg.cache_dtype!r} "
                                          f"(pages are pooled in the model "
                                          f"dtype, the state in float32)"),
            (cfg.weight_dtype is not None,
             f"weight_dtype={cfg.weight_dtype!r}"),
            (cfg.spill_host_bytes is not None,
             "spill_host_bytes (a spilled block's state snapshot is not "
             "carried)"),
            (cfg.prefill_chunk is not None
             and cfg.prefill_chunk % cfg.kv_block != 0,
             f"prefill_chunk={cfg.prefill_chunk} (a window must be whole "
             f"pages of {cfg.kv_block})"),
            (cfg.prefill_chunk is None and cfg.prompt_cap % cfg.kv_block != 0,
             f"prompt_cap={cfg.prompt_cap} without prefill_chunk (a window "
             f"must be whole pages of {cfg.kv_block})")) if cond]
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

    step_counter_names = STATS

    def detach_step_counters(self):
        """The counters of the prefill and decode calls made since the
        last detach, as the device array the last of them returned."""
        stats, self._stats = self._stats, \
            np.zeros((len(STATS),), np.float32)
        return stats

    def prefill_paged(self, input_ids, prompt_lens, pools, block_tables,
                      temperature: float = 0.0, top_k: int = 0,
                      top_p: float = 1.0, seed: int = 0,
                      weight_dtype: str = None, cache_dtype: str = None,
                      start=None, state_slots=None):
        """As GPTForCausalLM.prefill_paged: writes the window into the
        rows' pages and returns (pools', first token [n]). `state_slots`
        [n] names the row of the state planes each prompt advances: the
        window starts from the state found there (zeroed or restored by
        the caller) and leaves the state at its last live token."""
        ids = _arr(input_ids)
        b, p_cap = ids.shape
        lens = _arr(prompt_lens, jnp.int32).reshape(b)
        tables = _arr(block_tables, jnp.int32)
        st = jnp.zeros((b,), jnp.int32) if start is None \
            else _arr(start, jnp.int32)
        if state_slots is None:
            raise ValueError("prefill_paged needs state_slots: the rows of "
                             "the state planes the prompts advance")
        slots = _arr(state_slots, jnp.int32).reshape(b)

        def run(arrays, pools, ids, lens, tables, st, slots, key, stats):
            p = self._tree(arrays)
            pos = st[:, None] + jnp.arange(p_cap, dtype=jnp.int32)[None]
            live = jnp.arange(p_cap)[None] < lens[:, None]
            call = _Call(pools, tables, pos, lens, live, slots, p_cap)
            x = self._stream(p, ids, call)
            last = self._logits(p, x[jnp.arange(b), lens - 1])
            nxt = sample_logits(last, key, temperature=temperature,
                                top_k=top_k, top_p=top_p).astype(jnp.int32)
            return call.pools, nxt, stats + call.stats_array()

        sig = ("sala_prefill", b, p_cap, _shapes(pools),
               int(tables.shape[1]), float(temperature), int(top_k),
               float(top_p))
        fn = self._gen_cache_get(
            sig, lambda: jax.jit(run, donate_argnums=(1,)))
        pools2, nxt, self._stats = fn(
            tuple(q._data for q in self.parameters()), pools, ids, lens,
            tables, st, slots, jax.random.PRNGKey(seed), self._stats)
        return pools2, Tensor(nxt)

    def decode_paged(self, pools, block_tables, lens, pending, done,
                     max_new_tokens: int, temperature: float = 0.0,
                     top_k: int = 0, top_p: float = 1.0, seed: int = 0,
                     eos_token_id: int = None, weight_dtype: str = None,
                     cache_dtype: str = None):
        """As GPTForCausalLM.decode_paged: one compiled chunk of
        `max_new_tokens` steps over the whole slot batch (row b is row b
        of the state planes); returns (tokens [B, n] int64, pools', lens',
        done'). A done row neither selects nor moves its state."""
        if max_new_tokens <= 0:
            raise ValueError("decode_paged needs max_new_tokens >= 1")
        tables, lens_a, pend = (_arr(block_tables, jnp.int32),
                                _arr(lens, jnp.int32),
                                _arr(pending, jnp.int32))
        done_a = _arr(done, bool)

        def run(arrays, pools, tables, lens_, pending_, done_, key0, stats0):
            p = self._tree(arrays)

            def body(carry, _):
                pools, ln, cur, key, dn, stats = carry
                call = _Call(pools, tables, ln[:, None], None,
                             ~dn[:, None], None, 1)
                x = self._stream(p, cur[:, None], call)
                key, kk = jax.random.split(key)
                new = sample_logits(self._logits(p, x[:, 0]), kk,
                                    temperature=temperature, top_k=top_k,
                                    top_p=top_p).astype(jnp.int32)
                if eos_token_id is not None:
                    new = jnp.where(dn, jnp.asarray(eos_token_id, new.dtype),
                                    new)
                    dn = dn | (new == eos_token_id)
                return (call.pools, ln + 1, new, key, dn,
                        stats + call.stats_array()), new

            (pools, lens_, _, _, done_, stats), toks = lax.scan(
                body, (list(pools), lens_, pending_, key0, done_, stats0),
                None, length=max_new_tokens)
            return (jnp.moveaxis(toks, 0, 1).astype(jnp.int64), pools,
                    lens_, done_, stats)

        sig = ("sala_decode", tables.shape, _shapes(pools),
               int(max_new_tokens), float(temperature), int(top_k),
               float(top_p),
               None if eos_token_id is None else int(eos_token_id))
        fn = self._gen_cache_get(
            sig, lambda: jax.jit(run, donate_argnums=(1,)))
        toks, pools2, lens2, done2, self._stats = fn(
            tuple(q._data for q in self.parameters()), pools, tables,
            lens_a, pend, done_a, jax.random.PRNGKey(seed), self._stats)
        return Tensor(toks), pools2, lens2, done2


def _shapes(pools) -> tuple:
    """The planes' shapes and dtypes, for an executable's signature."""
    return tuple(tuple((tuple(a.shape), str(a.dtype)) for a in layer)
                 for layer in pools)
