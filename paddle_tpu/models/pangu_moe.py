"""openPangu-Ultra-MoE decoder (model_type `pangu_ultra_moe`) for the paged
serving engine, and a chip's share of it under expert parallelism.

    x <- x + N_post_attn(Attn(N_in(x)));  x <- x + N_post_mlp(F(N_pre_mlp(x)))

RMSNorm before and after each sub-layer (sandwich norm), multi-head latent
attention with rotary positions on part of each head, a SiLU-gated MLP in
the leading dense layers and `nn.layers.experts.HeldExperts` after them,
an untied head. Attention is computed in its ABSORBED form everywhere: the
cache holds one latent `[N_kv(c_kv) | RoPE(k_pe)]` a token and layer
(ops/latent_attention.py), a query head is taken through W_uk before the
scores and the context through W_uv after them, so no key or value head is
ever built.

The model implements what `inference.ServingEngine` calls:
`config`, `prefill_paged`, `decode_paged`, `_gen_cache_get`, the pool
geometry (`kv_pool_geometry`) and the per-step expert counters
(`pop_step_counters`), plus a plain differentiable `forward` for tests.
Speculative decoding, head-sharded pools and int8 latents are refused by
`check_serving_config`.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.tensor import Tensor, apply_op
from ..jit.api import (DECODE_PROGRAM, EXPERT_CHOICES_PROGRAM,
                       PREFILL_PROGRAM, named_program)
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.layers.experts import (LEAVES, STATS, HeldExperts, gated_mlp,
                                 route)
from ..ops.latent_attention import (latent_cache_write,
                                    latent_paged_attention,
                                    latent_paged_decode)
from .decoder_parts import GatedMLP, _arr, _mm, _rms, _rope
from .gpt import GPTForCausalLM, sample_logits


@dataclass
class PanguMoEConfig:
    vocab_size: int = 153600
    hidden_size: int = 7680
    num_layers: int = 61
    num_dense_layers: int = 3          # first_k_dense_replace
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_experts: int = 256             # the router's outputs
    experts_held: int = 256            # of which this chip computes ...
    first_expert: int = 0              # ... these, from this one on
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-5
    rope_theta: float = 25.6e6
    initializer_range: float = 0.02
    dtype: str = "float32"

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim


class PanguMoEBlock(Layer):
    def __init__(self, c: PanguMoEConfig, dense: bool):
        super().__init__()
        init = I.Normal(0.0, c.initializer_range)
        one = I.Constant(1.0)
        mk = lambda shape, i=init: self.create_parameter(  # noqa: E731
            list(shape), dtype=c.dtype, default_initializer=i)
        h, nh = c.hidden_size, c.num_heads
        self.n_in = mk((h,), one)
        self.w_dq = mk((h, c.q_lora_rank))
        self.n_q = mk((c.q_lora_rank,), one)
        self.w_uq = mk((c.q_lora_rank,
                        nh * (c.qk_nope_head_dim + c.qk_rope_head_dim)))
        self.w_dkv = mk((h, c.latent_width))
        self.n_kv = mk((c.kv_lora_rank,), one)
        self.w_ukv = mk((c.kv_lora_rank,
                         nh * (c.qk_nope_head_dim + c.v_head_dim)))
        self.w_o = mk((nh * c.v_head_dim, h))
        self.n_post_attn = mk((h,), one)
        self.n_pre_mlp = mk((h,), one)
        self.n_post_mlp = mk((h,), one)
        if dense:
            self.mlp = GatedMLP(h, c.intermediate_size, init, c.dtype)
        else:
            self.mlp = HeldExperts(
                h, c.moe_intermediate_size, c.num_experts, c.experts_held,
                c.first_expert, c.num_experts_per_tok,
                scale=c.routed_scaling_factor,
                shared_width=c.moe_intermediate_size * c.n_shared_experts,
                initializer_range=c.initializer_range, dtype=c.dtype)


class PanguMoEForCausalLM(Layer):
    def __init__(self, config: PanguMoEConfig):
        super().__init__()
        c = self.config = config
        init = I.Normal(0.0, c.initializer_range)
        mk = lambda shape, i=init: self.create_parameter(  # noqa: E731
            list(shape), dtype=c.dtype, default_initializer=i)
        self.emb = mk((c.vocab_size, c.hidden_size))
        self.layers = [PanguMoEBlock(c, i < c.num_dense_layers)
                       for i in range(c.num_layers)]
        for i, blk in enumerate(self.layers):
            self.add_sublayer(f"layers.{i}", blk)
        self.n_final = mk((c.hidden_size,), I.Constant(1.0))
        self.head = mk((c.vocab_size, c.hidden_size))
        self._names = [n for n, _ in self.named_parameters()]
        self._stats = np.zeros((len(STATS),), np.float32)

    # ------------------------------------------------ the pure functions
    def _tree(self, arrays):
        """The flat parameter arrays as {name: array}."""
        return dict(zip(self._names, arrays))

    def _queries_and_latent(self, p, pre, h, pos):
        """h [B, S, H] (normed), pos [B, S] -> the absorbed queries
        [B, S, nh, W] and the tokens' latents [B, S, W]."""
        c = self.config
        nh, dn, dr, r = (c.num_heads, c.qk_nope_head_dim,
                         c.qk_rope_head_dim, c.kv_lora_rank)
        b, s, _ = h.shape
        dt = p[pre + "w_dq"].dtype
        c_q = _rms(_mm(h, p[pre + "w_dq"]), p[pre + "n_q"], c.rms_norm_eps)
        q = _mm(c_q, p[pre + "w_uq"]).reshape(b, s, nh, dn + dr)
        dkv = _mm(h, p[pre + "w_dkv"])
        lat = jnp.concatenate(
            [_rms(dkv[..., :r], p[pre + "n_kv"], c.rms_norm_eps),
             _rope(dkv[..., r:], pos, c.rope_theta)], -1)
        w_uk = p[pre + "w_ukv"].reshape(r, nh, -1)[..., :dn]
        q_lat = jnp.concatenate(
            [jnp.einsum("bshd,rhd->bshr", q[..., :dn].astype(dt), w_uk,
                        preferred_element_type=jnp.float32),
             _rope(q[..., dn:], pos, c.rope_theta)], -1)
        return q_lat.astype(dt), lat.astype(dt)

    def _attn_out(self, p, pre, ctx):
        """The latent context [B, S, nh, rank] through W_uv and W_o."""
        c = self.config
        w_uv = p[pre + "w_ukv"].reshape(
            c.kv_lora_rank, c.num_heads, -1)[..., c.qk_nope_head_dim:]
        v = jnp.einsum("bshr,rhd->bshd", ctx.astype(w_uv.dtype), w_uv,
                       preferred_element_type=jnp.float32)
        return _mm(v.reshape(v.shape[:2] + (-1,)), p[pre + "w_o"])

    def _block(self, p, i, x, pos, attend, live=None, choices=None):
        """One block on the float32 residual stream x [B, S, H].
        `attend(q_lat, lat)` returns the latent context [B, S, nh, rank].
        Returns (x, expert stats); an expert layer also appends what its
        router chose [B, S, k] to `choices`, if that is a list."""
        c, pre = self.config, f"layers.{i}."
        eps = c.rms_norm_eps
        q_lat, lat = self._queries_and_latent(
            p, pre, _rms(x, p[pre + "n_in"], eps), pos)
        a = self._attn_out(p, pre, attend(q_lat, lat))
        x = x + _rms(a, p[pre + "n_post_attn"], eps)
        h = _rms(x, p[pre + "n_pre_mlp"], eps)
        mlp = self.layers[i].mlp
        if isinstance(mlp, GatedMLP):
            f = gated_mlp(h.astype(p[pre + "mlp.w_gate"].dtype),
                          p[pre + "mlp.w_gate"], p[pre + "mlp.w_up"],
                          p[pre + "mlp.w_down"])
            stats = jnp.zeros((len(STATS),), jnp.float32)
        else:
            f, stats = mlp.apply([p[pre + "mlp." + n] for n in LEAVES],
                                 h.reshape(-1, h.shape[-1]),
                                 None if live is None else live.reshape(-1))
            f = f.reshape(h.shape)
            if choices is not None:
                choices.append(route(h, p[pre + "mlp.w_r"], mlp.top_k,
                                     mlp.scale)[0])
        return x + _rms(f, p[pre + "n_post_mlp"], eps), stats

    def _logits(self, p, x):
        return _mm(_rms(x, p["n_final"], self.config.rms_norm_eps),
                   p["head"].T)

    # --------------------------------------------------- plain forward
    def _plain(self, ids):
        """fn(*arrays) -> (logits [B, S, V], each expert layer's choices
        [B, S, k]) of whole sequences ids [B, S], no cache."""
        c = self.config
        scale = (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5
        b, s = ids.shape
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        prec = lax.Precision.HIGHEST if c.dtype == "float32" else None

        def attend(q_lat, lat):
            sc = jnp.einsum("bshw,btw->bhst", q_lat, lat, precision=prec,
                            preferred_element_type=jnp.float32) * scale
            sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
            return jnp.einsum("bhst,btr->bshr",
                              jax.nn.softmax(sc, -1).astype(lat.dtype),
                              lat[..., :c.kv_lora_rank], precision=prec)

        def fn(*arrays):
            p = self._tree(arrays)
            x = p["emb"][ids].astype(jnp.float32)
            choices = []
            for i in range(c.num_layers):
                x, _ = self._block(p, i, x, pos, attend, choices=choices)
            return self._logits(p, x), choices
        return fn

    def forward(self, input_ids):
        """Logits [B, S, V] of whole sequences, no cache: causal attention
        over the sequence's own latents. Differentiable."""
        fn = self._plain(_arr(input_ids))
        return apply_op("pangu_moe_forward", lambda *a: fn(*a)[0],
                        list(self.parameters()))

    def expert_choices(self, input_ids) -> list:
        """What each expert layer's router chose under the plain forward:
        a list of [B, S, k] int arrays, numbers among all the layer's
        experts. For diagnosis: how a change of arithmetic moves tokens
        between experts."""
        fn = self._plain(_arr(input_ids))
        out = named_program(lambda *a: fn(*a)[1], EXPERT_CHOICES_PROGRAM)(
            *(q._data for q in self.parameters()))
        return [np.asarray(a) for a in out]  # lint: allow(tracer-asarray)

    # ------------------------------------------------ the paged engine
    _gen_cache_get = GPTForCausalLM._gen_cache_get

    def check_serving_config(self, cfg) -> None:
        """Refuses what this model does not implement, at engine build."""
        bad = [why for cond, why in (
            (cfg.spec_decode, "spec_decode=True (no verify_paged)"),
            ((cfg.shards or 1) > 1, "shards > 1 (the latent pool has no "
                                    "head axis to shard)"),
            (cfg.cache_dtype is not None, f"cache_dtype="
                                          f"{cfg.cache_dtype!r} (latents "
                                          f"are pooled in the model dtype)"),
            (cfg.weight_dtype is not None, f"weight_dtype="
                                           f"{cfg.weight_dtype!r}")) if cond]
        if bad:
            raise ValueError("PanguMoEForCausalLM does not serve under "
                             + "; ".join(bad))

    def kv_pool_geometry(self, block_size: int) -> dict:
        """What `BlockPool.for_model` builds: one plane a layer, a page of
        `block_size` latents as [W, block_size] (ops/latent_attention.py)."""
        return {"num_layers": self.config.num_layers,
                "block_shapes": ((self.config.latent_width, block_size),),
                "dtype": self.emb._data.dtype}

    step_counter_names = STATS

    def detach_step_counters(self):
        """The experts' counts of the prefill and decode calls made since
        the last detach, as the array [len(STATS)] the last of them
        returned: still on the device, nothing is read. The next call
        counts from zero, so a caller can launch on and read these counts
        when their own calls have run (the serving engine does, with the
        tokens)."""
        stats, self._stats = self._stats, \
            np.zeros((len(STATS),), np.float32)
        return stats

    def _scale_rank(self):
        c = self.config
        return ((c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5,
                c.kv_lora_rank)

    def prefill_paged(self, input_ids, prompt_lens, pools, block_tables,
                      temperature: float = 0.0, top_k: int = 0,
                      top_p: float = 1.0, seed: int = 0,
                      weight_dtype: str = None, cache_dtype: str = None,
                      start=None):
        """As GPTForCausalLM.prefill_paged: writes the window's latents
        into the rows' pages (padding into the trash page) and returns
        (pools', first token [n]); the window attends the cached prefix
        and itself. `start` [n] offsets the window; one executable serves
        both forms, `start` being data."""
        ids = _arr(input_ids)
        b, p_cap = ids.shape
        lens = _arr(prompt_lens, jnp.int32).reshape(b)
        tables = _arr(block_tables, jnp.int32)
        st = jnp.zeros((b,), jnp.int32) if start is None \
            else _arr(start, jnp.int32)
        scale, rank = self._scale_rank()
        c = self.config

        def run(arrays, pools, ids, lens, tables, st, key, stats):
            p = self._tree(arrays)
            pos = st[:, None] + jnp.arange(p_cap, dtype=jnp.int32)[None]
            live = jnp.arange(p_cap)[None] < lens[:, None]
            x = p["emb"][ids].astype(jnp.float32)
            new_pools = []
            for i, (pool,) in enumerate(pools):
                def attend(q_lat, lat, pool=pool):
                    pool = latent_cache_write(pool, lat, tables, st, lens)
                    new_pools.append((pool,))
                    return latent_paged_attention(q_lat, pool, tables, st,
                                                  rank=rank, scale=scale)
                x, s_i = self._block(p, i, x, pos, attend, live)
                stats = stats + s_i
            last = self._logits(p, x[jnp.arange(b), lens - 1])
            nxt = sample_logits(last, key, temperature=temperature,
                                top_k=top_k, top_p=top_p).astype(jnp.int32)
            return new_pools, nxt, stats

        pool0 = pools[0][0]
        sig = ("pangu_prefill", b, p_cap, pool0.shape, int(tables.shape[1]),
               float(temperature), int(top_k), float(top_p), str(pool0.dtype))
        fn = self._gen_cache_get(sig, lambda: named_program(
            run, PREFILL_PROGRAM, donate_argnums=(1,)))
        pools2, nxt, self._stats = fn(
            tuple(q._data for q in self.parameters()), pools, ids, lens,
            tables, st, jax.random.PRNGKey(seed), self._stats)
        return pools2, Tensor(nxt)

    def decode_paged(self, pools, block_tables, lens, pending, done,
                     max_new_tokens: int, temperature: float = 0.0,
                     top_k: int = 0, top_p: float = 1.0, seed: int = 0,
                     eos_token_id: int = None, weight_dtype: str = None,
                     cache_dtype: str = None):
        """As GPTForCausalLM.decode_paged: one compiled chunk of
        `max_new_tokens` steps over the whole slot batch; returns (tokens
        [B, n] int64, pools', lens', done')."""
        if max_new_tokens <= 0:
            raise ValueError("decode_paged needs max_new_tokens >= 1")
        tables, lens_a, pend = (_arr(block_tables, jnp.int32),
                                _arr(lens, jnp.int32),
                                _arr(pending, jnp.int32))
        done_a = _arr(done, bool)
        scale, rank = self._scale_rank()

        def run(arrays, pools, tables, lens_, pending_, done_, key0, stats0):
            p = self._tree(arrays)

            def body(carry, _):
                pools, ln, cur, key, dn, stats = carry
                x = p["emb"][cur][:, None].astype(jnp.float32)
                pos = ln[:, None]
                new_pools = []
                for i, (pool,) in enumerate(pools):
                    def attend(q_lat, lat, pool=pool):
                        pool = latent_cache_write(pool, lat, tables, ln)
                        new_pools.append((pool,))
                        # a done row attends nothing: nobody reads it
                        return latent_paged_decode(
                            q_lat[:, 0], pool, tables,
                            jnp.where(dn, 0, ln + 1), rank=rank,
                            scale=scale)[:, None]
                    x, s_i = self._block(p, i, x, pos, attend, ~dn[:, None])
                    stats = stats + s_i
                key, kk = jax.random.split(key)
                new = sample_logits(self._logits(p, x[:, 0]), kk,
                                    temperature=temperature, top_k=top_k,
                                    top_p=top_p).astype(jnp.int32)
                if eos_token_id is not None:
                    new = jnp.where(dn, jnp.asarray(eos_token_id, new.dtype),
                                    new)
                    dn = dn | (new == eos_token_id)
                return (new_pools, ln + 1, new, key, dn, stats), new

            (pools, lens_, _, _, done_, stats), toks = lax.scan(
                body, (pools, lens_, pending_, key0, done_, stats0), None,
                length=max_new_tokens)
            return (jnp.moveaxis(toks, 0, 1).astype(jnp.int64), pools,
                    lens_, done_, stats)

        pool0 = pools[0][0]
        sig = ("pangu_decode", tables.shape, pool0.shape,
               int(max_new_tokens), float(temperature), int(top_k),
               float(top_p),
               None if eos_token_id is None else int(eos_token_id),
               str(pool0.dtype))
        fn = self._gen_cache_get(sig, lambda: named_program(
            run, DECODE_PROGRAM, donate_argnums=(1,)))
        toks, pools2, lens2, done2, self._stats = fn(
            tuple(q._data for q in self.parameters()), pools, tables,
            lens_a, pend, done_a, jax.random.PRNGKey(seed), self._stats)
        return Tensor(toks), pools2, lens2, done2
