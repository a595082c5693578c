"""Jamba decoder (model_type `jamba`; Lieber et al., arXiv:2403.19887) for
the paged serving engine: Mamba-1 state-space layers (Gu and Dao,
arXiv:2312.00752) with Jamba's three inner RMSNorms, one attention layer a
period with grouped KV heads and no positional encoding, a SiLU-gated MLP
after every mixer, a tied head. `num_experts` 1: no router anywhere.

    x0 = E[ids]
    x <- x + mixer_i(RMSNorm(x));  x <- x + MLP(RMSNorm(x))
    logits = E^T RMSNorm(x_L)

Layer i is attention where i % attn_layer_period == attn_layer_offset. An
attention layer pools pages of keys and values [Hkv, block_size, D]
(ops/sparse_attention.py's cache write, window attention and page-list
decode, every row's list being its whole table). A Mamba layer pools no
pages and holds TWO recurrent state arrays a row, float32, kept by the
cache manager in planes of their own beside the pages
(`kv_pool_geometry`; inference/kv_cache.py), zeroed, snapshot and restored
together:

    conv  [(d_conv - 1) d_inner]   the last three inputs of the causal
                                   convolution, oldest first, side by side
                                   along the lanes (a [3, d_inner] plane
                                   would be padded to 8 sublanes a row)
    scan  [d_state, d_inner]       S of ops/selective_scan.py, d_inner
                                   along the lanes

A prefill window advances both from what the slot holds: the convolution
reads the three inputs before the window from the conv state and leaves
the last three LIVE ones there (a window of fewer than three tokens shifts
the state), the scan starts from S and leaves S at the last live token. A
decode step shifts the one and updates the other in place; a done row
moves neither.

One code path: the plain `forward` (whole sequences, differentiable) runs
the prefill window's functions over a private pool, so what the tests
hold against the reference is what the engine runs. What
`inference.ServingEngine` calls is `PagedStateDecoder`'s
(models/decoder_parts.py). Speculative decoding, shards, an int8 cache or
weights and the host spill tier are refused by `check_serving_config`.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.tensor import apply_op
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.layers.experts import gated_mlp
from ..ops.selective_scan import scan_step, scan_window
from ..ops.sparse_attention import (grouped_paged_decode, kv_cache_write,
                                    sparse_window_attention)
from .decoder_parts import (GatedMLP, PagedStateDecoder, StepCall, _arr, _mm,
                            _rms)

ATTENTION = "attention"
MAMBA = "mamba"

# per-call device counters: decode row-steps a Mamba layer that moved a
# state, live prefill tokens and windows a Mamba layer scanned, (query,
# token) pairs an attention layer attended (prefill and decode), pages a
# decode row-step walked an attention layer (all KV heads)
STATS = ("ssm_rows_updated", "ssm_tokens_scanned", "ssm_windows_scanned",
         "attn_pairs", "attn_pages_walked")


@dataclass
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    num_layers: int = 28
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    num_heads: int = 20
    num_kv_heads: int = 1
    head_dim: int = 128
    intermediate_size: int = 8192
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    dtype: str = "float32"

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def mixers(self) -> tuple:
        return tuple(ATTENTION if i % self.attn_layer_period
                     == self.attn_layer_offset else MAMBA
                     for i in range(self.num_layers))


def _time_step(r, w_dt, b_dt):
    """dt = softplus(W_dt r + b_dt), float32 whatever the parameters'
    dtype: it stands in an exponent."""
    f32 = jnp.float32
    return jax.nn.softplus(
        jnp.matmul(r, w_dt.astype(f32), precision=lax.Precision.HIGHEST)
        + b_dt.astype(f32))


class JambaBlock(Layer):
    def __init__(self, c: JambaConfig, kind: str):
        super().__init__()
        init = I.Normal(0.0, c.initializer_range)
        one = I.Constant(1.0)
        mk = lambda shape, i=init: self.create_parameter(  # noqa: E731
            list(shape), dtype=c.dtype, default_initializer=i)
        h, din, n, r = c.hidden_size, c.d_inner, c.d_state, c.dt_rank
        self.kind = kind
        self.n_in = mk((h,), one)
        if kind == ATTENTION:
            wide = c.num_heads * c.head_dim
            # q, k and v of the mixer's input as one matrix [q | k | v]
            self.w_qkv = mk((h, wide + 2 * c.num_kv_heads * c.head_dim))
            self.w_o = mk((wide, h))
        else:
            self.w_in = mk((h, 2 * din))            # [u | z]
            self.conv_w = mk((c.d_conv, din))       # tap j on u_(t-3+j)
            self.conv_b = mk((din,), I.Constant(0.0))
            self.w_x = mk((din, r + 2 * n))         # [r | B | C]
            self.n_dt, self.n_b, self.n_c = mk((r,), one), mk((n,), one), \
                mk((n,), one)
            self.w_dt = mk((r, din))
            # Mamba-1's own: dt = softplus(b_dt) = 0.01 where W_dt r = 0,
            # A[n, d] = -(n + 1), D = 1
            self.b_dt = mk((din,), I.Constant(math.log(math.expm1(0.01))))
            self.a_log = mk((n, din), I.Assign(np.broadcast_to(
                np.log(np.arange(1, n + 1, dtype=np.float32))[:, None],
                (n, din))))
            self.d_skip = mk((din,), one)
            self.w_out = mk((din, h))
        self.n_mlp = mk((h,), one)
        self.mlp = GatedMLP(h, c.intermediate_size, init, c.dtype)


class JambaForCausalLM(PagedStateDecoder):
    STATS = STATS

    def __init__(self, config: JambaConfig):
        super().__init__()
        c = self.config = config
        self.emb = self.create_parameter(
            [c.vocab_size, c.hidden_size], dtype=c.dtype,
            default_initializer=I.Normal(0.0, c.initializer_range))
        self.layers = [JambaBlock(c, kind) for kind in c.mixers]
        for i, blk in enumerate(self.layers):
            self.add_sublayer(f"layers.{i}", blk)
        self.n_final = self.create_parameter(
            [c.hidden_size], dtype=c.dtype,
            default_initializer=I.Constant(1.0))
        self._init_serving()

    # ------------------------------------------------ the pure functions
    def _attention_mixer(self, p, pre, h, call: StepCall, i: int):
        """h [B, S, H] (normed) -> the mixer's output; the window's (or
        the step's) keys and values go into the layer's pages first. No
        positions: the Mamba layers carry them."""
        c = self.config
        b, s, _ = h.shape
        hkv, hd = c.num_kv_heads, c.head_dim
        g = c.num_heads // hkv
        scale = hd ** -0.5
        q, k, v = jnp.split(_mm(h, p[pre + "w_qkv"]),
                            np.cumsum([hkv * g * hd, hkv * hd]), axis=-1)
        q = q.reshape(b, s, hkv, g, hd)
        k_pool, v_pool = call.pools[i]
        k_pool, v_pool = kv_cache_write(
            k_pool, v_pool, k.reshape(b, s, hkv, hd),
            v.reshape(b, s, hkv, hd), call.tables, call.pos[:, 0], call.lens)
        call.pools[i] = (k_pool, v_pool)
        bs = k_pool.shape[2]
        if call.lens is None:                       # a decode step
            lv = call.live[:, 0]
            # a row that is not live attends nothing: nobody reads it
            seen = jnp.where(lv, call.pos[:, 0] + 1, 0)
            o = grouped_paged_decode(
                q[:, 0], k_pool, v_pool,
                jnp.broadcast_to(call.tables[:, None], (b, hkv)
                                 + call.tables.shape[1:]),
                jnp.broadcast_to(seen[:, None], (b, hkv)), scale)[:, None]
            call.count("attn_pairs", seen)
            call.count("attn_pages_walked", hkv * ((seen + bs - 1) // bs))
        else:
            o, pairs = sparse_window_attention(
                q, k_pool, v_pool, call.tables, call.pos,
                jnp.zeros((b, s, hkv, 1), jnp.int32),
                jnp.zeros((b, s), bool), scale)
            call.count("attn_pairs", jnp.where(call.live, pairs, 0))
        return _mm(o.reshape(b, s, -1), p[pre + "w_o"])

    def _mamba_mixer(self, p, pre, h, call: StepCall, i: int):
        c = self.config
        b, s, _ = h.shape
        din, n, r, taps = c.d_inner, c.d_state, c.dt_rank, c.d_conv
        eps = c.rms_norm_eps
        f32 = jnp.float32
        u, z = jnp.split(_mm(h, p[pre + "w_in"]), 2, axis=-1)
        w = p[pre + "conv_w"].astype(f32)
        conv, conv_snap, scan, scan_snap = call.pools[i]
        decode = call.lens is None
        # the causal convolution over the last `taps` inputs, the first
        # taps - 1 of them from the conv state
        if decode:
            old = conv                                      # [B, 3 din]
            past = [old[:, j * din:(j + 1) * din] for j in range(taps - 1)]
            acc = p[pre + "conv_b"].astype(f32) + w[taps - 1] * u[:, 0]
            for j, u_j in enumerate(past):
                acc = acc + w[j] * u_j
            x = jax.nn.silu(acc)[:, None]                   # [B, 1, din]
            live = call.live[:, 0]
            conv = jnp.where(live[:, None], jnp.concatenate(
                past[1:] + [u[:, 0]], axis=-1), old)
        else:
            old = conv[call.slots].reshape(b, taps - 1, din)
            ext = jnp.concatenate([old, u], axis=1)         # [b, 3 + S, din]
            x = jax.nn.silu(p[pre + "conv_b"].astype(f32) + sum(
                w[j] * ext[:, j:j + s] for j in range(taps)))
            # the inputs of the last three LIVE tokens (lens < 3 shifts)
            new = jax.vmap(lambda e, n_live: lax.dynamic_slice_in_dim(
                e, n_live, taps - 1))(ext, call.lens)
            conv = conv.at[call.slots].set(new.reshape(b, -1))
        rbc = _mm(x, p[pre + "w_x"])
        dt_in, bm, cm = jnp.split(rbc, np.cumsum([r, n]), axis=-1)
        dt_in = _rms(dt_in, p[pre + "n_dt"], eps)
        bm, cm = _rms(bm, p[pre + "n_b"], eps), _rms(cm, p[pre + "n_c"], eps)
        # the time step, the decay and the scan in float32 whatever the
        # parameters' dtype
        dt = _time_step(dt_in, p[pre + "w_dt"], p[pre + "b_dt"])
        a_t = -jnp.exp(p[pre + "a_log"].astype(f32))        # [N, din]
        d_skip = p[pre + "d_skip"].astype(f32)
        if decode:
            y, scan = scan_step(x[:, 0], dt[:, 0], bm[:, 0], cm[:, 0], a_t,
                                d_skip, scan, live)
            y = y[:, None]
            call.count("ssm_rows_updated", live)
        else:
            y, rows = scan_window(x, dt, bm, cm, a_t, d_skip,
                                  scan[call.slots], call.lens)
            scan = scan.at[call.slots].set(rows)
            call.count("ssm_tokens_scanned", call.lens)
            call.count("ssm_windows_scanned", call.lens > 0)
        call.pools[i] = (conv, conv_snap, scan, scan_snap)
        return _mm(y * jax.nn.silu(z), p[pre + "w_out"])

    def _stream(self, p, ids, call: StepCall):
        """The final residual stream [B, S, H] of the call's tokens."""
        eps = self.config.rms_norm_eps
        x = p["emb"][ids].astype(jnp.float32)
        for i, blk in enumerate(self.layers):
            pre = f"layers.{i}."
            mixer = self._attention_mixer if blk.kind == ATTENTION \
                else self._mamba_mixer
            x = x + mixer(p, pre, _rms(x, p[pre + "n_in"], eps), call, i)
            h = _rms(x, p[pre + "n_mlp"], eps)
            x = x + gated_mlp(
                h.astype(p[pre + "mlp.w_gate"].dtype), p[pre + "mlp.w_gate"],
                p[pre + "mlp.w_up"], p[pre + "mlp.w_down"])
        return x

    def _logits(self, p, x):
        return _mm(_rms(x, p["n_final"], self.config.rms_norm_eps),
                   p["emb"].T)

    # --------------------------------------------------- plain forward
    def forward(self, input_ids):
        """Logits [B, S, V] of whole sequences, no cache: one prefill
        window over a private pool whose pages lie in order, from a zero
        state. Differentiable."""
        ids = _arr(input_ids)
        b, s = ids.shape
        bs = 16
        mb = -(-s // bs)
        ids = jnp.pad(ids, ((0, 0), (0, mb * bs - s)))
        tables = 1 + jnp.arange(b * mb, dtype=jnp.int32).reshape(b, mb)
        pos = jnp.broadcast_to(jnp.arange(mb * bs, dtype=jnp.int32),
                               (b, mb * bs))
        lens = jnp.full((b,), s, jnp.int32)
        geo = self.kv_pool_geometry(bs)

        def fn(*arrays):
            p = self._tree(arrays)
            pools = [tuple(jnp.zeros((b * mb + 1,) + shp, p["emb"].dtype)
                           for shp in paged)
                     + tuple(jnp.zeros((n,) + shp, jnp.float32)
                             for shp in state for n in (b, 1))
                     for paged, state in zip(geo["layer_block_shapes"],
                                             geo["state_shapes"])]
            call = StepCall(STATS, pools, tables, pos, lens, pos < s,
                            jnp.arange(b), mb * bs)
            return self._logits(p, self._stream(p, ids, call)[:, :s])
        return apply_op("jamba_forward", fn, list(self.parameters()))

    # ------------------------------------------------ the paged engine
    # (`prefill_paged`, `decode_paged`, the counters: PagedStateDecoder)
    def check_serving_config(self, cfg) -> None:
        """Refuses what this model does not implement, at engine build."""
        bad = self._refusals(cfg, "neither the state planes nor one KV "
                                  "head are sharded")
        if bad:
            raise ValueError("JambaForCausalLM does not serve under "
                             + "; ".join(bad))

    def kv_pool_geometry(self, block_size: int) -> dict:
        """What `BlockPool.for_model` builds. An attention layer pools
        pages of keys and values [Hkv, block_size, D] and no state; a
        Mamba layer pools none and holds two float32 state arrays a row
        (and a snapshot of each): the convolution's last inputs
        [(d_conv - 1) d_inner] and the scan's [d_state, d_inner]."""
        c = self.config
        kv = (c.num_kv_heads, block_size, c.head_dim)
        st = (((c.d_conv - 1) * c.d_inner,), (c.d_state, c.d_inner))
        attn = [blk.kind == ATTENTION for blk in self.layers]
        return {"num_layers": c.num_layers,
                "layer_block_shapes": [(kv, kv) if a else () for a in attn],
                "state_shapes": [() if a else st for a in attn],
                "dtype": self.emb._data.dtype}
