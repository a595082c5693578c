"""What the decoders written for the paged serving engine share
(models/pangu_moe.py, models/minicpm_sala.py, models/jamba.py): the float32
RMSNorm, the product in the parameters' dtype, rotary positions with
dimension i paired with i + d/2, the SiLU-gated MLP's parameters, and, for
the two whose layers keep a recurrent state beside the pages
(`PagedStateDecoder`), what `inference.ServingEngine` calls: the prefill
window and the decode chunk over planes of both kinds, the per-call
counters, the refusals."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.tensor import Tensor
from ..jit.api import DECODE_PROGRAM, PREFILL_PROGRAM, named_program
from ..nn.layer import Layer
from .gpt import GPTForCausalLM, sample_logits


def _arr(a, dtype=None):
    """A Tensor's or an array-like's array, in `dtype` if given."""
    return jnp.asarray(a._data if isinstance(a, Tensor) else a, dtype)


def _rms(x, g, eps):
    """RMSNorm in float32, float32 out."""
    x = x.astype(jnp.float32)
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y * g.astype(jnp.float32)


def _mm(a, w):
    """a @ w with a rounded to the weights' dtype, float32 out: the
    residual stream, the norms and the router's input stay float32, every
    product runs in the parameters' dtype."""
    return jnp.matmul(a.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def _rope(x, pos, theta):
    """x [B, S, ..., d] rotated by pos [B, S]; dimension i pairs with
    i + d/2."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freq
    ang = ang.reshape(pos.shape + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


class GatedMLP(Layer):
    def __init__(self, hidden_size, width, init, dtype):
        super().__init__()
        mk = lambda *s: self.create_parameter(  # noqa: E731
            list(s), dtype=dtype, default_initializer=init)
        self.w_gate, self.w_up = mk(hidden_size, width), mk(hidden_size, width)
        self.w_down = mk(width, hidden_size)


class StepCall:
    """What one traced call (a prefill window or a decode step) hands its
    layers: where the tokens sit, the planes of each layer as they are
    consumed and replaced, and the counters (`names`)."""

    def __init__(self, names, pools, tables, pos, lens, live, slots, width):
        self.pools = list(pools)
        self.tables, self.pos, self.lens = tables, pos, lens
        self.live, self.slots, self.width = live, slots, width
        self.names = names
        self.stats = dict.fromkeys(names, jnp.float32(0))
        self.chosen = []

    def count(self, name, x):
        self.stats[name] = self.stats[name] + jnp.sum(x).astype(jnp.float32)

    def stats_array(self):
        return jnp.stack([self.stats[k] for k in self.names])


def _shapes(pools) -> tuple:
    """The planes' shapes and dtypes, for an executable's signature."""
    return tuple(tuple((tuple(a.shape), str(a.dtype)) for a in layer)
                 for layer in pools)


class PagedStateDecoder(Layer):
    """The serving entry points of a decoder whose layers pool pages, a
    recurrent state a row, or both (`kv_pool_geometry`: `layer_block_
    shapes` and `state_shapes`). The model gives `STATS` (its per-call
    device counters), `_stream(p, ids, call)` (the final residual stream of
    the call's tokens, replacing `call.pools` layer by layer) and
    `_logits(p, x)`; it calls `_init_serving()` once its parameters exist.
    One executable serves every prefill window (offset and length are
    data), one the decode chunk."""

    STATS: tuple = ()
    _gen_cache_get = GPTForCausalLM._gen_cache_get

    def _init_serving(self):
        self._names = [n for n, _ in self.named_parameters()]
        self._stats = np.zeros((len(self.STATS),), np.float32)

    def _tree(self, arrays):
        return dict(zip(self._names, arrays))

    @property
    def step_counter_names(self):
        return self.STATS

    def detach_step_counters(self):
        """The counters of the prefill and decode calls made since the
        last detach, as the device array the last of them returned."""
        stats, self._stats = self._stats, \
            np.zeros((len(self.STATS),), np.float32)
        return stats

    def _refusals(self, cfg, shards_why: str) -> list:
        """What no such decoder serves under, each with its reason."""
        return [why for cond, why in (
            (cfg.spec_decode, "spec_decode=True (no verify_paged; a "
                              "rejected draft would have to roll the "
                              "recurrent state back)"),
            ((cfg.shards or 1) > 1, f"shards > 1 ({shards_why})"),
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
            call = StepCall(self.STATS, pools, tables, pos, lens, live,
                            slots, p_cap)
            x = self._stream(p, ids, call)
            last = self._logits(p, x[jnp.arange(b), lens - 1])
            nxt = sample_logits(last, key, temperature=temperature,
                                top_k=top_k, top_p=top_p).astype(jnp.int32)
            return call.pools, nxt, stats + call.stats_array()

        sig = (type(self).__name__ + ".prefill", b, p_cap, _shapes(pools),
               int(tables.shape[1]), float(temperature), int(top_k),
               float(top_p))
        fn = self._gen_cache_get(sig, lambda: named_program(
            run, PREFILL_PROGRAM, donate_argnums=(1,)))
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
        done'). A done row moves no state."""
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
                call = StepCall(self.STATS, pools, tables, ln[:, None], None,
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

        sig = (type(self).__name__ + ".decode", tables.shape, _shapes(pools),
               int(max_new_tokens), float(temperature), int(top_k),
               float(top_p),
               None if eos_token_id is None else int(eos_token_id))
        fn = self._gen_cache_get(sig, lambda: named_program(
            run, DECODE_PROGRAM, donate_argnums=(1,)))
        toks, pools2, lens2, done2, self._stats = fn(
            tuple(q._data for q in self.parameters()), pools, tables,
            lens_a, pend, done_a, jax.random.PRNGKey(seed), self._stats)
        return Tensor(toks), pools2, lens2, done2
