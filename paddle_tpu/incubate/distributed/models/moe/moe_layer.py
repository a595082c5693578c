"""Mixture-of-Experts layer with expert parallelism over the `ep` mesh axis.

Reference surface: python/paddle/incubate/distributed/models/moe/moe_layer.py:260
(MoELayer with Naive/GShard/Switch gates, moe/gate/*.py) whose expert-parallel
all-to-all is the global_scatter/global_gather op pair
(paddle/fluid/operators/collective/global_scatter_op.cu).

TPU-native inversion: experts live as STACKED weights [E, ...] annotated
P("ep", ...) — each ep shard owns E/ep experts — and dispatch/combine are
GShard-style one-hot einsums with a static capacity, so the whole layer is
three einsums XLA lowers onto the MXU; the resharding of the dispatched
[E, C, M] tensor across the ep axis IS the all-to-all (XLA inserts it from
the sharding annotations — no bespoke global_scatter kernel). Static capacity
(capacity_factor) replaces the reference's dynamic per-expert buffers because
XLA requires static shapes; overflow tokens are dropped exactly as GShard
does.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .....core.tensor import apply_op
from .....nn.layer import Layer
from .....nn import initializer as I
from .....distributed import mesh as _mesh


def _capacity(num_tokens: int, num_experts: int, top_k: int,
              capacity_factor: float) -> int:
    c = int(math.ceil(capacity_factor * top_k * num_tokens / num_experts))
    return max(4, c + (-c) % 4)   # pad to a multiple of 4 lanes


# ---- drop-rate telemetry (bench/debug) -------------------------------------
# When enabled, each EAGER MoE forward accumulates how many (token, slot)
# assignments overflowed their expert's static capacity — the quantity the
# capacity_factor knob trades against padding compute. Tracer-safe: inside
# jit traces the values are symbolic and recording is skipped, so enable it
# and run one eager forward.
_DROP_REC = {"on": False, "kept": 0, "assigned": 0}


def record_drop_rate(on: bool = True):
    """Toggle (and reset) eager drop-rate accumulation."""
    _DROP_REC.update(on=bool(on), kept=0, assigned=0)


def measured_drop_rate():
    """Fraction of (token, slot) assignments dropped since enabling, or
    None if nothing eager was recorded."""
    a = _DROP_REC["assigned"]
    return None if a == 0 else 1.0 - _DROP_REC["kept"] / a


def _record_keeps(kept, assigned):
    if _DROP_REC["on"] and not isinstance(kept, jax.core.Tracer):
        _DROP_REC["kept"] += int(kept)
        _DROP_REC["assigned"] += int(assigned)


def _topk_dispatch(probs, top_k: int, capacity: int):
    """GShard one-hot dispatch: probs [N, E] -> combine/dispatch [N, E, C].

    Returns (combine weights, boolean dispatch mask, fraction-routed per
    expert from the top-1 slot — the aux-loss ingredient).
    """
    n, e = probs.shape
    gate_vals, idx = lax.top_k(probs, top_k)                  # [N, k]
    if top_k > 1:
        denom = jnp.sum(gate_vals, axis=-1, keepdims=True)
        gate_vals = gate_vals / jnp.maximum(denom, 1e-9)
    # top_k == 1 (Switch): keep the RAW router probability so the output is
    # scaled by it and the router learns from the task loss (renormalizing
    # would make the weight a constant 1 with zero gradient).
    combine = jnp.zeros((n, e, capacity), probs.dtype)
    counts = jnp.zeros((e,), jnp.int32)
    frac_top1 = None
    for slot in range(top_k):
        oh = jax.nn.one_hot(idx[:, slot], e, dtype=jnp.int32)  # [N, E]
        if frac_top1 is None:
            frac_top1 = jnp.mean(oh.astype(probs.dtype), axis=0)
        pos = jnp.cumsum(oh, axis=0) - 1 + counts              # [N, E]
        counts = counts + jnp.sum(oh, axis=0)
        loc = jnp.sum(pos * oh, axis=-1)                       # [N]
        keep = (loc < capacity).astype(probs.dtype)
        loc_oh = jax.nn.one_hot(loc, capacity, dtype=probs.dtype)  # [N, C]
        combine = combine + (gate_vals[:, slot] * keep)[:, None, None] \
            * oh.astype(probs.dtype)[:, :, None] * loc_oh[:, None, :]
    dispatch = combine > 0
    return combine, dispatch, frac_top1


def _topk_routing(probs, top_k: int, capacity: int):
    """Index-form routing: per (token, slot) the expert id, capacity slot,
    and keep flag — same GShard cumsum assignment as _topk_dispatch but
    WITHOUT materializing [N, E, C] one-hot tensors."""
    n, e = probs.shape
    gate_vals, idx = lax.top_k(probs, top_k)                  # [N, k]
    if top_k > 1:
        denom = jnp.sum(gate_vals, axis=-1, keepdims=True)
        gate_vals = gate_vals / jnp.maximum(denom, 1e-9)
    # ONE slot-major pass (r5): flattening [N, k] slot-major makes a single
    # cumsum reproduce the loop's priority order (every slot-0 assignment
    # outranks every slot-1 assignment) with k fewer op chains
    ohf = jax.nn.one_hot(idx.T.reshape(-1), e, dtype=jnp.int32)  # [k·N, E]
    frac_top1 = jnp.mean(ohf[:n].astype(probs.dtype), axis=0)
    pos = jnp.cumsum(ohf, axis=0) - 1
    loc_f = jnp.sum(pos * ohf, axis=-1)                          # [k·N]
    locs = loc_f.reshape(top_k, n).T                             # [N, k]
    keeps = locs < capacity
    return gate_vals, idx, locs, keeps, frac_top1


def _moe_forward(x, gw, w1, b1, w2, b2, *, top_k, capacity_factor, gate_type,
                 activation, ext_logits=None):
    b, s, m = x.shape
    e = w1.shape[0]
    tokens = x.reshape(b * s, m)
    if ext_logits is None:
        logits = jnp.einsum("nm,me->ne", tokens, gw,
                            preferred_element_type=jnp.float32)
    else:
        logits = ext_logits.reshape(b * s, e).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    cap = _capacity(b * s, e, top_k, capacity_factor)
    import os
    n = tokens.shape[0]
    # the index-gather dispatch is the default; =0 takes the one-hot
    # einsum pair (the exact reference the parity tests compare against)
    gather_mode = os.environ.get("PADDLE_TPU_MOE_GATHER", "1") == "1"

    if gather_mode:
        # INDEX dispatch (r4): the one-hot einsum pair costs
        # O(N·E·C·M) MXU FLOPs — at the measured bench shape as much as
        # the experts themselves (66% routing overhead). Scatter each
        # (token, slot) id into its [E·C] slot and GATHER rows instead:
        # O(N·k·M) bytes, zero matmul FLOPs. Dropped tokens (loc >= C)
        # target the sentinel row; empty slots read the appended zero row.
        gate_vals, idx, locs, keeps, frac = _topk_routing(probs, top_k, cap)
        if _DROP_REC["on"]:  # guard BEFORE the reduction: off = zero cost
            _record_keeps(jnp.sum(keeps), keeps.size)
        me = jnp.mean(probs, axis=0)
        aux = e * jnp.sum(me * frac) if gate_type in ("gshard", "switch") \
            else jnp.zeros((), probs.dtype)

        flatpos = idx * cap + locs                             # [N, k]
        safe_pos = jnp.where(keeps, flatpos, e * cap)          # drop slot
        src = jnp.full((e * cap,), n, jnp.int32)
        tok_ids = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None],
                                   (n, top_k))
        src = src.at[safe_pos.reshape(-1)].set(tok_ids.reshape(-1),
                                               mode="drop")
        tokens_ext = jnp.concatenate(
            [tokens, jnp.zeros((1, m), tokens.dtype)], axis=0)
        expert_in = tokens_ext[src].reshape(e, cap, m)
        expert_in = _mesh.shard_constraint(expert_in, "ep", None, None)
        h = activation(jnp.einsum("ecm,emh->ech", expert_in, w1)
                       + b1[:, None, :])
        out = jnp.einsum("ech,ehm->ecm", h, w2) + b2[:, None, :]
        out = _mesh.shard_constraint(out, "ep", None, None)
        out_ext = jnp.concatenate(
            [out.reshape(e * cap, m), jnp.zeros((1, m), out.dtype)], axis=0)
        # ONE batched combine gather (r5): all N·k rows in a single gather
        # + a k-reduction, instead of k sequential gather/axpy chains
        rows = out_ext[safe_pos]                               # [N, k, M]
        w_all = (gate_vals * keeps.astype(probs.dtype)).astype(x.dtype)
        y = jnp.einsum("nk,nkm->nm", w_all, rows)
        return y.reshape(b, s, m), aux.astype(jnp.float32)

    combine, dispatch, frac = _topk_dispatch(probs, top_k, cap)
    if _DROP_REC["on"]:  # guard BEFORE the [N,E,C] reduction
        _record_keeps(jnp.sum(dispatch), n * top_k)

    # load-balance aux loss: GShard/Switch  E * sum_e mean_prob_e * frac_e
    me = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(me * frac) if gate_type in ("gshard", "switch") \
        else jnp.zeros((), probs.dtype)

    # dispatch -> [E, C, M], sharded over ep: XLA inserts the all-to-all here
    expert_in = jnp.einsum("nec,nm->ecm", dispatch.astype(x.dtype), tokens)
    expert_in = _mesh.shard_constraint(expert_in, "ep", None, None)
    h = activation(jnp.einsum("ecm,emh->ech", expert_in, w1) + b1[:, None, :])
    out = jnp.einsum("ech,ehm->ecm", h, w2) + b2[:, None, :]
    out = _mesh.shard_constraint(out, "ep", None, None)
    y = jnp.einsum("nec,ecm->nm", combine.astype(x.dtype), out)
    return y.reshape(b, s, m), aux.astype(jnp.float32)


class MoELayer(Layer):
    """Top-k routed expert FFN (reference: moe_layer.py:260).

    gate: "naive" (top-k, no aux loss), "gshard" (top-2 + load-balance
    loss), or "switch" (top-1 + load-balance loss). The auxiliary loss of
    the latest forward is exposed as `.aux_loss` and should be added to the
    training loss (reference handles this inside its gates the same way).
    """

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 gate: str = "gshard", top_k: Optional[int] = None,
                 capacity_factor: float = 1.25, activation=None,
                 moe_group=None, name=None):
        super().__init__()
        from .gate import BaseGate
        if isinstance(gate, BaseGate):
            if top_k is None:
                top_k = gate.top_k
            gate = gate.gate_type
        if gate not in ("naive", "gshard", "switch"):
            raise ValueError(f"unknown gate {gate!r}")
        self.d_model, self.d_hidden, self.num_experts = d_model, d_hidden, num_experts
        self.gate_type = gate
        self.top_k = top_k if top_k is not None else (1 if gate == "switch" else 2)
        if gate == "switch" and self.top_k != 1:
            raise ValueError("switch gate is top-1 by definition")
        self.capacity_factor = capacity_factor
        self._activation = activation if activation is not None else jax.nn.gelu
        self.aux_loss = None

        self.gate_weight = self.create_parameter(
            [d_model, num_experts], default_initializer=I.XavierUniform())
        self.w1 = self.create_parameter(
            [num_experts, d_model, d_hidden], default_initializer=I.XavierUniform())
        self.b1 = self.create_parameter(
            [num_experts, d_hidden], default_initializer=I.Constant(0.0))
        self.w2 = self.create_parameter(
            [num_experts, d_hidden, d_model], default_initializer=I.XavierUniform())
        self.b2 = self.create_parameter(
            [num_experts, d_model], default_initializer=I.Constant(0.0))
        # expert-parallel shardings (no-ops on meshes without an ep axis)
        self.w1.pspec = P("ep", None, None)
        self.b1.pspec = P("ep", None)
        self.w2.pspec = P("ep", None, None)
        self.b2.pspec = P("ep", None)

    def forward(self, x, gate_logits=None):
        """gate_logits: optional externally computed router logits
        [B, S, E] (FusedEcMoe contract); routes with them instead of the
        internal gate projection."""
        args = [x, self.gate_weight, self.w1, self.b1, self.w2, self.b2]
        if gate_logits is not None:
            args.append(gate_logits)

        def fn(a, gw, w1, b1, w2, b2, *ext):
            return _moe_forward(
                a, gw, w1, b1, w2, b2, top_k=self.top_k,
                capacity_factor=self.capacity_factor,
                gate_type=self.gate_type, activation=self._activation,
                ext_logits=ext[0] if ext else None)

        y, aux = apply_op("moe_layer", fn, args)
        self.aux_loss = aux
        return y
