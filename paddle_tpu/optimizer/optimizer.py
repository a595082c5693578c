"""Optimizer base + SGD/Momentum/Adam/AdamW/...

Reference: python/paddle/optimizer/optimizer.py (Optimizer base),
adam.py/adamw.py/momentum.py etc., lowering to phi kernels (sgd_kernel,
adam_kernel). TPU-native design: every optimizer is a PURE update rule
`(param, grad, state, lr, step) -> (param', state')` over jnp arrays, used

1. eagerly by `.step()` (per-parameter, jit-cached by shape), and
2. functionally by paddle_tpu.jit.TrainStep over whole pytrees — the fused,
   donated, XLA-compiled path where real training runs.

This removes the reference's duality of C++ optimizer kernels vs python
wrappers: one rule, two drivers.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, Parameter
from ..core import autograd
from ..device import on_tpu
from ..ops.pallas import q8_moments as _q8k
from .lr import LRScheduler


def _global_norm_clip(grads, clip_norm):
    total = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in grads))
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(total, 1e-12))
    return [g * scale.astype(g.dtype) for g in grads], total


class Optimizer:
    """Base optimizer (reference: optimizer/optimizer.py Optimizer).

    Subclasses implement `init_state(param) -> dict` and
    `update(param, grad, state, lr, step) -> (param, state)` as pure fns.
    """

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        self._lr = learning_rate
        self._parameters = list(parameters) if parameters is not None else None
        self._weight_decay = 0.0 if weight_decay is None else weight_decay
        self._grad_clip = grad_clip
        self._step_count = 0
        self._states: Dict[int, dict] = {}
        self._accumulated_grads: Dict[int, jnp.ndarray] = {}

    # ------------------------------------------------------------- pure rule
    def init_state(self, param: jnp.ndarray) -> dict:
        return {}

    def state_spec(self, param, key, state_array, base_spec):
        """PartitionSpec for one optimizer-state entry (used by TrainStep's
        sharded placement). Default: param-shaped state follows the param's
        (possibly ZeRO-extended) spec; anything else replicates. Optimizers
        with non-param-shaped state (e.g. blockwise int8 moments) override
        to keep that state sharded."""
        from jax.sharding import PartitionSpec as P
        if tuple(state_array.shape) == tuple(param.shape):
            return base_spec
        return P()

    def update(self, param, grad, state, lr, step):
        raise NotImplementedError

    # ------------------------------------------------------------ eager API
    def get_lr(self) -> float:
        if isinstance(self._lr, LRScheduler):
            return float(self._lr.get_lr())
        return float(self._lr)

    def set_lr(self, value: float):
        if isinstance(self._lr, LRScheduler):
            raise RuntimeError("optimizer's lr is an LRScheduler; call scheduler.step()")
        self._lr = value

    @property
    def _param_list(self):
        if self._parameters is None:
            raise ValueError("Optimizer created without parameters; pass parameters=")
        return self._parameters

    def step(self):
        """Apply one eager update from `.grad` fields (reference:
        Optimizer.step → _apply_optimize)."""
        lr = self.get_lr()
        self._step_count += 1
        params, grads = [], []
        for p in self._param_list:
            if p.grad is None or p.stop_gradient:
                continue
            params.append(p)
            grads.append(p.grad._data)

        wd_applicable = [self._wd_for(p) for p in params]
        if self._grad_clip is not None and grads:
            cls = type(self._grad_clip).__name__
            if cls == "ClipGradByGlobalNorm":
                grads, _ = _global_norm_clip(grads, self._grad_clip.clip_norm)
            elif cls == "ClipGradByNorm":
                cn = self._grad_clip.clip_norm
                grads = [g * jnp.minimum(1.0, cn / jnp.maximum(
                    jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32)))), 1e-12)).astype(g.dtype)
                    for g in grads]
            elif cls == "ClipGradByValue":
                grads = [jnp.clip(g, self._grad_clip.min, self._grad_clip.max) for g in grads]

        for p, g, wd in zip(params, grads, wd_applicable):
            st = self._states.get(id(p))
            if st is None:
                try:
                    st = self.init_state(p._data, param_obj=p)
                except TypeError:
                    st = self.init_state(p._data)
                self._states[id(p)] = st
            new_p, new_st = self._jit_update(wd)(p._data, g, st, jnp.float32(lr),
                                                 jnp.int32(self._step_count))
            p._data = new_p
            p._node = None
            self._states[id(p)] = new_st

    def _wd_for(self, p) -> float:
        return float(self._weight_decay) if self._weight_decay else 0.0

    def _jit_update(self, wd):
        key = ("u", wd)
        cache = self.__dict__.setdefault("_jit_cache", {})
        if key not in cache:
            def fn(param, grad, state, lr, step, _wd=wd):
                return self.update(param, grad, state, lr, step, _wd)
            cache[key] = jax.jit(fn)
        return cache[key]

    def clear_grad(self, set_to_zero: bool = False):
        for p in self._param_list:
            p.clear_gradient(set_to_zero)

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        from ..static.program import Variable as _StaticVar
        if isinstance(loss, _StaticVar):
            # static-graph path (reference: Optimizer.minimize appends
            # backward + update ops to the program; here the Executor fuses
            # grads + this optimizer's pure `update` rule into the jitted
            # replay — see static/executor.py)
            from ..static.program import append_backward, default_main_program
            pairs = append_backward(loss, parameter_list=parameters)
            prog = loss.program or default_main_program()
            prog._optimizer = self
            return None, pairs
        loss.backward()
        self.step()
        self.clear_grad()

    # --------------------------------------------------------- state_dict
    def state_dict(self) -> dict:
        out = {"master_step": self._step_count}
        if isinstance(self._lr, LRScheduler):
            out["LR_Scheduler"] = self._lr.state_dict()
        for i, p in enumerate(self._param_list):
            st = self._states.get(id(p))
            if st:
                for k, v in st.items():
                    out[f"{p.name or f'param_{i}'}__{k}"] = Tensor(v)
        return out

    def set_state_dict(self, state_dict: dict):
        self._step_count = int(state_dict.get("master_step", 0))
        if isinstance(self._lr, LRScheduler) and "LR_Scheduler" in state_dict:
            self._lr.set_state_dict(state_dict["LR_Scheduler"])
        for i, p in enumerate(self._param_list):
            prefix = f"{p.name or f'param_{i}'}__"
            st = {}
            for k, v in state_dict.items():
                if isinstance(k, str) and k.startswith(prefix):
                    st[k[len(prefix):]] = v._data if isinstance(v, Tensor) else jnp.asarray(v)
            if st:
                self._states[id(p)] = st

    # lr scheduler hookup
    @property
    def _learning_rate(self):
        return self._lr


class SGD(Optimizer):
    """Reference: optimizer/sgd.py → phi sgd kernel."""

    _fusable_elementwise = True
    _fused_state_keys = ()

    def update(self, param, grad, state, lr, step, wd=0.0):
        g = grad.astype(jnp.float32)
        if isinstance(wd, jnp.ndarray) or wd:
            g = g + wd * param.astype(jnp.float32)
        return (param - lr * g.astype(param.dtype)).astype(param.dtype), state


class Momentum(Optimizer):
    """Reference: optimizer/momentum.py (use_nesterov supported)."""

    # elementwise math — safe for the fused multi-tensor apply; the win is
    # biggest here: conv nets have hundreds of tiny BN scale/bias params
    # (r3 ResNet-50 profile: 628 per-weight update fusions, 5.8 ms of a
    # 38 ms step)
    _fusable_elementwise = True
    _fused_state_keys = ("velocity",)

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def init_state(self, param):
        return {"velocity": jnp.zeros_like(param, dtype=jnp.float32)}

    def update(self, param, grad, state, lr, step, wd=0.0):
        g = grad.astype(jnp.float32)
        # wd may be a per-element vector under the fused multi-tensor apply
        if isinstance(wd, jnp.ndarray) or wd:
            g = g + wd * param.astype(jnp.float32)
        v = self._momentum * state["velocity"] + g
        if self._nesterov:
            upd = g + self._momentum * v
        else:
            upd = v
        new_p = (param.astype(jnp.float32) - lr * upd).astype(param.dtype)
        return new_p, {"velocity": v}


# 8-bit moments (Adam / AdamW with moment_dtype="int8" or q8_param_fun).
# The stored state is fixed: codes [nb, _Q_BLOCK] and one float32 scale a
# block, [nb], blocks cut from the moment flattened in row-major order —
# checkpoints and the benchmark (benchmarks/reference.py `Q8`) read it so.
# Each moment is coded through its root r (signed sqrt for the first, sqrt
# for the second), linearly per block over the block's absmax. Two forms
# store the same bits:
#   * the Pallas update (ops/pallas/q8_moments.py `pallas_q8_adamw`) reads
#     the gradient, the parameter and the codes of a leaf in the
#     parameter's own [R, C] tiling, cuts blocks in VMEM and writes the new
#     parameter and codes, one pass — taken on the chip for a 2-D leaf
#     whose shape `row_tile` accepts, outside a mesh that splits anything
#     (`q8_codec`);
#   * the flat XLA form below decodes both roots as [nb, _Q_BLOCK], updates
#     them in XLA's fusion and encodes them again: on the chip that view
#     is a float32 relayout copy of the whole moment each way, kept for
#     1-D leaves, sizes that need padding, and sharded meshes.
_Q_BLOCK = _q8k.BLOCK


def q8_codec(shape) -> str:
    """"kernel" or "flat": which form updates a leaf of this shape with
    8-bit moments, under the mesh active now (TrainStep traces its update
    under its own)."""
    from ..distributed import mesh as _dmesh
    m = _dmesh.get_mesh()
    if not on_tpu() or (m is not None and m.size > 1):
        return "flat"
    return "kernel" if _q8k.row_tile(shape) is not None else "flat"


def _q8_block(r, second):
    """Flat form: codes [nb, B] and scales [nb] of a root. x / 127 is
    written as XLA compiles it on the chip (times the float32 reciprocal),
    so the kernel and every backend agree to the bit."""
    n = r.size
    nb = -(-n // _Q_BLOCK)
    flat = jnp.pad(r.reshape(-1), (0, nb * _Q_BLOCK - n))
    blocks = flat.reshape(nb, _Q_BLOCK)
    amax = jnp.max(blocks if second else jnp.abs(blocks), axis=1)
    scale = amax * jnp.float32(1 / (255 if second else 127))
    q = jnp.round(blocks / jnp.maximum(scale, 1e-30)[:, None])
    return (q.astype(jnp.uint8 if second else jnp.int8),
            scale.astype(jnp.float32))


def _q8_unblock(q, scale, shape, second):
    """Flat form: the root of shape `shape` that codes and scales hold."""
    q = q.astype(jnp.float32)
    r = ((q + 0.5) if second else q) * scale[:, None]
    n = 1
    for s in shape:
        n *= s
    return r.reshape(-1)[:n].reshape(shape)


def _q8_encode(x32):
    """Blockwise SIGNED-SQRT int8 quantization (FIRST moment): code the
    sign-preserving sqrt, r = sign(x)*sqrt(|x|), linearly per block. Plain
    linear coding freezes any coordinate whose |m| stays ~254x below the
    block absmax (rounds to 0 forever); sqrt compression moves that
    underflow floor to ~max/64516, the same treatment the second moment
    gets. Returns (int8 codes [nb, B], f32 scales [nb] in the r domain)."""
    return _q8_block(jnp.sign(x32) * jnp.sqrt(jnp.abs(x32)), False)


def _q8_decode(q, scale, shape):
    r = _q8_unblock(q, scale, shape, False)
    return jnp.sign(r) * jnp.square(r)


def _q8v_encode(v32):
    """SECOND-moment quantization: store sqrt(v) as uint8 per-block. Linear
    int8 on v itself underflows small entries to 0 → 1/(sqrt(0)+eps) blows
    the update up; sqrt halves the dynamic range and the +0.5-step decode
    bias below acts as a per-block adaptive epsilon."""
    return _q8_block(jnp.sqrt(jnp.maximum(v32, 0.0)), True)


def _q8v_decode(q, scale, shape):
    return jnp.square(_q8_unblock(q, scale, shape, True))


class Adam(Optimizer):
    """Reference: optimizer/adam.py → phi adam kernel (bias-corrected).

    `moment_dtype` ("float32" default) stores m/v in a narrower dtype —
    the dominant fixed HBM cost of large-model single-chip training is
    8 bytes/param of f32 moments:
      * "bfloat16": 4 bytes/param — f32-range exponent keeps v's dynamic
        range, only mantissa precision drops;
      * "int8": ~2 bytes/param — blockwise (2048) absmax-scaled symmetric
        quantization (the bitsandbytes-style 8-bit Adam); what fits
        GPT-2.7B + Adam on one 16G chip.
    The update itself always computes in f32."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None, lazy_mode=False,
                 multi_precision=True, moment_dtype="float32",
                 q8_param_fun=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._q8 = str(moment_dtype) in ("int8", "uint8")
        # q8_param_fun(name) -> bool: blockwise-int8 moments for SELECTED
        # params (embedding tables are the usual target: wte+wpe moments are
        # ~8% of a 1.3B model's optimizer HBM — the margin that fits the
        # S=8192 config) while the rest keeps moment_dtype. Mirrors
        # apply_decay_param_fun's shape.
        self._q8_param_fun = q8_param_fun
        self._moment_dtype = (jnp.dtype(jnp.int8) if self._q8
                              else jnp.dtype(moment_dtype))

    def init_state(self, param, param_obj=None, name=None):
        name = name or getattr(param_obj, "name", None)
        use_q8 = self._q8 or (self._q8_param_fun is not None and name
                              and self._q8_param_fun(name))
        if use_q8:
            # what either codec stores for a zero moment: zero codes and
            # scales (made directly: a kernel call a leaf at set-up cost
            # cell 3 tens of seconds of compiles)
            nb = -(-math.prod(param.shape) // _Q_BLOCK)
            return {"moment1_q": jnp.zeros((nb, _Q_BLOCK), jnp.int8),
                    "moment1_s": jnp.zeros((nb,), jnp.float32),
                    "moment2_q": jnp.zeros((nb, _Q_BLOCK), jnp.uint8),
                    "moment2_s": jnp.zeros((nb,), jnp.float32)}
        return {"moment1": jnp.zeros_like(param, dtype=self._moment_dtype),
                "moment2": jnp.zeros_like(param, dtype=self._moment_dtype)}

    def _moments(self, state, grad32, b1, b2):
        if "moment1_q" in state:
            shape = grad32.shape
            m0 = _q8_decode(state["moment1_q"], state["moment1_s"], shape)
            v0 = _q8v_decode(state["moment2_q"], state["moment2_s"], shape)
        else:
            m0 = state["moment1"].astype(jnp.float32)
            v0 = state["moment2"].astype(jnp.float32)
        m = b1 * m0 + (1 - b1) * grad32
        v = b2 * v0 + (1 - b2) * grad32 * grad32
        return m, v

    def state_spec(self, param, key, state_array, base_spec):
        from jax.sharding import PartitionSpec as P
        if key.endswith(("_q", "_s")) and key.startswith("moment"):
            # codes [nb, BLOCK] / scales [nb]: shard the block dim over the
            # first axis the param's spec uses — the dominant 8-bit state
            # stays distributed (ZeRO axis included via base_spec). jax
            # requires the dim divisible by the axis size; replicate the
            # (small) remainder cases rather than fail.
            from ..distributed import mesh as _dmesh
            axes = [a for a in (base_spec or ()) if a is not None]
            for first in axes:
                names = (first,) if isinstance(first, str) else tuple(first)
                size = 1
                for nm in names:
                    size *= max(1, _dmesh.mesh_axis_size(nm))
                if size > 1 and state_array.shape[0] % size == 0:
                    return P(first) if state_array.ndim == 1 \
                        else P(first, None)
            return P()
        return super().state_spec(param, key, state_array, base_spec)

    def _pack_moments(self, m, v, q8=None):
        if (q8 if q8 is not None else self._q8):
            mq, ms = _q8_encode(m)
            vq, vs = _q8v_encode(v)
            return {"moment1_q": mq, "moment1_s": ms,
                    "moment2_q": vq, "moment2_s": vs}
        md = self._moment_dtype
        return {"moment1": m.astype(md), "moment2": v.astype(md)}

    # elementwise update math: concatenating params changes nothing, so the
    # fused multi-tensor apply in TrainStep may group small params into one
    # flat update (reference analog: distributed_fused_lamb.py:82's
    # flattened apply; LAMB itself is NOT elementwise — per-tensor trust
    # ratios — which is why only elementwise optimizers carry this flag)
    _fusable_elementwise = True
    _fused_state_keys = ("moment1", "moment2")

    def _q8_kernel(self, param, grad, state, lr, step, wd, decoupled):
        """The update of a leaf whose 8-bit moments take the kernel form
        (`q8_codec`), or None: one Pallas pass from the raw gradient and
        the codes to the new parameter and codes, the flat form's
        arithmetic op for op."""
        if "moment1_q" not in state or q8_codec(param.shape) != "kernel":
            return None
        b1, b2 = self._beta1, self._beta2
        t = step.astype(jnp.float32)
        hyper = jnp.stack([jnp.asarray(x, jnp.float32) for x in (
            lr, (1 - lr * wd) if decoupled else wd,
            1 - jnp.power(b1, t), 1 - jnp.power(b2, t))])
        new_p, mq, ms, vq, vs = _q8k.adamw(
            grad, param, state["moment1_q"], state["moment1_s"],
            state["moment2_q"], state["moment2_s"], hyper, b1=b1, b2=b2,
            eps=self._eps, decoupled=decoupled,
            l2=not decoupled and bool(isinstance(wd, jnp.ndarray) or wd))
        return new_p, {"moment1_q": mq, "moment1_s": ms,
                       "moment2_q": vq, "moment2_s": vs}

    def update(self, param, grad, state, lr, step, wd=0.0):
        out = self._q8_kernel(param, grad, state, lr, step, wd, False)
        if out is not None:
            return out
        b1, b2, eps = self._beta1, self._beta2, self._eps
        g = grad.astype(jnp.float32)
        p32 = param.astype(jnp.float32)
        # L2-regularization semantics (coupled), like reference Adam+L2Decay;
        # wd may be a per-element vector under the fused multi-tensor apply
        if isinstance(wd, jnp.ndarray) or wd:
            g = g + wd * p32
        m, v = self._moments(state, g, b1, b2)
        t = step.astype(jnp.float32)
        m_hat = m / (1 - jnp.power(b1, t))
        v_hat = v / (1 - jnp.power(b2, t))
        new_p = p32 - lr * m_hat / (jnp.sqrt(v_hat) + eps)
        return new_p.astype(param.dtype), self._pack_moments(
            m, v, q8="moment1_q" in state)


class AdamW(Adam):
    """Reference: optimizer/adamw.py — decoupled weight decay, with
    apply_decay_param_fun to exempt bias/norm params."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=0.01, lr_ratio=None,
                 apply_decay_param_fun=None, grad_clip=None, multi_precision=True,
                 moment_dtype="float32", q8_param_fun=None, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, moment_dtype=moment_dtype,
                         q8_param_fun=q8_param_fun, name=name)
        self._wd_coeff = weight_decay
        self._apply_decay_param_fun = apply_decay_param_fun

    def _wd_for(self, p):
        if self._apply_decay_param_fun is not None and not self._apply_decay_param_fun(p.name):
            return 0.0
        return float(self._wd_coeff)

    def update(self, param, grad, state, lr, step, wd=0.0):
        out = self._q8_kernel(param, grad, state, lr, step, wd, True)
        if out is not None:
            return out
        b1, b2, eps = self._beta1, self._beta2, self._eps
        g = grad.astype(jnp.float32)
        p32 = param.astype(jnp.float32)
        m, v = self._moments(state, g, b1, b2)
        t = step.astype(jnp.float32)
        m_hat = m / (1 - jnp.power(b1, t))
        v_hat = v / (1 - jnp.power(b2, t))
        p32 = p32 * (1 - lr * wd)  # decoupled decay
        new_p = p32 - lr * m_hat / (jnp.sqrt(v_hat) + eps)
        return new_p.astype(param.dtype), self._pack_moments(
            m, v, q8="moment1_q" in state)


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def init_state(self, param):
        return {"moment": jnp.zeros_like(param, dtype=jnp.float32),
                "inf_norm": jnp.zeros_like(param, dtype=jnp.float32)}

    def update(self, param, grad, state, lr, step, wd=0.0):
        b1, b2, eps = self._beta1, self._beta2, self._eps
        g = grad.astype(jnp.float32)
        if wd:
            g = g + wd * param.astype(jnp.float32)
        m = b1 * state["moment"] + (1 - b1) * g
        u = jnp.maximum(b2 * state["inf_norm"], jnp.abs(g))
        t = step.astype(jnp.float32)
        new_p = param.astype(jnp.float32) - (lr / (1 - jnp.power(b1, t))) * m / (u + eps)
        return new_p.astype(param.dtype), {"moment": m, "inf_norm": u}


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._eps = epsilon
        self._init_acc = initial_accumulator_value

    def init_state(self, param):
        return {"moment": jnp.full_like(param, self._init_acc, dtype=jnp.float32)}

    def update(self, param, grad, state, lr, step, wd=0.0):
        g = grad.astype(jnp.float32)
        if wd:
            g = g + wd * param.astype(jnp.float32)
        acc = state["moment"] + g * g
        new_p = param.astype(jnp.float32) - lr * g / (jnp.sqrt(acc) + self._eps)
        return new_p.astype(param.dtype), {"moment": acc}


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95, parameters=None,
                 weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._eps, self._rho = epsilon, rho

    def init_state(self, param):
        return {"avg_squared_grad": jnp.zeros_like(param, dtype=jnp.float32),
                "avg_squared_update": jnp.zeros_like(param, dtype=jnp.float32)}

    def update(self, param, grad, state, lr, step, wd=0.0):
        g = grad.astype(jnp.float32)
        if wd:
            g = g + wd * param.astype(jnp.float32)
        e_g = self._rho * state["avg_squared_grad"] + (1 - self._rho) * g * g
        upd = g * jnp.sqrt(state["avg_squared_update"] + self._eps) / jnp.sqrt(e_g + self._eps)
        e_u = self._rho * state["avg_squared_update"] + (1 - self._rho) * upd * upd
        new_p = param.astype(jnp.float32) - lr * upd
        return new_p.astype(param.dtype), {"avg_squared_grad": e_g, "avg_squared_update": e_u}


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._rho, self._eps, self._momentum, self._centered = rho, epsilon, momentum, centered

    def init_state(self, param):
        st = {"mean_square": jnp.zeros_like(param, dtype=jnp.float32),
              "momentum": jnp.zeros_like(param, dtype=jnp.float32)}
        if self._centered:
            st["mean_grad"] = jnp.zeros_like(param, dtype=jnp.float32)
        return st

    def update(self, param, grad, state, lr, step, wd=0.0):
        g = grad.astype(jnp.float32)
        if wd:
            g = g + wd * param.astype(jnp.float32)
        ms = self._rho * state["mean_square"] + (1 - self._rho) * g * g
        new_state = {"mean_square": ms}
        if self._centered:
            mg = self._rho * state["mean_grad"] + (1 - self._rho) * g
            denom = jnp.sqrt(ms - mg * mg + self._eps)
            new_state["mean_grad"] = mg
        else:
            denom = jnp.sqrt(ms + self._eps)
        mom = self._momentum * state["momentum"] + lr * g / denom
        new_state["momentum"] = mom
        new_p = param.astype(jnp.float32) - mom
        return new_p.astype(param.dtype), new_state


class Lamb(Optimizer):
    """Reference: optimizer/lamb.py — layerwise adaptive large-batch opt."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, name=None):
        super().__init__(learning_rate, parameters, lamb_weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def _wd_for(self, p):
        if self._exclude_fn is not None and self._exclude_fn(p):
            return 0.0
        return float(self._weight_decay)

    def init_state(self, param):
        return {"moment1": jnp.zeros_like(param, dtype=jnp.float32),
                "moment2": jnp.zeros_like(param, dtype=jnp.float32)}

    def update(self, param, grad, state, lr, step, wd=0.0):
        b1, b2, eps = self._beta1, self._beta2, self._eps
        g = grad.astype(jnp.float32)
        p32 = param.astype(jnp.float32)
        m = b1 * state["moment1"] + (1 - b1) * g
        v = b2 * state["moment2"] + (1 - b2) * g * g
        t = step.astype(jnp.float32)
        m_hat = m / (1 - jnp.power(b1, t))
        v_hat = v / (1 - jnp.power(b2, t))
        r = m_hat / (jnp.sqrt(v_hat) + eps) + wd * p32
        w_norm = jnp.linalg.norm(p32)
        r_norm = jnp.linalg.norm(r)
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        new_p = p32 - lr * trust * r
        return new_p.astype(param.dtype), {"moment1": m, "moment2": v}


class LarsMomentum(Momentum):
    """Layer-wise Adaptive Rate Scaling (reference:
    fluid/optimizer.py LarsMomentumOptimizer + the fleet `lars` meta
    optimizer, meta_optimizers/lars_optimizer.py): the effective lr per
    parameter is scaled by ||w|| / (||g|| + wd*||w||), which keeps the
    update/weight ratio uniform across layers for very large batches."""

    def __init__(self, learning_rate=0.001, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, parameters=None, grad_clip=None,
                 epsilon=1e-9, name=None):
        super().__init__(learning_rate, momentum, parameters,
                         weight_decay=lars_weight_decay, grad_clip=grad_clip,
                         name=name)
        self._lars_coeff = lars_coeff
        self._eps = epsilon

    def update(self, param, grad, state, lr, step, wd=0.0):
        g = grad.astype(jnp.float32)
        w = param.astype(jnp.float32)
        w_norm = jnp.sqrt(jnp.sum(jnp.square(w)))
        g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
        local_lr = jnp.where(
            (w_norm > 0) & (g_norm > 0),
            self._lars_coeff * w_norm / (g_norm + wd * w_norm + self._eps),
            1.0)
        v = state["velocity"]
        v = self._momentum * v + lr * local_lr * (g + wd * w)
        return (w - v).astype(param.dtype), {"velocity": v}


class DGCMomentum(Momentum):
    """Deep Gradient Compression momentum (reference: the DGC op +
    DGCMomentumOptimizer, meta_optimizers/dgc_optimizer.py): momentum
    correction + error feedback, with only the top-`rampup` fraction of
    gradient magnitudes applied per step.

    On TPU the *communication* motivation disappears — XLA collectives over
    ICI are not the bottleneck NCCL rings were — so this is semantic parity:
    the same sparsified-update training dynamics (useful over DCN-separated
    slices), implemented densely with a per-step magnitude threshold (exact
    top-k is a sort per tensor; the quantile approximation keeps the update
    one fused XLA program)."""

    def __init__(self, learning_rate=0.001, momentum=0.9,
                 sparsity=0.999, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, momentum, parameters,
                         weight_decay=weight_decay, grad_clip=grad_clip,
                         name=name)
        self._sparsity = float(sparsity)

    def init_state(self, param):
        return {"velocity": jnp.zeros_like(param, dtype=jnp.float32),
                "error": jnp.zeros_like(param, dtype=jnp.float32)}

    def update(self, param, grad, state, lr, step, wd=0.0):
        g = grad.astype(jnp.float32)
        w = param.astype(jnp.float32)
        if wd:
            g = g + wd * w
        # momentum correction: accumulate velocity then add error feedback
        u = self._momentum * state["velocity"] + g
        acc = state["error"] + u
        if acc.size > 1:
            thresh = jnp.quantile(jnp.abs(acc).reshape(-1), self._sparsity)
            mask = (jnp.abs(acc) >= thresh).astype(jnp.float32)
        else:
            mask = jnp.ones_like(acc)
        comm = acc * mask          # the "transmitted" sparse update
        err = acc * (1.0 - mask)   # error feedback kept locally
        return (w - lr * comm).astype(param.dtype), \
            {"velocity": u, "error": err}
