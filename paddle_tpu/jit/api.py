"""@to_static — trace-and-compile (reference: python/paddle/jit/api.py:222,
dy2static/program_translator.py:283 StaticFunction + ProgramCache).

The reference rewrites Python AST into a static Program executed by
InterpreterCore (run_program op). TPU-native: jax.jit IS the tracer/compiler —
we functionalize a Layer by swapping its Parameters' storage for tracers,
trace the Python forward once per input signature (cache keyed like
CacheKey: shapes/dtypes/training flag), and register the whole compiled
function as ONE tape op so eager `.backward()` differentiates through it
(jax.vjp of a jitted function stays compiled).
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import Any, Callable, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, Parameter, apply_op
from ..core import random as _random
from ..core import autograd
from ..core.dtype import convert_dtype

_trace_state = threading.local()

# process-wide compile-cache miss counter (StaticFunction + TrainStep feed
# it; profiler.StepMonitor reads the per-step delta)
_compile_cache_misses = [0]

# analysis.lint_capture sink: while set (a list), serving executables
# fetched through the models' compiled-runner caches are wrapped so each
# call records (kind, jitted_fn, abstract args) for GraphLint.check_calls
_lint_capture_sink = None


def _maybe_wrap_lint_capture(fn, kind):
    """Identity unless a lint_capture() context is active."""
    sink = _lint_capture_sink
    if sink is None:
        return fn

    def wrapper(*args, **kwargs):
        from ..analysis.lint import _capture_record
        _capture_record(sink, kind, fn, args, kwargs)
        return fn(*args, **kwargs)
    return wrapper


# ---------------------------------------------------------------- programs
# One name per program the package hands to the chip, as `ops/pallas/*`
# `*_NAME` does for kernels: a device trace's `XLA Modules` line holds one
# event per program run, named `jit_<name>(<hash>)`, and the hash changes
# with every compile. A name says the program's ROLE, not the model, so
# one reader serves every served family (PERF.md section 3 says which
# metric reads which; tests/test_chip_compile.py holds the table against
# the lowered modules).
PREFILL_PROGRAM = "serve_prefill"        # prefill_paged: one window
DECODE_PROGRAM = "serve_decode"          # decode_paged: one chunk
VERIFY_PROGRAM = "serve_verify"          # verify_paged: one draft window
STAGE_PROGRAM = "serve_stage"            # the engine picks pending / done
PUT_FIRST_PROGRAM = "serve_put_first"    # a final window's first token
PAGE_COPY_PROGRAM = "serve_page_copy"    # copy-on-write of one page
STATE_MOVE_PROGRAM = "serve_state_move"  # zero / save / load a state row
SPILL_PROGRAM = "serve_spill"            # a spilled page written back
GENERATE_PROGRAM = "generate_static"     # generate_static(_ragged)
EXPERT_CHOICES_PROGRAM = "expert_choices"    # diagnostics, off the
SELECTED_BLOCKS_PROGRAM = "selected_blocks"  # serving path
TRAIN_PROGRAM = "pure_step"              # TrainStep: one optimizer step
TRAIN_SCAN_PROGRAM = "pure_steps"        # TrainStep.run_steps: n in one
GRAD_PROBE_PROGRAM = "loss_and_grad_norm"

PROGRAM_NAMES = tuple(v for k, v in sorted(globals().items())
                      if k.endswith("_PROGRAM"))


def named_program(fn, name: str, **jit_kwargs):
    """`jax.jit(fn, **jit_kwargs)` whose module, and so its event on a
    device trace's `XLA Modules` line, is called `jit_<name>`. The name is
    set once, on a wrapper (a module-level `fn` keeps its own); a call
    costs what a bare `jax.jit` call costs."""
    if name not in PROGRAM_NAMES:
        raise ValueError(f"{name!r} is no program of the table "
                         f"{PROGRAM_NAMES}")

    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = name
    return jax.jit(program, **jit_kwargs)


def compile_cache_misses() -> int:
    """Total jit compile-cache misses (new trace signatures) this process."""
    return _compile_cache_misses[0]


def _note_cache_miss():
    _compile_cache_misses[0] += 1


def _in_jit_trace() -> bool:
    return getattr(_trace_state, "depth", 0) > 0


@contextlib.contextmanager
def _trace_guard():
    _trace_state.depth = getattr(_trace_state, "depth", 0) + 1
    try:
        yield
    finally:
        _trace_state.depth -= 1


class InputSpec:
    """Reference: paddle.static.InputSpec (static/input.py)."""

    def __init__(self, shape, dtype="float32", name=None):
        self.shape = tuple(shape)
        self.dtype = convert_dtype(dtype)
        self.name = name

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype}, name={self.name})"


@contextlib.contextmanager
def _swap_params(params: List[Tensor], arrays):
    """Temporarily rebind Tensor storage to (traced) arrays."""
    saved = [p._data for p in params]
    saved_nodes = [p._node for p in params]
    for p, a in zip(params, arrays):
        p._data = a
        p._node = None
    try:
        yield
    finally:
        for p, s, n in zip(params, saved, saved_nodes):
            p._data = s
            p._node = n


def _tree_unwrap(obj):
    if isinstance(obj, Tensor):
        return obj._data
    if isinstance(obj, (list, tuple)):
        return type(obj)(_tree_unwrap(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _tree_unwrap(v) for k, v in obj.items()}
    return obj


def _tree_wrap(obj):
    if isinstance(obj, jax.Array):
        return Tensor(obj)
    if isinstance(obj, (list, tuple)):
        return type(obj)(_tree_wrap(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _tree_wrap(v) for k, v in obj.items()}
    return obj


def _collect_layers(fn):
    """Find Layer instances reachable from fn (bound self or closure)."""
    from ..nn.layer import Layer
    layers = []
    self_obj = getattr(fn, "__self__", None)
    if isinstance(self_obj, Layer):
        layers.append(self_obj)
    closure = getattr(fn, "__closure__", None)
    if closure:
        for cell in closure:
            try:
                v = cell.cell_contents
            except ValueError:
                continue
            if isinstance(v, Layer):
                layers.append(v)
    return layers


class StaticFunction:
    def __init__(self, function, input_spec=None, layer=None, **kwargs):
        # dy2static: rewrite data-dependent Python if/while into
        # lax.cond/while_loop convert_* calls (jit/dy2static.py). Falls back
        # to the original function when source is unavailable.
        from .dy2static import ast_transform
        self._original_fn = function
        self._fn = ast_transform(function)
        self._input_spec = input_spec
        self._layer = layer
        self._cache = {}
        self.__name__ = getattr(function, "__name__", "static_fn")

    @property
    def _layers(self):
        if self._layer is not None:
            return [self._layer]
        return _collect_layers(self._fn)

    def _params_and_buffers(self):
        params, buffers = [], []
        for layer in self._layers:
            for _, p in layer.named_parameters():
                params.append(p)
            for _, b in layer.named_buffers():
                buffers.append(b)
        return params, buffers

    def __call__(self, *args, **kwargs):
        from . import _to_static_enabled
        if not _to_static_enabled:
            return self._original_fn(*args, **kwargs)
        params, buffers = self._params_and_buffers()
        arg_arrays = _tree_unwrap(args)
        kw_arrays = _tree_unwrap(kwargs)
        flat_args, treedef = jax.tree.flatten((arg_arrays, kw_arrays))
        training = any(getattr(l, "training", False) for l in self._layers)
        key_shapes = tuple(
            (tuple(a.shape), str(a.dtype)) if hasattr(a, "shape") else repr(a)
            for a in flat_args)
        cache_key = (treedef, key_shapes, training, len(params), len(buffers))

        entry = self._cache.get(cache_key)
        if entry is None:
            _note_cache_miss()
            fn = self._fn
            out_treedef_box = []

            def pure(param_arrays, buffer_arrays, key, *flat):
                a_args, a_kwargs = jax.tree.unflatten(treedef, flat)
                with _trace_guard(), _swap_params(params + buffers,
                                                  list(param_arrays) + list(buffer_arrays)), \
                        _random.trace_key_scope(key), autograd.no_grad():
                    w_args = _tree_wrap(a_args)
                    w_kwargs = _tree_wrap(a_kwargs)
                    out = fn(*w_args, **w_kwargs)
                flat_out, out_treedef = jax.tree.flatten(_tree_unwrap(out))
                if not out_treedef_box:
                    out_treedef_box.append(out_treedef)
                return tuple(flat_out)

            entry = (jax.jit(pure), out_treedef_box)
            self._cache[cache_key] = entry
        jitted, out_treedef_box = entry

        key = _random.split_key()
        buffer_arrays = [b._data for b in buffers]

        # Register as one tape op: grads flow to params (and tensor args).
        def op_fn(*xs):
            p_arrays = xs[:len(params)]
            rest = xs[len(params):]
            return jitted(p_arrays, buffer_arrays, key, *rest)

        n_out_hint = None if not out_treedef_box else out_treedef_box[0].num_leaves
        out = apply_op(f"to_static[{self.__name__}]", op_fn,
                       list(params) + [a if isinstance(a, jax.Array) else jnp.asarray(a)
                                       for a in flat_args],
                       n_outputs=n_out_hint)
        leaves = list(out) if isinstance(out, tuple) else [out]
        structured = jax.tree.unflatten(out_treedef_box[0], leaves)
        return structured

    # reference-API compat
    def concrete_program_specify_input_spec(self, *a, **k):
        return None

    @property
    def code(self):
        import inspect
        try:
            return inspect.getsource(self._original_fn)
        except (OSError, TypeError):
            return "<source unavailable>"


def to_static(function=None, input_spec=None, build_strategy=None, backend=None,
              **kwargs):
    """Decorator: compile a function/Layer.forward with XLA
    (reference: paddle.jit.to_static, jit/api.py:222)."""
    from ..nn.layer import Layer

    def decorate(fn):
        if isinstance(fn, Layer):
            layer = fn
            sf = StaticFunction(layer.forward, input_spec, layer=layer)
            layer.forward = sf
            return layer
        return StaticFunction(fn, input_spec)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    pass
