"""Inference model serialization — AOT-compiled StableHLO artifacts.

TPU-native redesign of the reference's save/load_inference_model
(python/paddle/static/io.py → __model__ ProgramDesc + params files, consumed
by AnalysisPredictor, SURVEY §2.4): the portable artifact here is the XLA
ecosystem's native one — a serialized `jax.export` StableHLO module (the
replayed Program lowered and captured AOT) plus an .npz of parameter values
and a small JSON header for feed/fetch metadata. Loading needs no IR passes
or op converters: deserialize + call.
"""
from __future__ import annotations

import json
import os
from typing import List, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import export as jax_export

from ..core.tensor import Tensor
from .program import Program, Variable, default_main_program
from .executor import Executor


def normalize_program(program, feed_vars, fetch_vars):
    return program


def _export_platforms():
    from ..device import on_tpu
    return ("cpu", "tpu") if on_tpu() else ("cpu",)


def save_inference_model(path_prefix: str, feed_vars: Sequence[Variable],
                         fetch_vars: Sequence[Variable], executor: Executor,
                         program: Program = None):
    """reference: paddle.static.save_inference_model (static/io.py)."""
    program = program or default_main_program()
    feed_vars = list(feed_vars)
    fetch_vars = list(fetch_vars)

    infer = program.clone(for_test=True)
    # bind current parameter values as constants into the exported module
    fetch_vids = tuple(v.vid for v in fetch_vars)
    exe = Executor()
    fn = exe._build(infer, fetch_vids, train=False, feed_vars=feed_vars)

    diff_params = [p for p in infer._params if not p.stop_gradient
                   and np.issubdtype(np.dtype(p._data.dtype), np.floating)]
    _diff_ids = {id(p) for p in diff_params}
    const_params = [p for p in infer._params if id(p) not in _diff_ids]
    keys = tuple(jax.random.key(infer.random_seed + i)
                 for i in range(len(infer._key_vars)))

    def serving(*feeds):
        return fn(tuple(p._data for p in diff_params),
                  tuple(p._data for p in const_params), keys, *feeds)

    # feed dims declared -1/None export as symbolic dims (jax shape
    # polymorphism) — the artifact then serves any batch size, the analog of
    # the reference predictor's dynamic-shape support (TRT dynamic shapes)
    def _avals(symbolic):
        out = []
        scope = jax_export.SymbolicScope() if symbolic else None
        for i, v in enumerate(feed_vars):
            decl = v.declared_shape or tuple(v._data.shape)
            if symbolic and any(d == -1 for d in decl):
                spec = ",".join(f"d{i}_{j}" if d == -1 else str(d)
                                for j, d in enumerate(decl))
                shape = jax_export.symbolic_shape(spec, scope=scope)
            else:
                shape = tuple(v._data.shape)
            out.append(jax.ShapeDtypeStruct(shape, v._data.dtype))
        return out

    exported = None
    for symbolic in (True, False):
        try:
            exported = jax_export.export(jax.jit(serving),
                                         platforms=_export_platforms())(*_avals(symbolic))
            break
        except Exception:
            continue
    if exported is None:
        exported = jax_export.export(jax.jit(serving))(*_avals(False))

    os.makedirs(os.path.dirname(path_prefix) or ".", exist_ok=True)
    with open(path_prefix + ".pdmodel", "wb") as f:
        f.write(exported.serialize())
    meta = {
        "feed_names": [v.feed_name or v.name for v in feed_vars],
        "feed_shapes": [list(v._data.shape) for v in feed_vars],
        "feed_dtypes": [str(np.dtype(v._data.dtype)) for v in feed_vars],
        "fetch_names": [v.name for v in fetch_vars],
    }
    with open(path_prefix + ".pdmeta", "w") as f:
        json.dump(meta, f)
    # params are baked into the module; keep a sidecar copy for tooling parity
    np.savez(path_prefix + ".pdiparams.npz",
             **{(p.name or f"param_{i}"): np.asarray(p._data)
                for i, p in enumerate(program._params)})
    return path_prefix


class _LoadedInferenceProgram:
    """Replayable artifact: Executor.run(program=this, feed=..., fetch_list=...)
    works, and `.run(feed_arrays)` calls directly."""

    def __init__(self, exported, meta):
        self._exported = exported
        self.meta = meta
        self.feed_target_names = meta["feed_names"]
        self.fetch_target_names = meta["fetch_names"]

    def run(self, *feeds):
        outs = self._exported.call(*[jnp.asarray(f) for f in feeds])
        return [np.asarray(o) for o in outs]


def load_inference_model(path_prefix: str, executor: Executor = None):
    """reference: paddle.static.load_inference_model — returns
    (program, feed_target_names, fetch_targets)."""
    with open(path_prefix + ".pdmodel", "rb") as f:
        exported = jax_export.deserialize(f.read())
    with open(path_prefix + ".pdmeta") as f:
        meta = json.load(f)
    prog = _LoadedInferenceProgram(exported, meta)
    return prog, meta["feed_names"], meta["fetch_names"]


def save(program: Program, path_prefix: str):
    """Persist parameter values (reference: paddle.static.save →
    .pdparams/.pdopt). Program structure is python-held; parameters are the
    durable state."""
    np.savez(path_prefix + ".pdparams.npz",
             **{(p.name or f"param_{i}"): np.asarray(p._data)
                for i, p in enumerate(program._params)})


def load(program: Program, path_prefix: str, executor=None, var_list=None):
    data = np.load(path_prefix + ".pdparams.npz")
    for i, p in enumerate(program._params):
        key = p.name or f"param_{i}"
        if key in data:
            p._data = jnp.asarray(data[key])
