"""TrainStep — one fused XLA program for forward+backward+optimizer.

TPU-native replacement for the reference's training executors: where the
reference threads every op through InterpreterCore instruction lists
(framework/new_executor/interpretercore.cc) and fuses DP gradients with
EagerReducer buckets (distributed/collective/reducer.cc:1038), here the whole
step — loss, grads, clip, optimizer update — is ONE jitted function with
donated parameter/optimizer buffers: XLA fuses, schedules, overlaps
collectives, and reuses memory. Sharding comes from PartitionSpec annotations
on parameters (`Tensor.pspec`), so DP/TP/FSDP are all configurations of this
single code path (SURVEY §7 design mapping).

Numerics observability (r8): with `numerics=` enabled the step also carries
a per-layer stats tree (debugging.sentinel) — activation rows recorded by
instrumented sublayers while tracing, per-layer grad rows, the global
grad-norm, and an in-graph found-inf scalar — reduced on device to one
compact [rows, 6] float32 array returned as an ordinary output. The host
fetches it every N steps or on demand; the hot path pays a few reductions
and ZERO device->host syncs. `scaler=` threads GradScaler's
(scale, good, bad) through the step so dynamic loss scaling works under
jit: loss scaled in-graph, grads unscaled, the update select-skipped on
overflow, state advanced by the same pure rule the eager path uses.
"""
from __future__ import annotations

import logging
import time
from typing import Callable, Dict, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor, Parameter
from ..core import random as _random
from ..core import autograd
from ..profiler.timeline import current as _tl_current
from .api import (GRAD_PROBE_PROGRAM, TRAIN_PROGRAM, TRAIN_SCAN_PROGRAM,
                  _swap_params, _trace_guard, _tree_unwrap, _tree_wrap,
                  _note_cache_miss, named_program)

_logger = logging.getLogger("paddle_tpu.jit.train_step")


def _spec_or_replicated(p):
    return p.pspec if getattr(p, "pspec", None) is not None else P()


def _opt_state_spec(p, optimizer):
    """Optimizer-state spec = param spec, further sharded over the ZeRO axis
    when distributed.sharding marked the optimizer (stage>=1): this is what
    turns XLA's grad all-reduce into reduce-scatter + sharded update —
    ZeRO 1/2 with no bespoke runtime (see distributed/sharding.py)."""
    spec = _spec_or_replicated(p)
    stage = getattr(optimizer, "_sharding_stage", 0)
    if stage >= 1:
        from ..distributed.sharding import _with_axis
        from ..distributed import mesh as _dmesh
        axis = getattr(optimizer, "_sharding_axis", "sdp")
        size = _dmesh.mesh_axis_size(axis)
        if size > 1:
            return _with_axis(spec, p.shape, axis, size)
    return spec


class TrainStep:
    """Compile `loss = loss_fn(model(*inputs), *labels)`-style steps.

    train_step = TrainStep(model, opt, loss_fn)   # loss_fn(batch...)->Tensor
    loss = train_step(x, y)                       # updates model in place

    With `mesh`, parameters/optimizer state are placed by their pspec
    annotations and batch inputs are sharded over `data_axes`.

    `numerics`: True or a debugging.NumericsConfig — thread the per-layer
    stats tree through the compiled step (see module docstring);
    `train_step.numerics_stats()` fetches the latest tree on demand.
    `scaler`: an amp.GradScaler — dynamic loss scaling entirely in-graph.
    """

    def __init__(self, model, optimizer, loss_fn: Callable, mesh: Optional[Mesh] = None,
                 data_axes=("dp",), donate: bool = True, grad_accum_steps: int = 1,
                 monitor=None, numerics=None, scaler=None, lint=None,
                 preemption=None, chaos=None, timeline=None, memz=None,
                 grad_comm: Optional[str] = None, grad_comm_chunk: int = 256,
                 grad_comm_stochastic: bool = False,
                 grad_comm_f32_fallback: Optional[Callable] = None):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.data_axes = data_axes
        self.donate = donate
        self.grad_accum_steps = grad_accum_steps
        # profiler.StepMonitor: per-step wall/MFU/HBM telemetry + the
        # recompilation detector (assignable after construction too)
        self.monitor = monitor
        # resilience wiring: `preemption` (a resilience.PreemptionHandler)
        # is polled at every step boundary — the in-flight XLA launch
        # always completes, then the handler takes its emergency
        # checkpoint and raises Preempted. `chaos` (a resilience.Injector)
        # fires the `step.end` fault site so kill-at-step-k tests die at
        # exactly the boundary a real preemption would.
        self.preemption = preemption
        self.chaos = chaos
        # goodput accounting (profiler.timeline): the step records every
        # launch as a `compile` span (compile-cache miss calls — trace +
        # XLA compile dominate their wall) or a `step` span (goodput).
        # Falls back to the process-wide installed recorder when unset.
        self.timeline = timeline
        # HBM ledger (ISSUE 18): params/opt-state register as owners
        # after the first compile (opt state materializes lazily), and a
        # device allocation failure unwinding out of a launch dumps the
        # OOM post-mortem artifact before re-raising
        self.memz = memz
        self._memz_registered = False
        self._step_i = 0
        self._compiled = {}
        self._last_sig = {}     # kind -> last compiled shape signature

        self._scaler = scaler if (scaler is not None
                                  and scaler.is_enable()) else None
        self._numerics = None
        self._sentinel_handle = None
        self._act_paths = []      # activation row paths, filled at 1st trace
        self._grad_groups = []    # [(path, [param indices])]
        self._last_aux = None     # latest step's aux pytree (device arrays)
        self._last_loss_arr = None
        self._last_key = None
        self._last_batch_struct = None   # nested python batch (array leaves)

        self._param_names, self._params = [], []
        for name, p in model.named_parameters():
            if not p.stop_gradient:
                self._param_names.append(name)
                self._params.append(p)
        self._buffers = [b for _, b in model.named_buffers()]

        if numerics is not None:
            self.set_numerics(numerics)

        # explicit gradient-sync modes (ISSUE 20): None keeps the
        # partitioner's implicit f32 psum; "f32"/"int8" step OUT of
        # auto-sharding into a shard_map over the dp axis with one
        # collective per `_grad_groups` layer bucket — per-layer so the
        # latency-hiding scheduler overlaps them with backward, int8 with
        # per-chunk factored scales for the ~4x wire cut (EQuARX).
        self.grad_comm = grad_comm
        self.grad_comm_chunk = int(grad_comm_chunk)
        self.grad_comm_stochastic = bool(grad_comm_stochastic)
        self._comm_groups = None
        if grad_comm is not None:
            if grad_comm not in ("f32", "int8"):
                raise ValueError(f"grad_comm={grad_comm!r}: expected None, "
                                 "'f32' or 'int8'")
            if mesh is None:
                raise ValueError("grad_comm requires TrainStep(mesh=...) — "
                                 "there is no gradient sync to replace "
                                 "without a data-parallel mesh")
            if len(data_axes) != 1 or tuple(mesh.axis_names) != tuple(data_axes):
                raise ValueError(
                    f"grad_comm needs a pure data-parallel mesh whose only "
                    f"axis is {data_axes!r} (got mesh axes "
                    f"{tuple(mesh.axis_names)}): partial-manual shard_map "
                    "lowers through PartitionId, which this runtime's "
                    "partitioner rejects")
            if grad_accum_steps > 1:
                raise ValueError("grad_comm with grad_accum_steps>1 is not "
                                 "supported yet — the accumulation scan "
                                 "would need the sync inside its body")
            if not self._grad_groups:
                from ..debugging import grad_layer_groups
                self._grad_groups = grad_layer_groups(
                    self._param_names, type(model).__name__)
            from ..distributed.quant_collectives import build_comm_groups
            shapes = [tuple(p.shape) for p in self._params]
            if grad_comm == "int8":
                self._comm_groups = build_comm_groups(
                    self._param_names, shapes, self._grad_groups,
                    grad_comm_f32_fallback)
            else:
                # "f32": same per-layer-group bucketing, every leaf on the
                # f32 lane — isolates the overlap effect from quantization
                self._comm_groups = [(path, (), tuple(idxs))
                                     for path, idxs in self._grad_groups]

        # static analysis (analysis.GraphLint): True/"error"/GraphLint —
        # the step's pure function is audited ABSTRACTLY (no execution)
        # before its first compile; findings land on `lint_findings` and
        # guard mode raises GraphLintError pre-compile
        from ..analysis import GraphLint as _GraphLint
        self._lint = _GraphLint.coerce(lint)
        self._lint_done = False
        self.lint_findings = None
        # sharding lint (ISSUE 15): under a mesh the lint additionally
        # compiles the step and audits the post-SPMD HLO — the static
        # collective inventory + resharding/replication/CommPlan passes.
        # The latest audit (a analysis.ShardingAudit) lands here.
        self.comm_audit = None

        # optimizer state as pytree (init lazily so shapes match cast params)
        self._opt_state = None

    def set_numerics(self, numerics):
        """(Re)configure the numerics mode after construction: installs the
        layer sentinels + per-layer grad grouping and invalidates compiled
        executables so the stats tree joins the step outputs on the next
        compile. Pass None/False to disable."""
        from ..debugging import (NumericsConfig, check_layer_numerics,
                                 grad_layer_groups)
        self._numerics = NumericsConfig.coerce(numerics)
        if self._numerics is not None:
            if self._sentinel_handle is None:
                # idempotent: reuses hooks another handle already installed
                self._sentinel_handle = check_layer_numerics(self.model)
            if self._numerics.grad_stats and not self._grad_groups:
                self._grad_groups = grad_layer_groups(
                    self._param_names, type(self.model).__name__)
        if self._compiled:
            self._compiled.clear()
            # deliberate re-trace, not shape instability: reset the
            # recompile detector's signatures so it stays quiet
            self._last_sig.clear()

    # ------------------------------------------------------------------
    def _init_opt_state(self):
        def _init(p, name):
            try:
                return self.optimizer.init_state(p._data, param_obj=p,
                                                 name=name)
            except TypeError:   # optimizers with the older signature
                return self.optimizer.init_state(p._data)
        return [_init(p, n)
                for p, n in zip(self._params, self._param_names)]

    def _shard_param_tree(self, tree_template):
        if self.mesh is None:
            return None
        specs = []
        for p in self._params:
            specs.append(_spec_or_replicated(p))
        return specs

    def _placement(self, spec):
        # drop axis names the mesh doesn't have (a TP-annotated model run on
        # a dp-only mesh just replicates those dims)
        from ..distributed import mesh as _dmesh
        with _dmesh.mesh_scope(self.mesh):
            spec = _dmesh.filter_spec(*spec) if spec is not None else P()
        return NamedSharding(self.mesh, spec)

    def _to_global(self, arr, spec):
        """Place a host array onto the (possibly multi-host) mesh.

        Multi-process: jax.device_put cannot target non-addressable devices;
        host_local_array_to_global_array assembles the global array from each
        process's local piece — for axes sharded ACROSS hosts (e.g. dp over
        processes) the caller passes its local shard; for host-local axes
        (mp within a host) and replicated specs, the full array."""
        from ..distributed import mesh as _dmesh
        with _dmesh.mesh_scope(self.mesh):
            fspec = _dmesh.filter_spec(*spec) if spec is not None else P()
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            return multihost_utils.host_local_array_to_global_array(
                arr, self.mesh, fspec)
        return jax.device_put(arr, NamedSharding(self.mesh, fspec))

    def _to_global_from_full(self, arr, spec):
        """Place a host array that EVERY process holds in full (params and
        optimizer state — same-seed init) onto the mesh: each process
        contributes exactly the slices its devices own
        (make_array_from_callback), so specs sharded over process-CROSSING
        axes (e.g. pipeline stages split across hosts) assemble correctly.
        host_local_array_to_global_array would instead CONCATENATE the full
        copies — doubling any param sharded across the process boundary.
        Data batches keep the host-local-shard convention (_to_global)."""
        from ..distributed import mesh as _dmesh
        with _dmesh.mesh_scope(self.mesh):
            fspec = _dmesh.filter_spec(*spec) if spec is not None else P()
        sh = NamedSharding(self.mesh, fspec)
        if jax.process_count() > 1:
            import numpy as _np
            host = _np.asarray(arr)  # lint: allow(tracer-asarray)
            return jax.make_array_from_callback(host.shape, sh,
                                                lambda idx: host[idx])
        return jax.device_put(arr, sh)

    def _apply_param_shardings(self):
        """place params/opt state by their pspec (once)."""
        if self.mesh is None:
            return
        for p in self._params:
            p._data = self._to_global_from_full(p._data,
                                                _spec_or_replicated(p))
        if self._opt_state is not None:
            for p, st in zip(self._params, self._opt_state):
                spec = _opt_state_spec(p, self.optimizer)
                for k in st:
                    st[k] = self._to_global_from_full(
                        st[k], self.optimizer.state_spec(p, k, st[k], spec))

    # ------------------------------------------------------------------
    def _build(self, treedef, ndims):
        opt = self.optimizer
        params = self._params
        pure_step = self._build_pure(treedef)

        kwargs = {}
        if self.mesh is not None:
            pspecs = tuple(_spec_or_replicated(p) for p in params)
            sspecs = tuple(_opt_state_spec(p, opt) for p in params)
            # per-entry spec comes from the optimizer (param-shaped state
            # follows the param; e.g. int8 moment codes shard their block
            # dim) — see Optimizer.state_spec
            state_specs = tuple(
                {k: opt.state_spec(params[i], k, self._opt_state[i][k],
                                   sspecs[i])
                 for k in (self._opt_state[i] or {})}
                for i in range(len(params)))
            flat_specs = [P(*self.data_axes) if nd > 0 else P() for nd in ndims]
            in_shardings = (
                tuple(self._placement(s) for s in pspecs),
                tuple({k: self._placement(s[k]) for k in s} for s in state_specs),
                None, None, None, None,
                *[self._placement(s) for s in flat_specs],
            )
            out_shardings = (
                None,
                tuple(self._placement(s) for s in pspecs),
                tuple({k: self._placement(s[k]) for k in s} for s in state_specs),
                None, None,
            )
            kwargs = dict(in_shardings=in_shardings, out_shardings=out_shardings)
        donate = (0, 1) if self.donate else ()
        return named_program(pure_step, TRAIN_PROGRAM,
                             donate_argnums=donate, **kwargs)

    # ------------------------------------------------------------------
    def _build_scan(self, treedef, n_steps):
        """N optimizer steps in ONE executable via lax.scan over stacked
        batches [n, ...]. Amortizes host dispatch (one launch per N steps)
        and lets XLA overlap step boundaries — the analog of the reference's
        gradient_merge/program-level multi-batch execution, and the honest
        way to benchmark on remote-dispatch runtimes. Numerics stats and the
        scaler state ride the scan (stats stacked [n, rows, 6]; scaler state
        as carry — per-step overflow decisions, same as N eager updates)."""
        single = self._build_pure(treedef)

        def multi(param_arrays, opt_state, scaler_state, step0, lr, key,
                  *flat_batches):
            def body(carry, xs):
                params, state, sstate, i = carry
                ks, batch_leaves = xs[0], xs[1:]
                loss, new_p, new_s, new_ss, aux = single(
                    params, state, sstate, i, lr, ks, *batch_leaves)
                return (new_p, new_s, new_ss, i + 1), (loss, aux)

            keys = jax.random.split(key, n_steps)
            (pa, st, ss, _), (losses, auxs) = jax.lax.scan(
                body,
                (tuple(param_arrays), tuple(opt_state), scaler_state, step0),
                (keys, *flat_batches))
            return losses, pa, st, ss, auxs

        return named_program(multi, TRAIN_SCAN_PROGRAM,
                             donate_argnums=(0, 1))

    def _build_pure(self, treedef):
        """The single-step pure function (shared by __call__ and scan)."""
        opt = self.optimizer
        params = self._params
        loss_fn = self.loss_fn
        wds = [opt._wd_for(p) for p in params]
        grad_clip = opt._grad_clip
        accum = max(1, int(self.grad_accum_steps))
        numerics = self._numerics
        scaler = self._scaler
        grad_groups = self._grad_groups
        act_paths_box = self._act_paths
        grad_comm = self.grad_comm
        comm_groups = self._comm_groups
        if grad_comm is not None:
            from ..distributed import quant_collectives as _qc
            comm_axis = self.data_axes[0]
            comm_D = int(self.mesh.shape[comm_axis])
            comm_chunk = self.grad_comm_chunk
            comm_stoch = self.grad_comm_stochastic
            comm_mesh = self.mesh
        if numerics is not None or scaler is not None:
            from ..debugging import sentinel as _sentinel
        else:
            _sentinel = None

        def pure_step(param_arrays, opt_state, scaler_state, step_i, lr, key,
                      *flat_batch):
            batch = jax.tree.unflatten(treedef, flat_batch)
            scale = scaler_state[0] if scaler_state is not None else None

            def loss_of(pa, microbatch, k):
                import contextlib
                col_cm = _sentinel.collect_stats() if numerics is not None \
                    else contextlib.nullcontext()
                with _trace_guard(), _swap_params(params, list(pa)), \
                        _random.trace_key_scope(k), autograd.no_grad(), \
                        col_cm as col:
                    out = loss_fn(*_tree_wrap(microbatch))
                loss_arr = out._data if isinstance(out, Tensor) else out
                loss_arr = loss_arr.astype(jnp.float32)
                act_rows = None
                if numerics is not None:
                    act_rows = col.stacked()
                    if col.paths and not act_paths_box:
                        act_paths_box.extend(col.paths)
                # loss scaling happens in-graph: autodiff sees the SCALED
                # loss, the aux carries the true loss back out
                scaled = loss_arr * scale if scale is not None else loss_arr
                return scaled, (loss_arr, act_rows)

            if accum == 1 and grad_comm is not None:
                # explicit gradient sync (ISSUE 20): shard_map manual over
                # the dp axis — per-shard backward on the local microbatch,
                # then one collective per layer group (int8 psum with
                # per-chunk scales, or the f32 twin), so the scheduler can
                # overlap group N's all-reduce with layer N-1's backward
                from jax import shard_map as _shard_map
                from jax import lax as _lax

                def _shard_step(pa, b, k):
                    # the region is MANUAL over the dp axis: the model's
                    # activation shard_constraints (global-mesh specs) are
                    # illegal here — and on the pure-dp mesh grad_comm
                    # requires they pin nothing the manual region doesn't
                    # already fix, so trace the loss with no active mesh
                    from ..distributed import mesh as _dmesh
                    with _dmesh.mesh_scope(None):
                        (_, (l, rows)), g = jax.value_and_grad(
                            loss_of, has_aux=True)(list(pa), b, k)
                    sk = jax.random.fold_in(k, 0x5C) if comm_stoch else None
                    g = _qc.sync_grad_groups(
                        g, comm_groups, comm_axis, comm_D,
                        chunk=comm_chunk, stochastic=comm_stoch, key=sk)
                    l = _lax.pmean(l, comm_axis)
                    if rows is not None:
                        rows = _lax.pmean(rows, comm_axis)
                    return l, rows, g

                bspec = jax.tree.map(
                    lambda a: P(comm_axis) if getattr(a, "ndim", 0) > 0
                    else P(), batch)
                loss, act_rows, grads = _shard_map(
                    _shard_step, mesh=comm_mesh, axis_names={comm_axis},
                    in_specs=(P(), bspec, P()),
                    out_specs=(P(), P(), [P()] * len(params)),
                    check_vma=False)(list(param_arrays), batch, key)
            elif accum == 1:
                (_, (loss, act_rows)), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(list(param_arrays), batch, key)
            else:
                # gradient accumulation (reference: gradient_merge /
                # GradientMergeOptimizer): split the batch dim into `accum`
                # microbatches, scan fwd+bwd accumulating mean grads, ONE
                # optimizer update — same memory as a 1/accum-size batch
                def to_micro(a):
                    if a.ndim == 0:
                        raise ValueError(
                            "grad_accum_steps requires batched inputs; got a "
                            "scalar batch leaf")
                    if a.shape[0] % accum:
                        raise ValueError(
                            f"batch size {a.shape[0]} is not divisible by "
                            f"grad_accum_steps={accum}")
                    return a.reshape((accum, a.shape[0] // accum) + a.shape[1:])

                micro = jax.tree.map(to_micro, batch)
                keys = jax.random.split(key, accum)

                def acc_body(carry, xs):
                    loss_acc, g_acc = carry
                    mb, k = xs
                    (_, (l, rows)), g = jax.value_and_grad(
                        loss_of, has_aux=True)(list(param_arrays), mb, k)
                    return (loss_acc + l / accum,
                            [ga + (gi / accum).astype(ga.dtype)
                             for ga, gi in zip(g_acc, g)]), rows

                # accumulate in the PARAM dtype: autodiff grads already come
                # out in param dtype (bf16 for bf16 models), and an f32
                # accumulator would double the grad footprint — the very
                # memory the microbatching exists to save
                zeros = [jnp.zeros(p.shape, p.dtype)
                         for p in param_arrays]
                (loss, grads), micro_rows = jax.lax.scan(
                    acc_body, (jnp.float32(0.0), zeros), (micro, keys))
                act_rows = None if micro_rows is None else \
                    _sentinel.merge_stacked(micro_rows)

            # unscale BEFORE clip/sentinels so grad stats and the update see
            # true gradients (found-inf is scale-invariant)
            if scale is not None:
                inv = jnp.float32(1.0) / scale
                grads = [g * inv.astype(g.dtype) for g in grads]

            aux = {}
            found = None
            need_found = scaler is not None or (
                numerics is not None and numerics.skip_nonfinite_updates)
            if numerics is not None:
                rows = list(act_rows) if act_rows is not None else []
                grow_mat = None
                if grad_groups:
                    _, grows = _sentinel.grad_stat_rows(grads, grad_groups)
                    rows += grows
                    grow_mat = jnp.stack(grows)
                if rows:
                    aux["stats"] = jnp.stack(rows)
                if grow_mat is not None:
                    # found-inf and the global grad-norm DERIVE from the
                    # grad rows — no second scan over grad memory (the rows
                    # mask non-finites out of l2, so the norm stays finite
                    # and the nan/inf counts carry the overflow signal)
                    if need_found:
                        found = jnp.sum(grow_mat[:, 1] + grow_mat[:, 2]) > 0
                    aux["grad_norm"] = jnp.sqrt(
                        jnp.sum(grow_mat[:, 5] ** 2))
                else:
                    if need_found:
                        found = _sentinel.found_inf(grads)
                    aux["grad_norm"] = jnp.sqrt(
                        sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                            for g in grads))
                if found is not None:
                    aux["found_inf"] = found
            elif need_found:
                found = _sentinel.found_inf(grads)
                aux["found_inf"] = found
            if grad_clip is not None and type(grad_clip).__name__ == "ClipGradByGlobalNorm":
                total = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                     for g in grads))
                scale_c = jnp.minimum(1.0, grad_clip.clip_norm / jnp.maximum(total, 1e-12))
                grads = [g * scale_c.astype(g.dtype) for g in grads]
            new_params = [None] * len(param_arrays)
            new_state = [None] * len(param_arrays)
            # fused multi-tensor apply (reference analog:
            # distributed_fused_lamb.py:82): concatenate each (dtype,
            # moment-dtype) group of small params into ONE flat elementwise
            # update; weight decay becomes a per-element constant vector.
            # MEASURED OFF by default on v5e: XLA already fuses per-param
            # updates into the weight-grad producing fusions, and the
            # separate flattened pass DEFEATS that — GPT-1.3B break-even
            # (73.4 vs 73.6% MFU), ResNet-50 −12% (1471 vs 1681 img/s).
            # Kept as an opt-in (PADDLE_TPU_FUSE_SMALL_UPDATES=<bytes>)
            # for runtimes where the trade lands differently.
            import os as _os
            fuse_t = int(_os.environ.get("PADDLE_TPU_FUSE_SMALL_UPDATES",
                                         "0"))
            groups = {}
            fkeys = tuple(getattr(opt, "_fused_state_keys", ()))
            if getattr(opt, "_fusable_elementwise", False) and fuse_t > 0:
                for i, (pa, st) in enumerate(zip(param_arrays, opt_state)):
                    if (pa.size <= fuse_t and pa.ndim >= 1
                            and st is not None and set(st) == set(fkeys)):
                        key_g = (str(pa.dtype),) + tuple(
                            str(st[k].dtype) for k in fkeys)
                        groups.setdefault(key_g, []).append(i)
            fused_idx = set()
            for idxs in groups.values():
                if len(idxs) < 2:
                    continue
                fused_idx.update(idxs)
                sizes = [param_arrays[i].size for i in idxs]
                offs = [0]
                for s_ in sizes:
                    offs.append(offs[-1] + s_)
                flat_p = jnp.concatenate(
                    [param_arrays[i].reshape(-1) for i in idxs])
                flat_g = jnp.concatenate(
                    [grads[i].reshape(-1) for i in idxs])
                flat_st = {
                    k: jnp.concatenate(
                        [opt_state[i][k].reshape(-1) for i in idxs])
                    for k in fkeys}
                wd_vec = jnp.concatenate(
                    [jnp.full((param_arrays[i].size,), float(wds[i]),  # lint: allow(tracer-float)
                              jnp.float32) for i in idxs])
                fp, fs = opt.update(flat_p, flat_g, flat_st, lr, step_i,
                                    wd_vec)
                for j, i in enumerate(idxs):
                    sl = slice(offs[j], offs[j + 1])
                    new_params[i] = fp[sl].reshape(param_arrays[i].shape)
                    new_state[i] = {
                        k: fs[k][sl].reshape(opt_state[i][k].shape)
                        for k in fkeys}
            for i, (pa, g, st, wd) in enumerate(
                    zip(param_arrays, grads, opt_state, wds)):
                if i in fused_idx:
                    continue
                np_, ns_ = opt.update(pa, g, st, lr, step_i, wd)
                new_params[i] = np_
                new_state[i] = ns_
            # select-skip the update on overflow: params/opt-state never
            # ingest a non-finite value (GradScaler semantics; also what
            # makes an anomaly dump hold the exact pre-step state)
            if found is not None:
                new_params = [jnp.where(found, pa, np_)
                              for pa, np_ in zip(param_arrays, new_params)]
                new_state = [
                    ({k: jnp.where(found, st[k], ns_[k]) for k in ns_}
                     if ns_ and st else ns_)
                    for st, ns_ in zip(opt_state, new_state)]
            new_scaler_state = None
            if scaler_state is not None:
                from ..amp.grad_scaler import GradScaler
                new_scaler_state = GradScaler._update_rule(
                    *scaler_state, found, **scaler._hyper())
            return (loss, tuple(new_params), tuple(new_state),
                    new_scaler_state, aux)

        return pure_step

    # ------------------------------------------------------------------
    def _on_compile(self, kind: str, sig):
        """Compile-cache miss bookkeeping: feed the global jit miss counter
        and the recompilation detector — a second compile of the same kind
        means the abstract shape signature changed, and the delta names the
        offending leaf (the thing you want when a training loop silently
        recompiles every step)."""
        _note_cache_miss()
        prev = self._last_sig.get(kind)
        self._last_sig[kind] = sig
        if self.monitor is not None:
            self.monitor.record_compile(kind, sig, prev_sig=prev)
        elif prev is not None and prev != sig:
            from ..profiler.monitor import shape_delta
            _logger.warning("recompilation of %s: %s", kind,
                            shape_delta(prev, sig))

    # ------------------------------------------------------------------
    # numerics: fetch / detect / dump
    @property
    def numerics_paths(self):
        """Stats-tree row names: activation paths (trace order) then
        per-layer grad rows. Populated after the first compile."""
        return list(self._act_paths) + [k for k, _ in self._grad_groups]

    def numerics_stats(self, sync: bool = True):
        """The latest step's StatsTree (device->host fetch happens HERE, not
        in the step). None before the first numerics-enabled step."""
        if self._last_aux is None or "stats" not in self._last_aux:
            return None
        from ..debugging import StatsTree
        vals = self._last_aux["stats"]
        return StatsTree(self.numerics_paths,
                         np.asarray(vals) if sync else vals,  # lint: allow(tracer-asarray)
                         step=self._step_i)

    def _scaler_state_in(self):
        return self._scaler.state_arrays() if self._scaler is not None else None

    def _after_step(self, loss_arr, new_scaler_state, aux, *, steps=1):
        if self._scaler is not None and new_scaler_state is not None:
            self._scaler.set_state_arrays(
                new_scaler_state, found_inf=aux.get("found_inf"))
        if self._numerics is None:
            return
        self._last_aux = aux
        self._last_loss_arr = loss_arr
        cfg = self._numerics
        n = cfg.every_n_steps
        if n and (self._step_i % n == 0
                  or (steps > 1 and self._step_i % n < steps)):
            self._fetch_and_detect()

    def _fetch_and_detect(self):
        """One host fetch of the latest stats + loss/grad-norm scalars, run
        the detectors, route events (monitor / on_event / dump / raise)."""
        cfg = self._numerics
        tree = self.numerics_stats()
        loss = None
        if self._last_loss_arr is not None:
            la = np.asarray(self._last_loss_arr)  # lint: allow(tracer-asarray)
            loss = float(la.reshape(-1)[-1])  # run_steps: last step's loss  # lint: allow(tracer-float)
        gn = self._last_aux.get("grad_norm") if self._last_aux else None
        gn = float(np.asarray(gn).reshape(-1)[-1]) if gn is not None else None  # lint: allow(tracer-float, tracer-asarray)
        events = cfg.detector.observe(self._step_i, tree=tree, loss=loss,
                                      grad_norm=gn)
        monitor = cfg.monitor or self.monitor
        if monitor is not None and hasattr(monitor, "record_numerics"):
            monitor.record_numerics(step=self._step_i, loss=loss,
                                    grad_norm=gn, events=events)
        for e in events:
            _logger.warning("numerics: %r", e)
            if cfg.on_event is not None:
                cfg.on_event(e)
        if events and cfg.dump_dir:
            self._write_dump(events, tree, loss)
        if cfg.raise_on_nonfinite and any(
                e.kind in ("nan", "inf") for e in events):
            bad = next(e for e in events if e.kind in ("nan", "inf"))
            raise FloatingPointError(
                f"non-finite values detected at step {self._step_i} in "
                f"{bad.path}: {bad.message} (numerics.raise_on_nonfinite)")
        return events

    def _write_dump(self, events, tree, loss):
        from ..debugging import dump as _dump
        leaves, _ = jax.tree.flatten(self._last_batch_struct)
        spec = _dump.tree_spec(self._last_batch_struct)
        path = _dump.write_dump(
            self._numerics.dump_dir, step=self._step_i, events=events,
            batch_leaves=leaves, batch_spec=spec,
            param_names=self._param_names,
            param_arrays=[p._data for p in self._params],
            opt_state=self._opt_state, key=self._last_key, loss=loss,
            stats=tree,
            extra_meta={"model": type(self.model).__name__,
                        "skip_nonfinite_updates":
                            self._numerics.skip_nonfinite_updates})
        _logger.warning("numerics: dumped failing step %d to %s",
                        self._step_i, path)
        return path

    # ------------------------------------------------------------------
    # resilience: step-boundary hooks + the resumable state snapshot
    def _post_step(self):
        """Step-boundary resilience hooks, in hazard order: the chaos
        injector's `step.end` site first (a simulated kill must not get
        the checkpoint a real SIGKILL wouldn't), then the preemption
        poll (emergency checkpoint + Preempted)."""
        if self.chaos is not None:
            self.chaos.fire("step.end", step=self._step_i)
        if self.preemption is not None:
            self.preemption.poll(
                state=self.preemption.state or self, step=self._step_i)

    def state_dict(self) -> Dict:
        """Host snapshot of everything the COMPILED step owns: step
        counter, parameter arrays, the step's own optimizer-state pytree
        (not optimizer._states — the jitted path never touches those),
        host-side optimizer scalars (master step + LR-scheduler state) and
        the GradScaler triple. The device→host gather here is the ONE
        deliberate sync of the checkpoint path — at save time syncing is
        the job (allowlisted in the r11 source lint)."""
        out: Dict = {"step": int(self._step_i)}
        out["params"] = {
            n: np.asarray(p._data)  # lint: allow(tracer-asarray)
            for n, p in zip(self._param_names, self._params)}
        if self._opt_state is not None:
            out["opt"] = {
                n: {k: np.asarray(v)  # lint: allow(tracer-asarray)
                    for k, v in (st or {}).items()}
                for n, st in zip(self._param_names, self._opt_state)}
        extra: Dict = {"master_step": int(self.optimizer._step_count)}
        from ..optimizer.lr import LRScheduler as _LRS
        if isinstance(self.optimizer._lr, _LRS):
            extra["lr_sched"] = {
                k: v for k, v in self.optimizer._lr.state_dict().items()
                if isinstance(v, (bool, int, float, str))}
        out["opt_extra"] = extra
        if self._scaler is not None:
            out["scaler"] = self._scaler.state_dict()
        return out

    def set_state_dict(self, state: Dict):
        """Adopt a state_dict() snapshot: params/opt state land back on
        device (re-sharded by pspec under a mesh) with their saved dtypes
        — the compiled executables keep matching, so a resume costs one
        re-trace of a fresh TrainStep object and zero steady-state
        recompiles after."""
        params = state.get("params", {})
        missing = [n for n in self._param_names if n not in params]
        if missing:
            raise KeyError(f"checkpoint is missing parameters: "
                           f"{missing[:5]}{'...' if len(missing) > 5 else ''}")
        for n, p in zip(self._param_names, self._params):
            p._data = jnp.asarray(params[n])
            p._node = None
        opt = state.get("opt")
        if opt is not None:
            if self._opt_state is None:
                self._opt_state = self._init_opt_state()
            self._opt_state = [
                {k: jnp.asarray(v) for k, v in opt.get(n, {}).items()}
                or st
                for n, st in zip(self._param_names, self._opt_state)]
        self._apply_param_shardings()
        self._step_i = int(state.get("step", 0))
        extra = state.get("opt_extra", {})
        if "master_step" in extra:
            self.optimizer._step_count = int(extra["master_step"])
        if "lr_sched" in extra:
            from ..optimizer.lr import LRScheduler as _LRS
            if isinstance(self.optimizer._lr, _LRS):
                self.optimizer._lr.set_state_dict(dict(extra["lr_sched"]))
        if self._scaler is not None and "scaler" in state:
            self._scaler.set_state_dict(dict(state["scaler"]))
        return self

    # ------------------------------------------------------------------
    def lint(self, *batch, lint=None):
        """Statically audit the compiled step over this batch's shapes:
        trace (never execute) the pure step function through the
        analysis suite — host-transfer, dtype-promotion, baked-const and
        donation passes, with tracing under the transfer guard so an
        implicit `.item()` in a layer names its path. `batch` leaves may
        be Tensors, arrays, or jax.ShapeDtypeStructs. Returns Findings
        (also stored on `self.lint_findings`); a guard-mode linter
        raises GraphLintError. Works standalone (`TrainStep(...).lint(x,
        y)`) — `TrainStep(lint=...)` runs the same audit automatically
        before the first compile."""
        from ..analysis import GraphLint
        linter = GraphLint.coerce(lint) or self._lint or GraphLint()
        arrays = _tree_unwrap(batch)
        flat, treedef = jax.tree.flatten(arrays)
        return self._lint_check(linter, treedef, flat)

    @staticmethod
    def _sds(a):
        return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype) \
            if hasattr(a, "shape") else a

    def _abstract_step_args(self):
        """(params, opt state, scaler state) as ShapeDtypeStructs — the
        abstract leading arguments of the pure/built step, shared by the
        abstract lint and the sharded audit."""
        p_sds = tuple(self._sds(p._data) for p in self._params)
        s_sds = tuple({k: self._sds(v) for k, v in (st or {}).items()}
                      for st in self._opt_state)
        sstate = None
        if self._scaler is not None:
            sstate = tuple(jax.ShapeDtypeStruct((), d)
                           for d in (jnp.float32, jnp.int32, jnp.int32))
        return p_sds, s_sds, sstate

    @staticmethod
    def _plan_guard(linter, findings):
        """Guard-mode raise for CommPlan violations — the sharper
        CommPlanError, ahead of the generic GraphLintError guard."""
        if linter.mode != "error":
            return
        from ..analysis import CommPlanError
        plan_active = findings.for_pass("comm_plan").active(linter.fail_on)
        if plan_active:
            raise CommPlanError(plan_active, "train_step")

    def _lint_check(self, linter, treedef, flat):
        if self._opt_state is None:
            self._opt_state = self._init_opt_state()
            self._apply_param_shardings()
        pure = self._build_pure(treedef)
        sds = self._sds
        p_sds, s_sds, sstate = self._abstract_step_args()
        built = None
        if self.mesh is not None:
            # under a mesh the abstract passes audit the BUILT jitted
            # step (shardings + donation baked in): lowering the bare
            # pure function would mix in-graph sharding constraints with
            # unsharded parameters, and the donation pass would report
            # aliasing misses the real executable does not have
            built = self._build(
                treedef,
                [getattr(a, "ndim", len(getattr(a, "shape", ())))
                 for a in flat])
        findings = linter.check(
            built if built is not None else pure,
            p_sds, s_sds, sstate, jnp.int32(1), jnp.float32(1e-3),
            jax.random.PRNGKey(0), *[sds(a) for a in flat],
            # audit the donation config the REAL executable uses — with
            # donate=False the pass must report the donatable params/state,
            # not prove an aliasing the step doesn't have
            donate_argnums=(0, 1) if self.donate else (),
            name="train_step", guard=False)
        if self.mesh is not None:
            audit = self._sharded_audit(linter, treedef, flat, sstate,
                                        built=built)
            findings.extend(audit.findings)
        # stored BEFORE the guard fires: a caller catching GraphLintError
        # can still read step.lint_findings post-mortem
        self.lint_findings = findings
        self._plan_guard(linter, findings)
        linter._guard(findings, "train_step")
        return findings

    def _sharded_audit(self, linter, treedef, flat, sstate=None,
                       built=None):
        """The sharded half of the lint (ISSUE 15): build the jitted
        step with its REAL in/out shardings, lower + compile it with
        abstract inputs (nothing executes), and audit the
        post-partitioning HLO — collective inventory, resharding and
        replication passes, and the linter's CommPlan if one is
        declared. Entry-parameter keypaths translate back to model
        parameter names so a finding names the offending LAYER."""
        if built is None:
            built = self._build(
                treedef,
                [getattr(a, "ndim", len(getattr(a, "shape", ())))
                 for a in flat])
        sds = self._sds
        p_sds, s_sds, _ = self._abstract_step_args()
        key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        names = {f"param_arrays[{i}]": n
                 for i, n in enumerate(self._param_names)}
        for i, n in enumerate(self._param_names):
            for k in (self._opt_state[i] or {}):
                names[f"opt_state[{i}][{k!r}]"] = f"{n}/{k}"
        audit = linter.check_sharded(
            built, p_sds, s_sds, sstate,
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.float32), key,
            *[sds(a) for a in flat],
            name="train_step", param_names=names,
            mesh_axes=dict(self.mesh.shape), guard=False)
        self.comm_audit = audit
        return audit

    def sharding_audit(self, *batch, lint=None, plan=None):
        """The sharded audit alone (ISSUE 15): compile the step under
        its mesh for this batch's shapes and statically inventory /
        lint its collectives. Returns the analysis.ShardingAudit (also
        on `self.comm_audit`); `plan` overrides the linter's CommPlan.
        Requires a mesh — without one there is no SPMD partition to
        audit."""
        if self.mesh is None:
            raise ValueError("sharding_audit requires TrainStep(mesh=...) "
                             "— an unsharded step has no communication "
                             "plan to prove")
        from ..analysis import GraphLint
        linter = GraphLint.coerce(lint) or self._lint or GraphLint()
        if plan is not None:
            import copy
            linter = copy.copy(linter)
            linter.comm_plan = plan
        if self._opt_state is None:
            self._opt_state = self._init_opt_state()
            self._apply_param_shardings()
        arrays = _tree_unwrap(batch)
        flat, treedef = jax.tree.flatten(arrays)
        _, _, sstate = self._abstract_step_args()
        audit = self._sharded_audit(linter, treedef, flat, sstate)
        self._plan_guard(linter, audit.findings)
        linter._guard(audit.findings, "train_step")
        return audit

    def _maybe_lint(self, treedef, flat):
        """TrainStep(lint=...): one audit before the first compile (the
        guard-mode raise happens while nothing has executed yet)."""
        if self._lint is None or self._lint_done:
            return
        self._lint_done = True
        self._lint_check(self._lint, treedef, flat)

    # ------------------------------------------------------------------
    def loss_and_grad_norm(self, *batch, key=None):
        """(loss, global grad norm) WITHOUT updating — the distributed-vs-
        single-device parity probe (reference strategy: test_dist_base.py:899
        compares distributed loss against a single-process replay). Pass the
        same `key` to both runs for identical dropout/rng."""
        params = self._params
        loss_fn = self.loss_fn
        arrays = _tree_unwrap(batch)
        flat, treedef = jax.tree.flatten(arrays)
        key_sig = ("lgn", treedef,
                   tuple((tuple(a.shape), str(a.dtype)) for a in flat))
        cached = self._compiled.get(key_sig)
        if cached is not None:
            if self.mesh is not None:
                flat = [self._to_global(a, P(*self.data_axes))
                        if a.ndim > 0 else a for a in flat]
            loss, gn = cached(tuple(p._data for p in params),
                              key if key is not None else jax.random.PRNGKey(0),
                              *flat)
            return float(loss), float(gn)

        def f(param_arrays, k, *flat_batch):
            b = jax.tree.unflatten(treedef, flat_batch)

            def loss_of(pa):
                with _trace_guard(), _swap_params(params, list(pa)), \
                        _random.trace_key_scope(k), autograd.no_grad():
                    out = loss_fn(*_tree_wrap(b))
                arr = out._data if isinstance(out, Tensor) else out
                return arr.astype(jnp.float32)

            loss, grads = jax.value_and_grad(loss_of)(list(param_arrays))
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in grads))
            return loss, gn

        kwargs = {}
        if self.mesh is not None:
            pspecs = tuple(_spec_or_replicated(p) for p in params)
            flat_specs = [P(*self.data_axes) if a.ndim > 0 else P()
                          for a in flat]
            kwargs = dict(in_shardings=(
                tuple(self._placement(s) for s in pspecs), None,
                *[self._placement(s) for s in flat_specs]))
            if self._opt_state is None:
                self._opt_state = self._init_opt_state()
            self._apply_param_shardings()
            flat = [self._to_global(a, P(*self.data_axes))
                    if a.ndim > 0 else a for a in flat]
        if key is None:
            key = jax.random.PRNGKey(0)
        compiled = named_program(f, GRAD_PROBE_PROGRAM, **kwargs)
        self._compiled[key_sig] = compiled
        loss, gn = compiled(tuple(p._data for p in params), key, *flat)
        return float(loss), float(gn)

    def _abstract_opt_state(self):
        """Optimizer-state tree as ShapeDtypeStructs — no arrays allocated
        (jax.eval_shape over init_state). Lets memory planning for very
        large models run without materializing moments."""
        out = []
        for p, n in zip(self._params, self._param_names):
            sds = jax.ShapeDtypeStruct(p._data.shape, p._data.dtype)

            def init(a, _p=p, _n=n):
                try:
                    return self.optimizer.init_state(a, param_obj=_p, name=_n)
                except TypeError:
                    return self.optimizer.init_state(a)

            out.append(jax.eval_shape(init, sds))
        return out

    def memory_plan(self, axes: Optional[Dict[str, int]] = None) -> Dict:
        """Analytic per-device HBM accounting from shapes + PartitionSpecs
        (the "jax.eval_shape math" plan; reference capability anchor:
        group_sharded_stage3.py:60 gather-on-use memory arithmetic).

        axes: mesh axis sizes to divide by — defaults to self.mesh's. Pass a
        hypothetical dict (e.g. a v4-64 factorization) to extrapolate the
        plan to meshes this host cannot build. Returns bytes/device for
        params, grads (same layout as params), and optimizer state.
        """
        if axes is None:
            axes = dict(self.mesh.shape) if self.mesh is not None else {}

        def div_of(spec, shape):
            d = 1
            for e, s in zip(tuple(spec or ()), shape):
                names = (e,) if isinstance(e, str) else tuple(e or ())
                for nm in names:
                    d *= axes.get(nm, 1)
            return d

        state = self._opt_state or self._abstract_opt_state()
        plan = {"params": 0, "grads": 0, "opt_state": 0}
        for p, st in zip(self._params, state):
            spec = _spec_or_replicated(p)
            nbytes = int(np.prod(p._data.shape)) * p._data.dtype.itemsize
            per_dev = nbytes // div_of(spec, p._data.shape)
            plan["params"] += per_dev
            plan["grads"] += per_dev
            sspec = _opt_state_spec(p, self.optimizer)
            for k, arr in (st or {}).items():
                s = self.optimizer.state_spec(p, k, arr, sspec)
                plan["opt_state"] += (int(np.prod(arr.shape))
                                      * jnp.dtype(arr.dtype).itemsize
                                      ) // div_of(s, arr.shape)
        plan["total"] = sum(plan.values())
        plan["axes"] = dict(axes)
        return plan

    def aot_lower(self, *batch):
        """Lower the full step ahead-of-time from ABSTRACT inputs (params,
        optimizer state, and batch as ShapeDtypeStructs — nothing is
        materialized or executed). `batch` leaves may be
        jax.ShapeDtypeStruct or arrays. Returns the jax Lowered: its
        as_text() is the program handed to the compiler, compile() the
        executable the step would run."""
        abstract_state = self._abstract_opt_state()
        saved = self._opt_state
        self._opt_state = abstract_state
        try:
            # array leaves go in by shape and dtype only: where they sit
            # must not pin the lowering (a caller's own ShapeDtypeStruct,
            # sharding included, is kept as it is)
            flat, treedef = jax.tree.flatten(tuple(
                b if isinstance(b, jax.ShapeDtypeStruct)
                else self._sds(b._data if isinstance(b, Tensor)
                               else jnp.asarray(b))
                for b in batch))
            built = self._build(treedef, [len(a.shape) for a in flat])
            p_sds = tuple(jax.ShapeDtypeStruct(p._data.shape, p._data.dtype)
                          for p in self._params)
            s_sds = tuple(abstract_state)
            key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
            sstate = None
            if self._scaler is not None:
                sstate = tuple(jax.ShapeDtypeStruct((), d)
                               for d in (jnp.float32, jnp.int32, jnp.int32))
            return built.lower(
                p_sds, s_sds, sstate, jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.float32), key, *flat)
        finally:
            self._opt_state = saved

    def aot_compile(self, *batch):
        """`aot_lower(*batch).compile()`: the compiled step, for its
        as_text() (which kernels and collectives are in it) and its
        memory_analysis()."""
        return self.aot_lower(*batch).compile()

    def aot_memory_analysis(self, *batch):
        """XLA's buffer-assignment memory analysis of the ahead-of-time
        compiled step: the compiler-accounted per-device argument/output/
        temp bytes, i.e. the true activation+workspace footprint of the
        chosen remat/pipeline schedule."""
        return self.aot_compile(*batch).memory_analysis()

    def _register_memz(self):
        """Register params/opt-state as HBM-ledger owners (ISSUE 18) —
        after the first compile, once opt state has materialized at its
        final (possibly cast) dtypes. Reader-backed: the ledger reads
        host-side nbytes metadata, never device values."""
        if self.memz is None or self._memz_registered:
            return
        self._memz_registered = True
        self.memz.register(
            "train_params",
            lambda: int(sum(p._data.nbytes for p in self._params)),
            kind="params", replace=True)
        self.memz.register(
            "train_opt_state",
            lambda: int(sum(getattr(leaf, "nbytes", 0)
                            for leaf in jax.tree.leaves(
                                self._opt_state or ()))),
            kind="opt_state", replace=True)
        self.memz.sample("train_params", "train_opt_state")
        if self.monitor is not None and getattr(self.monitor, "memz",
                                                None) is None:
            # per-record memory samples now read the ledger's host
            # counters instead of rationing live-array scans (r7 fix)
            self.monitor.memz = self.memz

    def _launch(self, compiled, *args):
        """Run one compiled launch; a device allocation failure dumps the
        OOM post-mortem (census + growth curve + the offending step)
        before re-raising — RESOURCE_EXHAUSTED leaves with a named
        owner attached."""
        try:
            return compiled(*args)
        except BaseException as e:
            if self.memz is not None:
                from ..obs.memz import looks_like_oom
                if looks_like_oom(e):
                    self.memz.post_mortem(
                        error=e,
                        context={"site": "train_step.launch",
                                 "step": self._step_i})
            raise

    def run_steps(self, n_steps: int, *stacked_batch):
        """Run `n_steps` steps from batches stacked on dim 0 ([n, ...] per
        leaf), one compiled launch. Returns the per-step losses Tensor."""
        tl = self.timeline if self.timeline is not None else _tl_current()
        tl_t0 = tl.now() if tl is not None else None
        if self._opt_state is None:
            self._opt_state = self._init_opt_state()
            self._apply_param_shardings()
        arrays = _tree_unwrap(stacked_batch)
        flat, treedef = jax.tree.flatten(arrays)
        key_sig = ("scan", n_steps,
                   tuple((tuple(a.shape), str(a.dtype)) for a in flat))
        compiled = self._compiled.get((treedef, key_sig))
        was_compile = compiled is None
        if compiled is None:
            # lint audits the SINGLE-step pure function with per-step
            # batch slices — the scan wrapper adds only the loop carry
            self._maybe_lint(treedef, [
                jax.ShapeDtypeStruct(tuple(a.shape[1:]), a.dtype)
                for a in flat])
            # scan length is part of the kind: different n_steps is a
            # deliberately different executable (warmup vs timed runs),
            # not shape instability — only same-length re-traces count
            self._on_compile(f"train_step.run_steps[n={n_steps}]", key_sig)
            compiled = self._build_scan(treedef, n_steps)
            self._compiled[(treedef, key_sig)] = compiled
        self._register_memz()
        lr = jnp.float32(self.optimizer.get_lr())
        key = _random.split_key()
        if self.mesh is not None:
            flat = [self._to_global(a, P(None, *self.data_axes))
                    if a.ndim > 1 else a for a in flat]
        t0 = time.perf_counter() if self.monitor is not None else None
        losses, new_params, new_state, new_sstate, auxs = self._launch(
            compiled,
            tuple(p._data for p in self._params), tuple(self._opt_state),
            self._scaler_state_in(), jnp.int32(self._step_i + 1), lr, key,
            *flat)
        if self.monitor is not None:
            # launch wall time (includes waiting on the previous launch's
            # donated buffers — the steady-state device rate from the 2nd
            # launch on; fence with a host read for an exact figure)
            self.monitor.end_step(steps=n_steps,
                                  wall_s=time.perf_counter() - t0)
        tl_t1 = tl.now() if tl is not None else None
        self._step_i += n_steps
        if tl is not None:
            # the whole launch is one span: a cache-miss call is compile
            # badput (trace + XLA compile dominate), a steady call is
            # `step` goodput; `step` names the LAST step of the window
            tl.record("compile" if was_compile else "step", tl_t0, tl_t1,
                      step=self._step_i, steps=n_steps)
        for p, na in zip(self._params, new_params):
            p._data = na
            p._node = None
        self._opt_state = list(new_state)
        if self._numerics is not None:
            # the fetched stats (and hence any dump) describe the LAST step
            # of the launch — record that step's batch slice and the key the
            # scan actually used for it, so the dump replays that step
            self._last_batch_struct = jax.tree.map(lambda a: a[-1], arrays)
            self._last_key = jax.random.split(key, n_steps)[-1]
        # aux leaves are stacked [n_steps, ...]; keep the last step's view
        # (still device arrays — no sync)
        last_aux = jax.tree.map(lambda v: v[-1], auxs) if auxs else auxs
        self._after_step(losses, new_sstate, last_aux, steps=n_steps)
        self._post_step()
        return Tensor(losses)

    def __call__(self, *batch):
        tl = self.timeline if self.timeline is not None else _tl_current()
        tl_t0 = tl.now() if tl is not None else None
        if self._opt_state is None:
            self._opt_state = self._init_opt_state()
            self._apply_param_shardings()
        arrays = _tree_unwrap(batch)
        flat, treedef = jax.tree.flatten(arrays)
        key_sig = tuple((tuple(a.shape), str(a.dtype)) for a in flat)
        compiled = self._compiled.get((treedef, key_sig))
        was_compile = compiled is None
        if compiled is None:
            self._maybe_lint(treedef, flat)
            self._on_compile("train_step", key_sig)
            compiled = self._build(treedef, [a.ndim for a in flat])
            self._compiled[(treedef, key_sig)] = compiled
        self._register_memz()

        self._step_i += 1
        lr = jnp.float32(self.optimizer.get_lr())
        key = _random.split_key()
        if self.mesh is not None:
            flat = [self._to_global(a, P(*self.data_axes))
                    if a.ndim > 0 else a for a in flat]
        t0 = time.perf_counter() if self.monitor is not None else None
        loss, new_params, new_state, new_sstate, aux = self._launch(
            compiled,
            tuple(p._data for p in self._params), tuple(self._opt_state),
            self._scaler_state_in(), jnp.int32(self._step_i), lr, key, *flat)
        if self.monitor is not None:
            self.monitor.end_step(wall_s=time.perf_counter() - t0)
        if tl is not None:
            tl.record("compile" if was_compile else "step", tl_t0, tl.now(),
                      step=self._step_i)

        for p, na in zip(self._params, new_params):
            p._data = na
            p._node = None
        self._opt_state = list(new_state)
        self._last_batch_struct = arrays
        self._last_key = key
        self._after_step(loss, new_sstate, aux)
        self._post_step()
        if isinstance(self.optimizer._lr, object) and hasattr(self.optimizer._lr, "step") \
                and not isinstance(self.optimizer._lr, (int, float)):
            pass  # user drives scheduler.step() per reference convention
        return Tensor(loss)
