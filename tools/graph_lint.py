#!/usr/bin/env python
"""Audit the framework's standard executables with the static-analysis
suite (paddle_tpu.analysis) and print a findings table.

Targets (--all = every one):

  gpt-paged    the serving engine's {prefill_paged, decode_paged} pair,
               captured from a real warmup batch (bf16 model: the serving
               dtype story the dtype-promotion pass audits) — donated
               block pools cross-checked against the lowered modules'
               input_output_alias tables
  gpt-paged-int8  the int8 paged engine WITH the prefix cache: the int8
               {prefill, decode} pair plus the suffix-prefill and COW
               executables (warmup traffic repeats + diverges a prompt
               so every admission path lowers)
  gpt-paged-spec  the SPECULATIVE engine (ISSUE 11): prefix cache + trie
               drafting, so the [B, k] verify executable lowers alongside
               prefill / decode / COW / suffix-prefill — donation and
               host-transfer audited over the whole spec set, and the
               run asserts the steady loop added zero jit cache misses
               (the zero-recompile invariant, proven not claimed)
  train-step   TrainStep(gpt) — traced abstractly (never executed):
               host-transfer / dtype / baked-const / donation over the
               fused fwd+bwd+optimizer step
  resnet50     the vision forward executable (+ its TrainStep with
               --vision-train), channels-last flag as configured

Sharded targets (ISSUE 15 — run on an 8-device host-platform CPU mesh,
XLA_FLAGS=--xla_force_host_platform_device_count=8 is set automatically
when one is requested; nothing executes, the step is lowered + compiled
and its post-SPMD HLO statically audited):

  train-step-dp   TrainStep(gpt) on a {"dp": 8} mesh. Declared CommPlan:
                  all-reduce only (grad sync + loss reductions) — ANY
                  other collective kind is a partitioner-inserted
                  resharding and fails the plan check. Plus the full
                  abstract pass suite and the resharding/replication
                  sharding passes.
  train-step-tp   the same step on a {"dp": 2, "mp": 4} hybrid mesh.
                  CommPlan: all-reduce + all-gather (TP activation
                  traffic); the vocab-parallel table gather arrives
                  allowlisted with its documented reason.
  comm-xcheck     static-vs-runtime bytes cross-check: compile the
                  mini-step twin of the checked-in trace fixture
                  (tests/fixtures/mini_step.trace.json.gz) and assert
                  the static collective-bytes table matches the runtime
                  trace-ledger bytes per collective kind within
                  --xcheck-rtol (default 1%).
  gpt-paged-sharded  the MULTI-CHIP paged engine (ISSUE 16): serve a real
                  warmup batch at --shards (default 4) on the host-
                  platform mesh, then statically prove the whole paged
                  executable set — the abstract pass suite (pool donation
                  included), a zero-steady-state-recompile loop, and the
                  compiled-HLO sharding audit of every executable against
                  the DECLARED serving CommPlan: model executables are
                  exactly 2*num_layers mp-group all-reduces (one per
                  row-parallel matmul), the COW copy is zero collectives
                  (shard-local by plan). A partitioner-inserted KV
                  gather/resharding fails the plan check with the op
                  named.

--plant-reshard is a self-test of the detector: it gives one layer's
weight a deliberately wrong pspec on the sharded train-step targets and
INVERTS the expectation — exit 0 only if the planted resharding is
detected and named, 1 if the lint missed it.

Exit status: 0 = clean (allowlisted findings are clean — each carries its
documented reason; with --plant-reshard: the planted resharding was
detected), 1 = active findings at/above --fail-on (comm-plan violations
and a failed comm-xcheck land here; with --plant-reshard: the planted
resharding was MISSED), 2 = bad usage.

    python tools/graph_lint.py --all
    python tools/graph_lint.py train-step-dp train-step-tp comm-xcheck
    python tools/graph_lint.py --target gpt-paged --json
    python tools/graph_lint.py --all --fail-on error --allow my_allow.json
    python tools/graph_lint.py train-step-dp --plant-reshard
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

TARGETS = ("gpt-paged", "gpt-paged-int8", "gpt-paged-spec",
           "train-step", "resnet50",
           "train-step-dp", "train-step-tp", "train-step-int8",
           "comm-xcheck", "gpt-paged-sharded")
#: targets that need the multi-device host-platform mesh
SHARDED_TARGETS = ("train-step-dp", "train-step-tp", "train-step-int8",
                   "comm-xcheck", "gpt-paged-sharded")

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tests", "fixtures",
    "mini_step.trace.json.gz")


def _tiny_gpt(dtype="bfloat16"):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=128,
                    intermediate_size=128, param_dtype=dtype)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model, cfg


def audit_gpt_engine(lint, *, int8: bool = False,
                     prefix: bool = False, spec: bool = False):
    """Serve one warmup batch through the real engine with lint enabled;
    the engine captures + audits its executables itself. With `prefix`
    the traffic repeats a block-aligned prompt (COW executable) and
    diverges from it mid-prefix (suffix-prefill executable), so the
    whole prefix-cache executable set lowers and is audited. With `spec`
    (ISSUE 11) the repeated prompt's decode drafts the first run's
    cached chain from the trie, so the [B, k] verify executable lowers
    too — and the target additionally PROVES the zero-recompile
    invariant: a steady spec loop after warmup must add zero jit cache
    misses."""
    import numpy as np
    from paddle_tpu.inference import ServingConfig, ServingEngine
    model, _ = _tiny_gpt()
    cfg = ServingConfig(max_batch=2, prompt_cap=8, max_new_tokens=6,
                        decode_chunk=2, eos_token_id=None,
                        kv_block=4, lint=lint,
                        cache_dtype="int8" if int8 else None,
                        prefix_cache=prefix,
                        kv_blocks=65 if spec else
                        (33 if prefix else None),
                        spec_decode=spec)
    eng = ServingEngine(model, cfg)
    rng = np.random.RandomState(0)
    eng.submit(rng.randint(1, 100, (5,)))
    eng.submit(rng.randint(1, 100, (8,)))
    eng.drain()
    if prefix:
        # the shared warmup choreography: aligned miss + COW repeat +
        # mid-prefix divergence, so every admission executable lowers
        eng.warmup_prefix_cache(100, clear=False)
    if spec:
        from paddle_tpu.jit.api import compile_cache_misses
        miss0 = compile_cache_misses()
        for _ in range(2):                 # steady repeats: trie-drafted
            eng.submit(rng.randint(1, 100, (8,)))
            eng.drain()
        p = rng.randint(1, 100, (8,))
        for _ in range(2):
            eng.submit(p)
            eng.drain()
        dm = compile_cache_misses() - miss0
        if dm:
            raise SystemExit(f"gpt-paged-spec: steady speculative loop "
                             f"added {dm} jit cache miss(es) — the "
                             f"zero-recompile invariant is broken")
        if eng.metrics.counters["spec_windows"] < 1:
            # not an assert: under python -O it would vanish and the
            # target would silently audit only the non-spec executables
            raise SystemExit("gpt-paged-spec: warmup never ran a verify "
                             "window — the speculative executable was "
                             "never lowered, nothing was audited")
    return eng.lint_findings


def audit_gpt_engine_sharded(lint, shards: int = 4, audits=None):
    """Multi-chip sharded serving audit (ISSUE 16): run a real warmup
    batch through a head-sharded paged engine on the host-platform mesh,
    then prove the plan statically —

      1. abstract pass suite over every captured executable (host
         transfer, dtype, baked consts, POOL DONATION via the
         input_output_alias cross-check);
      2. zero steady-state recompiles: post-warmup traffic at the same
         shard count must add zero jit cache misses;
      3. compiled-HLO sharding audit of each executable under the mesh
         against the DECLARED serving CommPlan
         (analysis.commplan.serving_comm_plan): prefill/decode/verify
         are EXACTLY 2*num_layers mp-group all-reduces (the row-parallel
         matmuls) and nothing else; the COW block copy is ZERO
         collectives (shard-locality, proven not claimed). Any
         partitioner-inserted KV gather shows up as comm_extra with the
         op named and fails the run.
    """
    import numpy as np
    from paddle_tpu.analysis import Findings, lint_capture
    from paddle_tpu.analysis.commplan import serving_comm_plan
    from paddle_tpu.analysis.lint import _kind_name
    from paddle_tpu.inference import ServingConfig, ServingEngine
    from paddle_tpu.jit.api import compile_cache_misses
    model, mcfg = _tiny_gpt()
    cfg = ServingConfig(max_batch=2, prompt_cap=8, max_new_tokens=6,
                        decode_chunk=2, eos_token_id=None,
                        kv_block=4, shards=shards)
    eng = ServingEngine(model, cfg)
    rng = np.random.RandomState(0)
    with lint_capture() as calls:
        eng.submit(rng.randint(1, 100, (5,)))
        eng.submit(rng.randint(1, 100, (8,)))
        eng.drain()
    if not calls:
        raise SystemExit("gpt-paged-sharded: warmup captured no "
                         "executables — nothing was audited")

    # zero steady-state recompiles at this shard count
    miss0 = compile_cache_misses()
    for _ in range(2):
        eng.submit(rng.randint(1, 100, (7,)))
        eng.drain()
    dm = compile_cache_misses() - miss0
    if dm:
        raise SystemExit(f"gpt-paged-sharded: steady sharded loop added "
                         f"{dm} jit cache miss(es) — a shard-dependent "
                         f"signature component is missing")

    # abstract passes (donation included) over the captured set
    findings = lint.check_calls(calls, guard=False)

    # compiled-HLO sharding audit per unique executable, under the
    # engine's mesh, against the declared serving plan
    model_plan = serving_comm_plan(mcfg.num_layers)
    local_plan = serving_comm_plan(0)     # COW copy: zero collectives
    seen, audited = set(), set()
    with eng._mesh_scope():
        for kind, fn, (args, kwargs) in calls:
            head = kind[0] if isinstance(kind, tuple) else str(kind)
            if not str(head).startswith("paged_"):
                continue
            name = _kind_name(kind)
            if (id(fn), name) in seen:
                continue
            seen.add((id(fn), name))
            plan = local_plan if head == "paged_cow" else model_plan
            audit = lint.check_sharded(fn, *args, name=name, plan=plan,
                                       mesh_axes={"mp": shards},
                                       guard=False, **kwargs)
            findings.extend(audit.findings)
            audited.add(str(head))
            if audits is not None:
                audits[name] = audit
    if "paged_decode" not in audited:
        raise SystemExit("gpt-paged-sharded: the decode executable was "
                         "never captured/audited — the comm-plan gate "
                         "proved nothing")
    return findings


def audit_train_step(lint):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import optimizer as opt
    from paddle_tpu.jit.train_step import TrainStep
    model, cfg = _tiny_gpt()
    model.train()
    o = opt.AdamW(parameters=model.parameters(), learning_rate=1e-4)

    def loss_fn(ids, labels):
        return model.loss(ids, labels)

    ts = TrainStep(model, o, loss_fn)
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 16)))
    return ts.lint(ids, ids, lint=lint)


def audit_resnet50(lint, train: bool = False):
    import numpy as np
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.core import autograd
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit.api import _swap_params, _trace_guard
    from paddle_tpu.vision.models.resnet import resnet50
    paddle.seed(0)
    model = resnet50()
    model.eval()
    params = [p for _, p in model.named_parameters()]
    buffers = [b for _, b in model.named_buffers()]

    def fwd(pa, ba, x):
        with _trace_guard(), _swap_params(params + buffers,
                                          list(pa) + list(ba)), \
                autograd.no_grad():
            return model(Tensor(x))._data

    sds = lambda t: jax.ShapeDtypeStruct(tuple(t.shape), t.dtype)  # noqa
    findings = lint.check(
        fwd, tuple(sds(p._data) for p in params),
        tuple(sds(b._data) for b in buffers),
        jax.ShapeDtypeStruct((2, 3, 224, 224), "float32"),
        name="resnet50_forward")
    if train:
        from paddle_tpu import optimizer as opt, nn
        from paddle_tpu.jit.train_step import TrainStep
        model.train()
        o = opt.Momentum(parameters=model.parameters(), learning_rate=0.1)
        ce = nn.CrossEntropyLoss()

        def loss_fn(x, y):
            return ce(model(x), y)

        ts = TrainStep(model, o, loss_fn)
        x = jax.ShapeDtypeStruct((2, 3, 224, 224), "float32")
        y = jax.ShapeDtypeStruct((2,), "int64")
        findings.extend(ts.lint(x, y, lint=lint))
    return findings


def audit_train_step_sharded(lint, axes, plan=None, plant=False,
                             audits=None):
    """Sharded train-step audit (ISSUE 15): TrainStep(gpt) under a mesh,
    audited end-to-end through TrainStep.lint — the abstract pass suite
    PLUS the compiled-HLO sharding passes and the target's CommPlan.
    With `plant`, one layer's weight gets a deliberately wrong pspec and
    the run asserts the resharding is detected and NAMED (the detector's
    self-test); detection inverts into a clean exit."""
    import numpy as np
    import jax
    from jax.sharding import PartitionSpec as P
    import paddle_tpu as paddle
    from paddle_tpu import optimizer as opt
    from paddle_tpu.analysis import Findings
    from paddle_tpu.jit.train_step import TrainStep
    import paddle_tpu.distributed as dist
    mesh = dist.build_mesh(axes)
    dist.set_mesh(mesh)
    try:
        model, cfg = _tiny_gpt()
        model.train()
        planted = "gpt.h.0.mlp.up.weight"
        if plant:
            model.gpt.h[0].mlp.up.weight.pspec = P("dp", None)
        o = opt.AdamW(parameters=model.parameters(), learning_rate=1e-4)
        ts = TrainStep(model, o, lambda ids, lab: model.loss(ids, lab),
                       mesh=mesh)
        linter = copy.copy(lint)
        linter.comm_plan = None if plant else plan
        ids = jax.ShapeDtypeStruct((8, 16), "int64")
        findings = ts.lint(ids, ids, lint=linter)
        if audits is not None and ts.comm_audit is not None:
            audits[f"train-step-{'x'.join(map(str, axes.values()))}"] = \
                ts.comm_audit
        if plant:
            hits = [f for f in findings if f.code == "param_gather"
                    and planted in (f.where or "")]
            if not hits:
                raise SystemExit(
                    f"--plant-reshard: the planted wrong pspec on "
                    f"{planted} was NOT detected — the resharding pass "
                    f"is blind")
            print(f"  plant-reshard: detected and named — {hits[0]}",
                  file=sys.stderr)
            # detection is the pass criterion; the planted findings must
            # not fail the run
            return Findings()
        return findings
    finally:
        dist.set_mesh(None)


def audit_train_step_int8(lint, audits=None, min_ratio: float = 3.5):
    """Quantized gradient-sync audit (ISSUE 20): the dp=8 tiny-GPT
    TrainStep is built twice — the f32 twin (implicit partitioner psum)
    and ``grad_comm="int8"`` — both statically audited, and two
    invariants gated:

      1. the int8 inventory satisfies ``train_comm_plan`` — the s8
         per-layer-group all-reduces are present and every f32 all-reduce
         stays under the side-channel byte cap (an eighth of the twin's
         gradient-sync bytes): an f32 gradient all-reduce sneaking back
         (fallback-classifier regression, shard_map bypass) fails here;
      2. the static all-reduce bytes-per-step drop >= ``min_ratio`` vs
         the twin (the EQuARX ~4x wire cut, measured on the very HLO that
         will run).
    """
    import jax
    from paddle_tpu import optimizer as opt
    from paddle_tpu.analysis import Finding, Findings, train_comm_plan
    from paddle_tpu.jit.train_step import TrainStep
    import paddle_tpu.distributed as dist
    mesh = dist.build_mesh({"dp": 8})
    dist.set_mesh(mesh)
    try:
        ids = jax.ShapeDtypeStruct((8, 16), "int64")

        def build(mode):
            model, _ = _tiny_gpt("float32")
            model.train()
            o = opt.AdamW(parameters=model.parameters(),
                          learning_rate=1e-4)
            return TrainStep(model, o,
                             lambda i, l: model.loss(i, l),
                             mesh=mesh, grad_comm=mode)

        def ar_bytes(audit):
            return sum(r.get("bytes") or 0 for r in audit.rows
                       if r.get("kind") == "all-reduce")

        twin_audit = build(None).sharding_audit(ids, ids)
        twin_b = ar_bytes(twin_audit)
        ts = build("int8")
        plan = train_comm_plan(len(ts._comm_groups), dtype="int8",
                               max_f32_bytes=max(twin_b // 8, 1))
        linter = copy.copy(lint)
        linter.comm_plan = plan
        audit = ts.sharding_audit(ids, ids, lint=linter)
        findings = Findings()
        findings.extend(audit.findings)
        int8_b = ar_bytes(audit)
        ratio = twin_b / max(int8_b, 1)
        print(f"  train-step-int8: all-reduce bytes/step "
              f"{twin_b} (f32 twin) -> {int8_b} (int8), "
              f"ratio {ratio:.2f}x (gate >= {min_ratio}x)",
              file=sys.stderr)
        if ratio < min_ratio:
            findings.add(Finding(
                "comm_plan", "comm_bytes", "error",
                f"int8 gradient sync moves {int8_b} all-reduce "
                f"bytes/step vs the f32 twin's {twin_b} — only "
                f"{ratio:.2f}x, gate requires >= {min_ratio}x "
                f"(quantized lanes regressed or fallback grew)",
                where="all-reduce", executable="train-step-int8",
                data={"twin_bytes": twin_b, "int8_bytes": int8_b,
                      "ratio": ratio, "min_ratio": min_ratio}))
        if audits is not None and ts.comm_audit is not None:
            audits["train-step-int8"] = ts.comm_audit
        return findings
    finally:
        dist.set_mesh(None)


def audit_comm_xcheck(rtol: float = 0.01, audits=None):
    """Static-vs-runtime cross-check (ISSUE 15 acceptance): compile the
    jitted twin of the checked-in mini-step fixture — one dp=8 grad-sync
    all-reduce moving the fixture's 1 MiB per step — and assert the
    static inventory's bytes match the runtime trace ledger's per-step
    bytes per collective kind within `rtol`. A mismatch is a Finding
    (exit 1), not an assert: the table prints either way."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.analysis import (Finding, Findings,
                                     collective_inventory,
                                     compiled_hlo_text)
    from paddle_tpu.obs.collectives import CollectiveLedger

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp",))
    # the twin: a data-parallel partial-sum + all-reduce whose buffer is
    # exactly the fixture's bytes_accessed (f32[131072]: 0.5 MiB operand
    # + 0.5 MiB output = 1 MiB per step)
    jfn = jax.jit(lambda x: jnp.sum(x, axis=0),
                  in_shardings=(NamedSharding(mesh, P("dp", None)),),
                  out_shardings=NamedSharding(mesh, P()))
    text = compiled_hlo_text(
        jfn, jax.ShapeDtypeStruct((8, 131072), jnp.float32))
    rows = collective_inventory(text, "mini_step_twin")
    ledger = CollectiveLedger.from_trace(FIXTURE, steps=2)
    diff = ledger.check_static(rows, rtol=rtol)
    findings = Findings()
    for d in diff:
        rel = f"{d['rel_err'] * 100:.2f}%" if d["rel_err"] is not None \
            else "-"
        if not d["ok"]:
            findings.add(Finding(
                "sharding", "static_runtime_bytes", "error",
                f"{d['kind']}: static {d['static_bytes']} B/step vs "
                f"runtime {d['runtime_bytes']} B/step "
                f"(rel err {rel}, rtol {rtol:.0%}) — the audited "
                f"executable is not the one the trace measured",
                where=d["kind"], executable="comm-xcheck", data=d))
    if audits is not None:
        audits["comm-xcheck"] = {"diff": diff,
                                 "rows": [dict(r) for r in rows]}
    return findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n")[0],
        epilog="Exit status: 0 = clean (allowlisted findings count as "
               "clean; with --plant-reshard: planted resharding "
               "detected), 1 = active findings at/above --fail-on "
               "(comm-plan violations and comm-xcheck byte mismatches "
               "included; with --plant-reshard: detection MISSED), "
               "2 = bad usage.")
    ap.add_argument("targets", nargs="*", metavar="TARGET",
                    help=f"targets to audit (positional form of "
                         f"--target; one of {', '.join(TARGETS)})")
    ap.add_argument("--all", action="store_true",
                    help="audit every target")
    ap.add_argument("--target", choices=TARGETS, action="append",
                    default=None)
    ap.add_argument("--fail-on", choices=("info", "warn", "error"),
                    default="warn",
                    help="exit 1 when a non-allowlisted finding at/above "
                         "this severity survives (default warn)")
    ap.add_argument("--allow", default=None,
                    help="JSON allowlist file (list of entry dicts) "
                         "appended to the built-in allowlist")
    ap.add_argument("--vision-train", action="store_true",
                    help="also audit TrainStep(resnet50) — slower trace")
    # thresholds default LOW: the audited models are CPU-sized toys, and
    # the point is to see every site — deliberate ones arrive allowlisted
    # with their documented reason, so low thresholds still exit 0
    ap.add_argument("--upcast-bytes", type=int, default=256)
    ap.add_argument("--const-bytes", type=int, default=1 << 16)
    ap.add_argument("--donate-bytes", type=int, default=1 << 16)
    # replicated-parameter threshold stays at 1 MiB by default: the toy
    # models' replicated layernorm/bias params are design, not findings
    ap.add_argument("--replicated-bytes", type=int, default=1 << 20)
    ap.add_argument("--plant-reshard", action="store_true",
                    help="self-test: plant a wrong pspec on one layer "
                         "of the sharded train-step targets and require "
                         "the resharding pass to detect + name it")
    ap.add_argument("--xcheck-rtol", type=float, default=0.01,
                    help="comm-xcheck static-vs-runtime bytes tolerance "
                         "(default 1%%)")
    ap.add_argument("--shards", type=int, default=4,
                    help="mp degree for gpt-paged-sharded (default 4; "
                         "must divide the toy model's 4 heads)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report: per-target findings, "
                         "the static comm tables of the sharded targets "
                         "and the comm-xcheck diff, plus the active "
                         "count (exit semantics unchanged)")
    args = ap.parse_args(argv)

    bad = [t for t in args.targets if t not in TARGETS]
    if bad:
        ap.error(f"unknown target(s) {bad} (choose from "
                 f"{', '.join(TARGETS)})")
    # dedupe, first mention wins (a target named both positionally and
    # via --target must not be audited/counted twice)
    targets = list(dict.fromkeys(
        list(args.targets) + list(args.target or [])))
    if args.all or not targets:
        targets = list(TARGETS)
    if args.plant_reshard and not any(
            t in ("train-step-dp", "train-step-tp") for t in targets):
        ap.error("--plant-reshard applies to the sharded train-step "
                 "targets (train-step-dp / train-step-tp)")

    # the sharded targets need the virtual multi-device mesh. XLA reads
    # XLA_FLAGS at first BACKEND INIT (not at jax import), so setting it
    # here still works even when jax was imported earlier — only an
    # already-initialized small backend is unrecoverable.
    if any(t in SHARDED_TARGETS for t in targets):
        if "--xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8")
        if "jax" in sys.modules:
            try:
                from jax._src import xla_bridge as _xb
                initialized = bool(getattr(_xb, "_backends", None))
            except Exception:
                initialized = True   # can't tell: probe (may init)
            import jax
            if initialized and len(jax.devices()) < 8:
                print("graph_lint: jax already initialized with "
                      f"{len(jax.devices())} device(s); sharded targets "
                      "need 8 (set XLA_FLAGS="
                      "--xla_force_host_platform_device_count=8 before "
                      "the first jax backend use)",
                      file=sys.stderr)
                return 2

    from paddle_tpu.analysis import (Allowlist, CommPlan, Findings,
                                     GraphLint)
    extra = Allowlist.from_json(args.allow).entries if args.allow else None
    lint = GraphLint(allow=extra, upcast_bytes=args.upcast_bytes,
                     const_bytes=args.const_bytes,
                     donate_bytes=args.donate_bytes,
                     replicated_bytes=args.replicated_bytes)

    audits = {}
    # the declared communication plans of the shipped sharded configs:
    # dp trains on grad-sync all-reduces ALONE; the hybrid tp mesh adds
    # the TP activation all-gathers. Anything else = partitioner crept.
    runners = {
        "gpt-paged": lambda: audit_gpt_engine(lint),
        "gpt-paged-int8": lambda: audit_gpt_engine(lint, int8=True,
                                                   prefix=True),
        "gpt-paged-spec": lambda: audit_gpt_engine(lint, prefix=True,
                                                   spec=True),
        "train-step": lambda: audit_train_step(lint),
        "resnet50": lambda: audit_resnet50(lint,
                                           train=args.vision_train),
        "train-step-dp": lambda: audit_train_step_sharded(
            lint, {"dp": 8}, plan=CommPlan({"all-reduce": "+"}),
            plant=args.plant_reshard, audits=audits),
        "train-step-tp": lambda: audit_train_step_sharded(
            lint, {"dp": 2, "mp": 4},
            plan=CommPlan({"all-reduce": "+", "all-gather": "+"}),
            plant=args.plant_reshard, audits=audits),
        "train-step-int8": lambda: audit_train_step_int8(
            lint, audits=audits),
        "comm-xcheck": lambda: audit_comm_xcheck(
            rtol=args.xcheck_rtol, audits=audits),
        "gpt-paged-sharded": lambda: audit_gpt_engine_sharded(
            lint, shards=args.shards, audits=audits),
    }

    all_findings = Findings()
    report = {}
    for t in targets:
        t0 = time.perf_counter()
        findings = runners[t]() or Findings()
        dt = time.perf_counter() - t0
        report[t] = {"seconds": round(dt, 1),
                     "findings": findings.to_dicts()}
        all_findings.extend(findings)
        if not args.json:
            print(findings.grouped().table(f"{t} ({dt:.1f}s):"))

    if not args.json:
        for key, audit in audits.items():
            if hasattr(audit, "table"):
                print("\n" + audit.table())
            elif isinstance(audit, dict) and "diff" in audit:
                print(f"\n---- Static-vs-runtime bytes ({key}) ----")
                print(f"  {'kind':<20} {'static B/step':>14} "
                      f"{'runtime B/step':>14} {'rel err':>8}")
                for d in audit["diff"]:
                    rel = f"{d['rel_err'] * 100:.2f}%" \
                        if d["rel_err"] is not None else "-"
                    print(f"  {d['kind']:<20} "
                          f"{str(d['static_bytes']):>14} "
                          f"{str(d['runtime_bytes']):>14} {rel:>8}"
                          + ("" if d["ok"] else "  MISMATCH"))

    active = all_findings.active(args.fail_on)
    if args.json:
        report["comm"] = {
            k: (a.to_dict() if hasattr(a, "to_dict") else a)
            for k, a in audits.items()}
        report["active"] = len(active)
        print(json.dumps(report, indent=2))
    else:
        n_allowed = sum(1 for f in all_findings if f.allowed)
        print(f"\ngraph_lint: {len(all_findings)} finding(s), "
              f"{n_allowed} allowlisted, {len(active)} active "
              f"(fail-on {args.fail_on})")
    return 1 if active else 0


if __name__ == "__main__":
    raise SystemExit(main())
