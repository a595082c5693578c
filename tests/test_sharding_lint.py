"""Sharding lint (ISSUE 15): the SPMD communication plan proven
statically, before the job runs.

Covers: the HLO collective inventory (schema-compatible with the runtime
trace ledger, static bytes math, replica-group parsing in both the iota
and explicit forms), the CommPlan default-deny check + CommPlanError,
partitioner-inserted-resharding detection on a PLANTED wrong pspec
(named down to the layer), the large-replicated-parameter pass with its
suggested pspec, the static-vs-runtime bytes cross-check against the
checked-in mini-step fixture, the sharding-aware recompile signature
(ISSUE 15 satellite), the TrainStep(lint=) wiring under a mesh, and the
DEFAULT_ALLOWLIST drift guard."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.analysis import (
    Allowlist, CommPlan, CommPlanError, DEFAULT_ALLOWLIST, Findings,
    GraphLint, abstract_signature, audit_hlo, collective_inventory,
    collective_kind, compiled_hlo_text, diff_ledgers, diff_signatures,
    rows_by_kind)

SDS = jax.ShapeDtypeStruct

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device host mesh")


def _mesh(axes={"dp": 8}):
    return dist.build_mesh(axes)


# ------------------------------------------------------- HLO inventory

_HLO_SNIPPET = """\
HloModule jit_f

ENTRY %main.1 (param.1: f32[8,16], param.2: bf16[4,32]) -> f32[8,16] {
  %param.1 = f32[8,16]{1,0} parameter(0), sharding={replicated}, metadata={op_name="x"}
  %param.2 = bf16[4,32]{1,0} parameter(1), sharding={devices=[8,1]<=[8]}, metadata={op_name="w"}
  ROOT %all-reduce.3 = f32[8,16]{1,0} all-reduce(f32[8,16]{1,0} %param.1), channel_id=1, replica_groups={{0,1},{2,3}}, use_global_device_ids=true, metadata={op_name="jit(f)/jit(main)/add" source_file="/a/b/layer.py" source_line=42}
}
"""


def test_inventory_parses_shapes_groups_and_where():
    rows = collective_inventory(_HLO_SNIPPET, "snippet")
    assert len(rows) == 1
    r = rows[0]
    assert r["name"] == "all-reduce.3" and r["kind"] == "all-reduce"
    # static bytes = operand + output buffer bytes (bytes_accessed twin)
    assert r["bytes"] == 8 * 16 * 4 * 2
    assert r["group_size"] == 2 and r["shapes"] == [[8, 16]]
    assert r["where"] == "layer.py:42 (add)"
    # the runtime-ledger schema rides along, timing columns empty
    for k in ("calls", "dur_us", "busy_us", "overlapped_us",
              "exposed_us", "exposed_frac", "bytes", "bus_gbps"):
        assert k in r
    assert r["dur_us"] is None and r["bus_gbps"] is None


def test_inventory_from_real_compiled_hlo_iota_groups():
    mesh = _mesh()
    jfn = jax.jit(lambda x: jnp.sum(x, axis=0),
                  in_shardings=(NamedSharding(mesh, P("dp", None)),),
                  out_shardings=NamedSharding(mesh, P()))
    text = compiled_hlo_text(jfn, SDS((8, 1024), jnp.float32))
    rows = collective_inventory(text, "psum")
    kinds = rows_by_kind(rows)
    assert set(kinds) == {"all-reduce"}
    # one f32[1024] all-reduce: 4 KiB in + 4 KiB out
    assert kinds["all-reduce"]["bytes"] == 2 * 1024 * 4
    assert rows[0]["group_size"] == 8


def test_entry_param_sharding_and_global_shape():
    from paddle_tpu.analysis.sharding import parse_hlo
    _, entries, _ = parse_hlo(_HLO_SNIPPET)
    assert entries[0].replicated and not entries[0].sharded
    assert entries[1].sharded
    assert entries[1].arg_name == "w"
    # devices=[8,1]: dim 0 sharded 8 ways -> global [32, 32]
    assert entries[1].global_shape == (32, 32)


def test_static_table_renders_with_shared_formatter():
    audit = audit_hlo(_HLO_SNIPPET, executable="snippet")
    table = audit.table()
    assert "all-reduce.3" in table and "per kind" in table
    # the None timing columns render as '-' through the ONE formatter
    assert " - " in table or "-  " in table


# ------------------------------------------------------------ CommPlan

def test_comm_plan_default_deny_and_counts():
    rows = [{"name": "all-reduce.1", "calls": 3, "bytes": 300},
            {"name": "all-gather.2", "calls": 1, "bytes": 100}]
    fs = CommPlan({"all-reduce": "+"}).check(rows, executable="e")
    assert [f.code for f in fs] == ["comm_extra"]
    assert "all-gather" in fs[0].message
    fs = CommPlan({"all-reduce": 3, "all-gather": (1, 2)}).check(rows)
    assert not fs
    fs = CommPlan({"all-reduce": 2, "all-gather": "+"}).check(rows)
    assert [f.code for f in fs] == ["comm_count"]
    fs = CommPlan({"all-reduce": "+", "all-gather": "+",
                   "reduce-scatter": "+"}).check(rows)
    assert [f.code for f in fs] == ["comm_missing"]
    # allow_other flips the default-deny
    assert not CommPlan({"all-reduce": "+"},
                        allow_other=True).check(rows)


def test_comm_plan_verify_raises_structured_error():
    rows = [{"name": "all-gather", "calls": 1, "bytes": 64}]
    with pytest.raises(CommPlanError) as ei:
        CommPlan({"all-reduce": "+"}).verify(rows, executable="step")
    # structured: the findings ride on the error, per the lint schema
    codes = sorted(f.code for f in ei.value.findings)
    assert codes == ["comm_extra", "comm_missing"]
    from paddle_tpu.analysis import GraphLintError
    assert isinstance(ei.value, GraphLintError)


def test_collective_kind_normalization():
    assert collective_kind("all-reduce.37") == "all-reduce"
    assert collective_kind("all-gather-start.2") == "all-gather"
    assert collective_kind("reduce-scatter") == "reduce-scatter"
    assert collective_kind("fusion.3") is None
    bad = pytest.raises(ValueError, CommPlan, {"all-broadcast": "+"})
    assert "unknown collective kind" in str(bad.value)


# ------------------------------------------- resharding / replication

def _tiny_gpt_step(mesh, plant=False):
    from paddle_tpu import optimizer as opt
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=128,
                    intermediate_size=128, param_dtype="bfloat16")
    model = GPTForCausalLM(cfg)
    model.train()
    if plant:
        model.gpt.h[0].mlp.up.weight.pspec = P("dp", None)
    o = opt.AdamW(parameters=model.parameters(), learning_rate=1e-4)
    return TrainStep(model, o, lambda ids, lab: model.loss(ids, lab),
                     mesh=mesh)


def test_planted_resharding_detected_and_named():
    """The acceptance pin: a wrong pspec on ONE layer's weight makes the
    partitioner gather that weight back to replicated every step — the
    pass detects it and names the layer, and the dp CommPlan
    (all-reduce only) independently fails on the same hazard."""
    mesh = _mesh()
    dist.set_mesh(mesh)
    try:
        ts = _tiny_gpt_step(mesh, plant=True)
        audit = ts.sharding_audit(SDS((8, 16), "int64"),
                                  SDS((8, 16), "int64"),
                                  lint=GraphLint())
        hits = [f for f in audit.findings if f.code == "param_gather"]
        assert hits, "planted resharding not detected"
        assert any("gpt.h.0.mlp.up.weight" in f.where for f in hits)
        assert not any(f.allowed for f in hits)
        # the plan check sees the same hazard as forbidden traffic
        with pytest.raises(CommPlanError):
            CommPlan({"all-reduce": "+"}).verify(audit.rows,
                                                 executable="ts")
    finally:
        dist.set_mesh(None)


def test_dp_train_step_lint_clean_and_plan_holds():
    """Shipped dp config: TrainStep(lint=) under a {"dp": 8} mesh runs
    the FULL suite (abstract passes + sharded audit + CommPlan) and
    comes out clean — data parallelism is all-reduce-only traffic."""
    mesh = _mesh()
    dist.set_mesh(mesh)
    try:
        ts = _tiny_gpt_step(mesh)
        lint = GraphLint(comm_plan=CommPlan({"all-reduce": "+"}),
                         upcast_bytes=256, const_bytes=2048,
                         donate_bytes=2048)
        fs = ts.lint(SDS((8, 16), "int64"), SDS((8, 16), "int64"),
                     lint=lint)
        active = fs.active("warn")
        assert not active, [str(f) for f in active]
        assert ts.comm_audit is not None
        kinds = ts.comm_audit.by_kind()
        assert set(kinds) == {"all-reduce"}
        # the audit saw real traffic and sized it
        assert kinds["all-reduce"]["bytes"] > 0
    finally:
        dist.set_mesh(None)


def test_tp_train_step_wte_gather_is_allowlisted():
    """Shipped hybrid tp config: nothing fires. A gather of the
    vocab-parallel table, where the partitioner inserts one, is a REAL
    param-gather finding — reported, but allowlisted with its documented
    reason (scoped to wte). jax 0.9's partitioner no longer inserts it for
    this step (its all-gathers are the packed-qkv activation reshapes), so
    the finding may be absent; any that is there must be that one."""
    mesh = _mesh({"dp": 2, "mp": 4})
    dist.set_mesh(mesh)
    try:
        ts = _tiny_gpt_step(mesh)
        audit = ts.sharding_audit(
            SDS((8, 16), "int64"), SDS((8, 16), "int64"),
            lint=GraphLint(), plan=CommPlan({"all-reduce": "+",
                                             "all-gather": "+"}))
        active = audit.findings.active("warn")
        assert not active, [str(f) for f in active]
        gathers = [f for f in audit.findings
                   if f.code == "param_gather"]
        assert all(f.allowed and "wte" in f.where for f in gathers)
        assert {"all-reduce", "all-gather"} <= set(audit.by_kind())
    finally:
        dist.set_mesh(None)


def test_replicated_param_flagged_with_suggested_pspec():
    mesh = _mesh()
    lint = GraphLint(replicated_bytes=1 << 10)

    def f(w_big, w_sharded, x):
        return (x @ w_sharded) @ w_big

    audit = lint.check_sharded(
        f, SDS((64, 64), jnp.float32), SDS((64, 64), jnp.float32),
        SDS((8, 64), jnp.float32),
        in_shardings=(NamedSharding(mesh, P()),
                      NamedSharding(mesh, P(None, "dp")),
                      NamedSharding(mesh, P("dp", None))),
        name="repl", mesh_axes=dict(mesh.shape))
    hits = [f_ for f_ in audit.findings if f_.code == "replicated_param"]
    assert hits, [str(f_) for f_ in audit.findings]
    assert "w_big" in hits[0].where
    assert hits[0].data["suggested_pspec"] == "P('dp', None)"


def test_replicated_pass_quiet_on_pure_dp():
    """Pure data parallelism replicates every parameter BY DESIGN — no
    float WEIGHT is sharded (only the batch is), so the pass must stay
    silent even for big replicated weights. param_names scopes which
    args are parameters; the dp-sharded float batch is not evidence."""
    mesh = _mesh()
    lint = GraphLint(replicated_bytes=1 << 10)

    def f(w, x):
        return jnp.sum((x @ w) ** 2)

    audit = lint.check_sharded(
        f, SDS((64, 64), jnp.float32), SDS((8, 64), jnp.float32),
        in_shardings=(NamedSharding(mesh, P()),
                      NamedSharding(mesh, P("dp", None))),
        name="dp_only", param_names={"w": "w"},
        mesh_axes=dict(mesh.shape))
    assert not [f_ for f_ in audit.findings
                if f_.code == "replicated_param"]


# --------------------------------------- static-vs-runtime cross-check

def test_static_bytes_match_fixture_ledger_within_1pct():
    """The acceptance pin: the static inventory of the mini-step twin
    matches the checked-in runtime trace ledger's bytes per collective
    kind within 1%."""
    import tools.graph_lint as gl
    findings = gl.audit_comm_xcheck(rtol=0.01)
    assert not findings, [str(f) for f in findings]


def test_diff_ledgers_steps_normalization_and_mismatch():
    static = [{"name": "all-reduce.1", "calls": 1, "bytes": 1000}]
    runtime = [{"name": "all-reduce.9", "calls": 4, "bytes": 4000}]
    d = diff_ledgers(static, runtime, steps=4)
    assert d[0]["ok"] and d[0]["rel_err"] == 0.0
    assert d[0]["runtime_calls"] == 1.0
    d = diff_ledgers(static, runtime, steps=2)   # 2000 B/step vs 1000
    assert not d[0]["ok"] and d[0]["rel_err"] == pytest.approx(0.5)
    # a kind present on one side only is a (non-ok) row, not a crash
    d = diff_ledgers(static, [{"name": "all-gather", "calls": 1,
                               "bytes": 8}])
    assert {r["kind"] for r in d} == {"all-reduce", "all-gather"}
    assert not any(r["ok"] for r in d)


def test_collective_ledger_check_static_roundtrip():
    from paddle_tpu.obs.collectives import CollectiveLedger
    import os
    fixture = os.path.join(os.path.dirname(__file__), "fixtures",
                           "mini_step.trace.json.gz")
    ledger = CollectiveLedger.from_trace(fixture, steps=2)
    static = [{"name": "all-reduce", "calls": 1, "bytes": 1048576}]
    diff = ledger.check_static(static, rtol=0.01)
    assert len(diff) == 1 and diff[0]["ok"]


# --------------------------------- recompile signatures grow sharding

def test_signature_sharding_delta_names_resharded_leaf():
    """ISSUE 15 satellite: two calls differing ONLY by NamedSharding
    recompile — the differ must say so and name the leaf (it used to
    report "no difference")."""
    mesh = _mesh()
    a = abstract_signature(
        SDS((8, 64), jnp.float32,
            sharding=NamedSharding(mesh, P("dp", None))))
    b = abstract_signature(
        SDS((8, 64), jnp.float32, sharding=NamedSharding(mesh, P())))
    fs = diff_signatures(a, b, names=("activations",))
    assert [f.code for f in fs] == ["sharding"]
    assert fs[0].severity == "error"
    assert "activations" in fs[0].message or fs[0].where == "activations"
    assert "dp" in str(fs[0].data["old"])


def test_signature_sharding_ignores_host_and_uncommitted():
    """Host numpy arrays and default-device jax arrays normalize to the
    same (empty) sharding key — the serving preflight must not start
    rejecting plain host batches."""
    host = abstract_signature(np.zeros((4, 8), np.float32))
    dev = abstract_signature(jnp.zeros((4, 8), jnp.float32))
    assert not diff_signatures(host, dev)
    mesh = _mesh()
    named = abstract_signature(
        SDS((4, 8), jnp.float32, sharding=NamedSharding(mesh, P("dp"))))
    assert diff_signatures(host, named)[0].code == "sharding"


def test_signature_mesh_shape_is_part_of_the_key():
    m8 = _mesh({"dp": 8})
    m24 = _mesh({"dp": 2, "mp": 4})
    a = abstract_signature(
        SDS((8, 8), jnp.float32, sharding=NamedSharding(m8, P("dp"))))
    b = abstract_signature(
        SDS((8, 8), jnp.float32, sharding=NamedSharding(m24, P("dp"))))
    assert diff_signatures(a, b)[0].code == "sharding"


# --------------------------------------------- allowlist drift guard

def test_default_allowlist_entries_stay_live():
    """ISSUE 15 satellite: re-run the dtype-promotion pass over the
    standard targets and prove (a) every finding is covered by
    DEFAULT_ALLOWLIST (a new upcast cannot hide behind the allowlist's
    existence) and (b) every allowlist entry that these targets CAN
    exercise still matches at least one finding — an entry matching
    nothing is rot: the code it documented moved, and the allowlist
    keeps suppressing whatever inherits its `where` substring."""
    from paddle_tpu.inference import ServingConfig, ServingEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    intermediate_size=64, param_dtype="bfloat16")
    model = GPTForCausalLM(cfg)
    model.eval()
    lint = GraphLint(passes=("dtype_promotion",), upcast_bytes=1)
    eng = ServingEngine(model, ServingConfig(
        max_batch=2, prompt_cap=8, max_new_tokens=4, decode_chunk=2,
        lint=lint))
    eng.submit(np.arange(1, 6))
    eng.drain()
    findings = Findings().extend(eng.lint_findings or Findings())

    from paddle_tpu import optimizer as opt
    from paddle_tpu.jit.train_step import TrainStep
    model.train()
    o = opt.AdamW(parameters=model.parameters(), learning_rate=1e-4)
    ts = TrainStep(model, o, lambda ids, lab: model.loss(ids, lab))
    findings.extend(ts.lint(SDS((2, 8), "int64"), SDS((2, 8), "int64"),
                            lint=lint))

    dtype_findings = [f for f in findings
                     if f.pass_name == "dtype_promotion"]
    assert dtype_findings, "the pass saw no graphs — nothing was audited"
    # (a) nothing active: every upcast these targets lower is documented
    stray = [str(f) for f in dtype_findings if not f.allowed]
    assert not stray, f"undocumented upcasts appeared: {stray}"
    # (b) entry liveness. Entries whose `where` these two targets cannot
    # exercise are exempt: sampling variants and generate_static (the
    # engine routes through prefill/decode_ kinds here), the numerics
    # sentinel (numerics= off), the standalone norm module and the CE/
    # softmax sites (first-match-wins: the layer_norm/loss/attention
    # entries shadow them in these graphs), and train_step.py (its
    # grad-norm reductions only lower with numerics= enabled). Every
    # OTHER dtype entry must have matched at least once.
    exempt_wheres = {"sample_logits", "generate_static", "sentinel.py",
                     "norm.py", "cross_entropy", "softmax",
                     "train_step.py"}
    matched = set()
    for f in dtype_findings:
        e = DEFAULT_ALLOWLIST.match(f)
        if e is not None:
            matched.add(e["where"])
    for e in DEFAULT_ALLOWLIST.entries:
        if e["pass"] != "dtype_promotion" \
                or e["where"] in exempt_wheres:
            continue
        assert e["where"] in matched, \
            f"allowlist entry {e['where']!r} matched nothing — " \
            f"rotting entry (or the documented site moved)"
