"""Graph lint (ISSUE 6): the static-analysis suite that proves the
zero-recompile / zero-sync / donation invariants BEFORE the job runs.

Covers: each pass detects its planted violation (and names itself),
the transfer guard catches implicit host transfers under lax.scan and
grad-accum naming the LAYER, the recompile differ explains signature
deltas, the serving preflight/engine/TrainStep wiring, the structured
config-validation finding, the source lint, and — the acceptance pin —
the framework's own core executables (GPT prefill/decode static+paged,
TrainStep(gpt), a vision forward) are lint-clean modulo the documented
allowlist."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.analysis import (
    Allowlist, ConfigValidationError, Finding, Findings, GraphLint,
    GraphLintError, HostTransferError, abstract_signature,
    diff_signatures, explain_recompile, lint_capture, transfer_guard)

SDS = jax.ShapeDtypeStruct


# ------------------------------------------------------------ the passes

def test_dtype_promotion_detected_and_named():
    def up(x):
        return x.astype(jnp.float32) * 2.0

    fs = GraphLint().check(up, SDS((128, 256), jnp.bfloat16), name="up")
    hits = [f for f in fs if f.pass_name == "dtype_promotion"]
    assert hits, "planted bf16->f32 upcast not detected"
    assert hits[0].code == "bfloat16_to_float32"
    assert not hits[0].allowed
    assert "float32" in hits[0].message


def test_dtype_promotion_threshold_spares_small_tensors():
    def up(x):
        return x.astype(jnp.float32)

    fs = GraphLint(upcast_bytes=1 << 16).check(
        up, SDS((4, 4), jnp.bfloat16))
    assert not fs.for_pass("dtype_promotion")


def test_baked_const_detected():
    big = jnp.ones((512, 600), jnp.float32)   # 1.2 MB

    def f(x):
        return x + big

    fs = GraphLint().check(f, SDS((512, 600), jnp.float32), name="baked")
    hits = fs.for_pass("baked_const")
    assert hits and hits[0].code == "large_const"
    assert hits[0].data["bytes"] == 512 * 600 * 4


def test_donation_miss_detected():
    def f(a, b):
        return jnp.sum(a) + b     # donated `a` matches no output

    fs = GraphLint().check(f, SDS((512, 600), jnp.float32),
                           SDS((), jnp.float32), donate_argnums=(0,),
                           name="dm")
    hits = fs.for_pass("donation")
    assert hits and hits[0].code == "donated_unaliased"


def test_donation_honored_plus_candidate_advice():
    def f(a, b):
        return a + b

    fs = GraphLint().check(f, SDS((512, 600), jnp.float32),
                           SDS((512, 600), jnp.float32),
                           donate_argnums=(0,), name="ok")
    assert not [f_ for f_ in fs if f_.code == "donated_unaliased"]
    # b is large, not donated, and an output matches it exactly -> advice
    cand = [f_ for f_ in fs if f_.code == "donatable"]
    assert cand and cand[0].severity == "info"


def test_donation_alias_parse_survives_sharding_attrs():
    """Sharding attr values (`sdy.sharding = #sdy.sharding<@mesh, [{},
    {}]>` under jax 0.9's Shardy) contain nested braces and sort BEFORE
    tf.aliasing_output in the lowered signature — the alias parse must
    not truncate there (else every sharded donation reads as a silent
    copy)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.analysis import parse_io_aliases
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("dp",))
    sh = NamedSharding(mesh, P())

    def f(a, b):
        return a + b

    jf = jax.jit(f, donate_argnums=(0,), in_shardings=(sh, sh))
    txt = jf.lower(SDS((8, 8), jnp.float32),
                   SDS((8, 8), jnp.float32)).as_text()
    assert "sdy.sharding" in txt       # the hazard is actually present
    n, aliases = parse_io_aliases(txt)
    assert n == 2 and aliases == {0: 0}
    fs = GraphLint(donate_bytes=1).check(
        jf, SDS((8, 8), jnp.float32), SDS((8, 8), jnp.float32),
        name="sharded")
    assert not [f_ for f_ in fs if f_.code == "donated_unaliased"]


def test_host_transfer_callback_detected_inside_scan():
    def f(x):
        def body(c, _):
            y = jax.pure_callback(
                lambda v: np.asarray(v),
                SDS((), jnp.float32), c)
            return c + y, None
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    fs = GraphLint().check(f, jnp.float32(1.0), name="cb")
    hits = fs.for_pass("host_transfer")
    assert hits and hits[0].code == "pure_callback"
    assert hits[0].severity == "error"


# ------------------------------------------------- transfer guard / hook

class _BadInner(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(4, 4)

    def forward(self, x):
        y = self.fc(x)
        y.item()          # planted implicit host transfer
        return y


class _BadNet(nn.Layer):
    def __init__(self):
        super().__init__()
        self.inner = _BadInner()

    def forward(self, x):
        return self.inner(x)


def test_transfer_guard_names_layer_path():
    net = _BadNet()

    def fwd(x):
        return net(Tensor(x))._data

    with transfer_guard() as g:
        with pytest.raises(HostTransferError) as ei:
            jax.make_jaxpr(fwd)(SDS((2, 4), jnp.float32))
    assert "_BadNet/inner" in str(ei.value)
    assert "item" in str(ei.value)
    assert g.findings and g.findings[0].pass_name == "host_transfer"
    assert g.findings[0].code == "tracer_item"


def test_transfer_guard_inactive_on_concrete_tensors():
    t = paddle.to_tensor([3.5])
    with transfer_guard():
        assert t.item() == pytest.approx(3.5)     # eager reads stay legal
        assert float(t) == pytest.approx(3.5)


def test_graphlint_reports_planted_item_as_finding():
    net = _BadNet()

    def fwd(x):
        return net(Tensor(x))._data

    fs = GraphLint().check(fwd, SDS((2, 4), jnp.float32), name="bad")
    hits = fs.for_pass("host_transfer")
    assert hits and hits[0].code == "tracer_item"
    assert "_BadNet/inner" in hits[0].where


def test_transfer_guard_under_lax_scan_body():
    """r8's zero-sync claim is hardest to see inside scan bodies — the
    guard must catch a planted .item() there and still name the layer."""
    net = _BadNet()

    def scanned(x):
        def body(c, _):
            out = net(Tensor(c))._data
            return out, None
        y, _ = jax.lax.scan(body, x, None, length=2)
        return y

    fs = GraphLint().check(scanned, SDS((2, 4), jnp.float32),
                           name="scanned")
    hits = fs.for_pass("host_transfer")
    assert hits and "_BadNet/inner" in hits[0].where


# ---------------------------------------------------- recompile differ

def test_signature_diff_explains_each_delta():
    a = abstract_signature(np.zeros((4, 64), np.int64),
                           np.zeros((4,), np.int32))
    assert explain_recompile(a, a) == ""

    b = abstract_signature(np.zeros((4, 80), np.int64),
                           np.zeros((4,), np.int32))
    fs = diff_signatures(a, b, names=("ids", "lens"))
    assert len(fs) == 1 and fs[0].code == "shape"
    assert "ids" in fs[0].message and "[4, 80]" in fs[0].message

    c = abstract_signature(np.zeros((4, 64), np.float32),
                           np.zeros((4,), np.int32))
    assert diff_signatures(a, c)[0].code == "dtype"

    d = abstract_signature(np.zeros((4, 64), np.int64), "different")
    assert diff_signatures(a, d)[0].code == "structure"


def test_signature_weak_type_delta():
    strong = abstract_signature(SDS((), jnp.float32))
    weak = abstract_signature(SDS((), jnp.float32, weak_type=True))
    fs = diff_signatures(strong, weak)
    # same shape+dtype; only weak_type differs
    assert [f.code for f in fs] == ["weak_type"]


# ------------------------------------------------------- GraphLint modes

def test_guard_mode_raises_with_findings():
    def up(x):
        return x.astype(jnp.float32)

    with pytest.raises(GraphLintError) as ei:
        GraphLint(mode="error").check(up, SDS((128, 256), jnp.bfloat16),
                                      name="up")
    assert ei.value.findings
    assert "dtype_promotion" in str(ei.value)


def test_allowlist_marks_but_keeps_findings():
    def up(x):
        return x.astype(jnp.float32)

    lint = GraphLint(mode="error", allow=[
        {"pass": "dtype_promotion", "code": "*", "where": "",
         "reason": "test: deliberate accumulation"}])
    fs = lint.check(up, SDS((128, 256), jnp.bfloat16), name="up")
    assert len(fs) == 1 and fs[0].allowed
    assert fs[0].allow_reason == "test: deliberate accumulation"
    assert not fs.active("warn")


def test_findings_grouped_collapses_repeats():
    f1 = Finding("p", "c", "warn", "m", where="w", executable="e")
    f2 = Finding("p", "c", "warn", "m", where="w", executable="e")
    f3 = Finding("p", "other", "warn", "m2", where="w", executable="e")
    g = Findings([f1, f2, f3]).grouped()
    assert len(g) == 2
    assert g[0].data["count"] == 2 and g[0].message.startswith("[x2]")


# ------------------------------------------------------ model fixtures

def _tiny_gpt(dtype="bfloat16"):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    intermediate_size=64, param_dtype=dtype)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m, cfg


# low thresholds: the toy model must still exercise every pass — the
# deliberate sites arrive allowlisted with their documented reasons
_LINT = dict(upcast_bytes=256, const_bytes=2048, donate_bytes=2048)


# ------------------------------------- acceptance pin: core executables

def test_gpt_paged_engine_lint_clean_and_donation_aliased():
    from paddle_tpu.inference import ServingConfig, ServingEngine
    model, _ = _tiny_gpt()
    eng = ServingEngine(model, ServingConfig(
        max_batch=2, prompt_cap=8, max_new_tokens=4, decode_chunk=2,
        kv_block=4, lint=GraphLint(**_LINT)))
    eng.submit(np.arange(1, 6))
    eng.submit(np.arange(2, 9))
    done = eng.drain()
    assert all(r.status == "done" for r in done)
    fs = eng.lint_findings
    assert fs is not None
    active = fs.active("warn")
    assert not active, f"paged executables not lint-clean: " \
                       f"{[str(f) for f in active]}"
    # the audit must have SEEN the graphs: the documented bf16 exceptions
    # (attention softmax, layernorm moments, sampling head) show up
    # allowed — an empty report would mean the capture missed the calls
    assert any(f.allowed for f in fs)
    assert {f.pass_name for f in fs} >= {"dtype_promotion"}
    # r10's donated pools must be ALIASED, not silently copied: the
    # donation pass ran over the paged pair and reported no misses
    assert not [f for f in fs if f.code == "donated_unaliased"]


def test_train_step_gpt_lint_clean():
    from paddle_tpu import optimizer as opt
    from paddle_tpu.jit.train_step import TrainStep
    model, cfg = _tiny_gpt()
    model.train()
    o = opt.AdamW(parameters=model.parameters(), learning_rate=1e-4)
    ts = TrainStep(model, o, lambda ids, lab: model.loss(ids, lab))
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 8)))
    fs = ts.lint(ids, ids, lint=GraphLint(**_LINT))
    active = fs.active("warn")
    assert not active, f"TrainStep(gpt) not lint-clean: " \
                       f"{[str(f) for f in active]}"
    assert ts.lint_findings is fs


def test_vision_forward_lint_clean():
    from paddle_tpu.core import autograd
    from paddle_tpu.jit.api import _swap_params, _trace_guard
    from paddle_tpu.vision.models.small import LeNet
    paddle.seed(0)
    model = LeNet()
    model.eval()
    params = [p for _, p in model.named_parameters()]

    def fwd(pa, x):
        with _trace_guard(), _swap_params(params, list(pa)), \
                autograd.no_grad():
            return model(Tensor(x))._data

    fs = GraphLint(**_LINT).check(
        fwd, tuple(SDS(tuple(p._data.shape), p._data.dtype)
                   for p in params),
        SDS((2, 1, 28, 28), jnp.float32), name="lenet_forward")
    active = fs.active("warn")
    assert not active, f"vision forward not lint-clean: " \
                       f"{[str(f) for f in active]}"


# ----------------------------------------------- TrainStep lint wiring

class _MLP(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(8, 16)
        self.fc2 = nn.Linear(16, 4)

    def forward(self, x):
        return self.fc2(nn.functional.relu(self.fc1(x)))


class _SyncMLP(_MLP):
    def forward(self, x):
        y = super().forward(x)
        y.numpy()        # planted per-step host sync
        return y


def _mk_step(model, **kw):
    from paddle_tpu import optimizer as opt
    from paddle_tpu.jit.train_step import TrainStep
    o = opt.AdamW(parameters=model.parameters(), learning_rate=1e-3)

    def loss_fn(x, y):
        return nn.functional.mse_loss(model(x), y)

    return TrainStep(model, o, loss_fn, **kw)


def test_train_step_lint_option_runs_before_first_compile():
    paddle.seed(0)
    ts = _mk_step(_MLP(), lint=True)
    x = paddle.to_tensor(np.random.rand(4, 8).astype(np.float32))
    y = paddle.to_tensor(np.random.rand(4, 4).astype(np.float32))
    assert ts.lint_findings is None
    ts(x, y)
    assert ts.lint_findings is not None
    assert not ts.lint_findings.active("warn")


def test_train_step_guard_mode_catches_planted_sync_pre_compile():
    paddle.seed(0)
    ts = _mk_step(_SyncMLP(), lint="error")
    x = paddle.to_tensor(np.random.rand(4, 8).astype(np.float32))
    y = paddle.to_tensor(np.random.rand(4, 4).astype(np.float32))
    with pytest.raises(GraphLintError) as ei:
        ts(x, y)
    assert "_SyncMLP" in str(ei.value)      # names the layer path
    assert "tracer_numpy" in str(ei.value)  # and the transfer kind


def test_transfer_guard_under_grad_accum_path():
    """The grad-accum microbatch scan is the other place the zero-sync
    claim is hard to eyeball: a planted sync inside the scanned
    fwd+bwd body is still caught, still naming the layer."""
    paddle.seed(0)
    ts = _mk_step(_SyncMLP(), grad_accum_steps=2)
    x = SDS((4, 8), jnp.float32)
    y = SDS((4, 4), jnp.float32)
    fs = ts.lint(x, y, lint=GraphLint(**_LINT))
    hits = fs.for_pass("host_transfer")
    assert hits and hits[0].code == "tracer_numpy"
    assert "_SyncMLP" in hits[0].where


def test_train_step_lint_is_abstract_no_param_updates():
    paddle.seed(0)
    model = _MLP()
    ts = _mk_step(model)
    before = model.fc1.weight.numpy().copy()
    ts.lint(SDS((4, 8), jnp.float32), SDS((4, 4), jnp.float32))
    np.testing.assert_array_equal(before, model.fc1.weight.numpy())


# ------------------------------------------------- serving integration

def test_serving_preflight_findings_and_reject_reason():
    from paddle_tpu.inference import ServingConfig, ServingEngine
    model, _ = _tiny_gpt("float32")
    eng = ServingEngine(model, ServingConfig(
        max_batch=2, prompt_cap=8, max_new_tokens=4))
    # admissible -> empty findings
    assert not eng.preflight(np.arange(1, 5))
    # over-cap prompt -> recompile_hazard naming the shape delta
    pf = eng.preflight(np.arange(1, 20))
    assert len(pf) == 1 and pf[0].code == "prompt_shape"
    assert pf[0].pass_name == "recompile_hazard"
    assert "[2, 19]" in pf[0].message
    # the submit path carries the finding code as the refusal reason
    r = eng.submit(np.arange(1, 20))
    assert r.status == "rejected" and r.reason == "prompt_shape"
    r2 = eng.submit(np.arange(1, 5), max_new_tokens=0)
    assert r2.status == "rejected" and r2.reason == "max_new_tokens"


def test_serving_guard_mode_lint_raises_on_planted_hazard():
    """A guard-mode engine lint actually trips: plant a hazard by
    shrinking the upcast threshold to zero tolerance for the sampling
    head with an EMPTY allowlist. The findings are stored BEFORE the
    raise so a caller catching the error can still read them."""
    from paddle_tpu.inference import ServingConfig, ServingEngine
    model, _ = _tiny_gpt()
    lint = GraphLint(mode="error", upcast_bytes=64,
                     allowlist=Allowlist([]))
    eng = ServingEngine(model, ServingConfig(
        max_batch=2, prompt_cap=8, max_new_tokens=3, lint=lint))
    eng.submit(np.arange(1, 5))
    with pytest.raises(GraphLintError):
        eng.drain()
    assert eng.lint_findings is not None and eng.lint_findings.active("warn")


def test_serving_lint_audits_late_built_executables():
    """Traffic that finishes at prefill (budget-1) must not latch the
    audit shut: a decode executable built on a LATER step still gets
    audited the first step it appears."""
    from paddle_tpu.inference import ServingConfig, ServingEngine
    model, _ = _tiny_gpt()
    eng = ServingEngine(model, ServingConfig(
        max_batch=2, prompt_cap=8, max_new_tokens=4, decode_chunk=2,
        kv_block=4, lint=GraphLint(**_LINT)))
    # budget-1 request: finishes inside _admit_paged, decode never runs
    eng.submit(np.arange(1, 5), max_new_tokens=1)
    eng.drain()
    audited = {k for _, k in eng._lint_seen}
    assert any(k.startswith("paged_prefill") for k in audited)
    assert not any(k.startswith("paged_decode") for k in audited)
    # a real request later: the decode executable compiles NOW and is
    # audited now
    eng.submit(np.arange(1, 6), max_new_tokens=4)
    eng.drain()
    audited = {k for _, k in eng._lint_seen}
    assert any(k.startswith("paged_decode") for k in audited)
    assert not eng.lint_findings.active("warn")


def test_paged_cache_dtype_config_finding():
    """int8 pools SERVE; a cache dtype the engine cannot hold gets the
    structured config-validation finding (same schema as the lint),
    still a ValueError for existing callers, and says WHY + what to do."""
    from paddle_tpu.inference import ServingConfig
    cfg = ServingConfig(cache_dtype="int8")
    assert cfg.cache_dtype == "int8"       # the ISSUE-10 mode
    with pytest.raises(ConfigValidationError) as ei:
        ServingConfig(cache_dtype="float16")
    assert isinstance(ei.value, ValueError)
    f = ei.value.finding
    assert f.pass_name == "config"
    assert f.code == "paged_cache_dtype"
    assert "model dtype" in f.message.lower()
    assert "cache_dtype='int8'" in f.message   # the actionable way out
    assert f.data == {"cache_dtype": "float16"}


def test_lint_capture_records_serving_executables():
    model, _ = _tiny_gpt("float32")
    from paddle_tpu.inference import BlockPool
    pool = BlockPool.for_model(model, num_blocks=4, block_size=4)
    pool.alloc(0, 6)
    tables = pool.table_row(0, 2)[None]
    with lint_capture() as calls:
        pools, first = model.prefill_paged(
            np.ones((1, 4), np.int64), np.int32([4]), pool.make_pools(),
            tables)
        model.decode_paged(pools, tables, np.int32([4]),
                           first.numpy().astype(np.int32),
                           np.zeros((1,), bool), 2)
    kinds = [k[0] for k, _, _ in calls]
    assert "paged_prefill" in kinds and "paged_decode" in kinds
    fs = GraphLint(**_LINT).check_calls(calls)
    assert not fs.active("warn")


# ------------------------------------------------------- source lint

def test_source_lint_repo_clean():
    import tools.lint_source as ls
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert ls.run(root) == []


def test_source_lint_detects_and_allows(tmp_path):
    import tools.lint_source as ls
    bad = tmp_path / "paddle_tpu"
    bad.mkdir()
    (bad / "models").mkdir()
    src = (
        "import numpy as np\n"
        "def f(t, ok):\n"
        "    a = t.item()\n"
        "    b = float(t.sum())\n"
        "    c = np.asarray(t)\n"
        "    d = ok.item()  # lint: allow(tracer-item)\n"
        "    return a, b, c, d\n")
    (bad / "models" / "gpt.py").write_text(src)
    found = ls.lint_file("paddle_tpu/models/gpt.py", str(tmp_path))
    codes = sorted(f["code"] for f in found)
    assert codes == ["tracer-asarray", "tracer-float", "tracer-item"]
    assert all(f["pass"] == "source_lint" for f in found)


def test_check_tiers_lint_budget_line():
    import tools.check_tiers as ct
    recs = [{"nodeid": "a::b", "duration": 1.0, "markers": [],
             "outcome": "passed"}]
    ok = ct.check(recs, budget=780, slow_threshold=60,
                  lint_seconds=3.0, lint_budget=15.0)
    assert ok["ok"] and not ok["lint_over_budget"]
    bad = ct.check(recs, budget=780, slow_threshold=60,
                   lint_seconds=30.0, lint_budget=15.0)
    assert not bad["ok"] and bad["lint_over_budget"]
