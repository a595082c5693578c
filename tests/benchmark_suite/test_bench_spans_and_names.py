"""The readers of the program's own spans and kernel names, against a
trace small enough to count by hand (data/mini_trace_spans.json, whose
comment holds the counts)."""
import json
import os

import pytest

from benchmarks import flops, manifest
from benchmarks.readers import (device_idle, idle_under,
                                named_kernel_roofline, span_self_ms)
from benchmarks.trace import TraceSummary

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = {"chat": "serve-gpt3-1.3b-chat", "over": "serve-gpt3-1.3b-chat-over"}
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e12}


def _trace(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return TraceSummary(json.load(f))


@pytest.fixture(scope="module")
def spans():
    return _trace("mini_trace_spans.json")


@pytest.fixture(scope="module")
def parent():
    """A trace as the parent commit writes it: the two old spans only."""
    return _trace("mini_trace.json")


def _spec(metric):
    return manifest.metric_reader(metric)[1]


@pytest.mark.parametrize("metric,ns", [
    # each gap cut at the span boundaries (the data file's comment)
    ("idle_model_call_pct", 110 + 370 + 50),
    ("idle_engine_host_pct", 40 + 40 + 120 + 30 + 150),
    ("idle_harness_pct", 60 + 80 + 100 + 100 + 100)])
@pytest.mark.parametrize("cell", ["chat", "over"])
def test_idle_goes_to_the_group_of_the_innermost_span(spans, metric, ns,
                                                      cell):
    got = idle_under.read({"trace": spans}, _spec(f"{metric}.{cell}"))
    assert got == pytest.approx(100 * ns / 2200)


@pytest.mark.parametrize("cell", ["chat", "over"])
def test_the_three_idle_groups_add_up_to_the_devices_idle_share(
        spans, parent, cell):
    for tr, idle in ((spans, 100 * 1350 / 2200), (parent, 45.0)):
        parts = [idle_under.read({"trace": tr}, _spec(f"{m}.{cell}"))
                 for m in ("idle_model_call_pct", "idle_engine_host_pct",
                           "idle_harness_pct")]
        assert device_idle.read({"trace": tr}, {}) == pytest.approx(idle)
        assert sum(p for p in parts if p is not None) == pytest.approx(idle)


def test_a_gap_is_cut_at_the_span_boundaries(spans):
    """The gap 1000..1500 begins under no span, crosses a step's start, a
    prefill launch, the rest of the prefill, the decode's preparation and
    launch, and ends in the decode's read: each gets its part, where the
    breakdown's rule gives all 500 ns to the span at 1250."""
    by = idle_under.idle_by_span(spans)
    assert by[None] == pytest.approx((60 + 10 + 20 + 100 + 100 + 20) * 1e-9)
    assert by["serving/prefill_launch"] == pytest.approx(90e-9)
    assert by["serving/prefill"] == pytest.approx(100e-9)
    assert by["serving/decode_prep"] == pytest.approx(20e-9)
    assert by["serving/decode_launch"] == pytest.approx(20e-9)
    assert by["serving/decode_read"] == pytest.approx((110 + 160 + 50) * 1e-9)
    assert by["serving/step"] == pytest.approx((5 + 5 + 10 + 10 + 10) * 1e-9)
    assert by["bench/poll"] == pytest.approx(50e-9)
    assert sum(by.values()) == pytest.approx(1350e-9)
    assert dict(spans.idle_gaps())["serving/prefill"] == pytest.approx(500e-9)


def test_a_program_without_the_new_spans_reads_what_it_has(parent):
    """The parent commit in a traced run: its two spans are the model
    call's, the engine's own group has nothing to read and says nothing,
    and nothing raises."""
    ctx = {"trace": parent}
    assert idle_under.read(ctx, _spec("idle_model_call_pct.chat")) \
        == pytest.approx(12.0)      # 500..600 and 700..720
    assert idle_under.read(ctx, _spec("idle_engine_host_pct.chat")) is None
    assert idle_under.read(ctx, _spec("idle_harness_pct.chat")) \
        == pytest.approx(33.0)      # 0..100 and 720..950
    assert span_self_ms.read(ctx, _spec("engine_self_ms.chat")) is None
    for m in ("idle_model_call_pct.chat", "engine_self_ms.over"):
        reader = manifest.metric_reader(m)[0]
        assert reader({"trace": None}, _spec(m)) is None


@pytest.mark.parametrize("cell", ["chat", "over"])
def test_self_time_subtracts_only_the_listed_children(spans, cell):
    spec = _spec(f"engine_self_ms.{cell}")
    assert spec["children"] == ["serving/decode", "serving/prefill"]
    # (760 - 460) and (800 - 190 - 430): admission, delivery, bookkeeping
    # stay in, and a launch inside a decode is not taken off twice
    assert span_self_ms.read({"trace": spans}, spec) \
        == pytest.approx((300 + 180) / 2 / 1e6)
    whole = dict(spec, children=[])
    assert span_self_ms.read({"trace": spans}, whole) \
        == pytest.approx((760 + 800) / 2 / 1e6)
    other = dict(spec, children=["serving/deliver", "bench/poll"])
    assert span_self_ms.read({"trace": spans}, other) \
        == pytest.approx((760 - 250 + 800 - 50) / 2 / 1e6)


@pytest.mark.parametrize("event,kernel,found", [
    ("%pallas_paged_decode.4 = bf16[2] custom-call(s32[2] %a)",
     "pallas_paged_decode", True),
    ("%transpose_jvp_pallas_flash_dq__.3 = bf16[2] custom-call(bf16[2] %a)",
     "pallas_flash_dq", True),
    ("  ROOT %jvp_pallas_flash_fwd_.1 = (bf16[2], f32[2]) custom-call(%a)",
     "pallas_flash_fwd", True),
    ("%pallas_flash_fwd.2.clone = bf16[2] custom-call(bf16[2] %a)",
     "pallas_flash_fwd", True),
    # a longer name is another kernel
    ("%pallas_paged_decode_q8.9 = bf16[2] custom-call(s32[2] %a)",
     "pallas_paged_decode", False),
    ("%pallas_paged_q8_decode.5 = bf16[2] custom-call(s32[2] %a)",
     "pallas_paged_decode", False),
    ("%jvp_pallas_flash_dkv_.1 = bf16[2] custom-call(bf16[2] %a)",
     "pallas_flash_dk", False),
    ("%mypallas_flash_fwd.1 = bf16[2] custom-call(bf16[2] %a)",
     "pallas_flash_fwd", False),
    # the name in an operand or in the metadata is not the kernel
    ('%fusion.7 = bf16[2] fusion(bf16[2] %pallas_flash_dq.3), '
     'metadata={op_name="jvp(pallas_flash_dq)"}', "pallas_flash_dq", False),
    ("%pallas_flash_dq.3 = bf16[2] fusion(bf16[2] %a)",
     "pallas_flash_dq", False),
    ("pallas_flash_dq", "pallas_flash_dq", False),
])
def test_a_kernel_is_found_by_its_name_in_the_instructions_own(
        event, kernel, found):
    assert named_kernel_roofline.holds(event, kernel) is found


def test_named_kernel_seconds_on_the_trace(spans):
    sec, ops = named_kernel_roofline.named_seconds, spans.op_seconds()
    assert sec(ops, ["pallas_paged_decode"]) == pytest.approx(400e-9)
    assert sec(ops, ["pallas_paged_q8_decode"]) == pytest.approx(100e-9)
    assert sec(ops, ["pallas_flash_dq"]) == pytest.approx(100e-9)
    assert sec(ops, ["pallas_flash_dq", "pallas_paged_decode"]) \
        == pytest.approx(500e-9)
    assert sec(ops, ["pallas_linear_ce_fwd"]) == 0


class _ServeCell:
    config = {"num_heads": 4, "head_dim": 128, "num_layers": 2,
              "hidden_size": 512, "vocab_size": 1024}
    traffic = {}
    chips = 1


class _ServeRec:
    counters = {"serve/decode_kv_rows": 1000.0, "serve/decode_steps": 40.0}


@pytest.mark.parametrize("cell", ["chat", "over"])
def test_paged_decode_roofline_by_name(spans, cell):
    spec = _spec(f"paged_decode_kernel_roofline.{cell}")
    ctx = {"trace": spans, "cell": _ServeCell, "rec": _ServeRec,
           "peaks": PEAKS}
    cost = flops.paged_attention_cost(1000 * 2, 40 * 2, 4, 128)
    least = max(cost["flops"], cost["bytes"]) / 1e12
    assert named_kernel_roofline.read(ctx, spec) \
        == pytest.approx(100 * least / 400e-9)
    # the shape-matched twin on the same events: one yardstick
    from benchmarks.readers import paged_roofline
    twin = paged_roofline.read(
        dict(ctx, cell=type("C", (_ServeCell,), {"settings": {"engine": {
            "max_batch": 2, "kv_blocks": 8, "kv_block": 16}}})),
        {"patterns": [["bf16[2,4,128] custom-call(s32[2,8]"]]})
    # the twin's pattern also takes the two other kernels of that shape
    assert twin == pytest.approx(100 * least / 550e-9)


class _TrainCell:
    config = {"num_heads": 4, "head_dim": 128, "hidden_size": 512,
              "vocab_size": 1024, "num_layers": 2}
    traffic = {"batch": 2, "seq": 16}
    chips = 1


class _TrainRec:
    counters = {"train/steps": 3.0}


def test_train_kernel_rooflines_by_name(spans):
    ctx = {"trace": spans, "cell": _TrainCell, "rec": _TrainRec,
           "peaks": PEAKS}
    spec = {"calls": [{"kernels": ["pallas_flash_dq", "pallas_flash_dkv"],
                       "cost": "flash_attention_cost", "backward": True,
                       "per_step": "layers"}]}
    cost = flops.flash_attention_cost(2, 16, 4, 128, backward=True)
    least = max(cost["flops"], cost["bytes"]) / 1e12 * 2 * 3
    assert named_kernel_roofline.read(ctx, spec) \
        == pytest.approx(100 * least / 100e-9)
    # the forward kernel has no event in this trace: the metric, which
    # needs every one of its calls, gives nothing; so does linear-CE's
    assert named_kernel_roofline.read(
        ctx, _spec("flash_kernels_roofline")) is None
    assert named_kernel_roofline.read(
        ctx, _spec("linear_ce_kernel_roofline")) is None
    assert named_kernel_roofline.read(
        dict(ctx, rec=type("R", (), {"counters": {}})), spec) is None


def test_the_metric_files_name_kernels_the_program_has():
    import importlib
    import re
    have = set()
    for mod in ("flash_attention", "linear_ce", "paged_attention"):
        m = importlib.import_module(f"paddle_tpu.ops.pallas.{mod}")
        have |= {v for k, v in vars(m).items()
                 if re.fullmatch(r"[A-Z0-9_]*NAME", k)}
    for metric in ("flash_kernels_roofline", "linear_ce_kernel_roofline",
                   "paged_decode_kernel_roofline.chat",
                   "paged_decode_kernel_roofline.over"):
        for call in _spec(metric)["calls"]:
            assert set(call["kernels"]) <= have, (metric, call["kernels"])


def test_the_new_metrics_are_reported_where_the_issue_says():
    bench = manifest.benchmark_json()
    by = {m["name"]: m for m in bench["per_layer"]}
    for short, cell in CELLS.items():
        moves = {"chat": "tpot_p95_ms", "over": "serve_tokens_per_s"}[short]
        for stem in ("idle_model_call_pct", "idle_engine_host_pct",
                     "idle_harness_pct", "engine_self_ms"):
            m = by[f"{stem}.{short}"]
            assert (m["workloads"], m["moves"], m["source"], m["layer"]) \
                == ([cell], moves, "program_span", "serving engine")
        m = by[f"paged_decode_kernel_roofline.{short}"]
        assert (m["workloads"], m["moves"], m["source"], m["layer"]) \
            == ([cell], moves, "device_trace", "kernels")
    for name in ("flash_kernels_roofline", "linear_ce_kernel_roofline"):
        assert by[name]["workloads"] == by["flash_attention_roofline"][
            "workloads"]
        assert by[name]["moves"] == "train_tokens_per_s"
