"""The yardstick's arithmetic against counts made by hand."""
import json
import os

import pytest

from benchmarks import flops
from benchmarks.trace import TraceSummary

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def mini():
    with open(os.path.join(HERE, "data", "mini_trace.json")) as f:
        return TraceSummary(json.load(f))


def test_busy_union_counts_nothing_twice_and_clips_to_the_window(mini):
    assert mini.window_s == pytest.approx(1000e-9)
    assert mini.busy_s == pytest.approx(550e-9)


def test_kernel_sums_by_pattern(mini):
    seconds, events = mini.seconds_matching(["flash_fwd"])
    assert (seconds, events) == (pytest.approx(250e-9), 2)
    # the loop keeps only what its body does not cover: 400 - 150 - 200
    assert mini.seconds_matching(["while"]) == (pytest.approx(50e-9), 1)
    assert mini.op_seconds()["copy.9"] == pytest.approx(50e-9)


FLASH_FWD = ('%jvp__.26 = (bf16[8,16,128]{2,1,0:T(8,128)(2,1)}, '
             'f32[8,8,16]{2,1,0:T(8,128)}) custom-call(bf16[8,16,128]{2,1,0} '
             '%bitcast.1, bf16[8,16,128]{2,1,0} %bitcast.2, bf16[8,16,128]'
             '{2,1,0} %bitcast.3), custom_call_target="tpu_custom_call", '
             'operand_layout_constraints={bf16[8,16,128]{2,1,0}}')


def test_a_kernel_is_known_by_its_signature():
    from benchmarks.trace import matches, op_label, signature
    assert signature(FLASH_FWD) == (
        "(bf16[8,16,128], f32[8,8,16]) custom-call(bf16[8,16,128], "
        "bf16[8,16,128], bf16[8,16,128])")
    assert matches(FLASH_FWD, [["f32[8,8,16]) custom-call(", "bf16[8,16,"]])
    assert not matches(FLASH_FWD, [["custom-call(", "bf16[9,"]])
    assert matches(FLASH_FWD, ["no such", "custom-call(bf16[8,16,128]"])
    assert op_label(FLASH_FWD) == "custom-call (bf16[8,16,128], f32[8,8,16])"
    assert signature("fusion.7") == "fusion.7"


def test_an_event_keeps_its_own_time_and_a_wrapper_next_to_none():
    # a copy 100..260 overlaps the kernel 250..400 that outlives it: both
    # ran; a zero-length marker at 300 lies inside the kernel and takes
    # nothing from it; the loop 500..900 only wraps its body
    ts = TraceSummary({"window": [0, 1000], "host": [], "devices": {"d": [
        ["copy", 100, 160], ["kernel", 250, 150], ["marker", 300, 0],
        ["while", 500, 400], ["body.1", 500, 200], ["body.2", 700, 200]]}})
    own = ts.op_seconds()
    assert own["kernel"] == pytest.approx(150e-9)
    assert own["copy"] == pytest.approx(160e-9)
    assert "while" not in own and "marker" not in own
    assert ts.busy_s == pytest.approx((300 + 400) * 1e-9)


def test_kernel_roofline_reader_fills_the_cells_sizes_into_its_patterns():
    from benchmarks.readers import kernel_roofline

    class Cell:
        config = {"num_heads": 4, "head_dim": 80, "hidden_size": 320,
                  "vocab_size": 512, "num_layers": 2}
        traffic = {"batch": 2, "seq": 16}
        chips = 1

    class Rec:
        counters = {"train/steps": 1.0}
    ts = TraceSummary({"window": [0, 1000], "host": [], "devices": {"d": [
        [FLASH_FWD, 0, 400], [FLASH_FWD.replace("%jvp__.26", "%jvp__.27"),
                              500, 400]]}})
    spec = {"calls": [{"patterns": [[
        "(bf16[{BH},{S},{D}], f32[{BH},8,{S}]) custom-call("]],
        "cost": "flash_attention_cost", "backward": False,
        "per_step": "layers"}]}
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e12}
    got = kernel_roofline.read({"trace": ts, "cell": Cell, "rec": Rec,
                                "peaks": peaks}, spec)
    cost = flops.flash_attention_cost(2, 16, 4, 80)   # the head as published
    least = max(cost["flops"], cost["bytes"]) / 1e12
    assert got == pytest.approx(100 * 2 * least / 800e-9)
    spec["calls"][0]["patterns"] = [["bf16[{BH},{S},64]"]]
    assert kernel_roofline.read({"trace": ts, "cell": Cell, "rec": Rec,
                                 "peaks": peaks}, spec) is None


def test_device_time_under_a_host_span(mini):
    seconds, n = mini.device_seconds_under("serving/decode")
    assert (seconds, n) == (pytest.approx(320e-9), 1)


def test_idle_gaps_are_named_by_the_innermost_open_span(mini):
    gaps = dict(mini.idle_gaps())
    assert gaps["(no span open)"] == pytest.approx(100e-9)
    assert "bench/dispatch" not in gaps       # 250..300 lies inside the loop
    assert gaps["serving/decode"] == pytest.approx(100e-9)
    assert gaps["bench/loss_read"] == pytest.approx(250e-9)
    assert sum(gaps.values()) == pytest.approx(450e-9)


def test_breakdown_has_at_most_ten_entries_each(mini):
    b = mini.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0] == ["fusion.7", pytest.approx(200e-9)]


def test_train_flops_per_token_is_the_palm_count():
    # 1000 parameters, 2 layers of width 8 at 16 positions
    assert flops.train_flops_per_token(1000, 2, 8, 16) == 6000 + 12 * 2 * 8 * 16


def test_forward_flops():
    assert flops.forward_flops(1000, 2, 8, 5, 40) == 2 * 1000 * 5 + 4 * 2 * 8 * 40


def test_flash_attention_cost_forward_and_backward():
    # B=1 S=4 one head of 2: 8 causal-half pairs
    f = flops.flash_attention_cost(1, 4, 1, 2)
    assert f == {"flops": 4 * 8 * 2, "bytes": 4 * 4 * 2 * 2}
    b = flops.flash_attention_cost(1, 4, 1, 2, backward=True)
    assert b == {"flops": 10 * 8 * 2, "bytes": 8 * 4 * 2 * 2}


def test_linear_ce_cost():
    f = flops.linear_ce_cost(3, 4, 5)
    assert f == {"flops": 2 * 3 * 4 * 5, "bytes": (12 + 20) * 2}
    assert flops.linear_ce_cost(3, 4, 5, backward=True)["flops"] == 360


def test_paged_attention_cost_and_roof():
    c = flops.paged_attention_cost(100, 10, 2, 4)
    assert c == {"flops": 4 * 100 * 8, "bytes": (200 + 20) * 8 * 2}
    peaks = {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e3}
    assert flops.roofline_seconds(c, peaks) == (pytest.approx(3.52), "memory")
    assert flops.roofline_seconds({"flops": 9e3, "bytes": 1.0}, peaks)[1] \
        == "compute"


def test_unknown_device_kind_is_an_error():
    from benchmarks.peaks import peaks
    assert peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks("cpu")
