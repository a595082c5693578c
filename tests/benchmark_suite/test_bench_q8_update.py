"""`q8_update_share_pct.train`: the 8-bit moments' update kernels' share of
the device's busy time in cell 3, read from the hand-counted trace of
`test_bench_programs.py` (data/mini_trace_programs.json: busy 1010 ns)."""
import copy
import json
import os

import pytest

from benchmarks import manifest
from benchmarks.trace import TraceSummary

HERE = os.path.dirname(os.path.abspath(__file__))
METRIC = "q8_update_share_pct.train"


def _plain():
    with open(os.path.join(HERE, "data", "mini_trace_programs.json")) as f:
        return json.load(f)


def _read(plain):
    read, spec = manifest.metric_reader(METRIC)
    return read({"trace": TraceSummary(plain)}, spec)


def _named(plain, idx, name):
    """Operation `idx` of device 0 renamed as a kernel call `name`."""
    plain = copy.deepcopy(plain)
    ev = plain["devices"]["/device:TPU:0"][idx]
    ev[0] = f"%{name}.7 = bf16[2]{{0}} custom-call(bf16[2]{{0}} %p.1), " \
            'custom_call_target="tpu_custom_call"'
    return plain


@pytest.mark.parametrize("names,want", [
    # the one-pass update: operations 400..600 and 1500..1800
    (("pallas_q8_adamw", "pallas_q8_adamw"), 100 * 500 / 1010),
    # an older tree's codec reads the same: decode, then encode
    (("pallas_q8_decode", "pallas_q8_encode"), 100 * 500 / 1010),
    # the paged int8 decode kernel is no moment update
    (("pallas_paged_q8_decode", "fusion"), 0.0)])
def test_the_update_kernels_share_of_busy_time(names, want):
    plain = _named(_named(_plain(), 3, names[0]), 6, names[1])
    assert _read(plain) == pytest.approx(want)


def test_a_trace_without_the_kernels_reads_zero_and_no_device_nothing():
    assert _read(_plain()) == 0.0
    plain = _plain()
    plain["devices"] = {}
    assert _read(plain) is None


def test_the_metric_is_cell_3s_alone():
    bench = manifest.benchmark_json()
    m, = [m for m in bench["per_layer"] if m["name"] == METRIC]
    assert bench["per_layer"][-1] is m
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"],
            m["workloads"]) == ("%", "lower", "device_trace", "train step",
                                "train_tokens_per_s",
                                ["train-gpt3-2.7b-s2048"])
    assert manifest.Cell("train-gpt3-2.7b-s2048", bench).per_layer()[-1] is m
    assert METRIC not in [x["name"] for x in manifest.Cell(
        "train-gpt3-1.3b-s2048", bench).per_layer()]
