"""The readers of the device's own program events (the trace's `XLA
Modules` line), against a trace small enough to count by hand
(data/mini_trace_programs.json, whose comment holds the counts): two
programs, a stray one, a gap before each, a stalled launch, a cut tail."""
import copy
import json
import os

import pytest

from benchmarks import manifest
from benchmarks.readers import device_idle
from benchmarks.trace import TraceSummary

HERE = os.path.dirname(os.path.abspath(__file__))
SUFFIXES = ("chat", "tps")
SERVE_STEMS = ("decode_program_ms", "prefill_program_ms",
               "prefill_program_share_pct", "idle_before_decode_pct",
               "idle_before_prefill_pct", "idle_before_other_pct",
               "launch_to_start_p95_ms", "host_wait_max_ms",
               "unnamed_programs_per_launch", "trace_recorded_pct")
TRAIN = ("train_program_ms", "remat_share_pct.train")
# what a trace of a commit that calls every program `jit_run` still reads
BY_SPAN_OR_COUNT = ("host_wait_max_ms", "unnamed_programs_per_launch",
                    "trace_recorded_pct")


def _plain():
    with open(os.path.join(HERE, "data", "mini_trace_programs.json")) as f:
        return json.load(f)


@pytest.fixture()
def ctx():
    return {"trace": TraceSummary(_plain())}


def _read(ctx, metric):
    read, spec = manifest.metric_reader(metric)
    return read(ctx, spec)


@pytest.mark.parametrize("stem,want", [
    ("decode_program_ms", 350e-6),
    ("prefill_program_ms", 150e-6),
    ("prefill_program_share_pct", 100 * 300 / 1010),
    ("idle_before_decode_pct", 100 * 700 / 2100),
    ("idle_before_prefill_pct", 100 * 140 / 2100),
    ("idle_before_other_pct", 100 * 250 / 2100),
    ("launch_to_start_p95_ms", 515.5e-6),
    ("host_wait_max_ms", 850e-6),
    ("unnamed_programs_per_launch", 0.25),
    ("trace_recorded_pct", 70.0)])
@pytest.mark.parametrize("suffix", SUFFIXES)
def test_each_reader_on_the_hand_counted_trace(ctx, stem, want, suffix):
    assert _read(ctx, f"{stem}.{suffix}") == pytest.approx(want)


@pytest.mark.parametrize("suffix", SUFFIXES)
def test_the_three_idle_shares_add_up_to_the_recorded_parts_idle(ctx, suffix):
    parts = [_read(ctx, f"idle_before_{p}_pct.{suffix}")
             for p in ("decode", "prefill", "other")]
    assert sum(parts) == pytest.approx(100 * 1090 / 2100, abs=0.01)
    # the whole window's share counts the cut tail as idle
    assert device_idle.read(ctx, {}) == pytest.approx(100 * 1990 / 3000)


def test_a_whole_trace_is_recorded_to_its_end():
    plain = _plain()
    plain["window"] = [0, 2100]
    ctx = {"trace": TraceSummary(plain)}
    assert _read(ctx, "trace_recorded_pct.tps") == pytest.approx(100.0)
    parts = [_read(ctx, f"idle_before_{p}_pct.tps")
             for p in ("decode", "prefill", "other")]
    assert sum(parts) == pytest.approx(device_idle.read(ctx, {}))


def test_the_train_step_by_its_program_and_its_remat():
    plain = _plain()
    for ev in plain["modules"]["/device:TPU:0"]:
        if ev[0].startswith("jit_serve_decode"):
            ev[0] = "jit_pure_step(77)"
    ctx = {"trace": TraceSummary(plain)}
    assert _read(ctx, "train_program_ms") == pytest.approx(350e-6)
    # fusion.2.remat's 100 ns; fusion.3 only takes it as an operand
    assert _read(ctx, "remat_share_pct.train") \
        == pytest.approx(100 * 100 / 1010)
    plain["devices"]["/device:TPU:0"][2][0] = "%fusion.2 = bf16[2] fusion()"
    assert _read({"trace": TraceSummary(plain)},
                 "remat_share_pct.train") == 0.0


@pytest.mark.parametrize("suffix", SUFFIXES)
def test_a_parents_trace_names_nothing_and_still_counts(suffix, capsys):
    """A commit whose every program is `jit_run`: the by-name metrics
    find nothing to read and say nothing, the count of programs under no
    name falls back on the launch spans, and nothing raises."""
    plain = _plain()
    for ev in plain["modules"]["/device:TPU:0"]:
        ev[0] = "jit_run(%d)" % (len(ev[0]) * 7)
    ctx = {"trace": TraceSummary(plain)}
    for stem in SERVE_STEMS:
        got = _read(ctx, f"{stem}.{suffix}")
        if stem not in BY_SPAN_OR_COUNT:
            assert got is None, stem
    assert _read(ctx, f"unnamed_programs_per_launch.{suffix}") \
        == pytest.approx(6 / 4)
    assert "jit_run" in capsys.readouterr().err
    assert _read(ctx, "train_program_ms") is None


def test_no_trace_and_no_modules_line_read_nothing():
    plain = _plain()
    del plain["modules"]
    for c in ({"trace": None}, {"trace": TraceSummary(plain)}):
        for stem in SERVE_STEMS:
            if stem not in BY_SPAN_OR_COUNT or c["trace"] is None:
                assert _read(dict(c), f"{stem}.chat") is None, stem
        assert _read(dict(c), "train_program_ms") is None
    no_ops = copy.deepcopy(_plain())
    no_ops["devices"] = {}
    for name in [f"{s}.tps" for s in SERVE_STEMS] + list(TRAIN):
        if not name.startswith("host_wait_max_ms"):     # host spans only
            assert _read({"trace": TraceSummary(no_ops)}, name) is None, name


def test_the_metric_files_name_programs_the_program_has():
    from paddle_tpu.jit import api as programs
    have = set(programs.PROGRAM_NAMES)
    named = None
    for name in [f"{s}.{x}" for s in SERVE_STEMS for x in SUFFIXES] \
            + list(TRAIN):
        spec = manifest.metric_reader(name)[1]
        listed = set(spec.get("programs", ())) | set(spec.get("named", ())) \
            | set(spec.get("launches", ()))
        if "program" in spec:
            listed.add(spec["program"])
        assert listed <= have, (name, listed - have)
        named = set(spec["named"]) if "named" in spec else named
    # "under no name of the table" means the serving path's whole table
    assert named == {p for p in have if p.startswith("serve_")}
    waits = manifest.metric_reader("host_wait_max_ms.chat")[1]["spans"]
    assert "serving/gc" in waits and "serving/decode_read" in waits


def test_the_new_metrics_are_reported_where_the_issue_says():
    bench = manifest.benchmark_json()
    by = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    where = {"chat": ([cells[1]], "tpot_p95_ms"),
             # (cells 5-7 join this list when a `benchmark` PR frees
             # their tests' count of per-layer metrics: PERF.md section 7)
             "tps": (cells[3:4], "serve_tokens_per_s")}
    for stem in SERVE_STEMS:
        for suffix, (workloads, moves) in where.items():
            m = by[f"{stem}.{suffix}"]
            assert (m["workloads"], m["moves"]) == (workloads, moves)
            assert m["source"] == ("program_span" if stem in (
                "launch_to_start_p95_ms", "host_wait_max_ms")
                else "device_trace")
    for name in TRAIN:
        assert by[name]["workloads"] == [cells[0], cells[2]]
        assert (by[name]["moves"], by[name]["source"]) \
            == ("train_tokens_per_s", "device_trace")
    assert len(bench["per_layer"]) >= 91 + 22
