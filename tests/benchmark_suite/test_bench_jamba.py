"""The Jamba cell of the benchmark: its files are found by name, its toy
twin rehearses on the CPU through the cell's own runner, its configuration
is the catalog row's with nothing reduced, and what decides `correct`
fails on each planted fault (a snapshot restored without its conv state,
the inner norms left out, A without its sign, dt without softplus, an
altered token, the bfloat16 control)."""
import json
import os

import numpy as np
import pytest

from benchmarks import flops_jamba as F
from benchmarks import manifest, run
from benchmarks import weights_jamba as W
from benchmarks.manifest import Cell
from benchmarks.runners import serve_jamba as S
from benchmarks.tools import calibrate_jamba

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "data", "toy_benchmark_jamba.json")
CELL, TOY_CELL = "serve-jamba2-3b-reason-over", "toy-serve-jamba"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("serve_step_mfu.jamba", "ssm_scan_roofline.jamba",
       "ssm_update_roofline.jamba", "mqa_decode_roofline.jamba",
       "ssm_share_pct.jamba", "state_snapshot_restores.jamba")


@pytest.fixture(scope="module")
def toy():
    with open(TOY) as f:
        return json.load(f)


def test_the_manifest_finds_the_new_cells_files():
    bench = manifest.benchmark_json()
    cell = Cell(CELL, bench)
    assert cell.chips == 1 and cell.settings["runner"] == "serve_jamba"
    assert callable(cell.runner().check) and callable(cell.generator().make)
    assert {m["name"] for m in cell.end_to_end()} == {"serve_tokens_per_s",
                                                      "setup_s"}
    names = [m["name"] for m in cell.per_layer()]
    assert len(names) == 18 and all(n.endswith(".jamba") for n in names)
    assert set(NEW) <= set(names)
    assert [n for n in names if "mfu" in n.split(".")[0].split("_")]
    for n in names:
        read, spec = manifest.metric_reader(n)
        assert callable(read)
    entry, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(entry["why"]) <= 200 and entry["traffic"] == \
        "reason-8sys1k-r1.25"
    config, = [c for c in bench["configs"] if c["name"] == "jamba2-3b"]
    assert config["reduced"] == [] and len(config["source"]) <= 200 \
        and len(config["why"]) <= 200
    assert bench["workloads"][-1] is entry and bench["configs"][-1] is config
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert not [m for m in Cell(w["name"], bench).per_layer()
                        if m["name"].endswith(".jamba")]


def test_the_configuration_is_the_catalog_rows_with_nothing_reduced():
    config = Cell(CELL, manifest.benchmark_json()).config
    assert config["reduced"] == [] and config["param_dtype"] == "bfloat16"
    assert set(config["assumed"]) >= {"head_dim", "layer_order",
                                      "state_dtype", "weights"}
    assert config["deployment"] == {"chips": 1, "layers_here": 28,
                                    "vocabulary_here": 65536}
    assert [i for i, m in enumerate(W.mixers(config))
            if m == W.ATTENTION] == [7, 21]
    # ISSUE 36's count, to the parameter: 6.06 GB in bfloat16
    n = W.n_params(config)
    assert n["total"] == 3_029_337_472 and n["mamba_mixer"] == 41_241_792
    assert n["total"] == 26 * 104_161_472 + 2 * 76_682_240 + 167_774_720
    assert F.state_row_bytes(config) * 26 == 10_117_120
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r["name"] == "AI21-Jamba2-3B"]
    for k, v in row["config"].items():
        assert config[k] == v and config["published"][k] == v, k
    assert config["source"].startswith(row["source_url"])


def test_traffic_and_engine_of_the_cell_are_as_the_issue_fixed_them():
    cell = Cell(CELL, manifest.benchmark_json())
    t, eng = cell.traffic, cell.settings["engine"]
    assert (t["generator"], t["n_system"], t["system_len"]) == (
        "open_loop_chat", 8, 1024)
    assert (t["user_len_min"], t["user_len_max"], t["user_len_alpha"]) == (
        64, 1024, 1.2)
    assert (t["out_len_min"], t["out_len_max"]) == (256, 1536)
    assert t["real_vocab"] == cell.config["vocab_size"] == 65536
    assert (eng["kv_block"], eng["prompt_cap"], eng["max_new_tokens"],
            eng["decode_chunk"], eng["prefill_chunk"]) == (64, 2048, 1536, 8,
                                                           256)
    assert 128 <= eng["max_batch"] <= 256 and eng["max_batch"] % 8 == 0
    assert t["system_len"] % eng["prefill_chunk"] == 0      # the snapshot
    assert eng["state_snapshots"] > t["n_system"]
    assert t["system_len"] + t["user_len_max"] <= eng["prompt_cap"]
    assert t["out_len_max"] <= eng["max_new_tokens"]
    assert set(cell.settings["limits"]) == {"greedy_gap", "greedy_gap_mean"}
    assert "knee" in t["rate_note"] and "1.25" in t["rate_note"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_toy_cell_rehearses_on_the_cpu(capsys, trace):
    assert run.main(["--workload", TOY_CELL, "--seed", str(2 ** 31 + 17),
                     "--seconds", "1", "--trace", str(trace),
                     "--rehearse-cpu", "--manifest", TOY]) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True, res["compared"]
    assert res["device"]["platform"] == "cpu" and res["rehearsal"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    if trace:       # counts only
        assert set(res["metrics"]) == {
            "prefix_hit_pct.jamba", "decode_batch_fill_pct.jamba",
            "state_snapshot_restores.jamba"}
        assert res["metrics"]["state_snapshot_restores.jamba"]["value"] == 1.0


def _run(toy, seed=31, seconds=0.5):
    res = run.run_cell(Cell(TOY_CELL, toy), seed, seconds, trace=False,
                       rehearse=True)
    return res["correct"], res["_compared_full"]


def test_a_sound_run_is_correct(toy):
    ok, compared = _run(toy)
    assert ok and compared["greedy_gap"]["value"] <= 1e-5


@pytest.mark.parametrize("fault", ["snapshot", "snapshot_scan", "norms",
                                   "sign", "softplus"])
def test_a_fault_planted_in_the_program_is_not_correct(toy, monkeypatch,
                                                       fault):
    """Through the cell's own `check`, over the served tokens of a 3 s
    window. (The scan state kept in bfloat16 moves a toy logit by 2e-5,
    under what a token's choice can show at this size:
    tests/test_jamba.py holds it on the logits, the chip's calibration at
    the published widths.)"""
    real = S.build
    undo = []

    def build(cell, seed):
        model, eng = real(cell, seed)
        undo.append(calibrate_jamba.plant(model, eng, fault))
        return model, eng
    monkeypatch.setattr(S, "build", build)
    try:
        ok, compared = _run(toy, seconds=3.0)
    finally:
        for u in undo:
            u()
    assert not ok and not compared["greedy_gap"]["ok"], compared
    assert compared["unanswered"]["ok"]


def test_a_token_altered_where_it_is_produced_is_not_correct(toy,
                                                             monkeypatch):
    import paddle_tpu as paddle
    real = S.build

    def build(cell, seed):
        model, eng = real(cell, seed)
        decode = model.decode_paged

        def altered(*a, **kw):
            toks, *rest = decode(*a, **kw)
            wrong = (np.asarray(toks.numpy()) + 1) % 200 + 1
            return (paddle.to_tensor(wrong), *rest)
        model.decode_paged = altered
        return model, eng
    monkeypatch.setattr(S, "build", build)
    ok, compared = _run(toy)
    assert not ok and not compared["greedy_gap"]["ok"]


def test_the_control_in_bfloat16_is_not_correct(toy):
    """The tokens bfloat16 puts first, read in the float32 logits: over a
    few hundred positions some near-tie falls the other way."""
    cell = Cell(TOY_CELL, toy)
    rng = np.random.default_rng(3)
    sample = [(rng.integers(1, 255, 60), rng.integers(1, 255, 16))
              for _ in range(40)]
    gaps = S.sample_gaps(cell, 5, sample, mode="bf16", control=True)
    compared = S.compared_gaps(cell, gaps)
    assert not compared["greedy_gap"]["ok"]
    assert not compared["greedy_gap_mean"]["ok"]
    assert gaps["tokens"] == 40 * 16


def test_the_tools_read_the_cell_through_its_own_runner(toy, tmp_path):
    """calibrate_jamba: every case goes through `compared_gaps` as `check`
    does; the sound case is within both limits, the control and each
    planted fault outside one. sweep_cell takes the cell as it is."""
    from benchmarks.tools import calibrate_sala, sweep_cell
    out = tmp_path / "cal.jsonl"
    assert calibrate_jamba.main([
        "--workload", TOY_CELL, "--manifest", TOY, "--rehearse-cpu",
        "--seeds", "31,32", "--seconds", "3", "--control-seeds", "1",
        "--control-mode", "bf16", "--faults", "snapshot,norms,token",
        "--requests", "48", "--out", str(out)]) == 0
    assert calibrate_sala.FAULTS == ("block", "window", "decay", "snapshot",
                                     "token")     # put back
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    assert [(r["seed"], r["case"]) for r in rows] == [
        (31, "sound"), (31, "control"), (31, "snapshot"), (31, "norms"),
        (31, "token"), (32, "sound")]
    for r in rows:
        oks = [c["ok"] for c in r["compared"].values()]
        assert all(oks) == (r["case"] == "sound"), r
        assert r["restored"] == r["requests"]
    out = tmp_path / "sweep.jsonl"
    assert sweep_cell.main([
        "--workload", TOY_CELL, "--manifest", TOY, "--rehearse-cpu",
        "--seed", "4360001001", "--seconds", "0.5", "--rates", "40",
        "--engine", "max_batch=8", "--out", str(out)]) == 0
    row = json.loads(out.read_text())
    assert row["max_batch"] == 8 and row["failed"] == 0


def test_costs_from_shapes_and_counters():
    config = Cell(CELL, manifest.benchmark_json()).config
    mult = W.n_params(config)["multiplied"]
    # the matrices: all but 28 x 2 + 1 norm gains and, a Mamba layer, the
    # filter, three biases and vectors, A_log and the inner norms
    assert mult == 3_029_337_472 - 57 * 2560 - 26 * (
        4 * 5120 + 3 * 5120 + 16 * 5120 + 192)
    # a token: every matrix twice and 26 scans of 9 x 5,120 x 16
    assert F.forward_flops(config, 1, 0) == 2.0 * mult + 26 * 9.0 * 5120 * 16
    assert F.forward_flops(config, 0, 1) == 4.0 * 20 * 128
    s = F.scan_cost(config, 1, 0)
    assert s["bytes"] == 4 * (3 * 5120 + 32) == 61_568
    assert s["flops"] == 9.0 * 5120 * 16
    assert F.scan_cost(config, 0, 1)["bytes"] == 2 * 327_680
    assert F.update_cost(config, 1)["bytes"] == 2 * 389_120
    # a (page, KV head) pair: 32 KB of keys and values, 20 heads over 64
    # tokens: 20 FLOP a byte, far under the v5e's ridge of 240
    c = F.mqa_decode_cost(config, 1, 0, 64)
    assert c["bytes"] == 2 * 64 * 128 * 2 and c["flops"] / c["bytes"] == 20
    assert F.mqa_decode_cost(config, 0, 1, 64)["bytes"] == 2 * 20 * 128 * 2
    assert F.attention_row_steps(config, 26.0) == 2.0


def test_readers_give_nothing_where_the_program_has_no_such_counter():
    """On the parent's program, or any without these layers: no value and
    no error."""
    class Rec:
        counters = {"serve/total_s": 3.0, "serve/output_tokens": 5.0,
                    "serve/requests": 4.0}
        samples, spans = {}, {}

    class NoEvents:
        devices, busy_s = {"d": []}, 1.0

        def op_seconds(self):
            return {}

        def seconds_matching(self, patterns):
            return 0.0, 0
    cell = Cell(CELL, manifest.benchmark_json())
    for trace in (None, NoEvents()):
        ctx = {"cell": cell, "rec": Rec(), "trace": trace, "out": {},
               "peaks": {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0}}
        for name in NEW:
            read, spec = manifest.metric_reader(name)
            assert read(ctx, spec) is None
    # and in a cell of another model, whatever its trace holds
    other = Cell("serve-minicpm-sala-docs32k-over", manifest.benchmark_json())
    read, spec = manifest.metric_reader("ssm_share_pct.jamba")
    assert read({"cell": other, "rec": Rec(), "trace": NoEvents(),
                 "peaks": None}, spec) is None


def test_the_state_patterns_are_filled_from_the_cells_sizes():
    from benchmarks.readers import jamba_roofline as J
    from benchmarks.readers.kernel_roofline import fill
    cell = Cell(CELL, manifest.benchmark_json())
    f = J.fields(cell)
    b = cell.settings["engine"]["max_batch"]
    assert f == {"B": b, "N": 16, "DIN": 5120, "CONV": 15360}
    for name in ("ssm_update_roofline.jamba", "ssm_share_pct.jamba"):
        _, spec = manifest.metric_reader(name)
        assert fill(spec["patterns"], f) == [
            ["fusion(", f"f32[{b},16,5120]"], ["fusion(", f"f32[{b},15360]"]]
