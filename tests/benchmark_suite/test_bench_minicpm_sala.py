"""The MiniCPM-SALA cell of the benchmark: its files are found by name, its
toy twin rehearses on the CPU through the cell's own runner, its
configuration keeps the published widths, and what decides `correct` fails
on each planted fault (a selected block left out, the forced window left
out, one head's decay wrong, a stale state after a prefix hit, an altered
token, the bfloat16 control)."""
import json
import os

import numpy as np
import pytest

from benchmarks import flops_minicpm_sala as F
from benchmarks import manifest, run
from benchmarks import weights_minicpm_sala as W
from benchmarks.manifest import Cell
from benchmarks.runners import serve_minicpm_sala as S
from benchmarks.tools import calibrate_sala

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "data", "toy_benchmark_minicpm_sala.json")
CELL, TOY_CELL = "serve-minicpm-sala-docs32k-over", "toy-serve-minicpm-sala"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("serve_step_mfu.sala", "sparse_decode_roofline.sala",
       "state_update_roofline.sala", "sparse_select_share_pct.sala",
       "sparse_rows_pct.sala", "state_snapshot_restores.sala")


@pytest.fixture(scope="module")
def toy():
    with open(TOY) as f:
        return json.load(f)


def test_the_manifest_finds_the_new_cells_files():
    bench = manifest.benchmark_json()
    cell = Cell(CELL, bench)
    assert cell.chips == 1 and cell.settings["runner"] == "serve_minicpm_sala"
    assert callable(cell.runner().check) and callable(cell.generator().make)
    assert {m["name"] for m in cell.end_to_end()} == {"serve_tokens_per_s",
                                                      "setup_s"}
    names = [m["name"] for m in cell.per_layer()]
    assert len(names) == 18 and all(n.endswith(".sala") for n in names)
    assert set(NEW) <= set(names)
    for n in names:
        read, spec = manifest.metric_reader(n)
        assert callable(read)
    entry, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(entry["why"]) <= 200 and entry["traffic"] == \
        "docs32k-4doc-r1.25"
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert not [m for m in Cell(w["name"], bench).per_layer()
                        if m["name"].endswith(".sala")]


def test_the_configuration_keeps_every_published_width():
    config = Cell(CELL, manifest.benchmark_json()).config
    assert sorted(config["reduced"]) == ["mixer_types", "num_hidden_layers"]
    assert config["published"]["num_hidden_layers"] == 32
    assert config["mixer_types"] == config["published"]["mixer_types"][::2]
    assert [i for i, m in enumerate(config["mixer_types"])
            if m == "minicpm4"] == [0, 8, 11, 15]
    assert set(config["assumed"]) >= {"sparse_config", "qk_norm",
                                      "lightning_decay", "weights"}
    assert config["deployment"]["pipeline_stages"] == 2
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f) if r["name"] == "MiniCPM-SALA"]
    for k, v in row["config"].items():
        assert config[k] == v or k in config["reduced"], k
    assert config["source"].startswith(row["source_url"])
    # the cut of ISSUE 34: 5,039M parameters, 10.08 GB in bfloat16
    assert W.n_params(config) == {"total": 5_039_448_064,
                                  "multiplied": 5_039_448_064 - 73448 * 4096}


def test_traffic_and_engine_of_the_cell_are_as_the_issue_fixed_them():
    cell = Cell(CELL, manifest.benchmark_json())
    t, eng = cell.traffic, cell.settings["engine"]
    assert (t["generator"], t["n_system"], t["system_len"]) == (
        "open_loop_chat", 4, 32768)
    assert (t["user_len_min"], t["user_len_max"], t["user_len_alpha"]) == (
        64, 1024, 1.2)
    assert (t["out_len_min"], t["out_len_max"]) == (128, 512)
    assert t["real_vocab"] == cell.config["vocab_size"] == 73448
    assert (eng["kv_block"], eng["prompt_cap"], eng["max_new_tokens"],
            eng["decode_chunk"]) == (64, 33792, 512, 8)
    assert 96 <= eng["max_batch"] <= 128
    assert t["system_len"] % eng["prefill_chunk"] == 0      # the snapshot
    assert eng["state_snapshots"] > t["n_system"]
    assert t["system_len"] + t["user_len_max"] <= eng["prompt_cap"]
    assert set(cell.settings["limits"]) == {"greedy_gap", "greedy_gap_mean"}


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
            "prefix_hit_pct.sala", "decode_batch_fill_pct.sala",
            "sparse_rows_pct.sala", "state_snapshot_restores.sala"}
        assert res["metrics"]["sparse_rows_pct.sala"]["value"] == 100.0
        assert res["metrics"]["state_snapshot_restores.sala"]["value"] == 1.0


def _run(toy, seed=31, seconds=0.5):
    res = run.run_cell(Cell(TOY_CELL, toy), seed, seconds, trace=False,
                       rehearse=True)
    return res["correct"], res["_compared_full"]


def test_a_sound_run_is_correct(toy):
    ok, compared = _run(toy)
    assert ok and compared["greedy_gap"]["value"] <= 1e-5


@pytest.mark.parametrize("fault", ["block", "window", "decay", "snapshot"])
def test_a_fault_planted_in_the_program_is_not_correct(toy, monkeypatch,
                                                       fault):
    """A selected page left out of every selection (widest gap 2.5e-4
    where the limit is 1e-4), the forced window not forced (4.2e-4), the
    slowest head of the lightning layers decaying as the fastest (1.3e-2),
    a prefix's snapshot one prefill window stale (6.5e-3): each through the
    cell's own `check`, over the 426 served tokens of a 3 s window. A page
    of five left out of two layers of four moves a logit by 1e-4 where the
    two best lie 9e-3 apart, so one token in a hundred changes: the window
    has to be this long for `correct` to see it."""
    real = S.build
    undo = []

    def build(cell, seed):
        model, eng = real(cell, seed)
        undo.append(calibrate_sala.plant(model, eng, fault))
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
    """calibrate_sala: every case goes through `compared_gaps` as `check`
    does; the sound case is within both limits, the control and each
    planted fault outside one. sweep_cell takes the cell as it is."""
    from benchmarks.tools import sweep_cell
    out = tmp_path / "cal.jsonl"
    assert calibrate_sala.main([
        "--workload", TOY_CELL, "--manifest", TOY, "--rehearse-cpu",
        "--seeds", "31,32", "--seconds", "3", "--control-seeds", "1",
        "--control-mode", "bf16", "--faults", "block,snapshot,token",
        "--requests", "48", "--out", str(out)]) == 0
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    assert [(r["seed"], r["case"]) for r in rows] == [
        (31, "sound"), (31, "control"), (31, "block"), (31, "snapshot"),
        (31, "token"), (32, "sound")]
    for r in rows:
        oks = [c["ok"] for c in r["compared"].values()]
        assert all(oks) == (r["case"] == "sound"), r
        assert r["restored"] == r["requests"]
    out = tmp_path / "sweep.jsonl"
    assert sweep_cell.main([
        "--workload", TOY_CELL, "--manifest", TOY, "--rehearse-cpu",
        "--seed", "4340001001", "--seconds", "0.5", "--rates", "40",
        "--engine", "max_batch=8", "--out", str(out)]) == 0
    row = json.loads(out.read_text())
    assert row["max_batch"] == 8 and row["failed"] == 0


def test_costs_from_shapes_and_counters():
    config = Cell(CELL, manifest.benchmark_json()).config
    mult = 5_039_448_064 - 73448 * 4096
    # a token: every multiplied parameter twice, and the state of twelve
    # lightning layers written and read (4 x 32 x 128 x 128 each)
    assert F.forward_flops(config, 1, 0, 0) == 2.0 * mult + 12 * 4.0 * 32 \
        * 128 * 128
    assert F.forward_flops(config, 0, 1, 0) == 4.0 * 32 * 128
    assert F.forward_flops(config, 0, 0, 1) == 2.0 * 32 * 128
    # a (page, KV head) pair: 32 KB of keys and values, 16 heads over 64
    # tokens: 16 FLOP a byte, far under the v5e's ridge of 240
    c = F.sparse_decode_cost(config, 1, 0)
    assert c["bytes"] == 2 * 64 * 128 * 2 and c["flops"] / c["bytes"] == 16
    assert F.sparse_decode_cost(config, 0, 1)["bytes"] == 2 * 32 * 128 * 2
    s = F.state_update_cost(config, 1)
    assert s["bytes"] == 2 * 4 * 32 * 128 * 128       # 2 x 2.10 MB


def test_readers_give_nothing_where_the_program_has_no_such_counter():
    """On the parent's program, or any without these layers: no value and
    no error."""
    class Rec:
        counters = {"serve/total_s": 3.0, "serve/output_tokens": 5.0,
                    "serve/requests": 4.0}
        samples, spans = {}, {}
    cell = Cell(CELL, manifest.benchmark_json())
    ctx = {"cell": cell, "rec": Rec(), "trace": None, "out": {},
           "peaks": {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0}}
    for name in NEW:
        read, spec = manifest.metric_reader(name)
        assert read(ctx, spec) is None


def test_the_selection_patterns_are_filled_from_the_cells_sizes():
    from benchmarks.readers import pattern_device_share as P
    from benchmarks.readers.kernel_roofline import fill
    cell = Cell(CELL, manifest.benchmark_json())
    f = P.fields(cell)
    assert (f["MB"], f["M"], f["KC"], f["G"]) == (536, 2144, 1024, 16)
    _, spec = manifest.metric_reader("sparse_select_share_pct.sala")
    got = fill(spec["patterns"], f)
    b = f["B"]
    assert [f"[{b},536,1024]"] in got and [f"[{b},2,2144]"] in got
    _, spec = manifest.metric_reader("state_update_roofline.sala")
    assert fill(spec["patterns"], f) == [["fusion(", f"f32[{b},32,128,128]"]]
