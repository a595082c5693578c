"""The openPangu-Ultra-MoE cell of the benchmark: its files are found by
name, its toy twin rehearses on the CPU through the cell's own runner, its
configuration keeps the published widths, and what decides `correct` fails
on each planted fault (an expert left out, the shared expert left out, an
altered token, the bfloat16 control)."""
import json
import os

import numpy as np
import pytest

from benchmarks import flops_pangu_moe as F
from benchmarks import manifest, run
from benchmarks import weights_pangu_moe as W
from benchmarks.manifest import Cell
from benchmarks.runners import serve_pangu_moe as S

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "data", "toy_benchmark_pangu_moe.json")
CELL, TOY_CELL = "serve-pangu-ultra-moe-agent-over", "toy-serve-pangu-moe"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def toy():
    with open(TOY) as f:
        return json.load(f)


def test_the_manifest_finds_the_new_cells_files():
    bench = manifest.benchmark_json()
    cell = Cell(CELL, bench)
    assert cell.chips == 1 and cell.settings["runner"] == "serve_pangu_moe"
    assert callable(cell.runner().check) and callable(cell.generator().make)
    assert {m["name"] for m in cell.end_to_end()} == {"serve_tokens_per_s",
                                                      "setup_s"}
    names = [m["name"] for m in cell.per_layer()]
    assert len(names) == 17 and all(n.endswith(".moe") for n in names)
    for n in names:
        read, spec = manifest.metric_reader(n)
        assert callable(read)
    entry, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(entry["why"]) <= 200
    # nothing else reports the new metrics, and the old cells none of them
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert not [m for m in Cell(w["name"], bench).per_layer()
                        if m["name"].endswith(".moe")]


def test_the_configuration_keeps_every_published_width():
    config = Cell(CELL, manifest.benchmark_json()).config
    reduced = {"num_hidden_layers": 61, "first_k_dense_replace": 3,
               "n_routed_experts": 256, "vocab_size": 153600,
               "num_nextn_predict_layers": 1}
    assert sorted(config["reduced"]) == sorted(reduced)
    assert config["published"] == reduced
    assert set(config["assumed"]) >= {"scoring", "sandwich_norm", "weights"}
    assert config["deployment"]["expert_parallel"] == 16
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r["name"] == "openPangu-Ultra-MoE-718B"]
    for k, v in row["config"].items():
        assert config[k] == v or k in reduced, k
    # the cut of ISSUE 28: 4,919M parameters, 16 experts of 47.2M a layer
    n = W.n_params(config)
    assert n["total"] == 4_919_139_840 and n["one_expert"] == 47_185_920


def test_traffic_of_the_cell_is_as_the_issue_fixed_it():
    cell = Cell(CELL, manifest.benchmark_json())
    t = cell.traffic
    assert (t["n_system"], t["system_len"], t["user_len_alpha"]) == (8, 2048,
                                                                     1.2)
    assert (t["user_len_min"], t["user_len_max"]) == (64, 2048)
    assert (t["out_len_min"], t["out_len_max"]) == (128, 1024)
    assert t["real_vocab"] == cell.config["vocab_size"] == 19200
    eng = cell.settings["engine"]
    assert t["system_len"] + t["user_len_max"] <= eng["prompt_cap"]
    assert t["out_len_max"] <= eng["max_new_tokens"]
    reqs = cell.generator().make(t, cell.config, 2 ** 31 + 9, 4.0)["requests"]
    assert max(int(r["prompt"].max()) for r in reqs) < 19200


@pytest.mark.parametrize("trace", [0, 1])
def test_the_toy_cell_rehearses_on_the_cpu(capsys, trace):
    assert run.main(["--workload", TOY_CELL, "--seed", str(2 ** 31 + 17),
                     "--seconds", "1", "--trace", str(trace),
                     "--rehearse-cpu", "--manifest", TOY]) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True, res["compared"]
    assert res["device"]["platform"] == "cpu" and res["rehearsal"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    if trace:
        # counts only: 4 experts held of 16, 4 choices a token
        assert set(res["metrics"]) == {
            "prefix_hit_pct.moe", "decode_batch_fill_pct.moe",
            "expert_tokens_per_call.moe", "expert_load_max_over_mean.moe"}
        assert res["metrics"]["expert_load_max_over_mean.moe"]["value"] >= 1


def _run(toy, seed=31):
    res = run.run_cell(Cell(TOY_CELL, toy), seed, 0.5, trace=False,
                       rehearse=True)
    return res["correct"], res["_compared_full"]


def _with_weights_zeroed(monkeypatch, names):
    import jax.numpy as jnp
    real = S.build

    def build(cell, seed):
        model, eng = real(cell, seed)
        for name in names:
            p = dict(model.named_parameters())[name]
            p._data = p._data.at[0].set(0) if p._data.ndim == 3 \
                else jnp.zeros_like(p._data)
        return model, eng
    monkeypatch.setattr(S, "build", build)


def test_a_sound_run_is_correct(toy):
    ok, compared = _run(toy)
    assert ok and compared["greedy_gap"]["value"] <= 1e-5


@pytest.mark.parametrize("leaves", [
    [f"layers.{i}.mlp.we_down" for i in (1, 2, 3, 4)],
    ["layers.3.mlp.ws_down"]], ids=["expert", "shared"])
def test_an_expert_or_the_shared_expert_left_out_is_not_correct(
        toy, monkeypatch, leaves):
    """The first held expert's contribution (gap 0.08-0.09 where the limit
    is 1e-4), or one layer's shared expert (0.07-0.11), dropped in the
    program."""
    _with_weights_zeroed(monkeypatch, leaves)
    ok, compared = _run(toy)
    assert not ok and not compared["greedy_gap"]["ok"]
    assert not compared["greedy_gap_mean"]["ok"]


def test_a_token_altered_where_it_is_produced_is_not_correct(
        toy, monkeypatch):
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
    few hundred positions some near-tie falls the other way; and some
    (token, layer) expert choices differ, which the control counts."""
    cell = Cell(TOY_CELL, toy)
    rng = np.random.default_rng(3)
    sample = [(rng.integers(1, 255, 20), rng.integers(1, 255, 16))
              for _ in range(16)]
    gaps = S.sample_gaps(cell, 5, sample, mode="bf16", control=True)
    compared = S.compared_gaps(cell, gaps)
    assert not compared["greedy_gap"]["ok"]
    assert not compared["greedy_gap_mean"]["ok"]
    assert gaps["choices_total"] == 16 * 36 * 4
    assert 0 < gaps["choices_differ"] < gaps["choices_total"] / 2


def test_the_tools_read_the_cell_through_its_own_runner(toy, tmp_path):
    """calibrate_cell: every case goes through `compared_gaps` as `check`
    does; the sound case is within both limits, the control and each
    planted fault outside one. sweep_cell: an engine override, and the
    pool's pages in use."""
    from benchmarks.tools import calibrate_cell, sweep_cell
    out = tmp_path / "cal.jsonl"
    assert calibrate_cell.main([
        "--workload", TOY_CELL, "--manifest", TOY, "--rehearse-cpu",
        "--seeds", "31,32", "--seconds", "0.5", "--control-seeds", "1",
        "--control-mode", "bf16", "--faults", "expert,shared,token",
        "--choice-tokens", "40", "--out", str(out)]) == 0
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    assert [(r["seed"], r["case"]) for r in rows] == [
        (31, "sound"), (31, "control"), (31, "expert"), (31, "shared"),
        (31, "token"), (32, "sound")]
    for r in rows:
        oks = [c["ok"] for c in r["compared"].values()]
        assert all(oks) == (r["case"] == "sound"), r
    assert rows[0]["program_choices_differ"] == 0     # float32 on both sides
    assert rows[0]["program_choices"] == 40 * 4
    assert 0 < rows[1]["control_choices_differ"] < rows[1]["control_choices"]
    out = tmp_path / "sweep.jsonl"
    assert sweep_cell.main([
        "--workload", TOY_CELL, "--manifest", TOY, "--rehearse-cpu",
        "--seed", "4280001001", "--seconds", "0.5", "--rates", "40",
        "--engine", "max_batch=8", "--out", str(out)]) == 0
    row = json.loads(out.read_text())
    assert row["max_batch"] == 8 and row["failed"] == 0
    assert 0 < row["pool_pages_used_peak"] <= 1
    halves = lambda rate, a, b: {  # noqa: E731
        "rate": rate, "ttft_p50_first_half_ms": a,
        "ttft_p50_second_half_ms": b}
    rows = [halves(10, 266, 1891), halves(8, 182, 239), halves(9, 261, 232)]
    assert sweep_cell.knee_of(rows) == 9
    assert sweep_cell.knee_of(rows[:1]) is None


def test_costs_from_shapes_and_counters():
    config = Cell(CELL, manifest.benchmark_json()).config
    # a token through everything outside the routed experts but the
    # embedding: 2 x (1,899.2M - 147.5M)
    assert F.forward_flops(config, 1, 0, 0) == 2.0 * (1_899_240_960
                                                      - 19200 * 7680)
    assert F.forward_flops(config, 0, 1, 0) == 2.0 * 47_185_920
    assert F.forward_flops(config, 0, 0, 1) == 5 * 2.0 * 128 * 1088
    # the latent walk sits at the v5e's ridge: 241 FLOP a byte
    c = F.latent_decode_cost(config, 1e6, 0)
    assert round(c["flops"] / c["bytes"]) == 242
    e = F.expert_product_cost(config, 16, 64)
    assert e["bytes"] == (16 * 47_185_920 + 64 * 2 * 7680) * 2


def test_readers_give_nothing_where_the_program_has_no_such_counter():
    """On the parent's program, or any without expert layers: no value and
    no error."""
    class Rec:
        counters = {"serve/total_s": 3.0, "serve/output_tokens": 5.0}
        samples, spans = {}, {}
    cell = Cell(CELL, manifest.benchmark_json())
    ctx = {"cell": cell, "rec": Rec(), "trace": None, "out": {},
           "peaks": {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0}}
    for name in ("serve_step_mfu.moe", "expert_tokens_per_call.moe",
                 "expert_load_max_over_mean.moe",
                 "latent_decode_roofline.moe", "expert_product_roofline.moe"):
        read, spec = manifest.metric_reader(name)
        assert read(ctx, spec) is None
