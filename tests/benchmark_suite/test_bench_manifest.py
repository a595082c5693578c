"""BENCHMARK.json against the contract's limits, every name it gives found
as a file, and a configuration, a cell, a traffic mix and a per-layer
metric added as new files without editing one that exists."""
import json
import os
import re
import shutil

import pytest

import benchmarks.readers
from benchmarks import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return manifest.benchmark_json()


def test_keys_and_limits_of_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    cells = len(bench["workloads"])
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert 1 <= cells <= 24
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, cells // 4)
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    assert len(json.dumps(bench)) < 64 * 1024


def test_every_cell_finds_its_files_and_reports_what_it_must(bench):
    for w in bench["workloads"]:
        cell = manifest.Cell(w["name"], bench)
        assert callable(cell.runner().window)
        assert callable(cell.generator().make)
        e2e = {m["name"] for m in cell.end_to_end()}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = cell.per_layer()
        assert layer
        for m in layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
            read, spec = manifest.metric_reader(m["name"])
            assert callable(read) and spec["reader"]
        assert any("mfu" in re.split(r"[_.]", m["name"]) for m in layer)
        assert "limits" in cell.settings


def test_new_files_are_found_without_editing_one_that_exists(
        tmp_path, monkeypatch, bench):
    here = tmp_path / "benchmarks"
    for kind in ("configs", "workloads", "traffic", "metrics"):
        shutil.copytree(os.path.join(manifest.HERE, kind), here / kind)
    (here / "readers").mkdir()
    cfg = json.loads((here / "configs" / "toy-gpt.json").read_text())
    cfg.update(name="toy-gpt-wide", hidden_size=128, head_dim=32,
               intermediate_size=512)
    (here / "configs" / "toy-gpt-wide.json").write_text(json.dumps(cfg))
    (here / "traffic" / "toy-docs-b2.json").write_text(json.dumps(dict(
        json.loads((here / "traffic" / "toy-docs.json").read_text()),
        batch=2)))
    (here / "workloads" / "toy-train-wide.json").write_text(
        (here / "workloads" / "toy-train.json").read_text())
    (here / "metrics" / "steps_taken.json").write_text(json.dumps(
        {"reader": "count_steps", "counter": "train/steps"}))
    (here / "readers" / "count_steps.py").write_text(
        "def read(ctx, spec):\n"
        "    return ctx['rec'].counters.get(spec['counter'])\n")
    monkeypatch.setattr(manifest, "HERE", str(here))
    monkeypatch.setattr(benchmarks.readers, "__path__",
                        list(benchmarks.readers.__path__)
                        + [str(here / "readers")])
    grown = json.loads(json.dumps(bench))
    grown["configs"].append({
        "name": "toy-gpt-wide", "source": "test", "reduced": [],
        "file": "benchmarks/configs/toy-gpt-wide.json", "why": "test"})
    grown["workloads"].append({
        "name": "toy-train-wide", "config": "toy-gpt-wide",
        "traffic": "toy-docs-b2", "chips": 1, "why": "test"})
    grown["end_to_end"][0].setdefault("workloads", []).append(
        "toy-train-wide")
    grown["per_layer"].append({
        "name": "steps_taken", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": grown["end_to_end"][0]["name"],
        "workloads": ["toy-train-wide"]})
    cell = manifest.Cell("toy-train-wide", grown)
    assert cell.config["hidden_size"] == 128
    assert cell.traffic["batch"] == 2
    assert [m["name"] for m in cell.per_layer()] == ["steps_taken"]
    read, spec = manifest.metric_reader("steps_taken")

    class Rec:
        counters = {"train/steps": 7.0}
    assert read({"rec": Rec()}, spec) == 7.0
    # and the cell runs: a new configuration and traffic are only data
    from benchmarks.run import run_cell
    res = run_cell(cell, 5, 0.5, trace=True, rehearse=True)
    assert res["correct"] and res["metrics"]["steps_taken"]["value"] >= 1
