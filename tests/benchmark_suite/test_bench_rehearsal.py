"""Every runner end to end on the CPU at a toy size: the last line has the
contract's shape, names the CPU and carries counts only."""
import json
import os

import pytest

from benchmarks import run

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                   "toy_benchmark.json")

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _last_line(capsys, argv):
    assert run.main(argv) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("workload,trace", [
    ("toy-train", 0), ("toy-train", 1), ("toy-train-mesh", 0),
    ("toy-train-q8", 0),
    ("toy-serve", 0), ("toy-serve", 1)])
def test_cell_runs_end_to_end_and_names_the_cpu(capsys, workload, trace):
    res = _last_line(capsys, [
        "--workload", workload, "--seed", str(2 ** 31 + 17), "--seconds",
        "1", "--trace", str(trace), "--rehearse-cpu", "--manifest", TOY])
    assert CONTRACT_KEYS <= set(res)
    assert list(res)[-1] == "compared"
    assert res["correct"] is True, res["compared"]
    assert res["device"]["platform"] == "cpu" and res["rehearsal"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    with open(TOY) as f:
        bench = json.load(f)
    source = {m["name"]: m["source"]
              for m in bench["end_to_end"] + bench["per_layer"]}
    # no time, rate or share of a peak from a CPU run
    assert all(source[k] == "program_counter" for k in res["metrics"])
    assert "busy_s" not in res["device"]
    if workload == "toy-serve" and trace:
        assert res["metrics"]["prefix_hit_pct.chat"]["value"] > 50


def test_without_a_tpu_nothing_is_printed_and_the_exit_code_is_not_zero(
        capsys):
    rc = run.main(["--workload", "toy-train", "--seed", "1", "--seconds",
                   "1", "--trace", "0", "--manifest", TOY])
    assert rc != 0
    out = capsys.readouterr().out
    assert not [ln for ln in out.splitlines() if ln.startswith("{")]


def test_same_seed_same_inputs_and_every_seed_the_same_sizes():
    from benchmarks.manifest import Cell
    with open(TOY) as f:
        bench = json.load(f)
    cell = Cell("toy-serve", bench)
    gen = cell.generator()
    a = gen.make(cell.traffic, cell.config, 2 ** 31 + 5, 4.0)["requests"]
    b = gen.make(cell.traffic, cell.config, 2 ** 31 + 5, 4.0)["requests"]
    c = gen.make(cell.traffic, cell.config, 6, 4.0)["requests"]
    assert all((x["prompt"] == y["prompt"]).all() and x["at"] == y["at"]
               for x, y in zip(a, b))
    sizes = lambda rs: sorted(  # noqa: E731
        (len(r["prompt"]), r["max_new_tokens"]) for r in rs)
    assert sizes(a) == sizes(c) and len(a) == len(c)
    assert [r["at"] for r in a] != [r["at"] for r in c]
    # the same cycle of gaps and sizes, opened at another place
    gaps = lambda rs: [round(y["at"] - x["at"], 9)  # noqa: E731
                       for x, y in zip(rs, rs[1:])]
    ga, gc = gaps(a), gaps(c)
    assert any(ga[k:] == gc[:len(ga) - k] for k in range(1, len(ga) - 1)) \
        or any(gc[k:] == ga[:len(gc) - k] for k in range(1, len(gc) - 1))
    train = Cell("toy-train", bench)
    f1 = train.generator().make(train.traffic, train.config, 9)
    f2 = train.generator().make(train.traffic, train.config, 9)
    x1, y1 = f1.next()
    x2, _ = f2.next()
    assert (x1 == x2).all() and (x1[:, 1:] == y1[:, :-1]).all()
    assert len({tuple(r) for r in x1}) == len(x1)      # rows all differ
