"""What decides `correct`, shown to fail: the control (the reference in
the next precision down, put in the program's place) comes out as not
correct, and so does a run whose timed path is broken underneath."""
import json
import os

import numpy as np
import pytest

from benchmarks import run
from benchmarks.manifest import Cell
from benchmarks.runners import serve as S
from benchmarks.runners import train as T

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                   "toy_benchmark.json")


@pytest.fixture(scope="module")
def bench():
    with open(TOY) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_train_control_in_bfloat16_is_not_correct(bench, seed):
    cell = Cell("toy-train", bench)
    feed = cell.generator().make(cell.traffic, cell.config, seed)
    first = [feed.next() for _ in range(T.CHECK_STEPS)]
    ref = T.reference_run(cell.config, seed, first)
    ctl = T.reference_run(cell.config, seed, first, mode="bf16")
    again = T.reference_run(cell.config, seed, first)
    limits = cell.settings["limits"]
    assert all(c["ok"] for c in T.compare(again, ref, limits).values())
    assert not all(c["ok"] for c in T.compare(ctl, ref, limits).values())


def test_train_half_of_the_batch_left_out_is_not_correct(bench):
    cell = Cell("toy-train", bench)
    feed = cell.generator().make(cell.traffic, cell.config, 21)
    first = [feed.next() for _ in range(T.CHECK_STEPS)]
    ref = T.reference_run(cell.config, 21, first)
    half = T.reference_run(cell.config, 21, first, rows=slice(0, 2))
    got = T.compare(half, ref, cell.settings["limits"])
    assert not got["loss_gap"]["ok"] and not got["grad_norm_gap"]["ok"]


def _run(bench, workload, seed=31):
    res = run.run_cell(Cell(workload, bench), seed, 0.5, trace=False,
                       rehearse=True)
    return res["correct"], res["_compared_full"]


def test_sound_runs_are_correct(bench):
    assert _run(bench, "toy-train")[0] and _run(bench, "toy-serve")[0]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        bench, monkeypatch):
    import jax.numpy as jnp
    real = T.call_step

    def frozen(step, ids, labels):
        keep = [jnp.array(p._data, copy=True) for p in step._params]
        loss = real(step, ids, labels)
        for p, a in zip(step._params, keep):
            p._data = a
        return loss
    monkeypatch.setattr(T, "call_step", frozen)
    ok, compared = _run(bench, "toy-train")
    assert not ok
    assert compared["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(bench, monkeypatch):
    real = T.call_step
    monkeypatch.setattr(T, "call_step", lambda step, ids, labels: real(
        step, ids[:len(ids) // 2], labels[:len(ids) // 2]))
    ok, compared = _run(bench, "toy-train")
    assert not ok and not compared["grad_norm_gap"]["ok"]


def test_a_token_altered_where_it_is_produced_is_not_correct(
        bench, monkeypatch):
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
    ok, compared = _run(bench, "toy-serve")
    assert not ok and not compared["greedy_gap"]["ok"]


def test_serve_control_in_bfloat16_is_not_correct(bench):
    """The tokens bfloat16 puts first, read in the float32 logits at the
    positions of a sound run's sample: over a few hundred positions some
    near-tie is decided the other way."""
    cell = Cell("toy-serve", bench)
    limit = cell.settings["limits"]["greedy_gap"]
    rng = np.random.default_rng(3)
    sample = [(rng.integers(1, 255, 20), rng.integers(1, 255, 40))
              for _ in range(24)]
    worst = S.sample_gaps(cell.config, 5, sample, mode="bf16",
                          control=True)[0]
    assert worst > limit
