"""Readings that a serving cell's limits are set from, with the cell's own
runner (tools/calibrate_serve.py is runners/serve.py's): on the chip, the
cell's engine and load, a short window per seed, the seeds in one process.
The engine serves every case first and keeps each sample on the host;
then it is let go and the reference reads the samples (an engine of 14 GB
and a float32 reference do not fit side by side). Every case goes through
the runner's `sample_gaps` and `compared_gaps`, as `check` does, and a row
holds what `compared` would: each number beside its limit, and `ok`.

Per seed the sound case: the program's served tokens under the float32
reference. For the first `--control-seeds` seeds also:

- `control`: the tokens the reference in the next precision down puts
  first at the same positions, and how many of its (token, expert layer)
  choices differ from the float32 reference's;
- one case per `--faults` name, planted in the program and served again:
  `expert` (the first held expert's down-projection zeroed in every
  expert layer), `shared` (the last layer's shared expert's), `token`
  (one served token in each sampled request replaced by its neighbour);
- `program_choices_differ`: how many (token, expert layer) choices of the
  program's plain forward (`model.expert_choices`) over the first
  `--choice-tokens` tokens of the first sampled request differ from the
  float32 reference's.

    python3 -m benchmarks.tools.calibrate_cell --workload <cell> \
        --seeds 201,202,... --seconds 10 --control-seeds 3 \
        --faults expert,shared,token --out ...
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

from benchmarks import harness

FAULTS = ("expert", "shared", "token")


def plant(model, fault: str) -> None:
    """Zeroes in the program what the fault leaves out (the leaves
    tests/benchmark_suite/test_bench_pangu_moe.py zeroes at the toy
    size)."""
    params = dict(model.named_parameters())
    moe = sorted({n.split(".")[1] for n in params if ".mlp.we_down" in n},
                 key=int)
    if fault == "expert":
        for i in moe:
            p = params[f"layers.{i}.mlp.we_down"]
            p._data = p._data.at[0].set(0)
    elif fault == "shared":
        p = params[f"layers.{moe[-1]}.mlp.ws_down"]
        p._data = p._data * 0


def altered(sample, vocab: int):
    """Each request's middle served token replaced by its neighbour."""
    out = []
    for prompt, tokens in sample:
        tokens = np.array(tokens)
        if len(tokens):
            tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 1) % vocab
        out.append((prompt, tokens))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--control-mode", default="fp8")
    ap.add_argument("--faults", default="")
    ap.add_argument("--choice-tokens", type=int, default=1024)
    ap.add_argument("--out", required=True)
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    faults = [f for f in args.faults.split(",") if f]
    if set(faults) - set(FAULTS):
        ap.error(f"--faults takes {FAULTS}")
    from benchmarks.manifest import Cell, benchmark_json
    cell = Cell(args.workload, benchmark_json(args.manifest))
    try:
        harness.start_program(cell.chips, args.rehearse_cpu)
    except harness.NoChip as e:
        return e.code
    import jax
    runner = cell.runner()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    seeds = [int(x) for x in args.seeds.split(",")]
    model, eng = runner.build(cell, seeds[0])

    def serve(seed):
        rec = harness.Recording()
        state = {"model": model, "eng": eng, "cell": cell, "seed": seed}
        runner.prepare(state, args.seconds)
        out = runner.window(state, args.seconds, rec)
        eng._prefix.clear()     # the next case's weights: nothing cached
        return runner.sample_of(state), {
            "requests": out["attempted"], "failed": out["failed"],
            **out["end_to_end"], "total_s": rec.counters["serve/total_s"]}

    cases = []      # (seed, case, sample, row, the program's choices)
    for n, seed in enumerate(seeds):
        if n:
            runner.install_weights(model, cell.config, seed)
        sample, row = serve(seed)
        picked = None
        if n < args.control_seeds and sample and args.choice_tokens:
            prompt, tokens = sample[0]
            ids = np.concatenate([prompt, tokens])[:args.choice_tokens]
            try:
                picked = [np.sort(a[0], -1)
                          for a in model.expert_choices(ids[None])]
            except Exception as e:      # no room beside the engine
                harness.say(f"expert_choices failed: {e!r:.300}")
        cases.append((seed, "sound", sample, row, picked))
        if n < args.control_seeds:
            cases.append((seed, "control", sample, row, None))
            for fault in faults:
                if fault == "token":
                    cases.append((seed, fault, altered(
                        sample, int(cell.config["vocab_size"])), row, None))
                    continue
                plant(model, fault)
                cases.append((seed, fault, *serve(seed), None))
                runner.install_weights(model, cell.config, seed)
    del model, eng              # the reference needs the chip to itself
    gc.collect()
    jax.clear_caches()
    gc.collect()
    with open(args.out, "a") as f:
        for seed, case, sample, row, picked in cases:
            t0 = time.perf_counter()
            gaps = runner.sample_gaps(
                cell, seed, sample, mode=args.control_mode,
                control=case == "control")
            row = {"cell": cell.name, "seed": seed, "case": case,
                   "compared": runner.compared_gaps(cell, gaps),
                   "tokens": gaps["tokens"], **row}
            if case == "control":
                row.update(control_choices_differ=gaps["choices_differ"],
                           control_choices=gaps["choices_total"])
            if picked is not None:
                ref = gaps["choices"][0]
                row.update(
                    program_choices_differ=sum(
                        int(np.sum(np.any(a != b[:len(a)], -1)))
                        for a, b in zip(picked, ref)),
                    program_choices=sum(len(a) for a in picked))
            row["reference_s"] = time.perf_counter() - t0
            f.write(json.dumps(row) + "\n")
            f.flush()
            harness.say(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
