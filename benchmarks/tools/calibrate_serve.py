"""Readings that a serving cell's limit is set from (on the chip, the
cell's own engine and load, a short window per seed, seeds in one
process; several cells that share an engine, comma-separated, in one): the widest gap of the program's served tokens under the float32
reference (lower reading), and of the tokens the next precision down puts
first at the same positions (the control, upper reading).

    python3 -m benchmarks.tools.calibrate_serve --workload <cell> \
        --seeds 201,202,... --seconds 8 --control-seeds 3 --out ...
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

from benchmarks import harness
from benchmarks.runners import serve as S


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--control-mode", default="fp8")
    ap.add_argument("--out", required=True)
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    from benchmarks.manifest import Cell, benchmark_json
    bench = benchmark_json(args.manifest)
    try:
        harness.start_program(1, args.rehearse_cpu)
    except harness.NoChip as e:
        return e.code
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    cells = [Cell(w, bench) for w in args.workload.split(",")]
    cell = cells[0]
    if any(c.settings["engine"] != cell.settings["engine"]
           or c.config != cell.config for c in cells):
        print("the cells must share a configuration and an engine",
              file=sys.stderr)
        return 3
    # one model and one engine for every seed of every cell: a seed brings
    # its weights and its traffic, and building the engine again would be
    # most of the call. The engine stays on the chip while the reference
    # runs (it fits beside it); a run of the benchmark frees it first.
    seeds = [int(x) for x in args.seeds.split(",")]
    model, eng = S.build(cell, seeds[0])
    with open(args.out, "a") as f:
        for cell in cells:
            for n, seed in enumerate(seeds):
                if (cell, n) != (cells[0], 0):
                    S.install_weights(model, cell.config, seed)
                rec = harness.Recording()
                state = {"model": model, "eng": eng, "cell": cell,
                         "seed": seed}
                S.prepare(state, args.seconds)
                out = S.window(state, args.seconds, rec)
                sample = S.sample_of(state)
                t0 = time.perf_counter()
                widest, tokens, where = S.sample_gaps(
                    cell.config, seed, sample)
                row = {"cell": cell.name, "seed": seed,
                       "program_gap": widest, "tokens": tokens,
                       "where": where, "requests": out["attempted"],
                       "failed": out["failed"], **out["end_to_end"],
                       "total_s": rec.counters["serve/total_s"],
                       "reference_s": time.perf_counter() - t0}
                if n < args.control_seeds:
                    row["control_gap"], _, row["control_where"] = \
                        S.sample_gaps(cell.config, seed, sample,
                                      mode=args.control_mode, control=True)
                f.write(json.dumps(row) + "\n")
                f.flush()
                harness.say(json.dumps(row))
                del state, sample
                gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
