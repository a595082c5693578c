"""Readings that a training cell's limits are set from (on the chip, at
the cell's own size, many seeds in one process, no measured window):

  program   the program's first three steps against the float32 reference
            (the lower reading of each number, over a dozen seeds);
  control   the reference in the next precision down (fp8 operands for a
            bfloat16 configuration) against the float32 reference;
  half      the fault "half of the batch left out, the mean taken over the
            rest", planted in the reference.

    python3 -m benchmarks.tools.calibrate_train --workload <cell> \
        --seeds 101,102,... --control-seeds 3 --out chiprun_out/cal.jsonl
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

from benchmarks import harness
from benchmarks.runners import train as T


def _gaps(got, ref):
    c = T.compare(got, ref, {"loss_gap": 0, "grad_norm_gap": 0,
                             "change_norm_gap": 0})
    return {k: (v["value"], v["where"]) for k, v in c.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--control-mode", default="fp8")
    ap.add_argument("--out", required=True)
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks.manifest import Cell, benchmark_json
    cell = Cell(args.workload, benchmark_json(args.manifest))
    try:
        harness.start_program(cell.chips, args.rehearse_cpu)
    except harness.NoChip as e:
        return e.code
    seeds = [int(s) for s in args.seeds.split(",")]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    model, step, _ = T.build(cell, seeds[0])
    with open(args.out, "a") as f:
        for n, seed in enumerate(seeds):
            t0 = time.perf_counter()
            if n:
                T.install_weights(model, cell.config, seed)
                step._opt_state = None
                step._step_i = 0
            feed = cell.generator().make(cell.traffic, cell.config, seed)
            first = [feed.next() for _ in range(T.CHECK_STEPS)]
            prog = {"losses": []}
            for i, (ids, labels) in enumerate(first):
                prog["losses"].append(float(T.call_step(step, ids, labels)))
                if i == 0:
                    prog["grad_norms"] = T.program_grad_norms(
                        step, cell.config)
            prog["change_norms"] = T.program_change_norms(
                step, cell.config, seed)
            # the program's state leaves the chip before the reference runs
            for p in step._params:
                p._data = None
            step._opt_state = None
            gc.collect()
            t1 = time.perf_counter()
            ref = T.reference_run(cell.config, seed, first)
            t2 = time.perf_counter()
            row = {"seed": seed, "program": _gaps(prog, ref),
                   "losses": prog["losses"], "ref_losses": ref["losses"],
                   "program_s": t1 - t0, "reference_s": t2 - t1}
            if n < args.control_seeds:
                ctl = T.reference_run(cell.config, seed, first,
                                      mode=args.control_mode)
                row["control"] = _gaps(ctl, ref)
                b = first[0][0].shape[0]
                half = T.reference_run(cell.config, seed, first,
                                       rows=slice(0, (b + 1) // 2))
                row["half"] = _gaps(half, ref)
            f.write(json.dumps(row) + "\n")
            f.flush()
            harness.say(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
