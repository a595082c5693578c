"""Finds the knee of a serving cell once, on the chip: the cell's traffic
at each of several rates, one engine, one process. Prints one JSON line
per rate: tails, tokens per second, how many requests were unfinished when
the window closed and how long the wait for them took.

    python3 -m benchmarks.tools.sweep_serve --workload <cell> --seed 1 \
        --seconds 20 --rates 4,6,8,10,12,14 --out chiprun_out/sweep.jsonl
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from benchmarks import harness
from benchmarks.runners import serve as S


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--repeat", type=int, default=1,
                    help="windows per rate, all on the same seed: how far "
                         "two runs of one seed differ at that rate")
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
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    state = S.set_up(cell, args.seed, harness.Recording())
    with open(args.out, "a") as f:
        rates = [float(r) for r in args.rates.split(",")
                 for _ in range(args.repeat)]
        for n, rate in enumerate(rates):
            cell.traffic = dict(cell.traffic, rate=rate)
            rec = harness.Recording()
            state["seed"] = args.seed + n // args.repeat
            S.prepare(state, args.seconds)
            out = S.window(state, args.seconds, rec)
            ttft = rec.samples["ttft_s"]      # in order of arrival
            half = len(ttft) // 2
            row = {"rate": rate, **out["end_to_end"],
                   "ttft_p50_first_half_ms": 1e3 * harness.percentile(
                       ttft[:half], 50),
                   "ttft_p50_second_half_ms": 1e3 * harness.percentile(
                       ttft[half:], 50),
                   "requests": out["attempted"], "failed": out["failed"],
                   "ttft_p50_ms": 1e3 * harness.percentile(
                       rec.samples["ttft_s"], 50),
                   "tpot_p50_ms": 1e3 * harness.percentile(
                       rec.samples["tpot_s"], 50),
                   "lag_p95_ms": 1e3 * harness.percentile(
                       rec.samples["arrival_lag_s"], 95),
                   "batch_fill": sum(rec.samples.get("batch_fill", [0]))
                   / max(len(rec.samples.get("batch_fill", [0])), 1),
                   "unfinished_at_close":
                       rec.counters["serve/unfinished_at_close"],
                   "total_s": rec.counters["serve/total_s"],
                   "prefix_hits": rec.counters["serve/prefix_hit_total"],
                   "peak_bytes": harness.memory_peak_bytes(1)}
            f.write(json.dumps(row) + "\n")
            f.flush()
            harness.say(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
