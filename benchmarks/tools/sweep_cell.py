"""Finds the knee of a serving cell once, on the chip, with the cell's own
runner (tools/sweep_serve.py builds runners/serve.py's GPT by name): the
cell's traffic at each of several rates, one engine, one process. Prints
one JSON line per rate, as sweep_serve.py does. `--engine` overrides keys
of the cell's `engine` settings for a trial (`max_batch=256,kv_blocks=4096`);
a row also holds the largest share of the pool's pages that were not free
after any engine step of the window (rows' own pages and what the prefix
trie keeps).

    python3 -m benchmarks.tools.sweep_cell --workload <cell> --seed 1 \
        --seconds 30 --rates 6,8,10,12,14 --out chiprun_out/sweep.jsonl
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from benchmarks import harness


def knee_of(rows: list, factor: float = 1.5):
    """PR 25's rule: the highest rate up to which the second half of the
    window's arrivals waited no more than `factor` times as long for its
    first token as the first half (medians); None if the lowest rate
    already fails."""
    knee = None
    for row in sorted(rows, key=lambda r: r["rate"]):
        if row["ttft_p50_second_half_ms"] > \
                factor * row["ttft_p50_first_half_ms"]:
            break
        knee = row["rate"]
    return knee


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--engine", default="")
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    from benchmarks.manifest import Cell, benchmark_json
    cell = Cell(args.workload, benchmark_json(args.manifest))
    try:
        harness.start_program(cell.chips, args.rehearse_cpu)
    except harness.NoChip as e:
        return e.code
    runner = cell.runner()
    override = {k: int(v) for k, v in (
        kv.split("=") for kv in args.engine.split(",") if kv)}
    cell.settings["engine"] = {**cell.settings["engine"], **override}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    state = runner.set_up(cell, args.seed, harness.Recording())
    eng, used = state["eng"], [0.0]
    step = eng.step

    def step_and_look(*a, **kw):
        out = step(*a, **kw)
        pool = eng._pool
        used[0] = max(used[0], 1 - pool.free_blocks / pool.capacity_blocks)
        return out
    eng.step = step_and_look
    rows = []
    with open(args.out, "a") as f:
        for n, rate in enumerate(float(r) for r in args.rates.split(",")):
            cell.traffic = dict(cell.traffic, rate=rate)
            rec = harness.Recording()
            state["seed"] = args.seed + n
            used[0] = 0.0
            runner.prepare(state, args.seconds)
            out = runner.window(state, args.seconds, rec)
            ttft = rec.samples["ttft_s"]      # in order of arrival
            half = len(ttft) // 2
            fills = rec.samples.get("batch_fill") or [0.0]
            row = {"rate": rate, **override, **out["end_to_end"],
                   "pool_pages_used_peak": used[0],
                   "ttft_p50_first_half_ms": 1e3 * harness.percentile(
                       ttft[:half], 50),
                   "ttft_p50_second_half_ms": 1e3 * harness.percentile(
                       ttft[half:], 50),
                   "requests": out["attempted"], "failed": out["failed"],
                   "tpot_p50_ms": 1e3 * harness.percentile(
                       rec.samples["tpot_s"], 50),
                   "lag_p95_ms": 1e3 * harness.percentile(
                       rec.samples["arrival_lag_s"], 95),
                   "batch_fill": sum(fills) / len(fills),
                   "unfinished_at_close":
                       rec.counters["serve/unfinished_at_close"],
                   "total_s": rec.counters["serve/total_s"],
                   "prefix_hits": rec.counters["serve/prefix_hit_total"],
                   "peak_bytes": harness.memory_peak_bytes(1)}
            rows.append(row)
            f.write(json.dumps(row) + "\n")
            f.flush()
            harness.say(json.dumps(row))
    harness.say(json.dumps({"knee": knee_of(rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
