"""One run of one cell of BENCHMARK.json.

    python3 -m benchmarks.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result: `correct`, `attempted`,
`failed`, `metrics` (the cell's end-to-end metrics with --trace 0, its
per-layer metrics with --trace 1), `device`, with --trace 1 `breakdown`,
and last `compared`: each number that decided `correct` beside its limit.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result. `--rehearse-cpu` exists for the tests: the
line then names the CPU and carries counts only, never a time or a rate.
"""
from __future__ import annotations

from benchmarks import harness  # noqa: F401  (first: the clock's zero)

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=None,
                    help="tests only: another file than BENCHMARK.json")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tests only: run on the CPU, report counts only")
    return ap.parse_args(argv)


def _print_compared(compared: dict, stream) -> None:
    for name, c in compared.items():
        print(f"compared {name}: {c['value']:.6g} limit {c['limit']:.6g} "
              f"{'ok' if c['ok'] else 'FAIL'}"
              + (f" at {c['where']}" if c.get("where") else ""),
              file=stream, flush=True)


def run_cell(cell, seed: int, seconds: float, trace: bool,
             rehearse: bool = False) -> dict:
    """Everything after the look for a chip. Returns the result object."""
    import jax
    from benchmarks import peaks as peaks_mod
    from benchmarks.manifest import metric_reader
    from benchmarks.trace import TraceSummary, find_xplane, load_xplane

    info = harness.device_info()
    compiles = harness.CompileCounter()
    rec = harness.Recording()
    runner = cell.runner()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        if trace:           # a traced window is short: traces are large
            seconds = min(seconds, float(
                cell.settings.get("trace_seconds", seconds)))
        state = runner.set_up(cell, seed, rec)
        if hasattr(runner, "prepare"):
            runner.prepare(state, seconds)
        harness.say(f"set-up done: {compiles.compiles} backend compiles "
                    f"({compiles.seconds:.1f}s), persistent cache "
                    f"{compiles.hits} hits {compiles.misses} misses")
        if trace:
            jax.profiler.start_trace(trace_dir)
        compiled_before = compiles.compiles
        setup_s = time.perf_counter() - harness.PROCESS_T0
        with jax.profiler.TraceAnnotation("bench/window"):
            out = runner.window(state, seconds, rec)
        if trace:
            jax.profiler.stop_trace()
        in_window = compiles.compiles - compiled_before
        harness.say(f"window closed: {out['window_s']:.2f}s, "
                    f"{in_window} compiles inside it")
        peak = harness.memory_peak_bytes(cell.chips)
        runner.release(state)
        gc.collect()
        compared = runner.check(cell, seed, state, out)
    except BaseException:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        raise
    compared["compiles_in_window"] = {
        "value": float(in_window), "limit": 0.0, "ok": in_window == 0}

    device = dict(info, count=cell.chips if not rehearse else info["count"],
                  memory_peak_bytes=peak)
    result = {"correct": all(c["ok"] for c in compared.values()),
              "attempted": out["attempted"], "failed": out["failed"]}
    e2e = dict(out["end_to_end"], setup_s=setup_s)
    metrics = {}
    if not trace:
        for m in cell.end_to_end():
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        summary = None
        path = find_xplane(trace_dir)
        if path:
            t0 = time.perf_counter()
            summary = TraceSummary(load_xplane(path))
            harness.say(f"trace read in {time.perf_counter() - t0:.1f}s: "
                        f"{os.path.getsize(path) / 2**20:.0f} MiB")
            dump = os.environ.get("BENCH_TRACE_DUMP")
            if dump:
                from benchmarks.tools import trace_dump
                trace_dump.dump(path, summary, dump)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"cell": cell, "rec": rec, "out": out, "trace": summary,
               "device": info, "e2e": e2e, "rehearse": rehearse,
               "peaks": None if rehearse else peaks_mod.peaks(info["kind"])}
        for m in cell.per_layer():
            read, spec = metric_reader(m["name"])
            value = read(ctx, spec)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        if summary is not None and summary.devices:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            result["breakdown"] = summary.breakdown()
        elif not rehearse:
            raise RuntimeError("the traced run found no device operation "
                               "in its trace")
    if rehearse:
        # a CPU run gives counts, never a time, a rate or a share of a peak
        sources = {m["name"]: m["source"] for m in
                   cell.bench["end_to_end"] + cell.bench["per_layer"]}
        metrics = {k: v for k, v in metrics.items()
                   if sources[k] == "program_counter"}
        result["rehearsal"] = True
    result["metrics"] = metrics
    result["device"] = device
    result["compared"] = {k: {"value": c["value"], "limit": c["limit"]}
                          for k, c in compared.items()}
    result["_compared_full"] = compared
    return result


def main(argv=None) -> int:
    args = _parse(argv)
    from benchmarks.manifest import Cell, benchmark_json
    cell = Cell(args.workload, benchmark_json(args.manifest))
    try:
        harness.start_program(cell.chips, args.rehearse_cpu)
    except harness.NoChip as e:
        return e.code
    harness.say(f"cell {cell.name} seed {args.seed}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      rehearse=args.rehearse_cpu)
    full = result.pop("_compared_full")
    sys.stdout.flush()
    _print_compared(full, sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
