"""Readings that the limits of a MiniCPM-SALA serving cell are set from
(tools/calibrate_cell.py is the expert model's: its faults and its
choices are that model's): on the chip, the cell's engine and load, a
short window per seed, the seeds in one process. The engine serves every
case first and keeps each sample on the host; then it is let go and the
reference reads the samples (an engine of 14 GB and a float32 reference
do not fit side by side). Every case goes through the runner's
`sample_gaps` and `compared_gaps`, as `check` does, and a row holds what
`compared` would: each number beside its limit, and `ok`.

Per seed the sound case: the program's served tokens under the float32
reference. For the first `--control-seeds` seeds also:

- `control`: the tokens the reference in the next precision down puts
  first at the same positions;
- one case per `--faults` name, planted in the program (`plant`) and
  served again: `block` (every selecting query and decode step leaves out
  the lowest selected page after the first), `window` (the forced window
  before the query's own page is not forced), `decay` (the slowest head of
  every lightning layer decays as the fastest), `snapshot` (the state a
  prefix's snapshot holds is one prefill window stale), `token` (one
  served token in each sampled request replaced by its neighbour).

    python3 -m benchmarks.tools.calibrate_sala --workload <cell> \
        --seeds 201,202,... --seconds 10 --control-seeds 1 \
        --faults block,snapshot,token --out ...
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

from benchmarks import harness
from benchmarks.tools.calibrate_cell import altered

FAULTS = ("block", "window", "decay", "snapshot", "token")


def plant(model, eng, fault: str):
    """Plants `fault` in the program; returns the function that takes it
    out again. The model's compiled calls are dropped both times."""
    import jax.numpy as jnp
    from paddle_tpu.inference.kv_cache import STATE_SAVE
    from paddle_tpu.models import minicpm_sala as M
    from paddle_tpu.ops import lightning_attention as L
    undo = []

    def swap(obj, name, new):
        old = getattr(obj, name)
        setattr(obj, name, new)
        undo.append(lambda: setattr(obj, name, old))

    if fault == "block":
        select, lists = M.select_blocks, M.decode_lists

        def select_less(*a, **kw):          # a window's mask: never matches
            chosen, sparse, keys = select(*a, **kw)
            return chosen.at[..., 1].set(-1), sparse, keys

        def lists_less(tables, lens, chosen, sparse, sz):
            chosen = chosen.at[..., 1].set(chosen[..., 0])
            ids, toks = lists(tables, lens, chosen, sparse, sz)
            drop = jnp.concatenate([ids[..., :1], ids[..., 2:],
                                    jnp.zeros_like(ids[..., :1])], -1)
            sp = sparse[:, None]
            return (jnp.where(sp[..., None], drop, ids),
                    jnp.where(sp, toks - sz.block, toks))
        swap(M, "select_blocks", select_less)
        swap(M, "decode_lists", lists_less)
    elif fault == "window":
        select = M.select_blocks

        def no_window(q, kc_pool, tables, pos, sz):
            return select(q, kc_pool, tables, pos,
                          dataclasses.replace(sz, window=0))
        swap(M, "select_blocks", no_window)
    elif fault == "decay":
        decay = L.decay
        swap(L, "decay", lambda n: decay(n).at[-1].set(decay(n)[0]))
    elif fault == "snapshot":
        prefill = model.prefill_paged

        def stale(ids, lens, pools, tables, *a, start=None, state_slots=None,
                  **kw):
            slot, off, width = int(state_slots[0]), int(start[0]), \
                ids.shape[1]
            req = eng._slots[slot]
            plen = req.prompt_len
            if off + width < plen <= off + 2 * width \
                    and off + width > req._state_from:
                # the window before a prompt's last: save the state as it
                # is BEFORE it, under the prefix that ends AFTER it
                eng._insert_prefix(req, eng._pool.owned(req.id), off + width)
                row = eng._prefix.snapshot(req.prompt, off + width)
                if row is not None:
                    pools = eng._pool.state_move(pools, STATE_SAVE, slot, row)
            return prefill(ids, lens, pools, tables, *a, start=start,
                           state_slots=state_slots, **kw)
        swap(model, "prefill_paged", stale)
    elif fault != "token":
        raise KeyError(fault)
    model._gen_static_cache = None

    def take_out():
        for u in undo:
            u()
        model._gen_static_cache = None
    return take_out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--control-mode", default="fp8")
    ap.add_argument("--faults", default="")
    ap.add_argument("--requests", type=int, default=0,
                    help="sampled requests a case (0: the cell's)")
    ap.add_argument("--out", required=True)
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    faults = [f for f in args.faults.split(",") if f]
    if set(faults) - set(FAULTS):
        ap.error(f"--faults takes {FAULTS}")
    from benchmarks.manifest import Cell, benchmark_json
    cell = Cell(args.workload, benchmark_json(args.manifest))
    if args.requests:
        cell.settings["check_requests"] = args.requests
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
            **out["end_to_end"], "total_s": rec.counters["serve/total_s"],
            "restored": rec.counters["serve/state_snapshots_restored"]}

    cases = []      # (seed, case, sample, row)
    for n, seed in enumerate(seeds):
        if n:
            runner.install_weights(model, cell.config, seed)
        sample, row = serve(seed)
        cases.append((seed, "sound", sample, row))
        if n < args.control_seeds:
            cases.append((seed, "control", sample, row))
            for fault in faults:
                if fault == "token":
                    cases.append((seed, fault, altered(
                        sample, int(cell.config["vocab_size"])), row))
                    continue
                take_out = plant(model, eng, fault)
                cases.append((seed, fault, *serve(seed)))
                take_out()
    del model, eng              # the reference needs the chip to itself
    gc.collect()
    jax.clear_caches()
    gc.collect()
    with open(args.out, "a") as f:
        for seed, case, sample, row in cases:
            t0 = time.perf_counter()
            gaps = runner.sample_gaps(
                cell, seed, sample, mode=args.control_mode,
                control=case == "control")
            row = {"cell": cell.name, "seed": seed, "case": case,
                   "compared": runner.compared_gaps(cell, gaps),
                   "tokens": gaps["tokens"], **row,
                   "reference_s": time.perf_counter() - t0}
            f.write(json.dumps(row) + "\n")
            f.flush()
            harness.say(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
