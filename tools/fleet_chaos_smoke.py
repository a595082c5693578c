#!/usr/bin/env python
"""Fleet chaos smoke (ISSUE 14) — the tier-1 gate for fault-tolerant
fleet serving: three in-process toy replicas behind the prefix-aware
FleetRouter, a seeded Injector killing one replica mid-traffic, and a
fault-free oracle the surviving fleet must match bitwise:

  1. the ReplicaKill fault FIRES (a green run proves recovery ran, not
     that nothing happened), the router ejects the dead replica and
     re-submits its in-flight requests elsewhere;
  2. the AutoscaleController replaces the dead replica (membership back
     at min_replicas) and later scale-down is the graceful handshake:
     begin_drain -> reroute -> remove-once-empty, never a hard kill;
  3. EVERY completed request's greedy tokens are bit-identical to the
     fault-free single-engine oracle — failover changes placement, not
     one output bit;
  4. the host-RAM spill tier cycles under the tiny prefix-cache budget:
     blocks spill, later hits REHYDRATE, and the copy count is exactly
     one host->device payload per rehydrated block;
  5. zero post-warmup jit cache misses across every replica INCLUDING
     the autoscaler's replacement (shared model = shared executables);
  6. prefix-aware routing measurably beats random routing on
     shared-prefix traffic (fleet hit-rate A/B on clean fleets).

Exit 0 = all gates hold; 1 = any violation (named on stderr).

    PYTHONPATH=. python tools/fleet_chaos_smoke.py [--requests 30] [--json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--requests", type=int, default=30,
                    help="shared-prefix requests per leg")
    ap.add_argument("--seed", type=int, default=7,
                    help="chaos/traffic seed (the seed IS the scenario)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    import paddle_tpu as paddle
    from paddle_tpu.inference import (AutoscaleController, FleetRouter,
                                      ReplicaRegistry, ServingConfig,
                                      ServingEngine)
    from paddle_tpu.inference.serving import shared_prefix_traffic
    from paddle_tpu.jit.api import compile_cache_misses
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.resilience import Injector, ReplicaKill

    paddle.seed(0)
    gcfg = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                     num_heads=2, max_position_embeddings=64,
                     intermediate_size=64)
    # one toy model, every replica (and the oracle, and the autoscaler's
    # replacement) shares its executables — warmup once covers the fleet
    model = GPTForCausalLM(gcfg)
    model.eval()
    KB = 4
    from paddle_tpu.inference import BlockPool
    BPB = BlockPool.for_model(model, num_blocks=2,
                              block_size=KB).bytes_per_block

    def mk(spill: bool = True) -> ServingEngine:
        # a 3-block device budget under 3 prefixes x 2 blocks forces
        # constant LRU eviction -> the spill tier cycles for real
        return ServingEngine(model, ServingConfig(
            max_batch=2, prompt_cap=16, max_new_tokens=6, decode_chunk=3,
            prefix_cache=True, kv_block=KB, kv_blocks=48,
            prefix_cache_bytes=3 * BPB if spill else None,
            spill_host_bytes=1 << 22 if spill else None))

    traffic = shared_prefix_traffic(
        args.requests, n_prefixes=3, prefix_len=2 * KB, prompt_cap=16,
        vocab_size=gcfg.vocab_size, rate=1e9, seed=args.seed)
    prompts = [t["prompt"] for t in traffic]

    failures = []

    # ---------------------------------------------- fault-free oracle
    oracle_eng = mk(spill=False)
    oracle = {}
    for p in prompts:
        r = oracle_eng.submit(p)
        oracle_eng.drain()
        if r.status != "done":
            failures.append(f"oracle refused a prompt: {r.reason}")
        oracle[p.tobytes()] = r.tokens

    # ------------------------------------------------------ chaos leg
    chaos = Injector(args.seed, faults=[ReplicaKill("r1", step=2)])
    reg = ReplicaRegistry({f"r{i}": mk() for i in range(3)}, chaos=chaos)
    # warm every executable (prefill/suffix/COW/decode + the spill d2h
    # gather and rehydrate h2d scatter) BEFORE the miss snapshot
    for h in reg.handles():
        h.engine.warmup_prefix_cache(gcfg.vocab_size)
    miss0 = compile_cache_misses()

    router = FleetRouter(reg, policy="prefix", chaos=chaos,
                         retry_budget_s=5.0, seed=args.seed)
    # queue-depth/goodput triggers disabled: the ONLY spawn signal left
    # is membership-below-min, so the replacement decision is
    # deterministically a "replace" (the burst backlog would otherwise
    # legitimately scale_up first and mask it)
    auto = AutoscaleController(reg, lambda name: mk(),
                               min_replicas=3, max_replicas=4,
                               scale_up_queue_depth=1e9,
                               goodput_floor=0.0)
    freqs = [router.submit(p) for p in prompts]
    router.drain(tick=auto.tick)

    if chaos.fired("replica_kill") != 1:
        failures.append("ReplicaKill never fired — the scenario tested "
                        "nothing")
    if "r1" not in reg.ejected:
        failures.append("dead replica r1 was not ejected")
    if router.counters["redispatched"] < 1:
        failures.append("no in-flight request was redispatched off the "
                        "dead replica")
    if not any(d["action"] == "replace" for d in auto.decisions):
        failures.append("autoscaler never replaced the dead replica")
    if len(reg.names(("serving",))) != 3:
        failures.append(f"fleet did not recover to min_replicas=3 "
                        f"(serving={reg.names(('serving',))})")
    bad = [f for f in freqs if f.status != "done"]
    if bad:
        failures.append(f"{len(bad)} requests did not complete: "
                        f"{[(f.status, f.reason) for f in bad[:3]]}")
    mismatch = sum(1 for f in freqs if f.status == "done" and
                   not np.array_equal(f.tokens, oracle[f.prompt.tobytes()]))
    if mismatch:
        failures.append(f"{mismatch} completed requests differ from the "
                        f"fault-free oracle (must be bit-identical)")

    spilled = rehydrated = h2d = 0
    for h in list(reg.handles(("serving", "draining"))) + \
            list(reg.ejected.values()):
        t = h.engine._spill
        if t is not None:
            spilled += t.spilled_total
            rehydrated += t.rehydrated_total
            h2d += t.h2d_copies
    if spilled < 1 or rehydrated < 1:
        failures.append(f"spill tier never cycled (spilled={spilled}, "
                        f"rehydrated={rehydrated}) — shrink the budget")
    if h2d != rehydrated:
        failures.append(f"rehydrate copy count {h2d} != rehydrated "
                        f"blocks {rehydrated} (must be ONE host->device "
                        f"copy per block)")

    dm = compile_cache_misses() - miss0
    if dm:
        failures.append(f"{dm} post-warmup jit cache misses across the "
                        f"fleet incl. the replacement replica (must be 0)")

    # graceful scale-down: with the floor lowered, idle ticks drain the
    # least-loaded member and remove it only once empty
    down = AutoscaleController(reg, lambda name: mk(), min_replicas=2,
                               max_replicas=4,
                               idle_ticks_before_scale_down=2)
    victim = None
    for _ in range(8):
        rec = down.tick()
        if rec["action"] == "scale_down_begin":
            victim = reg.handle(rec["replica"])
        router.step()
    acts = [d["action"] for d in down.decisions]
    if "scale_down_begin" not in acts or "scale_down_done" not in acts:
        failures.append(f"graceful scale-down did not complete: {acts}")
    elif victim is not None and (victim.engine.busy
                                 or victim.engine.queue_depth):
        failures.append("scale-down removed a replica that still had "
                        "work (hard kill!)")
    if len(reg.names(("serving",))) != 2:
        failures.append(f"scale-down did not land at min_replicas=2 "
                        f"(serving={reg.names(('serving',))})")

    # ------------------------------------------------ routing A/B leg
    def hit_rate(policy: str) -> float:
        r = ReplicaRegistry({f"ab{i}": mk(spill=False)
                             for i in range(3)})
        rt = FleetRouter(r, policy=policy, retry_budget_s=5.0,
                         seed=args.seed)
        for p in prompts:
            rt.submit(p)
        rt.drain()
        return rt.fleet_prefix_stats()["hit_rate"] or 0.0

    prefix_rate = hit_rate("prefix")
    random_rate = hit_rate("random")
    if not prefix_rate > random_rate:
        failures.append(f"prefix routing ({prefix_rate:.3f}) does not "
                        f"beat random routing ({random_rate:.3f}) on "
                        f"shared-prefix traffic")

    out = {"requests": len(freqs),
           "completed": sum(1 for f in freqs if f.status == "done"),
           "redispatched": router.counters["redispatched"],
           "replicas_lost": router.counters["replicas_lost"],
           "spilled_blocks": spilled, "rehydrated_blocks": rehydrated,
           "rehydrate_h2d_copies": h2d,
           "post_warmup_jit_misses": dm,
           "prefix_hit_rate": round(prefix_rate, 4),
           "random_hit_rate": round(random_rate, 4),
           "ok": not failures, "failures": failures}
    if args.json:
        print(json.dumps(out, indent=2))
    else:
        print(f"fleet_chaos_smoke: {out['completed']}/{out['requests']} "
              f"requests bit-identical to oracle through a replica kill "
              f"({out['redispatched']} redispatched); spill "
              f"{spilled}->rehydrate {rehydrated} ({h2d} h2d copies); "
              f"post-warmup jit misses {dm}; hit rate prefix "
              f"{prefix_rate:.3f} vs random {random_rate:.3f}")
    for f in failures:
        print(f"fleet_chaos_smoke: VIOLATION: {f}", file=sys.stderr)
    if not failures:
        print("fleet_chaos_smoke: OK")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
