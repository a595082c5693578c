#!/usr/bin/env python
"""Active-probing smoke (ISSUE 19) — the tier-1 gate for golden-canary
correctness sentinels: three in-process toy replicas behind the
FleetRouter, each served by a TelemetryServer whose poller drives the
Prober at 2 Hz CONCURRENTLY with closed-loop user decode, then one
silently corrupted KV block the sentinels must catch:

  1. clean interleaved phase: probes ride the real submit()/step path
     while user traffic drains — zero probe failures, zero deep
     invariant violations, and ZERO post-warmup jit cache misses with
     the prober attached (warm() pre-lowered every probe executable);
  2. probe/SLO isolation: probe requests never touch the user-facing
     request counters or rejection totals on any replica;
  3. the fleet surface merges: /fleet/probez reports every prober
     passing, one config fingerprint fleet-wide, no drift finding;
  4. CorruptKVBlock flips bytes inside the victim's cached probe block
     — no exception, no accounting change, invisible to the invariant
     audits — and the next probe cycle catches it: EXACTLY ONE
     structured {"probe_fail"} row (the transition machine holds while
     the failure is sustained) and a pinned flight-recorder capture;
  5. router.step() consults the probers and ejects the failing replica
     like a dead one (probe_ejected=1) while the remaining fleet keeps
     serving bit-identically to the fault-free oracle and the fleet
     page keeps answering with the victim marked failing.

Exit 0 = all gates hold; 1 = any violation (named on stderr).

    PYTHONPATH=. python tools/probe_smoke.py [--requests 24] [--json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tests", "fixtures",
    "mini_step.trace.json.gz")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--requests", type=int, default=24,
                    help="shared-prefix user requests in the clean leg")
    ap.add_argument("--seed", type=int, default=7,
                    help="traffic/corruption seed")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    import paddle_tpu as paddle
    from paddle_tpu.inference import (BlockPool, FleetRouter,
                                      ReplicaRegistry, ServingConfig,
                                      ServingEngine)
    from paddle_tpu.inference.serving import shared_prefix_traffic
    from paddle_tpu.jit.api import compile_cache_misses
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.obs import (FixtureBackend, FleetAggregator,
                                FlightRecorder, GoldenStore, Prober)
    from paddle_tpu.resilience import CorruptKVBlock, Injector

    paddle.seed(0)
    gcfg = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                     num_heads=2, max_position_embeddings=64,
                     intermediate_size=64)
    model = GPTForCausalLM(gcfg)
    model.eval()
    KB = 4
    BPB = BlockPool.for_model(model, num_blocks=2,
                              block_size=KB).bytes_per_block

    def mk() -> ServingEngine:
        # spill tier configured (warmup lowers the d2h gather / h2d
        # scatter pair CorruptKVBlock's read/write round-trip reuses)
        # but the prefix budget is GENEROUS: the corrupted probe block
        # must stay resident until the sentinel attends it — eviction
        # churn would let the cache self-heal before detection
        return ServingEngine(model, ServingConfig(
            max_batch=2, prompt_cap=16, max_new_tokens=6, decode_chunk=3,
            prefix_cache=True, kv_block=KB, kv_blocks=48,
            prefix_cache_bytes=64 * BPB, spill_host_bytes=1 << 22))

    traffic = shared_prefix_traffic(
        args.requests, n_prefixes=3, prefix_len=2 * KB, prompt_cap=16,
        vocab_size=gcfg.vocab_size, rate=1e9, seed=args.seed)
    prompts = [t["prompt"] for t in traffic]
    post_prompts = prompts[: max(3, len(prompts) // 4)]

    failures = []

    # ---------------------------------------------- fault-free oracle
    oracle_eng = mk()
    oracle = {}
    for p in prompts:
        r = oracle_eng.submit(p)
        oracle_eng.drain()
        if r.status != "done":
            failures.append(f"oracle refused a prompt: {r.reason}")
        oracle[p.tobytes()] = r.tokens

    # --------------------------------------------------- fleet + probers
    reg = ReplicaRegistry({f"r{i}": mk() for i in range(3)})
    router = FleetRouter(reg, policy="prefix", retry_budget_s=5.0,
                         seed=args.seed)
    # ONE lock serializes every engine call fleet-wide: the poller
    # threads (probe cycles, invariant audits) and this driver's step
    # loop share it, per the engine's one-lock threading contract
    lock = threading.Lock()
    store = GoldenStore()                # shared: one golden per variant
    for h in reg.handles():
        h.engine.warmup_prefix_cache(gcfg.vocab_size)
        h.prober = Prober(h.engine, store=store, replica=h.name,
                          lock=lock).warm()
    miss0 = compile_cache_misses()
    # user-facing accounting baseline AFTER warmup (warmup submits are
    # real user-path requests) — the probe storm must not move it
    req0 = sum(h.engine.metrics.counters["requests"]
               for h in reg.handles())
    rej0 = sum(h.engine.metrics.counters["rejected"]
               for h in reg.handles())

    servers = {}
    for h in reg.handles():
        servers[h.name] = h.engine.serve_telemetry(
            prober=h.prober, probe_interval=0.5,     # the 2 Hz sentinel
            invariant_interval=0.25)
    agg = FleetAggregator({n: s.url() for n, s in servers.items()},
                          cache_ttl=0.0)

    try:
        # ------------------------- clean leg: probes ride live traffic
        cyc0 = {h.name: h.prober.cycles_total for h in reg.handles()}
        with lock:
            freqs = [router.submit(p) for p in prompts]
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            with lock:
                router.step()
                busy = any(f.status == "pending" for f in freqs)
            if not busy:
                break
            time.sleep(0.001)
        # ... and keep serving probes only until every sentinel ran at
        # least 2 cycles concurrently with (or right after) the traffic
        while time.monotonic() < deadline and any(
                h.prober.cycles_total - cyc0[h.name] < 2
                for h in reg.handles()):
            time.sleep(0.05)

        bad = [f for f in freqs if f.status != "done"]
        if bad:
            failures.append(f"{len(bad)} user requests did not complete: "
                            f"{[(f.status, f.reason) for f in bad[:3]]}")
        mismatch = sum(1 for f in freqs if f.status == "done" and not
                       np.array_equal(f.tokens, oracle[f.prompt.tobytes()]))
        if mismatch:
            failures.append(f"{mismatch} clean-leg requests differ from "
                            f"the oracle (must be bit-identical)")
        dm = compile_cache_misses() - miss0
        if dm:
            failures.append(f"{dm} post-warmup jit cache misses with the "
                            f"2 Hz prober attached (must be 0)")
        for h in reg.handles():
            pz = h.prober.probez()
            if pz["state"] != "passing" or pz["failures_total"]:
                failures.append(f"{h.name}: clean-leg probe state "
                                f"{pz['state']} (failures="
                                f"{pz['failures_total']})")
            inv = pz.get("invariants", {})
            if inv.get("violating") or not inv.get("audits_total"):
                failures.append(f"{h.name}: invariant audits "
                                f"{'violating' if inv.get('violating') else 'never ran'}")
            if h.engine.metrics.probe_counters["requests"] < 1:
                failures.append(f"{h.name}: no probe request was "
                                f"accounted on the probe side")
        # probe/SLO isolation: dozens of probe cycles ran, yet the
        # user-facing request/rejection counters only ever saw the
        # user traffic itself
        user_reqs = sum(h.engine.metrics.counters["requests"]
                        for h in reg.handles()) - req0
        user_rej = sum(h.engine.metrics.counters["rejected"]
                       for h in reg.handles()) - rej0
        if user_reqs != len(freqs) or user_rej:
            failures.append(f"probe traffic leaked into user accounting "
                            f"(requests={user_reqs} want {len(freqs)}, "
                            f"rejected={user_rej} want 0)")
        if store.minted_total != len(next(iter(
                reg.handles())).prober.variants):
            failures.append(f"goldens minted {store.minted_total} times "
                            f"for a 3-replica fleet sharing one "
                            f"fingerprint (must be once per variant)")

        fp = agg.fleet_probez()
        if fp["summary"]["with_prober"] != 3 or fp["summary"]["failing"]:
            failures.append(f"clean fleet page wrong: {fp['summary']}")
        if fp["summary"]["config_drift"] or \
                len(set(fp["summary"]["fingerprints"].values())) != 1:
            failures.append(f"config drift flagged on an identical "
                            f"fleet: {fp['summary']['fingerprints']}")
        page = agg.merged_metrics()
        if "paddle_tpu_probe_cycles_total" not in page or \
                "paddle_tpu_invariant_audits_total" not in page:
            failures.append("merged fleet /metrics page is missing the "
                            "probe_*/invariant_* families")

        # --------------------- corruption leg: one silently bad block
        victim = "r1"
        vh = reg.handle(victim)
        veng, vp = vh.engine, vh.prober
        rec = FlightRecorder(tempfile.mkdtemp(prefix="probe_smoke_"),
                             backend=FixtureBackend(FIXTURE),
                             trigger_steps=1, cooldown_s=0.0)
        rows = []
        with lock:
            rec.attach(monitor=veng.monitor, metrics=veng.metrics)
            prev = veng.metrics.on_record
            veng.metrics.on_record = lambda r: (prev(r), rows.append(r))
            blks = vp.probe_blocks("prefix_hit")
            if not blks:
                failures.append(f"{victim}: no cached probe block to "
                                f"corrupt (trie empty?)")
            fault = CorruptKVBlock(engine=veng,
                                   block=blks[0] if blks else None,
                                   seed=args.seed)
            veng.chaos = Injector(args.seed).add(fault)

        # the 2 Hz poller fires the next probe.cycle, the fault flips
        # bytes in-place, the hit-path sentinel attends them: detection
        # within one probe cycle, no driver involvement
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and not vp.failing:
            time.sleep(0.02)
        fail_cycle = vp.cycles_total
        if not fault.fired or fault.corrupted_block is None:
            failures.append("CorruptKVBlock never fired — the scenario "
                            "tested nothing")
        if not vp.failing:
            failures.append(f"{victim}: sentinel missed the corrupted "
                            f"block entirely")
        # sustained failure stays ONE structured row (transition machine)
        while time.monotonic() < deadline and \
                vp.cycles_total < fail_cycle + 2:
            time.sleep(0.02)
        fail_rows = [r for r in rows if "probe_fail" in r]
        if len(fail_rows) != 1:
            failures.append(f"expected exactly one probe_fail row, got "
                            f"{len(fail_rows)}")
        elif fail_rows[0]["probe_fail"].get("first_divergence") is None:
            failures.append("probe_fail row carries no first_divergence "
                            "position")
        while time.monotonic() < deadline and not \
                any(c.get("pinned") for c in rec.captures):
            time.sleep(0.02)
        caps = [c for c in rec.captures if c.get("pinned")]
        if not caps:
            failures.append("no pinned flight-recorder capture for the "
                            "probe failure")
        elif "probe_fail" not in [t["kind"] for c in caps
                                  for t in c["triggers"]]:
            failures.append("pinned capture was not triggered by "
                            "probe_fail")

        # --------------------------- ejection: fleet drops the replica
        with lock:
            router.step()
        if router.counters["probe_ejected"] != 1:
            failures.append(f"probe_ejected="
                            f"{router.counters['probe_ejected']}, "
                            f"expected 1")
        if victim not in reg.ejected:
            failures.append(f"{victim} still in the fleet after a "
                            f"correctness failure")
        elif not reg.ejected[victim].ejected_reason.startswith(
                "probe_fail:"):
            failures.append(f"ejection reason "
                            f"{reg.ejected[victim].ejected_reason!r} "
                            f"does not name the failing probe")
        if len(reg.names(("serving",))) != 2:
            failures.append(f"fleet did not keep serving on 2 replicas "
                            f"(serving={reg.names(('serving',))})")

        with lock:
            preqs = [router.submit(p) for p in post_prompts]
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            with lock:
                router.step()
                busy = any(f.status == "pending" for f in preqs)
            if not busy:
                break
            time.sleep(0.001)
        pbad = sum(1 for f in preqs if f.status != "done" or not
                   np.array_equal(f.tokens, oracle[f.prompt.tobytes()]))
        if pbad:
            failures.append(f"{pbad}/{len(preqs)} post-ejection requests "
                            f"not served bit-identically by the "
                            f"surviving fleet")

        fp2 = agg.fleet_probez()
        if fp2["summary"]["failing"] != [victim]:
            failures.append(f"fleet page after ejection should mark "
                            f"{victim} failing, got "
                            f"{fp2['summary']['failing']}")
        if fp2["summary"]["answered"] < 2:
            failures.append("fleet page stopped answering during the "
                            "ejection")
        with lock:
            rec.detach()
            veng.chaos = None
    finally:
        for s in servers.values():
            s.close()

    out = {"requests": len(prompts),
           "completed": sum(1 for f in freqs if f.status == "done"),
           "probe_cycles": {h.name: h.prober.cycles_total
                            for h in list(reg.handles()) +
                            list(reg.ejected.values())},
           "goldens_minted": store.minted_total,
           "post_warmup_jit_misses": compile_cache_misses() - miss0,
           "probe_fail_rows": len(fail_rows),
           "pinned_captures": len(caps),
           "probe_ejected": router.counters["probe_ejected"],
           "post_ejection_ok": len(post_prompts) - pbad,
           "ok": not failures, "failures": failures}
    if args.json:
        print(json.dumps(out, indent=2))
    else:
        print(f"probe_smoke: {out['completed']}/{out['requests']} user "
              f"requests bit-identical with 2 Hz probes interleaved; "
              f"{out['goldens_minted']} goldens for 3 replicas; "
              f"corruption -> {out['probe_fail_rows']} probe_fail row, "
              f"{out['pinned_captures']} pinned capture(s), "
              f"probe_ejected={out['probe_ejected']}; "
              f"{out['post_ejection_ok']}/{len(post_prompts)} served "
              f"bit-identically after ejection")
    for f in failures:
        print(f"probe_smoke: VIOLATION: {f}", file=sys.stderr)
    if not failures:
        print("probe_smoke: OK")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
