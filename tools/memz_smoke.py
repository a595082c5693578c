#!/usr/bin/env python
"""HBM-ledger smoke (ISSUE 18) — the tier-1 gate for memory
observability: boot a toy PAGED ServingEngine with a MemoryLedger
attached and prove the surface end-to-end:

  1. conservation: sum(device owner bytes) + unattributed ==
     `device.memory_allocated()` within tolerance, sampled repeatedly
     UNDER CHURN (admissions, frees, prefix sharing) — the ledger
     provably sums to the allocator's view;
  2. /memz (and the rest of the surface) answers CONCURRENTLY with live
     decode at ZERO post-warmup jit cache misses — a ledger read never
     syncs or compiles;
  3. OOM forensics: a chaos-injected allocation failure (AllocFailure at
     serving.step) produces a post-mortem artifact that names the
     largest owner and renders through tools/oom_report.py (subprocess,
     exit 0); the engine stays servable afterwards;
  4. mem-pressure episodes: forced pool oversubscription emits paired
     {"mem_pressure"} / {"mem_pressure_clear"} rows (one per episode).

Exit 0 = all gates hold; 1 = any violation (named on stderr).

    PYTHONPATH=. python tools/memz_smoke.py [--batches 6] [--json]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402


class Scraper(threading.Thread):
    """GET + validate /memz, /metrics and /statusz in a loop — the
    concurrent-scrape leg of the zero-miss gate."""

    def __init__(self, srv, interval: float = 0.05):
        super().__init__(name="memz-smoke-scraper", daemon=True)
        self.srv = srv
        self.interval = interval
        self.stop = threading.Event()
        self.scrapes = 0
        self.errors = []

    def _one_pass(self):
        from urllib.request import urlopen
        from paddle_tpu.obs import lint_exposition
        m = json.loads(urlopen(self.srv.url("/memz?deltas=16"),
                               timeout=5).read())
        for key in ("owners", "attributed_bytes", "unattributed_bytes",
                    "deltas"):
            if key not in m:
                raise AssertionError(f"/memz missing {key}")
        if not any(o["owner"] == "kv_pool" for o in m["owners"]):
            raise AssertionError("/memz owners missing kv_pool")
        text = urlopen(self.srv.url("/metrics"), timeout=5).read().decode()
        lint_exposition(text)
        if "hbm_bytes" not in text or "hbm_headroom_bytes" not in text:
            raise AssertionError("/metrics missing hbm gauges")
        s = json.loads(urlopen(self.srv.url("/statusz"), timeout=5).read())
        if "memory" not in s or "kv_pool" not in s["memory"]["owners"]:
            raise AssertionError("/statusz missing memory block")

    def run(self):
        while not self.stop.is_set():
            try:
                self._one_pass()
                self.scrapes += 1
            except Exception as e:             # noqa: BLE001 — the gate
                self.errors.append(f"{type(e).__name__}: {e}")
                return
            if self.stop.wait(timeout=self.interval):
                return


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--batches", type=int, default=6,
                    help="churn micro-batches under the concurrent "
                         "scraper")
    ap.add_argument("--tolerance-frac", type=float, default=0.15,
                    help="|unattributed| bound as a fraction of the "
                         "allocator view (CPU live-array fallback "
                         "carries temporaries; allocator platforms sit "
                         "near 0)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    import paddle_tpu as paddle
    from paddle_tpu.inference import ServingConfig, ServingEngine
    from paddle_tpu.jit.api import compile_cache_misses
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.obs import MemoryLedger
    from paddle_tpu.resilience import AllocFailure, Injector

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    intermediate_size=128)
    model = GPTForCausalLM(cfg)
    model.eval()
    engine = ServingEngine(model, ServingConfig(
        max_batch=2, prompt_cap=12, max_new_tokens=8, decode_chunk=4,
        kv_block=4, kv_blocks=24, prefix_cache=True))
    # explicit capacity so the headroom gauge renders on the CPU host
    # (no allocator bytes_limit); generous enough to stay quiet
    ledger = engine.attach_memory_ledger(
        MemoryLedger(capacity_bytes=1 << 30))
    rng = np.random.RandomState(0)
    prefix = rng.randint(1, cfg.vocab_size, (8,)).astype(np.int64)
    prompts = []
    for i in range(16):
        if i % 2:          # shared-prefix half: exercises retain/COW
            suffix = rng.randint(1, cfg.vocab_size,
                                 (int(rng.randint(1, 5)),))
            prompts.append(np.concatenate([prefix, suffix])
                           .astype(np.int64))
        else:
            prompts.append(rng.randint(
                1, cfg.vocab_size,
                (int(rng.randint(3, 13)),)).astype(np.int64))

    # warmup: one full pass over the prompt set — the churn loop replays
    # exactly these prompts, so every executable the measured leg can
    # touch (prefill, suffix prefill, chunk depths, COW copy) is built
    # here and the post-warmup miss gate is airtight
    for p in prompts:
        engine.submit(p)
    engine.drain()
    # second pass over the first batch: a now-fully-cached prompt admits
    # through the zero-prefill + COW path, building the one executable a
    # single cold pass cannot reach
    for p in prompts[:2]:
        engine.submit(p)
    engine.drain()

    failures = []
    miss0 = compile_cache_misses()
    srv = engine.serve_telemetry()
    scraper = Scraper(srv)
    scraper.start()

    # churn under the concurrent scraper, checking conservation between
    # batches (host-side: the census walk itself must not compile)
    worst_frac = 0.0
    checks = 0
    t0 = time.perf_counter()
    try:
        B = engine.config.max_batch
        for b in range(max(args.batches, 1)):
            for i in range(B):
                engine.submit(prompts[(b * B + i) % len(prompts)])
            engine.drain()
            c = ledger.census()
            alloc, unattr = c["allocated_bytes"], c["unattributed_bytes"]
            if alloc is None:
                failures.append("census returned no allocator view")
                break
            checks += 1
            frac = abs(unattr) / max(alloc, 1)
            worst_frac = max(worst_frac, frac)
            if frac > args.tolerance_frac:
                failures.append(
                    f"conservation violated at batch {b}: "
                    f"|unattributed| {unattr}B is "
                    f"{frac * 100:.1f}% of allocated {alloc}B "
                    f"(tolerance {args.tolerance_frac * 100:.0f}%)")
                break
    finally:
        churn_s = time.perf_counter() - t0
        scraper.stop.set()
        scraper.join(timeout=5)

    if scraper.errors:
        failures.append(f"endpoint validation failed: "
                        f"{scraper.errors[0]}")
    if scraper.scrapes < 1:
        failures.append("scraper completed zero full /memz passes")
    dm = compile_cache_misses() - miss0
    if dm:
        failures.append(f"{dm} jit cache misses post-warmup with /memz "
                        f"scraped concurrently (must be 0)")
    srv.close()

    # forced allocation failure -> post-mortem artifact -> oom_report
    pm_dir = tempfile.mkdtemp(prefix="memz_smoke_oom_")
    ledger.postmortem_dir = pm_dir
    engine.chaos = Injector(faults=[AllocFailure()])
    engine.submit(prompts[0])
    oom_seen = False
    try:
        while engine.busy:
            engine.step()
    except RuntimeError as e:
        oom_seen = "RESOURCE_EXHAUSTED" in str(e)
    if not oom_seen:
        failures.append("injected AllocFailure did not surface as a "
                        "RESOURCE_EXHAUSTED step error")
    if not engine.chaos.fired("alloc_failure"):
        failures.append("AllocFailure never fired (vacuous OOM leg)")
    engine.chaos = None
    artifacts = [os.path.join(pm_dir, n) for n in sorted(
        os.listdir(pm_dir)) if n.endswith(".jsonl")]
    if len(artifacts) != 1:
        failures.append(f"expected exactly 1 post-mortem artifact, "
                        f"found {len(artifacts)}")
    largest = None
    if artifacts:
        with open(artifacts[0]) as f:
            head = json.loads(f.readline())
        largest = head.get("oom", {}).get("largest_owner")
        if not largest:
            failures.append("post-mortem head row names no largest "
                            "owner")
        proc = subprocess.run(
            [sys.executable, os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "oom_report.py"),
             artifacts[0]],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        if proc.returncode != 0:
            failures.append(f"oom_report.py exited {proc.returncode}: "
                            f"{proc.stderr.strip()[:200]}")
        elif "kv_pool" not in proc.stdout:
            failures.append("oom_report rendering names no kv_pool "
                            "owner")
    # the engine must stay servable after the OOM recovery path
    r = engine.submit(prompts[1])
    engine.drain()
    if r.status != "done":
        failures.append(f"engine not servable after injected OOM "
                        f"(status {r.status})")

    # oversubscription: a pool too small for the concurrent load emits
    # paired mem_pressure episode rows
    tiny = ServingEngine(model, ServingConfig(
        max_batch=2, prompt_cap=12, max_new_tokens=8, decode_chunk=4,
        kv_block=4, kv_blocks=6))
    tiny.attach_memory_ledger()
    rows = []
    tiny.metrics.on_record = rows.append
    for _ in range(4):
        tiny.submit(rng.randint(1, cfg.vocab_size,
                                (10,)).astype(np.int64))
    tiny.drain()
    n_enter = sum(1 for r_ in rows if "mem_pressure" in r_)
    n_clear = sum(1 for r_ in rows if "mem_pressure_clear" in r_)
    if n_enter < 1 or n_enter != n_clear:
        failures.append(f"mem_pressure episodes malformed: "
                        f"{n_enter} enter vs {n_clear} clear rows")

    out = {"scrapes": scraper.scrapes,
           "conservation_checks": checks,
           "worst_unattributed_frac": round(worst_frac, 4),
           "tolerance_frac": args.tolerance_frac,
           "post_warmup_jit_misses": dm,
           "churn_wall_s": round(churn_s, 2),
           "oom_largest_owner": largest,
           "mem_pressure_episodes": n_enter,
           "ok": not failures, "failures": failures}
    if args.json:
        print(json.dumps(out, indent=2))
    else:
        print(f"memz_smoke: {checks} conservation checks under churn, "
              f"worst |unattributed| {worst_frac * 100:.2f}% of "
              f"allocated (tolerance {args.tolerance_frac * 100:.0f}%), "
              f"{scraper.scrapes} concurrent /memz passes, "
              f"{dm} post-warmup jit misses")
        print(f"memz_smoke: injected OOM -> post-mortem names "
              f"'{largest}', oom_report renders it; "
              f"{n_enter} mem_pressure episodes (paired)")
    for f in failures:
        print(f"memz_smoke: VIOLATION: {f}", file=sys.stderr)
    if not failures:
        print("memz_smoke: OK")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
