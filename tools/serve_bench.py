#!/usr/bin/env python
"""Serving benchmark — replay open-loop traffic through the ServingEngine
and report latency percentiles + SLO attainment.

The serving analog of bench.py: where bench rows measure training
step-time/MFU, this measures the signals a serving deployment is judged by
(PAPERS.md serving studies): TTFT / per-output-token / end-to-end latency
distributions under load, queue wait, batch fill, KV occupancy, and the
fraction of requests meeting their SLOs. Traffic is OPEN-LOOP (Poisson
arrivals at --rate req/s, scheduled independently of service speed) so
queueing shows up honestly: a single-threaded replayer submits each
request with its SCHEDULED arrival timestamp (`enqueue_at`), then serves
whatever is queued — exactly the accounting a load balancer would see.

    PYTHONPATH=. python tools/serve_bench.py \
        [--preset gpt3-125m] --requests 64 --rate 100 \
        --batch 4 --prompt-cap 16 --new 8 \
        --slo-ttft-ms 500 --slo-e2e-ms 2000 [--json] [--metrics]

Paged serving (ISSUE 5): ``--paged`` runs the block-pool engine
(slot-level continuous batching, mid-flight admission); ``--compare``
replays the SAME traffic through both engines and prints the
padded-vs-paged table (tok/s, p99 TTFT, true KV occupancy) — int8 KV
(``--int8-cache``) now runs on BOTH legs (the paged int8 pool landed in
ISSUE 10; only non-int8 narrow dtypes still refuse with a structured
finding). ``--length-dist longtail`` draws Pareto-shaped prompt lengths
— the mostly-short-with-heavy-tail mix where right-padding wastes the
most HBM and paging shows its gap.

Prefix cache (ISSUE 10): ``--shared-prefix N`` switches the workload to
N fixed system prompts (``--prefix-len`` tokens each) x Poisson-arriving
random suffixes, and replays it through the paged engine with the prefix
cache OFF and ON — printing hit rate, prefill-tokens-saved and the
TTFT-with/without-cache table. ``--prefix-cache`` alone enables the
cache on a plain ``--paged`` run.

Speculative decoding (ISSUE 11): ``--spec`` replays the workload through
the paged+prefix engine with speculative decode OFF and ON (``--spec-k``
drafts per verify window, prompt-lookup drafting from the trie) and
prints the acceptance table. ``--repeat N`` switches the workload to N
fixed prompts repeated verbatim — the agentic/retry shape where trie
drafting accepts end-to-end.

SLO gate (ISSUE 12): ``--slo "ttft_p99=500ms,e2e_p99=2s,goodput=0.95"``
evaluates the declarative targets as whole-run burn rates over the
replayed traffic's log-bucket histograms (obs.slo), prints the burn-rate
table, and exits NONZERO on any breach — the same exit-code convention
as the steady-state-recompile gate, so BENCH rows carry SLO attainment.
Under an A/B mode the gate judges the LAST leg (the feature-on engine).

Runs on whatever platform JAX selects (tests pass JAX_PLATFORMS=cpu from
outside). Without --preset a 2-layer toy GPT (CI-sized); --preset serves a
real size, in bf16 on a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_model(preset):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, GPTConfig, gpt_config
    paddle.seed(0)
    if preset:
        cfg = gpt_config(preset)
    else:
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_position_embeddings=128,
                        intermediate_size=128)
    model = GPTForCausalLM(cfg)
    if paddle.device.on_tpu():
        model.to(dtype="bfloat16")
    model.eval()
    return model, cfg


def _serving_config(args, paged, prefix_cache=False, spec=False):
    from paddle_tpu.inference import ServingConfig
    # int8 KV runs on BOTH --compare legs now (the paged int8 pool landed
    # in ISSUE 10); a cache dtype the paged engine still cannot serve gets
    # the structured config-validation finding explaining why
    return ServingConfig(max_batch=args.batch, prompt_cap=args.prompt_cap,
                         max_new_tokens=args.new,
                         decode_chunk=args.decode_chunk,
                         queue_capacity=args.queue_capacity,
                         eos_token_id=args.eos,
                         weight_dtype="int8" if args.int8_weights else None,
                         cache_dtype="int8" if args.int8_cache else None,
                         paged=paged, kv_block=args.kv_block,
                         kv_blocks=args.kv_blocks,
                         prefix_cache=prefix_cache,
                         prefix_cache_bytes=args.prefix_cache_bytes,
                         spec_decode=spec, spec_k=args.spec_k,
                         # paged-only knobs: --compare's padded leg must
                         # not trip the config validation on them
                         prefill_chunk=args.prefill_chunk if paged
                         else None,
                         shards=args.shards if paged else None)


def _make_traffic(args, cfg, *, n, rate, seed):
    from paddle_tpu.inference import (repeated_traffic,
                                      shared_prefix_traffic,
                                      synthetic_traffic)
    if args.repeat:
        return repeated_traffic(n, n_prompts=args.repeat,
                                prompt_len=args.prompt_cap,
                                vocab_size=cfg.vocab_size, rate=rate,
                                seed=seed)
    if args.shared_prefix:
        return shared_prefix_traffic(
            n, n_prefixes=args.shared_prefix, prefix_len=args.prefix_len,
            prompt_cap=args.prompt_cap, vocab_size=cfg.vocab_size,
            rate=rate, seed=seed)
    return synthetic_traffic(n, prompt_cap=args.prompt_cap,
                             vocab_size=cfg.vocab_size, rate=rate,
                             seed=seed, length_dist=args.length_dist)


def run_engine(model, cfg, args, *, paged, prefix_cache=False,
               spec=False):
    """Replay the workload through one engine; returns (report, engine)."""
    from paddle_tpu.inference import ServingEngine
    engine = ServingEngine(model,
                           _serving_config(args, paged, prefix_cache,
                                           spec))

    # warmup batch: compiles the (prefill + chunk) executables once, so the
    # measured replay is the steady state a long-lived server sits in.
    # With the prefix cache the warmup must also touch the suffix-prefill
    # and COW executables — engine.warmup_prefix_cache runs the whole
    # choreography and drops its cached prefixes so the replay starts cold.
    warm = _make_traffic(args, cfg, n=max(args.batch, 2), rate=1e9, seed=1)
    for item in warm:
        engine.submit(item["prompt"])
    engine.drain()
    if prefix_cache:
        engine.warmup_prefix_cache(cfg.vocab_size)
    engine.metrics = type(engine.metrics)()     # fresh aggregates

    traffic = _make_traffic(args, cfg, n=args.requests, rate=args.rate,
                            seed=args.seed)
    t0 = engine.clock()
    finished = []
    peak_kv = 0.0

    def _track():
        nonlocal peak_kv
        kv = engine.metrics.gauges.get("kv_occupancy")
        if kv is not None:
            peak_kv = max(peak_kv, kv)

    for item in traffic:
        due = t0 + item["at"]
        wait = due - engine.clock()
        if wait > 0:                   # open loop: arrivals keep schedule
            time.sleep(wait)
        # when serving fell BEHIND the schedule, enqueue_at backdates the
        # queue-wait span to the scheduled arrival — the load-balancer view
        engine.submit(item["prompt"], enqueue_at=due)
        while engine.queue_depth >= args.batch:
            finished.extend(engine.step())
            _track()
    while engine.busy:
        finished.extend(engine.step())
        _track()
    wall = engine.clock() - t0

    done = [r for r in finished if r.status == "done"]
    # timed-out traffic counts as an SLO MISS, not a dropped sample —
    # excluding it would report 100% attainment exactly under overload
    n_expired = sum(1 for r in finished if r.status == "timeout")
    ttfts = [r.trace.ttft_s for r in done if r.trace.ttft_s is not None]
    e2es = [r.trace.e2e_s for r in done if r.trace.e2e_s is not None]

    def attainment(vals, limit_ms):
        denom = len(vals) + n_expired
        if not denom:
            return None
        return sum(1 for t in vals if t * 1e3 <= limit_ms) / denom

    slo = {
        "ttft_ms": args.slo_ttft_ms,
        "e2e_ms": args.slo_e2e_ms,
        "expired": n_expired,
        "ttft_attainment": attainment(ttfts, args.slo_ttft_ms),
        "e2e_attainment": attainment(e2es, args.slo_e2e_ms),
    }
    s = engine.summary()
    mode = "paged" if paged else "padded"
    if prefix_cache:
        mode += "+prefix"
    if spec:
        mode += "+spec"
    if paged and args.shards and args.shards > 1:
        mode += f"+mp{args.shards}"
    out = {"mode": mode,
           "preset": args.preset or "toy", "requests": args.requests,
           "rate_req_s": args.rate, "length_dist": args.length_dist,
           "wall_s": round(wall, 3),
           "completed": len(done),
           "throughput_tok_s": round(s["tokens_out_total"] / wall, 1)
           if wall > 0 else None,
           "kv_occupancy_peak": round(peak_kv, 4),
           "slo": slo, "serving": s}
    if paged and args.shared_prefix:
        hits, misses = s["prefix_hit_total"], s["prefix_miss_total"]
        out["prefix"] = {
            "enabled": prefix_cache,
            "hits": hits, "misses": misses,
            "hit_rate": round(hits / max(hits + misses, 1), 4),
            "prefill_tokens_saved": s["prefill_tokens_saved_total"],
        }
    if spec:
        prop = s["spec_proposed_total"]
        out["spec"] = {
            "windows": s["spec_windows_total"],
            "proposed": prop, "accepted": s["spec_accepted_total"],
            "accept_rate": round(s["spec_accepted_total"] / prop, 4)
            if prop else None,
            "drafts_trie": s["spec_drafts_trie_total"],
            "drafts_model": s["spec_drafts_model_total"],
            "accept_len": s.get("spec_accept_len"),
        }
    # the recompiles counter is a pure churn signal: refused requests log
    # their shape delta without feeding it (record_compile count=False)
    out["steady_recompiles"] = engine.monitor.recompiles
    return out, engine


def run_bench(args):
    """Returns ([report, ...], engine_of_last_run) — one report per engine
    mode (two under --compare / --shared-prefix)."""
    model, cfg = build_model(args.preset)
    if args.spec:
        # the speculative A/B (ISSUE 11): same traffic, paged+prefix
        # engine, spec decode off then on
        modes = [(True, True, False), (True, True, True)]
    elif args.shared_prefix:
        # the prefix-cache A/B: same system-prompt traffic, paged engine,
        # cache off then on
        modes = [(True, False, False), (True, True, False)]
    elif args.compare:
        modes = [(False, False, False), (True, args.prefix_cache, False)]
    else:
        modes = [(args.paged, args.prefix_cache, False)]
    reports = []
    engine = None
    for paged, prefix, spec in modes:
        rep, engine = run_engine(model, cfg, args, paged=paged,
                                 prefix_cache=prefix, spec=spec)
        reports.append(rep)
    return reports, engine


def _print_report(out):
    s = out["serving"]
    tput = out["throughput_tok_s"]
    print(f"serve_bench[{out['mode']}]: {out['completed']}/"
          f"{out['requests']} requests at {out['rate_req_s']} req/s "
          f"({out['length_dist']}) -> "
          f"{'n/a' if tput is None else tput} tok/s over {out['wall_s']}s")
    for name in ("ttft_seconds", "tpot_seconds", "e2e_seconds",
                 "queue_seconds"):
        h = s.get(name)
        if h:
            print(f"  {name:<14} p50 {h['p50'] * 1e3:8.2f} ms   "
                  f"p90 {h['p90'] * 1e3:8.2f} ms   "
                  f"p99 {h['p99'] * 1e3:8.2f} ms")
    fill, kv = s["batch_fill_ratio"], out["kv_occupancy_peak"]
    print(f"  batch fill {'n/a' if fill is None else f'{fill:.2f}'}   "
          f"true kv occupancy (peak) {kv:.2f}   "
          f"batches {s['batches_total']}")
    slo = out["slo"]
    if slo["ttft_attainment"] is not None:
        print(f"  SLO: TTFT<= {slo['ttft_ms']:.0f}ms "
              f"{slo['ttft_attainment'] * 100:.1f}%   "
              f"e2e<= {slo['e2e_ms']:.0f}ms "
              f"{slo['e2e_attainment'] * 100:.1f}%")
    pre = out.get("prefix")
    if pre:
        print(f"  prefix cache {'on ' if pre['enabled'] else 'off'}: "
              f"hit rate {pre['hit_rate'] * 100:.1f}% "
              f"({pre['hits']}/{pre['hits'] + pre['misses']})   "
              f"prefill tokens saved {pre['prefill_tokens_saved']}")
    sp = out.get("spec")
    if sp:
        rate = sp["accept_rate"]
        print(f"  speculative: {sp['windows']} windows, accepted "
              f"{sp['accepted']}/{sp['proposed']} drafts "
              f"({'n/a' if rate is None else f'{rate * 100:.1f}%'})   "
              f"trie {sp['drafts_trie']} / model {sp['drafts_model']}")
    print(f"  steady-state recompiles: {out['steady_recompiles']}")


def _print_spec_comparison(off, on):
    print("\nspeculative decode off vs on (same traffic):")
    print(f"  {'mode':<18} {'tok/s':>10} {'accept rate':>12} "
          f"{'windows':>8}")
    for rep in (off, on):
        sp = rep.get("spec")
        acc = "n/a" if not sp or sp["accept_rate"] is None \
            else f"{sp['accept_rate'] * 100:.1f}%"
        print(f"  {rep['mode']:<18} {str(rep['throughput_tok_s']):>10} "
              f"{acc:>12} {sp['windows'] if sp else 0:>8}")
    if off["throughput_tok_s"] and on["throughput_tok_s"]:
        print(f"  speculative speedup: "
              f"{on['throughput_tok_s'] / off['throughput_tok_s']:.2f}x")


def _print_prefix_comparison(off, on):
    def ttft(rep, q):
        h = rep["serving"].get("ttft_seconds")
        return f"{h[q] * 1e3:10.2f}" if h else "       n/a"

    print("\nprefix cache off vs on (same shared-prefix traffic):")
    print(f"  {'mode':<14} {'tok/s':>10} {'p50 TTFT ms':>12} "
          f"{'p99 TTFT ms':>12} {'hit rate':>9} {'saved tok':>10}")
    for rep in (off, on):
        pre = rep["prefix"]
        print(f"  {rep['mode']:<14} {str(rep['throughput_tok_s']):>10} "
              f"{ttft(rep, 'p50'):>12} {ttft(rep, 'p99'):>12} "
              f"{pre['hit_rate'] * 100:>8.1f}% "
              f"{pre['prefill_tokens_saved']:>10}")


def _print_comparison(padded, paged):
    def p99(rep):
        h = rep["serving"].get("ttft_seconds")
        return f"{h['p99'] * 1e3:10.2f}" if h else "       n/a"

    print("\npadded vs paged (same traffic):")
    print(f"  {'mode':<8} {'tok/s':>10} {'p99 TTFT ms':>12} "
          f"{'true KV occ':>12}")
    for rep in (padded, paged):
        print(f"  {rep['mode']:<8} {str(rep['throughput_tok_s']):>10} "
              f"{p99(rep):>12} {rep['kv_occupancy_peak']:>12.2f}")
    if padded["throughput_tok_s"] and paged["throughput_tok_s"]:
        print(f"  paged speedup: "
              f"{paged['throughput_tok_s'] / padded['throughput_tok_s']:.2f}x")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--preset", default=None,
                    help="gpt3-125m … gpt3-13b (default: 2-layer toy)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=100.0,
                    help="open-loop arrival rate, requests/s")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-cap", type=int, default=16)
    ap.add_argument("--new", type=int, default=8,
                    help="max new tokens per request")
    ap.add_argument("--decode-chunk", type=int, default=None)
    ap.add_argument("--queue-capacity", type=int, default=256)
    ap.add_argument("--eos", type=int, default=None)
    ap.add_argument("--int8-weights", action="store_true")
    ap.add_argument("--int8-cache", action="store_true",
                    help="int8 KV cache (padded engine AND the paged "
                         "int8 pool)")
    ap.add_argument("--paged", action="store_true",
                    help="block-pool KV + slot-level continuous batching")
    ap.add_argument("--kv-block", type=int, default=16,
                    help="KV rows per pool block (paged)")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="total pool blocks incl. trash (paged; default "
                         "= worst case for the batch)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix-trie prefix cache over the paged pool")
    ap.add_argument("--prefix-cache-bytes", type=int, default=None,
                    help="LRU byte budget for cached prefixes")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="replay N system prompts x Poisson suffixes "
                         "through the paged engine with the prefix cache "
                         "off AND on; prints hit rate + TTFT table")
    ap.add_argument("--prefix-len", type=int, default=None,
                    help="system-prompt length for --shared-prefix "
                         "(default: half the prompt cap)")
    ap.add_argument("--spec", action="store_true",
                    help="replay through the paged+prefix engine with "
                         "speculative decode off AND on; prints the "
                         "acceptance table")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens per speculative verify window")
    ap.add_argument("--repeat", type=int, default=0, metavar="N",
                    help="workload = N fixed prompts repeated verbatim "
                         "(the agentic/retry shape trie drafting wants)")
    ap.add_argument("--shards", type=int, default=None, metavar="N",
                    help="tensor-parallel shards for the paged engine "
                         "(ISSUE 16): head-shard the KV pools and run "
                         "prefill/decode over an N-chip mp mesh (CPU "
                         "hosts get a virtual mesh via XLA_FLAGS "
                         "automatically)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="cap per-step prefill work at [1, N] tokens "
                         "(chunked prefill)")
    ap.add_argument("--length-dist", choices=("uniform", "longtail"),
                    default="uniform",
                    help="prompt-length mix; longtail = Pareto-shaped "
                         "mostly-short traffic")
    ap.add_argument("--compare", action="store_true",
                    help="replay the same traffic padded AND paged, "
                         "print the comparison table")
    ap.add_argument("--slo-ttft-ms", type=float, default=500.0)
    ap.add_argument("--slo-e2e-ms", type=float, default=5000.0)
    ap.add_argument("--slo", default=None, metavar="SPEC",
                    help="declarative SLO gate, e.g. "
                         "'ttft_p99=500ms,e2e_p99=2s,goodput=0.95': "
                         "prints the burn-rate table and exits nonzero "
                         "on breach (obs.slo; judges the last engine "
                         "run)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--metrics", action="store_true",
                    help="also dump the Prometheus /metrics payload "
                         "(last engine run)")
    args = ap.parse_args(argv)
    if args.prefix_len is None:
        args.prefix_len = max(1, args.prompt_cap // 2)

    # --shards needs a multi-device backend. The flag shapes the CPU
    # backend only (real chips are counted as they are). XLA reads
    # XLA_FLAGS at first BACKEND INIT (not at jax import), so setting it
    # here still works — only an already-initialized smaller backend is
    # unrecoverable.
    if args.shards and args.shards > 1:
        if "--xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count"
                  f"={max(8, args.shards)}")
        if len(jax.devices()) < args.shards:
            print(f"serve_bench: jax initialized with "
                  f"{len(jax.devices())} device(s); --shards "
                  f"{args.shards} needs at least that many (set "
                  f"XLA_FLAGS=--xla_force_host_platform_device_count=N "
                  f"before the first jax backend use)", file=sys.stderr)
            return 2
    from paddle_tpu.device import enable_compile_cache
    enable_compile_cache()

    try:
        reports, engine = run_bench(args)
    except Exception as e:
        # structured config-validation finding (analysis schema): print
        # WHY the configuration cannot be served, not just that it failed
        finding = getattr(e, "finding", None)
        if finding is None:
            raise
        from paddle_tpu.analysis import Findings
        print("serve_bench: invalid serving configuration")
        print(Findings([finding]).table())
        return 2
    # the SLO gate evaluates BEFORE any printing so --json stays ONE
    # parseable document (slo_gate rides the last report; the human
    # table prints after the reports)
    slo_rows = None
    if args.slo:
        from paddle_tpu.obs import (evaluate_slo, format_slo_table,
                                    parse_slo)
        try:
            targets = parse_slo(args.slo)
        except ValueError as e:
            print(f"serve_bench: bad --slo spec: {e}", file=sys.stderr)
            return 2
        slo_rows = evaluate_slo(targets, engine.metrics)
        reports[-1]["slo_gate"] = slo_rows
    if args.json:
        print(json.dumps(reports if len(reports) > 1 else reports[0],
                         indent=2))
    else:
        for rep in reports:
            _print_report(rep)
        if len(reports) == 2 and args.spec:
            _print_spec_comparison(reports[0], reports[1])
        elif len(reports) == 2 and args.shared_prefix:
            _print_prefix_comparison(reports[0], reports[1])
        elif len(reports) == 2:
            _print_comparison(reports[0], reports[1])
    if args.metrics:
        print(engine.metrics_text(), end="")
    rc = 0 if all(r["steady_recompiles"] == 0 for r in reports) else 1
    if slo_rows is not None:
        if not args.json:
            print(format_slo_table(
                slo_rows, title=f"serve_bench[{reports[-1]['mode']}]"))
        if not all(r["ok"] for r in slo_rows):
            breached = ", ".join(r["target"] for r in slo_rows
                                 if not r["ok"])
            print(f"serve_bench: SLO BREACH on {breached}",
                  file=sys.stderr)
            rc = 1
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
