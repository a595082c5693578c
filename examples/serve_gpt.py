"""GPT serving walkthrough: one-shot generation, then the serving engine.

Every path compiles ONCE and replays with fixed shapes (the TPU-native
analog of the reference's fused_multi_transformer CacheKV serving):

  1. generate_static          — one-shot: prefill + decode in ONE program
  2. generate_static_ragged   — ANY prompt length <= cap, one executable
  3. weight_dtype="int8"      — Pallas in-register-dequant GEMM weights
  4. cache_dtype="int8"       — int8 KV cache, factored-scale attention
  5. launch-level stats around live generate_static calls
  6. ServingEngine — request-level continuous batching over a paged KV
     pool with the prefix cache on (a shared prefix is prefilled ONCE,
     every later request maps its blocks), driven by open-loop
     system-prompt traffic, ending in the real /metrics payload a
     frontend scrapes (TTFT/TPOT/e2e histograms, queue/batch/KV gauges,
     zero-recompile steady state)
  7. the telemetry SERVER (obs, ISSUE 12) — the same engine scraped over
     HTTP: `curl /metrics` (collision-checked Prometheus page),
     `/healthz` (the autoscaler inputs: drain state + queue depth +
     overloaded_total; HTTP 503 once begin_drain() flips the replica
     out of rotation), `/statusz`, and `/tracez` tail-sampled traces

Usage: PYTHONPATH=. python examples/serve_gpt.py [gpt3-1.3b]
Runs on whatever platform JAX selects (JAX_PLATFORMS=cpu for a dry run);
on a TPU the model is bf16, and a preset name serves at real size.
"""
import sys

import numpy as np
import paddle_tpu as paddle


def main():
    from paddle_tpu.models import GPTForCausalLM, gpt_config, GPTConfig
    paddle.device.enable_compile_cache()
    paddle.seed(0)
    if len(sys.argv) > 1:
        cfg = gpt_config(sys.argv[1])
        B, cap, new, kv_block = 8, 128, 32, 16
    else:
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_position_embeddings=96,
                        intermediate_size=128)
        B, cap, new, kv_block = 2, 12, 8, 4
    model = GPTForCausalLM(cfg)
    if paddle.device.on_tpu():
        model.to(dtype="bfloat16")
    model.eval()
    rng = np.random.RandomState(0)

    # 1. one-shot fixed-length serving
    ids = paddle.to_tensor(rng.randint(1, cfg.vocab_size,
                                       (B, cap)).astype("int64"))
    out = model.generate_static(ids, max_new_tokens=new)
    print("one-shot:", out.shape)

    # 2. ragged prompts (right-padded; ONE executable serves any lengths)
    lens = [max(1, cap - 2 - i) for i in range(B)]
    r = model.generate_static_ragged(ids, lens, max_new_tokens=new)
    print("ragged:", r.shape, "lens:", lens)

    # 3+4. quantized serving: int8 weights + int8 KV cache
    q = model.generate_static(ids, max_new_tokens=new,
                              weight_dtype="int8", cache_dtype="int8")
    agree = float((q.numpy()[:, cap:] == out.numpy()[:, cap:]).mean())
    print(f"int8 weights+KV: greedy agreement {agree:.3f}")

    # 5. launch-level stats: a StepMonitor bracketing live decode launches —
    # steady tokens/s, device memory, and the recompile counter (a
    # shape-unstable serving loop shows up here immediately).
    from paddle_tpu.profiler import StepMonitor
    mon = StepMonitor(unit="tokens/s")
    for _ in range(3):
        with mon.step(items=B * new):
            out = model.generate_static(ids, max_new_tokens=new)
            _ = out.numpy()
    print(mon.metrics_text(), end="")

    # 6. request-level serving: the ServingEngine admits ragged prompts
    # into a bounded queue and splices each into a free batch slot over
    # the paged KV pool — with per-request traces
    # (enqueue→admit→prefill→first-token→finish), TTFT/TPOT/e2e latency
    # histograms and queue/batch/KV gauges. The prefix cache pays each
    # system prompt's prefill once. Open-loop traffic: arrivals follow
    # their own schedule regardless of service speed, so queue wait is a
    # real measurement, not an artifact of the replayer.
    from paddle_tpu.inference import (ServingEngine, ServingConfig,
                                      shared_prefix_traffic)
    engine = ServingEngine(model, ServingConfig(
        max_batch=B, prompt_cap=cap, max_new_tokens=new,
        decode_chunk=max(1, new // 2), kv_block=kv_block,
        prefix_cache=True))
    # boot the ops surface FIRST (ISSUE 12) — a real replica's telemetry
    # server is up before traffic lands, so /tracez sees every request
    srv = engine.serve_telemetry()
    traffic = shared_prefix_traffic(4 * B, n_prefixes=2,
                                    prefix_len=cap // 2 // kv_block
                                    * kv_block,
                                    prompt_cap=cap,
                                    vocab_size=cfg.vocab_size, rate=200.0,
                                    seed=3)
    import time
    t0 = engine.clock()
    finished = []
    for item in traffic:
        wait = t0 + item["at"] - engine.clock()
        if wait > 0:
            time.sleep(wait)                    # arrivals keep schedule
        engine.submit(item["prompt"], enqueue_at=t0 + item["at"])
        if engine.queue_depth >= B:
            finished += engine.step()           # serve while traffic lands
    finished += engine.drain()
    n_ok = sum(1 for r in finished if r.status == "done")
    s = engine.summary()
    print(f"engine: {n_ok} requests over {s['batches_total']} steps, "
          f"fill {s['batch_fill_ratio']:.2f}, "
          f"kv occupancy {s['kv_occupancy']:.2f} (true tokens), "
          f"{s['prefix_hit_total']} prefix hits saved "
          f"{s['prefill_tokens_saved_total']} prompt tokens of prefill")
    if s.get("ttft_seconds"):
        print(f"TTFT p50/p99: {s['ttft_seconds']['p50'] * 1e3:.1f} / "
              f"{s['ttft_seconds']['p99'] * 1e3:.1f} ms")
    assert s["batch_step"]["recompiles"] == 0   # steady loop never reshapes

    # 7. the ops surface over the wire (ISSUE 12): what a router /
    # autoscaler / dashboard actually scrapes. serve_telemetry() wires
    # /metrics (unified registry), /healthz, /statusz and /tracez around
    # the live engine on an ephemeral port — this is the in-process
    # `curl`, byte-for-byte what the network sees.
    import json as _json
    from urllib.request import urlopen
    from urllib.error import HTTPError
    print(f"---- telemetry server on {srv.url()} ----")
    metrics = urlopen(srv.url("/metrics")).read().decode()
    print(f"$ curl /metrics        -> {len(metrics.splitlines())} lines, "
          f"e.g.:")
    for line in metrics.splitlines():
        if line.startswith("paddle_tpu_serving_ttft_seconds_count") or \
                line.startswith("paddle_tpu_serving_completed_total"):
            print(f"    {line}")
    health = _json.loads(urlopen(srv.url("/healthz")).read())
    print(f"$ curl /healthz        -> 200 {health}")
    tz = _json.loads(urlopen(srv.url("/tracez?order=slowest&limit=1")).read())
    print(f"$ curl /tracez         -> {tz['summary']['retained']} traces "
          f"retained (tail-sampled), slowest trace_id "
          f"{tz['traces'][0]['trace_id']}")
    # graceful drain flips the replica out of rotation: /healthz turns
    # 503/"draining" the moment begin_drain() runs — the load balancer
    # ejects it while in-flight work finishes
    engine.begin_drain()
    try:
        urlopen(srv.url("/healthz"))
        raise AssertionError("draining replica must fail its health check")
    except HTTPError as e:
        print(f"$ curl /healthz        -> {e.code} "
              f"{_json.loads(e.read())['status']} (after begin_drain)")
    engine.drain(seal=True)
    srv.close()
    engine.resume_admission()

    print("---- /metrics ----")
    print(engine.metrics_text(), end="")
    print("OK")


if __name__ == "__main__":
    main()
