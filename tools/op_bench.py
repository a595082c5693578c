"""Op micro-benchmark suite + regression gate.

Reference capability: tools/ci_op_benchmark.sh + check_op_benchmark_result.py
— CI runs op benchmarks against the develop wheel and fails on relative
regressions. TPU-native analog: this file measures a curated set of op
kernels (the hot families: matmul, attention, norm, elementwise, reduction,
gather/scatter, CE) and writes JSON; `--check BASELINE.json` compares the
current run against a saved baseline and fails (exit 1) if any op regresses
beyond the tolerance — the same relative-gate contract.

Usage:
    python tools/op_bench.py --out op_bench.json          # record
    python tools/op_bench.py --check op_bench.json        # gate (±25%)
    python tools/op_bench.py --check op_bench.json --tol 0.10

Runs on whatever backend jax selects; baselines
are only comparable within one backend/host (store them per-machine, like
the reference's per-CI-pool baselines).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _cases():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.device import on_tpu as _on_tpu
    on_tpu = _on_tpu()
    F = 1 if on_tpu else 4  # shrink on CPU so the gate stays fast
    B, S, H = 8 // F or 1, 1024 // F, 2048 // F
    rng = np.random.RandomState(0)

    def f32(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32))

    def bf16(*shape):
        return f32(*shape).astype(jnp.bfloat16)

    x = bf16(B * S, H)
    w = bf16(H, 4 * H)
    ids = jnp.asarray(rng.randint(0, 50304, (B, S)).astype(np.int32))
    emb = bf16(50304, H)
    q = bf16(B, S, 16, H // 16)
    lnw, lnb = f32(H), f32(H)

    from paddle_tpu.ops.attention import attention_reference

    cases = {
        "matmul_bf16": (lambda: x @ w, ()),
        "elementwise_gelu": (lambda: jax.nn.gelu(x), ()),
        "reduce_mean_axis0": (lambda: x.astype(jnp.float32).mean(0), ()),
        "layer_norm": (lambda: _ln(x, lnw, lnb), ()),
        "embedding_gather": (lambda: jnp.take(emb, ids, axis=0), ()),
        "attention_sdpa": (lambda: attention_reference(q, q, q,
                                                       is_causal=True), ()),
        "softmax_ce": (lambda: _ce(x[: B * S // 4], ids.reshape(-1)[: B * S // 4]), ()),
        "cumsum": (lambda: jnp.cumsum(x, axis=1), ()),
        "sort": (lambda: jnp.sort(x[:256], axis=1), ()),
        "scatter_add": (lambda: jnp.zeros((50304, H), jnp.float32)
                        .at[ids.reshape(-1)].add(x.astype(jnp.float32)[: B * S]), ()),
    }

    def _ln(a, wg, bg):
        a32 = a.astype(jnp.float32)
        mu = a32.mean(-1, keepdims=True)
        var = a32.var(-1, keepdims=True)
        return ((a32 - mu) * jax.lax.rsqrt(var + 1e-5) * wg + bg).astype(a.dtype)

    def _ce(logit_in, labels):
        logits = (logit_in @ w[:, :H]).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, (labels[: logits.shape[0]] % H)[:, None], axis=-1)[..., 0]
        return (lse - gold).mean()

    return cases


def run(iters=20):
    import jax
    results = {}
    for name, (fn, _) in _cases().items():
        jitted = jax.jit(fn)
        out = jitted()
        jax.block_until_ready(out)       # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jitted()
        jax.block_until_ready(out)
        results[name] = (time.perf_counter() - t0) / iters * 1e6  # us
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="write baseline JSON")
    ap.add_argument("--check", default=None, help="baseline JSON to gate against")
    ap.add_argument("--tol", type=float, default=0.25,
                    help="max allowed relative slowdown (0.25 = +25%%)")
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()

    res = run(args.iters)
    for k, v in sorted(res.items()):
        print(f"{v:10.1f} us  {k}")

    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
        print(f"baseline written: {args.out}")
    if args.check:
        with open(args.check) as f:
            base = json.load(f)
        bad = []
        for k, us in res.items():
            if k in base and us > base[k] * (1 + args.tol):
                bad.append((k, base[k], us))
        if bad:
            for k, b, c in bad:
                print(f"REGRESSION {k}: {b:.1f}us -> {c:.1f}us "
                      f"(+{(c / b - 1) * 100:.0f}%)", file=sys.stderr)
            sys.exit(1)
        print(f"op benchmark gate OK ({len(res)} ops within "
              f"+{args.tol * 100:.0f}% of baseline)")


if __name__ == "__main__":
    main()
