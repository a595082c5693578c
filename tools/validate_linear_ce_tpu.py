"""Hardware validation + A/B timing for the fused linear-CE kernel.

Run on the chip (parity alone is also chip_smoke.py's kernel phase):
    python tools/validate_linear_ce_tpu.py

Checks (flagship shape T=6144 H=2048 V=50304 bf16):
  1. forward loss parity Pallas vs legacy chunked-XLA path
  2. dx/dW parity (bf16 tolerances)
  3. fwd+bwd wall time of both paths via a fused multi-step scan with a
     host-read fence (naive timing lies)
"""
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.ops.pallas.linear_ce import linear_cross_entropy  # noqa: E402


def legacy_ce(x2d, w, labels, chunk=512):
    t, h = x2d.shape
    nc = t // chunk
    xs = x2d.reshape(nc, chunk, h)
    ls = labels.reshape(nc, chunk)

    def chunk_loss(args):
        xc, lc = args
        def inner(xc, lc):
            logits = jnp.einsum("ch,vh->cv", xc, w).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
            return lse - gold
        return jax.checkpoint(inner)(xc, lc)

    return lax.map(chunk_loss, (xs, ls)).reshape(t)


def main():
    T, H, V = 6144, 2048, 50304
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(T, H).astype(np.float32) * 0.5, jnp.bfloat16)
    w = jnp.asarray(rng.randn(V, H).astype(np.float32) * 0.05, jnp.bfloat16)
    labels = jnp.asarray(rng.randint(0, V, T).astype(np.int32))
    coef = jnp.asarray(rng.rand(T).astype(np.float32))

    def loss_pallas(x, w):
        return jnp.sum(coef * linear_cross_entropy(x, w, labels))

    def loss_legacy(x, w):
        return jnp.sum(coef * legacy_ce(x, w, labels))

    # 1. forward parity
    fp = jax.jit(loss_pallas)(x, w)
    fl = jax.jit(loss_legacy)(x, w)
    print("fwd pallas", float(fp), "legacy", float(fl),
          "rel", abs(float(fp) - float(fl)) / abs(float(fl)))

    # 2. grad parity
    gp = jax.jit(jax.grad(loss_pallas, argnums=(0, 1)))(x, w)
    gl = jax.jit(jax.grad(loss_legacy, argnums=(0, 1)))(x, w)
    for name, a, b in (("dx", gp[0], gl[0]), ("dW", gp[1], gl[1])):
        a = np.asarray(a, np.float32); b = np.asarray(b, np.float32)
        denom = np.abs(b).max() + 1e-9
        print(f"{name} max-abs-diff {np.abs(a - b).max():.4e} "
              f"(rel-to-max {np.abs(a - b).max() / denom:.4e})")

    # 3. timed fwd+bwd scan (N steps fused into one launch)
    N = 20

    def make_step(fn):
        g = jax.grad(fn, argnums=(0, 1))
        def body(carry, _):
            xx, acc = carry
            dx, dw = g(xx, w)
            # fold grads back in so steps are data-dependent (no DCE)
            return (xx + 0.0 * dx, acc + jnp.float32(jnp.sum(dw[0, :1]))), None
        def run(xx):
            (xo, acc), _ = lax.scan(body, (xx, jnp.float32(0)), None, length=N)
            return acc + jnp.sum(xo[:1, :1].astype(jnp.float32))
        return jax.jit(run)

    for name, fn in (("pallas", loss_pallas), ("legacy", loss_legacy)):
        run = make_step(fn)
        _ = float(run(x))  # warm compile
        best = float("inf")
        for _rep in range(3):
            t0 = time.perf_counter()
            _ = float(run(x))
            best = min(best, time.perf_counter() - t0)
        print(f"{name}: {best / N * 1e3:.2f} ms/step (fwd+bwd, N={N})")


if __name__ == "__main__":
    main()
