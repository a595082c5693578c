"""Split A/B: fwd-only and fwd+bwd times for pallas vs legacy linear-CE.

Timing traps handled: per-step input varies via a runtime scale vector (no
loop-invariant hoisting), and outputs are consumed via sum-of-squares (no
slice-narrowing through the matmuls). Otherwise: one
fused scan launch, host-read fence, best of 3.
"""
import os, sys, time
import numpy as np
import jax, jax.numpy as jnp
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from paddle_tpu.ops.pallas.linear_ce import linear_cross_entropy  # noqa
from tools.validate_linear_ce_tpu import legacy_ce  # noqa

T, H, V = (int(os.environ.get(k, d)) for k, d in
           (("T", 6144), ("H", 2048), ("V", 50304)))
rng = np.random.RandomState(0)
x = jnp.asarray(rng.randn(T, H).astype(np.float32) * 0.5, jnp.bfloat16)
w = jnp.asarray(rng.randn(V, H).astype(np.float32) * 0.05, jnp.bfloat16)
labels = jnp.asarray(rng.randint(0, V, T).astype(np.int32))
coef = jnp.asarray(rng.rand(T).astype(np.float32))

cfg = dict(block_t=int(os.environ.get("BT", "512")),
           block_v=int(os.environ.get("BV", "384")),
           bwd_chunks=int(os.environ.get("BC", "4")))
print("cfg", cfg, "T,H,V", (T, H, V))

def loss_pallas(xx, ww):
    return jnp.sum(coef * linear_cross_entropy(xx, ww, labels, **cfg))

def loss_legacy(xx, ww):
    return jnp.sum(coef * legacy_ce(xx, ww, labels))

N = 30
ps = jnp.ones((N,), jnp.bfloat16)   # runtime values; compiler can't fold

def timeit(per_step):
    def body(acc, p):
        return acc + per_step(x * p), None
    def run(ps):
        acc, _ = lax.scan(body, jnp.float32(0), ps)
        return acc
    run = jax.jit(run)
    _ = float(run(ps))
    best = float("inf")
    for _r in range(3):
        t0 = time.perf_counter()
        _ = float(run(ps))
        best = min(best, time.perf_counter() - t0)
    return best / N * 1e3

only = os.environ.get("ONLY")
pairs = [p for p in (("pallas", loss_pallas), ("legacy", loss_legacy))
         if not only or p[0] == only]
for name, fn in pairs:
    f = 0.0 if os.environ.get("SKIP_FWD") else timeit(
        lambda xx, fn=fn: fn(xx, w))
    g = jax.grad(fn, argnums=(0, 1))
    def full(xx, g=g):
        dx, dw = g(xx, w)
        return (jnp.sum(dx.astype(jnp.float32) ** 2)
                + jnp.sum(dw.astype(jnp.float32) ** 2))
    fb = timeit(full)
    print(f"{name}: fwd {f:.2f} ms   fwd+bwd(+consume) {fb:.2f} ms")
