"""Runtime kernel autotuning + cache — the PHI autotune analog.

Reference (SURVEY §2.1): phi/kernels/autotune/ — cache.h keyed kernel
configs + switch_autotune.cc measuring candidate algorithms at runtime,
gated on FLAGS_use_autotune. TPU-native version: Pallas kernel tile sizes
(the flash-attention bq/bk) are the tunable axis; candidates are timed
eagerly on the real device with synthetic data of the call's static
shape. Results persist to a JSON cache keyed by
(device kind, kernel, shape signature) so the cost is paid once per
machine/shape, like the reference's AlgorithmsCache.

Opt-in via paddle.set_flags({'FLAGS_flash_autotune': True}) — runtime
measurement costs one compile per candidate, which on remote-compile
setups is seconds each (the reference's conv autotune is opt-in for the
same reason).

Tracing rule: measurement happens ONLY on eager (concrete) calls — under
an outer jit everything would be staged into the caller's trace and
nothing actually runs, so flash_attention consults the cache during
tracing but never tunes there. Warm the cache with one eager call (or
tune_flash_blocks directly, using your PER-DEVICE shapes when training
SPMD — the kernel tile choice is per-shard).

MEASURED CAVEAT (v5e, r2 session): isolated-kernel timing can MISLEAD —
for GPT-1.3B S=2048 the tuner picks (256,512) which wins in isolation but
loses 6 MFU points inside the full training step (smaller K/V tiles
re-read HBM; the bandwidth they steal is invisible when the kernel runs
alone). `tune_in_step` closes this trap: it times candidates inside a
caller-supplied FULL step. The isolated `tune_flash_blocks` remains
for quick exploration. What block tuning could not reach, the arithmetic
a large causal block spends above the diagonal, went with the sub-tile
plan of flash_attention.py (PERF.md section 6, PR 32: the in-step readings
its sub-tile side was chosen from).
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional, Tuple

_CACHE: Optional[Dict[str, list]] = None
_CACHE_PATH = os.environ.get(
    "PADDLE_TPU_AUTOTUNE_CACHE",
    os.path.join(os.path.expanduser("~"), ".cache", "paddle_tpu",
                 "autotune.json"))


def _load() -> Dict[str, list]:
    global _CACHE
    if _CACHE is None:
        try:
            with open(_CACHE_PATH) as f:
                _CACHE = json.load(f)
        except (OSError, ValueError):
            _CACHE = {}
    return _CACHE


def _save():
    try:
        os.makedirs(os.path.dirname(_CACHE_PATH), exist_ok=True)
        with open(_CACHE_PATH, "w") as f:
            json.dump(_CACHE, f, indent=1)
    except OSError:
        pass  # cache is an optimization, never an error


def clear_cache():
    global _CACHE
    _CACHE = {}
    try:
        os.remove(_CACHE_PATH)
    except OSError:
        pass


def flash_candidates(s_q: int, s_k: int) -> List[Tuple[int, int]]:
    """Tile candidates: powers of two dividing the sequence lengths,
    bounded by measured-VMEM-safe sizes (bq*bk <= 1024*1024 fits v5e's
    16M scoped vmem with d=128 bf16 operands; 2048-wide q blocks OOM —
    measured in the r2 bench session)."""
    qs = [b for b in (1024, 512, 256) if s_q % b == 0]
    ks = [b for b in (1024, 512, 256) if s_k % b == 0]
    out = [(bq, bk) for bq in qs for bk in ks]
    return out or [(min(1024, s_q), min(1024, s_k))]


def _cache_key(kernel: str, sig: Tuple) -> str:
    import jax
    dev = getattr(jax.devices()[0], "device_kind", "cpu")
    return f"{dev}|{kernel}|{'x'.join(str(s) for s in sig)}"


def _smallest(candidates):
    import math
    return min(candidates, key=lambda c: math.prod(c))


def cached_blocks(kernel: str, sig: Tuple) -> Optional[Tuple]:
    """Cache lookup only (no measurement) — safe during jit tracing."""
    hit = _load().get(_cache_key(kernel, sig))
    return tuple(hit) if hit is not None else None


def tune(kernel: str, sig: Tuple, candidates: List[Tuple],
         bench_fn, iters: int = 3) -> Tuple:
    """Generic measured selection with persistent caching.

    bench_fn(candidate) -> callable running the kernel once on synthetic
    data (compiled on first call); returns the fastest candidate. A
    candidate whose bench raises (tile too big for VMEM etc.) is skipped.
    """
    import jax

    cache = _load()
    key = _cache_key(kernel, sig)
    hit = cache.get(key)
    if hit is not None:
        return tuple(hit)

    if not candidates:
        raise ValueError(f"tune({kernel!r}): empty candidate list")
    import jax.core as _core
    best, best_t = None, float("inf")
    for cand in candidates:
        try:
            run = bench_fn(cand)
            out = run()
            if isinstance(jax.tree.leaves(out)[0], _core.Tracer):
                raise RuntimeError(
                    "tune() called under a jit trace: the benchmark would "
                    "be staged, not measured — call it eagerly")
            jax.block_until_ready(out)          # compile + warm
            t0 = time.perf_counter()
            for _ in range(iters):
                out = run()
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / iters
        except Exception:
            continue                             # infeasible tile
        if dt < best_t:
            best, best_t = cand, dt
    if best is None:
        # nothing measured (all candidates failed): fall back WITHOUT
        # caching, so a transient failure cannot poison the persistent
        # cache. Candidate lists are ordered largest-tile-first, and the
        # dominant failure mode is VMEM OOM — so pick the SMALLEST
        # candidate (most likely to compile), not candidates[0].
        import logging
        smallest = _smallest(candidates)
        logging.getLogger(__name__).warning(
            "autotune(%s): every candidate failed to run; falling back to "
            "smallest tile %s (unmeasured)", kernel, smallest)
        return tuple(smallest)
    cache[key] = list(best)
    _save()
    return tuple(best)


_OVERRIDE = None


def override_blocks(bq: int, bk: int):
    """Context manager forcing flash tile sizes — the hook tune_in_step
    uses to rebuild a caller's step under each candidate."""
    import contextlib

    @contextlib.contextmanager
    def cm():
        global _OVERRIDE
        prev = _OVERRIDE
        _OVERRIDE = (int(bq), int(bk))
        try:
            yield
        finally:
            _OVERRIDE = prev

    return cm()


def tune_in_step(kernel: str, sig: Tuple, candidates: List[Tuple],
                 build_step, iters: int = 2) -> Tuple:
    """Measured tile selection INSIDE a representative training step —
    closing the isolated-kernel trap documented above (r2: the isolated
    tuner's (256,512) pick lost 6 MFU points end-to-end because the HBM
    bandwidth small tiles steal is invisible when the kernel runs alone).

    build_step() -> run() must construct a FRESH step (fresh compile
    cache) and return a zero-arg callable that executes one full step AND
    fences on device completion (e.g. end with a host read like float(...)
    or jax.block_until_ready) — the tuner times run() wall-clock, and a
    fire-and-forget runner would measure async dispatch, not the step; the
    raw array case is fenced here as a safety net. Rebuilt once per
    candidate under override_blocks(cand), so every flash_attention call
    inside traces with that candidate's tiles. The winner persists in the
    same cache as tune() under key (device, kernel, sig) — reference
    contract: phi/kernels/autotune/switch_autotune.cc
    (measure-then-pick-then-cache).
    """
    import gc
    import logging

    cache = _load()
    key = _cache_key(kernel, sig)
    hit = cache.get(key)
    if hit is not None:
        return tuple(hit)

    log = logging.getLogger(__name__)
    best, best_t = None, float("inf")
    for cand in candidates:
        try:
            with override_blocks(*cand):
                import jax as _jax
                step = build_step()
                _jax.block_until_ready(step())   # compile (safety fence)
                _jax.block_until_ready(step())   # steady-state warm
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = step()
                _jax.block_until_ready(out)
                dt = (time.perf_counter() - t0) / iters
            log.info("tune_in_step(%s) %s: %.1f ms", kernel, cand, dt * 1e3)
        except Exception as e:
            log.info("tune_in_step(%s) %s: infeasible (%s)", kernel, cand,
                     str(e)[:120])
            dt = None
        finally:
            # each candidate holds a FULL model + optimizer state on
            # device; free before the next build (and before the caller's
            # own model allocates)
            step = None
            gc.collect()
        if dt is not None and dt < best_t:
            best, best_t = cand, dt
    if best is None:
        smallest = _smallest(candidates)
        log.warning("tune_in_step(%s): every candidate failed; falling "
                    "back to smallest tile %s", kernel, smallest)
        return tuple(smallest)
    cache[key] = list(best)
    _save()
    return tuple(best)


def tune_flash_blocks(b: int, s_q: int, s_k: int, h: int, d: int,
                      causal: bool, dtype) -> Tuple[int, int]:
    """Measure flash fwd+bwd across tile candidates for this shape."""
    import jax
    import jax.numpy as jnp

    def bench_fn(cand):
        bq, bk = cand
        from .flash_attention import flash_attention
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (b, s_q, h, d), jnp.float32).astype(dtype)
        k = jax.random.normal(key, (b, s_k, h, d), jnp.float32).astype(dtype)
        v = k

        def loss(q_, k_, v_):
            return flash_attention(q_, k_, v_, causal=causal,
                                   block_q=bq, block_k=bk).sum()

        f = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        return lambda: f(q, k, v)

    return tune("flash_attention", (b, s_q, s_k, h, d, int(causal),
                                    str(dtype)),
                flash_candidates(s_q, s_k), bench_fn)
