#!/usr/bin/env python3
"""chip_smoke.py — GPT-1.3B takes training steps and answers requests on
the TPU, through the entry points a user calls.

    python chip_smoke.py              one chip: device, kernels, train, serve
    python chip_smoke.py --chips 4    four chips: the dp2 x mp2 mesh step and
                                      its single-device replay, nothing else

One process, one import of JAX, no child that needs the chip, and no
platform set in code: the platform is whatever JAX selects, and anything
but a TPU fails the device phase. Every phase raises on failure. The last
line of standard output is one JSON object,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

printed only after every phase has passed. Times, rates and memory on the
earlier lines are information about one run on one chip, tagged with the
device, and no benchmark.

The phases are plain functions of a size so that tests/test_chip_smoke.py
can rehearse them on the CPU at a tiny size; main() has no option that
lets it pass without a chip.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import numpy as np

# --- sizes: GPT-1.3B (models/gpt.py "gpt3-1.3b") at the flagship training
# point, and the serving envelope of the paged-kernel compile tests ---------
PRESET = "gpt3-1.3b"
TRAIN_B, TRAIN_S = 3, 2048
MESH_B, MESH_S = 2, 2048            # one sequence per dp replica
MESH_AXES = {"dp": 2, "mp": 2}
SERVE = dict(max_batch=8, prompt_cap=128, max_new_tokens=32, kv_block=16,
             kv_blocks=1024)
PROMPT_LENS = (128, 17, 3, 64, 100, 1, 33, 128)
KERNEL_SHAPES = dict(nh=16, hd=128, hidden=2048, vocab=50304,
                     flash=(3, 2048), ce_tokens=6144,
                     # GPT-2.7B's heads: 80 wide, padded to the 128 lanes
                     flash_padded=(32, 80),
                     pool_blocks=1024, kv_block=16, table_slots=64,
                     serve_batch=8, prefix_s=(128, 4),
                     # latent decode at the expert model's serving cell:
                     # (heads, latent width, rank, page, table slots, pages)
                     latent=(128, 576, 512, 128, 40, 1024))

# --- tolerances -------------------------------------------------------------
# Kernel vs its jnp reference on bf16 inputs, as max |got - want| over
# max |want|. Both sides accumulate in f32 but round the probabilities and
# the output to bf16 (one ulp = 2**-8 = 3.9e-3 relative) at different
# points, so a handful of ulps of the largest value is the honest bound.
KERNEL_TOL = 2e-2
# Mesh vs single-device replay, relative, on the loss and the global
# grad-norm: bf16 parameters and activations, and the row-parallel layers
# round each shard's partial sum to bf16 before the all-reduce.
MESH_TOL = 2e-2
# Serving on the chip: the paged kernel keeps f32 scores, the oracles store
# them in bf16, so greedy chains may part at a near-tie and then stay apart.
# What is required instead: every token the engine emits is a greedy choice
# of the plain forward pass over the engine's own prefix, up to rounding —
# its logit within 4 bf16 ulps (4 * 2**-5 for logits in [4, 8)) of that
# position's maximum. Of 50304 logits about two lie that close to the top;
# a token from a broken cache or a wrong position sits several units below.
# On the CPU engine and oracle share every jnp path: bit-exact is required.
GREEDY_TOL = 0.125


def say(phase: str, msg: str) -> None:
    """One line of output, tagged with the device it was produced on."""
    import jax
    devs = jax.devices()
    print(f"{phase}: {msg} [{devs[0].platform} {devs[0].device_kind} "
          f"x{len(devs)}]", flush=True)


def _require(ok, why) -> None:
    """A phase's check. Not `assert`: `python -O` must not turn the smoke
    into a script that cannot fail."""
    if not ok:
        raise AssertionError(why)


class CompileClock:
    """Seconds JAX spent in backend compiles (persistent-cache reads
    included) and the cache's hits and misses, from jax.monitoring."""

    def __init__(self):
        import jax
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return (self.seconds, self.hits, self.misses)

    def since(self, mark) -> str:
        s, h, m = mark
        return (f"compile {self.seconds - s:.1f}s (persistent cache: "
                f"{self.hits - h} hits, {self.misses - m} misses)")


def _release():
    """Drop what the finished phase left on the device."""
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()


def _hbm(dev) -> str:
    st = dev.memory_stats() or {}
    # the allocator's peak: on this runtime it did not count a program's
    # temporaries (PERF.md, PR 22); the plan is memory_analysis()
    return (f"HBM in use {st.get('bytes_in_use', 0) / 2**30:.2f} GiB, "
            f"allocator peak {st.get('peak_bytes_in_use', 0) / 2**30:.2f} "
            f"GiB of {st.get('bytes_limit', 0) / 2**30:.2f} GiB")


def _count_kernels(hlo_text: str) -> int:
    return hlo_text.count("tpu_custom_call")


# ------------------------------------------------------------------ device
def device_phase(chips: int) -> dict:
    """Platform, kind, count, allocator statistics, the peak table. Raises
    unless JAX selected `chips` TPU devices or more."""
    import jax
    from paddle_tpu import device
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu" or not device.on_tpu():
        raise RuntimeError(
            f"chip_smoke needs a TPU; JAX selected platform "
            f"{info['platform']!r} ({info['kind']}). Nothing was run.")
    if len(devs) < chips:
        raise RuntimeError(f"--chips {chips} needs {chips} devices, "
                           f"JAX found {len(devs)}")
    stats = devs[0].memory_stats()
    if not stats or "bytes_in_use" not in stats:
        raise RuntimeError(f"device.memory_stats() gave {stats!r}")
    peak = device.chip_peak_flops(devs[0])      # raises on an unknown kind
    say("device", f"{len(devs)} device(s), peak {peak / 1e12:.0f} bf16 "
        f"TFLOP/s per chip, {_hbm(devs[0])}")
    return info


# ----------------------------------------------------------------- kernels
def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def kernels_phase(shapes: dict, seed: int, *, interpret: bool = False,
                  dtype="bfloat16") -> None:
    """Each main-path Pallas kernel, compiled, against its own jnp
    reference on the same inputs. Every case runs before any failure is
    raised, so one run names them all. `interpret` exists for the CPU
    rehearsal only; main() never passes it."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import attention as A
    from paddle_tpu.ops import latent_attention as L
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import latent_attention as la
    from paddle_tpu.ops.pallas import linear_ce as lce
    from paddle_tpu.ops.pallas import paged_attention as pa

    dt = jnp.dtype(dtype)
    rng = np.random.RandomState(seed)
    nh, hd = shapes["nh"], shapes["hd"]
    failures = []

    def rnd(*shape, scale=1.0, dtype=dt):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                           * scale, dtype)

    def case(name, fn, ref, args):
        t0 = time.perf_counter()
        lowered = jax.jit(fn).lower(*args)
        compiled = lowered.compile()
        got = jax.block_until_ready(compiled(*args))
        dt_s = time.perf_counter() - t0
        n_k = _count_kernels(compiled.as_text())
        want = jax.jit(ref)(*args)
        errs = [_rel_err(g, w) for g, w in zip(jax.tree.leaves(got),
                                               jax.tree.leaves(want))]
        ok = max(errs) <= KERNEL_TOL and (interpret or n_k >= 1)
        say("kernels", f"{'PASS' if ok else 'FAIL'} {name}: rel err "
            f"{max(errs):.2e} (tol {KERNEL_TOL:.0e}), tpu_custom_call x{n_k}"
            f", compile+run {dt_s:.1f}s")
        if not ok:
            failures.append(name)

    # flash attention, forward and gradients; the gradients again at heads
    # the kernel pads to the lanes
    b, s = shapes["flash"]

    def flash_fwd(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, interpret=interpret)

    def ref_fwd(q, k, v):
        return A.attention_reference(q, k, v, is_causal=True)

    def grads_of(fwd):
        def loss(q, k, v, cot):
            return jnp.sum(fwd(q, k, v).astype(jnp.float32)
                           * cot.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))

    bq, bk = min(fa.DEFAULT_BQ, s), min(fa.DEFAULT_BK, s)
    done, needed = fa.causal_work(s, s, bq, bk)
    say("kernels", f"flash causal S={s} in blocks of {bq} x {bk}, crossed "
        f"blocks in strips of {fa.sub_tile(bq, bk)} q rows: {done} score "
        f"pairs multiplied a head for {needed} under the diagonal "
        f"({done / needed:.3f} x)")
    qkv = [rnd(b, s, nh, hd) for _ in range(3)]
    cot = rnd(b, s, nh, hd)
    case(f"flash fwd q/k/v [{b},{s},{nh},{hd}] causal", flash_fwd, ref_fwd,
         qkv)
    case(f"flash grads [{b},{s},{nh},{hd}] causal", grads_of(flash_fwd),
         grads_of(ref_fwd), qkv + [cot])
    # the packed projection, q, k, v read as views of it: heads of 128
    # lanes only, which is what GPT's training attention hands over
    qkv_packed = rnd(b, s, 3 * nh * 128)
    cot = rnd(b, s, nh * 128)

    def out_and_grad(attend):
        def both(x, c):
            out, pull = jax.vjp(lambda x: attend(x).reshape(c.shape), x)
            return out, pull(c)[0]
        return both

    case(f"flash fwd and grad, packed qkv [{b},{s},{3 * nh * 128}] causal",
         out_and_grad(lambda x: fa.flash_attention_qkv(
             x, nh, causal=True, interpret=interpret)),
         out_and_grad(lambda x: A.attention_reference(
             *(t.reshape(b, s, nh, 128) for t in jnp.split(x, 3, axis=-1)),
             is_causal=True)),
         [qkv_packed, cot])
    del qkv_packed
    nh_p, hd_p = shapes["flash_padded"]
    qkv = [rnd(b, s, nh_p, hd_p) for _ in range(3)]
    cot = rnd(b, s, nh_p, hd_p)
    case(f"flash grads [{b},{s},{nh_p},{hd_p}] causal, padded lanes",
         grads_of(flash_fwd), grads_of(ref_fwd), qkv + [cot])
    del qkv, cot

    # linear cross-entropy against the unfused head: logits in f32, then
    # logsumexp minus the gold logit
    t, h, v = shapes["ce_tokens"], shapes["hidden"], shapes["vocab"]
    x, w = rnd(t, h), rnd(v, h, scale=0.02)
    labels = jnp.asarray(rng.randint(0, v, (t,)), jnp.int32)
    g = jnp.asarray(rng.rand(t), jnp.float32)

    def ce_kernel(x, w):
        return lce.linear_cross_entropy(x, w, labels, interpret=interpret)

    def ce_unfused(x, w):
        logits = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - gold

    case(f"linear-CE fwd x [{t},{h}] W [{v},{h}]", ce_kernel, ce_unfused,
         [x, w])
    case(f"linear-CE grads T={t}",
         jax.grad(lambda x, w: jnp.sum(ce_kernel(x, w) * g), (0, 1)),
         jax.grad(lambda x, w: jnp.sum(ce_unfused(x, w) * g), (0, 1)),
         [x, w])
    del x, w

    # paged attention over a scattered pool: every row live, lengths ragged
    nb, bs, mb, sb = (shapes["pool_blocks"], shapes["kv_block"],
                      shapes["table_slots"], shapes["serve_batch"])
    cap = mb * bs
    tables = jnp.asarray(np.stack([
        rng.permutation(np.arange(1, nb))[:mb] for _ in range(sb)]),
        jnp.int32)
    lens = jnp.asarray(
        [1, bs, bs + 1, cap // 2, cap, cap // 3, 4 * bs, cap - 1][:sb],
        jnp.int32)
    pools = [rnd(nb, bs, nh, hd, scale=0.5) for _ in range(2)]
    codes = [jnp.asarray(rng.randint(-127, 128, (nb, bs, nh, hd)), jnp.int8)
             for _ in range(2)]
    scales = [jnp.asarray(rng.rand(nb, bs, nh) * 0.01 + 1e-3, jnp.float32)
              for _ in range(2)]
    q8_pools = [codes[0], scales[0], codes[1], scales[1]]
    q1 = rnd(sb, 1, nh, hd)

    case(f"paged decode q [{sb},1,{nh},{hd}] pools [{nb},{bs},{nh},{hd}]",
         lambda q, k, v: pa.paged_attention_kernel(
             q, k, v, tables, lens, interpret=interpret),
         lambda q, k, v: A.paged_attention_reference(q, k, v, tables, lens),
         [q1, *pools])
    case("paged decode int8 pools",
         lambda q, *p: pa.paged_attention_q8_kernel(
             q, *p, tables, lens, interpret=interpret),
         lambda q, *p: A.paged_attention_reference_q8(q, *p, tables, lens),
         [q1, *q8_pools])
    for s_q in shapes["prefix_s"]:
        # windows that start at 0, on a block boundary, inside a block,
        # and that end exactly at the table's capacity
        start = jnp.asarray(
            [0, bs, bs + 5, cap // 2, cap - s_q, 3, 2 * bs, cap // 3][:sb],
            jnp.int32)
        qs = rnd(sb, s_q, nh, hd)
        case(f"paged prefix S={s_q}",
             lambda q, k, v: pa.paged_prefix_attention_kernel(
                 q, k, v, tables, start, interpret=interpret),
             lambda q, k, v: A.paged_prefix_attention_reference(
                 q, k, v, tables, start),
             [qs, *pools])
        case(f"paged prefix S={s_q} int8 pools",
             lambda q, *p: pa.paged_prefix_attention_q8_kernel(
                 q, *p, tables, start, interpret=interpret),
             lambda q, *p: A.paged_prefix_attention_reference_q8(
                 q, *p, tables, start),
             [qs, *q8_pools])
    del pools, codes, scales, q8_pools

    # latent decode: every head against one latent a token, the row's own
    # pages walked; rows empty, one token, mid-page, a full table, ragged
    lh, lw, rank, lbs, lmb, lnb = shapes["latent"]
    lcap = lmb * lbs
    ltables = jnp.asarray(np.stack([
        rng.permutation(np.arange(1, lnb))[:lmb] for _ in range(sb)]),
        jnp.int32)
    llens = jnp.asarray(
        [0, 1, lbs + 1, lcap // 2, lcap, lcap // 3, 2 * lbs, lcap - 1][:sb],
        jnp.int32)
    case(f"latent decode q [{sb},{lh},{lw}] pool [{lnb},{lw},{lbs}]",
         lambda q, p: la.latent_decode_kernel(
             q, p, ltables, llens, rank=rank, scale=lw ** -0.5,
             interpret=interpret)[1:],
         lambda q, p: L.latent_paged_attention(
             q[:, None], p, ltables, llens - 1, rank=rank,
             scale=lw ** -0.5)[1:, 0],
         [rnd(sb, lh, lw, scale=0.3), rnd(lnb, lw, lbs)])
    _require(not failures, f"kernel phase failed: {failures}")


# ------------------------------------------------------------------- train
def _build_train(cfg, seed, *, bf16, mesh=None, monitor=None):
    """The flagship step: bf16 parameters, AdamW with bf16 moments,
    TrainStep over the fused-head loss."""
    import paddle_tpu as paddle
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.models import GPTForCausalLM
    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    if bf16:
        model.to(dtype="bfloat16")
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters(),
        moment_dtype="bfloat16" if bf16 else "float32")
    kw = dict(mesh=mesh, data_axes=("dp",)) if mesh is not None else {}
    step = TrainStep(model, opt,
                     lambda a, b: model.loss(a, b, chunk_size=512),
                     monitor=monitor, **kw)
    return model, step


def train_phase(cfg, batch: int, seq: int, seed: int, *, bf16: bool = True,
                expect_kernels: bool = True) -> dict:
    """A warm-up call, three single steps and two run_steps(4) launches
    (the first compiles, the second is timed) on one repeated batch.
    Losses finite and falling, no recompile after the warm-up, the flash
    and linear-CE kernels in the compiled step."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.jit.api import compile_cache_misses
    from paddle_tpu.profiler import StepMonitor

    dev = jax.devices()[0]
    mon = StepMonitor(unit="tokens/s", items_per_step=batch * seq)
    model, step = _build_train(cfg, seed, bf16=bf16, monitor=mon)
    n_params = sum(p.size for p in model.parameters())
    rng = np.random.RandomState(seed)
    ids_np = rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int32")
    ids = paddle.to_tensor(ids_np)

    # the compiled step, before anything runs: what the compiler planned
    # and whether the kernels are in it. The real call below builds the
    # same program again and finds it in the persistent cache.
    t0 = time.perf_counter()
    compiled = step.aot_compile(ids, ids)
    ma = compiled.memory_analysis()
    n_k = _count_kernels(compiled.as_text())
    del compiled
    say("train", f"{cfg.num_layers} layers, {n_params / 1e9:.2f}B params, "
        f"B={batch} S={seq}; step compiled in "
        f"{time.perf_counter() - t0:.1f}s: arguments "
        f"{ma.argument_size_in_bytes / 2**30:.2f} GiB (aliased "
        f"{ma.alias_size_in_bytes / 2**30:.2f}), temporaries "
        f"{ma.temp_size_in_bytes / 2**30:.2f} GiB, code "
        f"{ma.generated_code_size_in_bytes / 2**30:.2f} GiB; "
        f"tpu_custom_call x{n_k}")
    if expect_kernels:
        # flash forward, dq and dkv per layer, and the linear-CE forward
        want = 3 * cfg.num_layers + 1
        _require(n_k >= want, (
            f"compiled step holds {n_k} tpu_custom_call, expected >= {want}:"
            f" a kernel gate gave way to its reference"))
        _require(ma.alias_size_in_bytes >= 0.99 * ma.argument_size_in_bytes,
                 "parameters and moments are not donated")

    t0 = time.perf_counter()
    losses = [float(step(ids, ids))]
    say("train", f"warm-up step {time.perf_counter() - t0:.1f}s, loss "
        f"{losses[0]:.4f}")
    miss0 = compile_cache_misses()
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        losses.append(float(step(ids, ids)))        # float() is the fence
        times.append(time.perf_counter() - t0)
    _require(compile_cache_misses() == miss0, "a steady step compiled again")

    stacked = paddle.to_tensor(np.broadcast_to(ids_np, (4, batch, seq)))
    t0 = time.perf_counter()
    scan_losses = step.run_steps(4, stacked, stacked).numpy().tolist()
    scan_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    scan_losses += step.run_steps(4, stacked, stacked).numpy().tolist()
    scan_s = time.perf_counter() - t0
    losses += scan_losses
    _require(compile_cache_misses() == miss0 + 1,
             "run_steps(4) should compile exactly one more executable")
    _require(mon.recompiles == 0, f"recompiles: {mon.recompile_events}")
    _require(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    _require(losses[-1] < losses[3] < losses[0],
             f"loss not falling: {losses}")

    step_s = float(np.median(times))
    say("train", "losses " + " ".join(f"{x:.4f}" for x in losses))
    say("train", f"single step median {step_s * 1e3:.1f} ms = "
        f"{batch * seq / step_s:,.0f} tokens/s; run_steps(4) first call "
        f"{scan_first:.1f}s, then {scan_s / 4 * 1e3:.1f} ms/step = "
        f"{4 * batch * seq / scan_s:,.0f} tokens/s (one run, no benchmark);"
        f" {_hbm(dev)}")
    return {"losses": losses, "kernels": n_k}


# ------------------------------------------------------------------- serve
def _prompts(cfg, lens, cap, seed):
    """Ragged prompts from the seed; rows 3 and 6 repeat the first two
    blocks of row 0, so the prefix cache and the suffix prefill have work."""
    rng = np.random.RandomState(seed)
    ids = np.zeros((len(lens), cap), np.int64)
    for r, ln in enumerate(lens):
        ids[r, :ln] = rng.randint(1, cfg.vocab_size, (ln,))
    for r in (3, 6):
        if r < len(lens):
            n = min(lens[r] - 1, lens[0], 32)
            ids[r, :n] = ids[0, :n]
    return ids


def _greedy_gaps(model, ids, lens, tokens):
    """For every emitted token, how far its logit lies below that
    position's maximum in the plain forward pass over the engine's own
    prefix (prompt + the tokens emitted before it). 0 = the greedy pick."""
    import paddle_tpu as paddle
    b, new = tokens.shape
    width = ids.shape[1] + new
    seqs = np.zeros((b, width), np.int64)
    for r, ln in enumerate(lens):
        seqs[r, :ln] = ids[r, :ln]
        seqs[r, ln:ln + new] = tokens[r]
    fwd = paddle.jit.to_static(lambda t: model(t))
    logits = np.asarray(fwd(paddle.to_tensor(seqs)).numpy(), np.float32)
    gaps = np.zeros((b, new), np.float32)
    for r, ln in enumerate(lens):
        rows = logits[r, ln - 1:ln - 1 + new]            # predicts token j
        gaps[r] = rows.max(-1) - rows[np.arange(new), tokens[r]]
    return gaps


def serve_phase(cfg, serve: dict, prompt_lens, seed: int, *,
                bf16: bool = True, expect_kernels: bool = True) -> dict:
    """The model behind the paged, prefix-caching ServingEngine: ragged
    prompts in, drain, and the tokens checked against the oracles."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.analysis import lint_capture
    from paddle_tpu.inference import ServingConfig, ServingEngine
    from paddle_tpu.jit.api import compile_cache_misses
    from paddle_tpu.models import GPTForCausalLM

    dev = jax.devices()[0]
    exact = dev.platform != "tpu"
    cap, new = serve["prompt_cap"], serve["max_new_tokens"]
    lens = list(prompt_lens)
    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    if bf16:
        model.to(dtype="bfloat16")
    model.eval()
    ids = _prompts(cfg, lens, cap, seed)

    t0 = time.perf_counter()
    oracle = model.generate_static_ragged(
        paddle.to_tensor(ids), lens, max_new_tokens=new).numpy()[:, cap:]
    say("serve", f"oracle generate_static_ragged {len(lens)} prompts x {new}"
        f" tokens in {time.perf_counter() - t0:.1f}s")

    eng = ServingEngine(model, ServingConfig(
        prefix_cache=True, **serve))
    t0 = time.perf_counter()
    with lint_capture() as calls:
        eng.warmup_prefix_cache(cfg.vocab_size)
    say("serve", f"engine warm-up (prefill, suffix prefill, copy-on-write,"
        f" decode) {time.perf_counter() - t0:.1f}s")
    decode = [c for c in calls if c[0][0] == "paged_decode"]
    _require(decode,
             f"no decode executable among {[c[0][0] for c in calls]}")
    _, fn, (a, kw) = decode[0]
    n_k = _count_kernels(fn.lower(*a, **kw).compile().as_text())
    say("serve", f"decode executable: tpu_custom_call x{n_k}")
    if expect_kernels:
        _require(n_k >= cfg.num_layers, (
            f"decode executable holds {n_k} tpu_custom_call for "
            f"{cfg.num_layers} layers: the paged gate gave way"))

    miss0 = compile_cache_misses()
    t0 = time.perf_counter()
    for r, ln in enumerate(lens):
        eng.submit(ids[r, :ln])
    done = eng.drain()
    wall = time.perf_counter() - t0
    _require(compile_cache_misses() == miss0,
             "steady serving compiled again")
    _require(len(done) == len(lens),
             f"{len(done)} of {len(lens)} came back")
    bad = [(r.id, r.status, r.reason) for r in done if r.status != "done"]
    _require(not bad, f"requests not done: {bad}")

    by_prompt = {tuple(r.prompt.tolist()): np.asarray(r.tokens)
                 for r in done}
    tokens = np.stack([by_prompt[tuple(ids[r, :ln].tolist())]
                       for r, ln in enumerate(lens)])
    _require(tokens.shape == oracle.shape, (tokens.shape, oracle.shape))
    same = (tokens == oracle).all(axis=1)
    gaps = _greedy_gaps(model, ids, lens, tokens)
    say("serve", f"{len(lens)} requests, {tokens.size} tokens in "
        f"{wall:.2f}s = {tokens.size / wall:,.0f} tokens/s (one run, no "
        f"benchmark); {int(same.sum())}/{len(lens)} chains equal the ragged "
        f"oracle bit for bit, {(tokens == oracle).mean():.3f} of tokens; "
        f"largest gap to the plain forward's greedy logit {gaps.max():.4f} "
        f"(allowed {0.0 if exact else GREEDY_TOL}); "
        f"prefix hits {eng.summary().get('prefix_hit_total')}; {_hbm(dev)}")
    if exact:
        _require(same.all(),
                 "CPU contract: engine == generate_static_ragged")
    _require(np.isfinite(gaps).all() and gaps.max() <= (
        0.0 if exact else GREEDY_TOL), (
        f"engine tokens are not greedy choices of the plain forward: "
        f"gaps {np.sort(gaps.ravel())[-5:]}"))

    eng._prefix.clear()
    pool = eng._pool
    _require(pool.free_blocks == pool.capacity_blocks, (
        f"pool leaked: {pool.free_blocks} free of {pool.capacity_blocks}"))
    return {"agree": float((tokens == oracle).mean()), "kernels": n_k}


# -------------------------------------------------------------------- mesh
def _check_placement(step, mesh):
    """Every parameter's shards lie on the devices its pspec says, and
    every device of the mesh holds some."""
    from jax.sharding import NamedSharding
    from paddle_tpu.jit.train_step import _spec_or_replicated
    held = {d.id: 0 for d in mesh.devices.flat}
    n_split = 0
    for name, p in zip(step._param_names, step._params):
        want = step._placement(_spec_or_replicated(p))
        arr = p._data
        _require(isinstance(arr.sharding, NamedSharding)
                 and arr.sharding.is_equivalent_to(want, arr.ndim), (
                f"{name}: placed as {arr.sharding}, pspec says {want}"))
        where = want.devices_indices_map(arr.shape)
        for sh in arr.addressable_shards:
            _require(where[sh.device] == sh.index, (
                f"{name}: device {sh.device.id} holds {sh.index}, "
                f"pspec says {where[sh.device]}"))
            held[sh.device.id] += sh.data.nbytes
        n_split += len({str(i) for i in where.values()}) > 1
    _require(n_split, "no parameter is split over the mesh")
    _require(min(held.values()) > 0, f"a device holds nothing: {held}")
    return held, n_split


def mesh_phase(cfg, batch: int, seq: int, seed: int, axes: dict, *,
               bf16: bool = True, expect_kernels: bool = True,
               steps: int = 3) -> dict:
    """The hybrid-parallel TrainStep over `axes`, then the single-device
    replay from the same seed on the same batch: loss and global grad-norm
    before the first update, and the loss of each step, within MESH_TOL."""
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist

    rng = np.random.RandomState(seed)
    ids_np = rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int32")
    n_dev = int(np.prod(list(axes.values())))

    def run(mesh):
        model, step = _build_train(cfg, seed, bf16=bf16, mesh=mesh)
        ids = paddle.to_tensor(ids_np)
        t0 = time.perf_counter()
        loss0, gnorm0 = step.loss_and_grad_norm(ids, ids)
        out = {"loss0": loss0, "gnorm0": gnorm0,
               "losses": [float(step(ids, ids)) for _ in range(steps)],
               "seconds": time.perf_counter() - t0}
        if mesh is not None:
            if expect_kernels:
                # the program handed to the compiler (no second compile on
                # four chips): a Mosaic call cannot be optimized away
                n_k = _count_kernels(step.aot_lower(ids, ids).as_text())
                _require(n_k >= 3 * cfg.num_layers + 1, (
                    f"mesh step holds {n_k} tpu_custom_call"))
                out["kernels"] = n_k
            held, n_split = _check_placement(step, mesh)
            in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
                      for d in mesh.devices.flat}
            say("mesh", f"{n_split} of {len(step._params)} parameters split"
                f"; parameter bytes per device {held}; bytes_in_use per "
                f"device {in_use}")
            for d, b in in_use.items():
                # allocator-less hosts (the CPU rehearsal) report None
                _require(b is None or b >= held[d], (
                    f"device {d}: {b} bytes in use, holds {held[d]} of "
                    f"parameters alone"))
        return out

    mesh = dist.build_mesh(axes, devices=jax.devices()[:n_dev])
    dist.set_mesh(mesh)
    try:
        on_mesh = run(mesh)
    finally:
        dist.set_mesh(None)
    _release()
    single = run(None)
    _release()

    say("mesh", f"mesh {axes}: loss0 {on_mesh['loss0']:.4f} gnorm0 "
        f"{on_mesh['gnorm0']:.4f} losses "
        + " ".join(f"{x:.4f}" for x in on_mesh["losses"])
        + f" ({on_mesh['seconds']:.1f}s with compiles)")
    say("mesh", f"single device: loss0 {single['loss0']:.4f} gnorm0 "
        f"{single['gnorm0']:.4f} losses "
        + " ".join(f"{x:.4f}" for x in single["losses"])
        + f" ({single['seconds']:.1f}s with compiles)")
    pairs = [("loss0", on_mesh["loss0"], single["loss0"]),
             ("gnorm0", on_mesh["gnorm0"], single["gnorm0"])] + [
        (f"loss[{i}]", a, b) for i, (a, b) in
        enumerate(zip(on_mesh["losses"], single["losses"]))]
    worst = 0.0
    for name, a, b in pairs:
        _require(np.isfinite(a) and np.isfinite(b), f"{name}: {a} vs {b}")
        rel = abs(a - b) / max(abs(b), 1e-12)
        worst = max(worst, rel)
        _require(rel <= MESH_TOL, (
            f"{name}: mesh {a} vs single {b}, rel {rel:.2e} > {MESH_TOL}"))
    _require(on_mesh["losses"][-1] < on_mesh["losses"][0],
             "loss not falling")
    say("mesh", f"parity holds: worst relative difference {worst:.2e} "
        f"(tol {MESH_TOL:.0e})")
    return {"worst": worst, **{k: on_mesh.get(k) for k in ("kernels",)}}


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the dp2 x mp2 mesh phase and its "
                         "single-device replay, and no other phase")
    args = ap.parse_args(argv)

    from paddle_tpu.device import enable_compile_cache
    from paddle_tpu.models import gpt_config

    info = device_phase(args.chips)
    clock = CompileClock()
    say("cache", f"persistent compile cache at {enable_compile_cache()}")
    cfg = gpt_config(PRESET, max_position_embeddings=max(1024, TRAIN_S))
    t_all = time.perf_counter()

    if args.chips == 4:
        mark = clock.mark()
        mesh_phase(cfg, MESH_B, MESH_S, args.seed, MESH_AXES)
        say("mesh", clock.since(mark))
    else:
        for name, phase in (
                ("kernels", lambda: kernels_phase(KERNEL_SHAPES, args.seed)),
                ("train", lambda: train_phase(cfg, TRAIN_B, TRAIN_S,
                                              args.seed)),
                ("serve", lambda: serve_phase(cfg, SERVE, PROMPT_LENS,
                                              args.seed))):
            mark, t0 = clock.mark(), time.perf_counter()
            phase()
            _release()
            say(name, f"passed in {time.perf_counter() - t0:.1f}s, "
                + clock.since(mark))
    say("total", f"{time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
