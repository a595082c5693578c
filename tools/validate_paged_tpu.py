"""Hardware validation for the paged attention kernels (run on TPU).

CPU CI exercises the Pallas kernels in interpret mode only (tests/
test_paged_kv.py, tests/test_spec_decode.py); Mosaic compilation and the
scalar-prefetched block-table fetch path are checked here on the chip:
  1. compiled kernel parity vs `paged_attention_reference` across ragged
     lengths (incl. a row at an exact block boundary and a dummy row),
     then the decode walk's own edges at the benchmark cells' geometry
     (`ragged_cases`: the cases tests/test_paged_kv.py runs in interpret
     mode, here compiled: B 32, table 128, 3,072 pages of 16, 16 x 128)
  2. MULTI-TOKEN kernel parity (ISSUE 11) vs the gather reference across
     (k, block, start) shapes — k=1 degenerate, windows starting at and
     crossing block boundaries, serving-scale geometry
  3. serving-shape sweep (gpt3-1.3b geometry: nh=16 hd=128, bf16 pool)
  4. end-to-end: paged engine greedy == generate_static_ragged per row
     (plain AND speculative), zero steady jit cache misses
  5. ``--shards N`` (ISSUE 16): sharded-parity mode — the SAME traffic
     through the head-sharded tensor-parallel engine on an N-chip mp
     mesh and the 1-chip engine; greedy output must be bit-identical,
     pools must carry the head sharding, steady state must not recompile

Usage: python tools/validate_paged_tpu.py [--shards N]
"""
import argparse
import sys

import numpy as np

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp


FAILED = []


def check(name, ok, detail=""):
    """Every check runs; `main` exits 1 at the end if one failed (a chip
    call is too dear to stop at the first)."""
    print(f"[{'PASS' if ok else 'FAIL'}] {name} {detail}", flush=True)
    if not ok:
        FAILED.append(name)


def kernel_parity(dtype, nh, hd, bs, tol):
    from paddle_tpu.ops.attention import paged_attention_reference
    from paddle_tpu.ops.pallas.paged_attention import paged_attention_kernel
    rng = np.random.RandomState(0)
    B, NB, MB = 4, 32, 6
    kp = jnp.asarray(rng.randn(NB, bs, nh, hd).astype(np.float32) * 0.3,
                     dtype)
    vp = jnp.asarray(rng.randn(NB, bs, nh, hd).astype(np.float32) * 0.3,
                     dtype)
    lens = jnp.asarray([1, bs, 2 * bs + 3, 0], jnp.int32)  # boundary + dummy
    tables = np.zeros((B, MB), np.int32)
    tables[0, :1] = [1]
    tables[1, :1] = [2]
    tables[2, :3] = [3, 4, 5]
    tables = jnp.asarray(tables)
    q = jnp.asarray(rng.randn(B, 1, nh, hd).astype(np.float32) * 0.3, dtype)
    got = np.asarray(paged_attention_kernel(q, kp, vp, tables, lens),
                     np.float32)
    want = np.asarray(paged_attention_reference(q, kp, vp, tables, lens),
                      np.float32)
    live = slice(0, 3)        # dummy row: kernel zeros vs reference garbage
    err = np.abs(got[live] - want[live]).max()
    check(f"kernel parity {dtype} nh={nh} hd={hd} bs={bs}", err < tol,
          f"max err {err:.2e}")


def ragged_cases(bs, pps, mb):
    """name -> lens: the edges of a walk that takes `pps` pages of `bs`
    tokens a step through a table of `mb` slots, and a batch mixing them
    ("dummy": a row of one token; rows of none are `idle_mixes`)."""
    blk = pps * bs
    edges = {"dummy": 1, "one_page": bs, "one_block": blk,
             "block_plus_one": blk + 1, "whole_table": mb * bs}
    cases = {name: (ln, 1) for name, ln in edges.items()}
    cases["ragged"] = tuple(edges.values()) + (bs + 1, 1, blk - 1)
    return cases


def idle_mixes(b=8):
    """name -> the rows of a batch of `b` that hold a request; the others
    attend nothing (length 0), as the engine ships a slot without a
    request, a slot in prefill and a row past its EOS: the first row
    empty, the last, runs of several, all but one, every row."""
    return {"first": tuple(range(1, b)), "last": tuple(range(b - 1)),
            "runs": (0, b // 2, b - 1), "all_but_one": (b - 3,),
            "every": ()}


def ragged_case(lens, *, bs, nh, hd, mb, dtype, nb=None, seed=0):
    """(q, k_pool, v_pool, tables, lens) for rows of `lens` tokens whose
    pages lie scattered through the pools. Table slots past a row's last
    page point at page 0; page 0 and every page no row owns hold NaN, so
    anything read past `lens` shows in the output."""
    rng = np.random.RandomState(seed)
    n_pages = [-(-ln // bs) for ln in lens]
    nb = nb or 1 + sum(n_pages) + 3
    owned = rng.permutation(np.arange(1, nb))[:sum(n_pages)]
    pools = []
    for _ in range(2):
        pool = np.full((nb, bs, nh, hd), np.nan, np.float32)
        pool[owned] = rng.randn(len(owned), bs, nh, hd) * 0.3
        pools.append(jnp.asarray(pool, dtype))
    tables = np.zeros((len(lens), mb), np.int32)
    at = 0
    for b, n in enumerate(n_pages):
        tables[b, :n] = owned[at:at + n]
        at += n
    q = jnp.asarray(rng.randn(len(lens), 1, nh, hd) * 0.3, dtype)
    return (q, pools[0], pools[1], jnp.asarray(tables),
            jnp.asarray(lens, jnp.int32))


def kernel_ragged_parity(dtype, tol, *, b=32, mb=128, nb=3072, bs=16, nh=16,
                         hd=128):
    """The decode kernel against the reference at the serving cells' own
    geometry, on every edge of its walk."""
    from paddle_tpu.ops.attention import paged_attention_reference
    from paddle_tpu.ops.pallas import paged_attention as pa
    pps = pa._pages_per_step(bs * nh * hd * jnp.dtype(dtype).itemsize, mb)
    for name, lens in ragged_cases(bs, pps, mb).items():
        lens = (lens * b)[:b]                   # the cell's batch of 32
        q, kp, vp, tables, la = ragged_case(lens, bs=bs, nh=nh, hd=hd,
                                            mb=mb, dtype=dtype, nb=nb)
        got = np.asarray(pa.paged_attention_kernel(q, kp, vp, tables, la),
                         np.float32)
        # the reference gathers the padded slots too, and 0 x NaN is NaN
        want = np.asarray(paged_attention_reference(
            q.astype(jnp.float32), jnp.nan_to_num(kp.astype(jnp.float32)),
            jnp.nan_to_num(vp.astype(jnp.float32)), tables, la), np.float32)
        err = np.abs(got - want).max()          # NaN fails the comparison
        check(f"decode walk {name} {jnp.dtype(dtype).name} pages/step={pps}",
              err < tol, f"max err {err:.2e}")


def kernel_prefix_parity(dtype, nh, hd, bs, s, starts, tol):
    """Multi-token [B, k] kernel vs the gather reference (ISSUE 11):
    per-row start offsets as data, causal-within-window masking."""
    from paddle_tpu.ops.attention import (paged_prefill_write,
                                          paged_prefix_attention_reference)
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_prefix_attention_kernel)
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    B, MB = len(starts), 6
    nb = 1 + B * MB
    kp = jnp.zeros((nb, bs, nh, hd), dtype)
    vp = jnp.zeros_like(kp)
    tables = jnp.asarray(
        np.arange(1, nb, dtype=np.int32).reshape(B, MB))
    K = rng.randn(B, MB * bs, nh, hd).astype(np.float32) * 0.3
    V = rng.randn(B, MB * bs, nh, hd).astype(np.float32) * 0.3
    for b in range(B):
        kp = paged_prefill_write(kp, jnp.asarray(K[b:b + 1], dtype),
                                 tables[b:b + 1])
        vp = paged_prefill_write(vp, jnp.asarray(V[b:b + 1], dtype),
                                 tables[b:b + 1])
    q = jnp.asarray(rng.randn(B, s, nh, hd).astype(np.float32) * 0.3,
                    dtype)
    st = jnp.asarray(starts, jnp.int32)
    got = np.asarray(paged_prefix_attention_kernel(q, kp, vp, tables, st),
                     np.float32)
    want = np.asarray(
        paged_prefix_attention_reference(q, kp, vp, tables, st),
        np.float32)
    err = np.abs(got - want).max()
    check(f"multi-token kernel parity {dtype} nh={nh} hd={hd} bs={bs} "
          f"k={s} starts={list(starts)}", err < tol, f"max err {err:.2e}")


def spec_engine_parity():
    """Speculative engine greedy == generate_static_ragged on repeated
    traffic, full trie acceptance, zero steady jit cache misses."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import (ServingConfig, ServingEngine,
                                      repeated_traffic)
    from paddle_tpu.jit.api import compile_cache_misses
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=256, num_layers=2,
                    num_heads=2, max_position_embeddings=512,
                    intermediate_size=512)
    m = GPTForCausalLM(cfg)
    m.eval()                     # f32: same numerics-class note as above
    CAP, NEW = 64, 16
    # kv_block=8 < NEW: trie drafts are block-granular, so a finished
    # chain only contributes drafts once its generated tokens fill at
    # least one pool block past the prompt
    eng = ServingEngine(m, ServingConfig(
        max_batch=2, prompt_cap=CAP, max_new_tokens=NEW, decode_chunk=4,
        kv_block=8, kv_blocks=256, prefix_cache=True,
        spec_decode=True, spec_k=4))
    eng.warmup_prefix_cache(cfg.vocab_size, clear=False)
    traffic = repeated_traffic(8, n_prompts=2, prompt_len=CAP,
                               vocab_size=cfg.vocab_size, rate=1e9,
                               seed=5)
    prompts = {t["prompt_id"]: t["prompt"] for t in traffic}
    ids = np.stack([prompts[i] for i in sorted(prompts)])
    ref = m.generate_static_ragged(paddle.to_tensor(ids),
                                   [CAP] * len(ids),
                                   max_new_tokens=NEW).numpy()[:, CAP:]
    miss0 = compile_cache_misses()
    for t in traffic:
        eng.submit(t["prompt"])
    done = eng.drain()
    ok = all(r.status == "done" for r in done)
    for r in done:
        row = next(i for i in sorted(prompts)
                   if np.array_equal(prompts[i], r.prompt))
        ok = ok and np.array_equal(r.tokens, ref[row])
    check("spec engine greedy == generate_static_ragged", ok)
    s = eng.metrics.counters
    check("spec windows drafted from the trie",
          s["spec_windows"] > 0 and s["spec_drafts_trie"] > 0,
          f"windows={s['spec_windows']} accepted={s['spec_accepted']}/"
          f"{s['spec_proposed']}")
    check("steady speculative loop: zero jit cache misses",
          compile_cache_misses() - miss0 == 0,
          f"recompiles={eng.monitor.recompiles}")


def engine_parity():
    import paddle_tpu as paddle
    from paddle_tpu.inference import ServingConfig, ServingEngine
    from paddle_tpu.jit.api import compile_cache_misses
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=256, num_layers=2,
                    num_heads=2, max_position_embeddings=512,
                    intermediate_size=512)
    m = GPTForCausalLM(cfg)
    # f32 deliberately: the static reference stores scores in the MODEL
    # dtype (bf16 under .to("bfloat16")) while the paged kernel always
    # keeps f32 scores — bit-exact greedy comparison needs both sides in
    # the same numerics class. bf16 KERNEL numerics are covered by the
    # kernel_parity sweeps above.
    m.eval()
    CAP, NEW = 64, 16
    lens = [64, 17, 3, 40, 1, 33]
    rng = np.random.RandomState(1)
    ids = rng.randint(1, cfg.vocab_size, (len(lens), CAP)).astype(np.int64)
    for r, ln in enumerate(lens):
        ids[r, ln:] = 0
    ref = m.generate_static_ragged(paddle.to_tensor(ids), lens,
                                   max_new_tokens=NEW).numpy()[:, CAP:]
    eng = ServingEngine(m, ServingConfig(max_batch=2, prompt_cap=CAP,
                                         max_new_tokens=NEW,
                                         decode_chunk=4,
                                         kv_block=16))
    eng.submit(ids[0, :lens[0]])
    eng.drain()
    miss0 = compile_cache_misses()
    for i in range(len(lens)):
        eng.submit(ids[i, :lens[i]])
    done = eng.drain()
    ok = all(r.status == "done" for r in done)
    for r in done:
        row = next(i for i in range(len(lens))
                   if np.array_equal(ids[i, :lens[i]], r.prompt))
        ok = ok and np.array_equal(r.tokens, ref[row])
    check("paged engine greedy == generate_static_ragged", ok)
    check("steady mixed-length loop: zero jit cache misses",
          compile_cache_misses() - miss0 == 0,
          f"recompiles={eng.monitor.recompiles}")


def sharded_engine_parity(shards):
    """Sharded-parity mode (ISSUE 16): greedy output bit-identical at
    shards=1 vs shards=N on the chip mesh, head-sharded pools, zero
    steady jit cache misses on the sharded engine. The collective
    inventory itself is proven statically by
    `tools/graph_lint.py gpt-paged-sharded`; this checks the numerics
    on real chips."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import ServingConfig, ServingEngine
    from paddle_tpu.jit.api import compile_cache_misses
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    ndev = len(jax.devices())
    check(f"--shards {shards}: enough local devices", shards <= ndev,
          f"({ndev} available)")
    if shards > ndev:
        return
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=256, num_layers=2,
                    num_heads=max(4, shards),  # divisible head count
                    max_position_embeddings=512,
                    intermediate_size=512)
    m = GPTForCausalLM(cfg)
    m.eval()                     # f32: same numerics-class note as above
    CAP, NEW = 64, 16
    lens = [64, 17, 3, 40]
    rng = np.random.RandomState(1)
    ids = rng.randint(1, cfg.vocab_size, (len(lens), CAP)).astype(np.int64)

    def serve(s):
        eng = ServingEngine(m, ServingConfig(
            max_batch=2, prompt_cap=CAP, max_new_tokens=NEW,
            decode_chunk=4, kv_block=16, shards=s))
        for i, ln in enumerate(lens):
            eng.submit(ids[i, :ln])
        eng.drain()
        miss0 = compile_cache_misses()
        for i, ln in enumerate(lens):
            eng.submit(ids[i, :ln])
        toks = {tuple(r.prompt.tolist()): list(r.tokens)
                for r in eng.drain()}
        return eng, toks, compile_cache_misses() - miss0

    _, one, _ = serve(1)
    eng, got, miss = serve(shards)
    check(f"sharded (mp={shards}) greedy == single-chip greedy",
          one == got)
    specs = {str(getattr(p.sharding, "spec", None))
             for layer in eng._pools for p in layer}
    check("pools carry the mp head sharding",
          all("'mp'" in s for s in specs), f"specs={sorted(specs)}")
    check("steady sharded loop: zero jit cache misses", miss == 0,
          f"recompiles={eng.monitor.recompiles}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", type=int, default=None, metavar="N",
                    help="also run the sharded-parity suite on an "
                         "N-chip mp mesh (ISSUE 16)")
    args = ap.parse_args()
    dev = jax.devices()[0]
    print("device:", dev)
    if dev.platform != "tpu":
        print("no TPU — run this on the chip (CPU CI covers interpret "
              "mode)")
        sys.exit(2)
    kernel_parity(jnp.float32, nh=4, hd=64, bs=16, tol=2e-5)
    kernel_parity(jnp.bfloat16, nh=16, hd=128, bs=16, tol=2e-2)
    kernel_parity(jnp.bfloat16, nh=12, hd=64, bs=32, tol=2e-2)
    kernel_ragged_parity(jnp.bfloat16, tol=2e-2)
    kernel_ragged_parity(jnp.float32, tol=2e-4)
    # multi-token (ISSUE 11): k=1 degenerate, boundary-start, boundary-
    # crossing windows, serving-scale geometry + a wide prefill window
    # float32 pools: the multi-token kernel's dots take the MXU's default
    # precision (one bf16 pass: 1.8e-3 on the v5e, PR 27), unlike the
    # decode walk's; ROADMAP S3 owns that kernel
    kernel_prefix_parity(jnp.float32, nh=4, hd=64, bs=16, s=1,
                         starts=(40, 16, 0), tol=5e-3)
    kernel_prefix_parity(jnp.float32, nh=4, hd=64, bs=16, s=8,
                         starts=(16, 13, 0), tol=5e-3)
    kernel_prefix_parity(jnp.bfloat16, nh=16, hd=128, bs=16, s=8,
                         starts=(32, 5, 0), tol=2e-2)
    kernel_prefix_parity(jnp.bfloat16, nh=16, hd=128, bs=16, s=64,
                         starts=(16, 0, 7), tol=2e-2)
    engine_parity()
    spec_engine_parity()
    if args.shards:
        sharded_engine_parity(args.shards)
    if FAILED:
        print(f"{len(FAILED)} validation(s) FAILED: {FAILED}")
        sys.exit(1)
    print("all paged serving validations passed")


if __name__ == "__main__":
    main()
