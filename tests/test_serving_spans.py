"""The engine step's host spans, as a profiler trace shows them.

`inference/serving.py` opens `jax.profiler.TraceAnnotation` spans around
each part of an engine step (its module docstring lists them); the
benchmark's per-layer metrics give every gap in the device's timeline to
the innermost span open over it (PERF.md section 3). That attribution
holds only while the spans form a tree: each inside its parent, no two
siblings overlapping, nothing outside a `serving/step`. The tests step a
toy engine under a real profiler session on the CPU and read the trace
back with `jax.profiler.ProfileData`: names and nesting, never a time.
"""
import glob
import os

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.jit.api import (DECODE_PROGRAM, PREFILL_PROGRAM,
                                PUT_FIRST_PROGRAM, STAGE_PROGRAM,
                                VERIFY_PROGRAM)
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

CAP, NEW = 8, 6

# span -> the spans that may be its innermost enclosing `serving/*` span
PARENTS = {
    "serving/step": {None},
    "serving/admit": {"serving/step"},
    "serving/prefill": {"serving/step"},
    "serving/prefill_launch": {"serving/prefill"},
    # a first token is read with the flight it was launched in: beside
    # the chunk's read, or (speculative engine) straight after the launch
    "serving/prefill_read": {"serving/decode", "serving/step"},
    "serving/decode_prep": {"serving/step"},
    "serving/decode": {"serving/step"},
    "serving/decode_launch": {"serving/decode"},
    "serving/decode_read": {"serving/decode"},
    "serving/deliver": {"serving/step"},
    "serving/bookkeep": {"serving/step"},
}
# a collection of the host's garbage collector comes when it comes: under
# any span of the step, or between two steps
PARENTS["serving/gc"] = set(PARENTS) | {None}


@pytest.fixture(scope="module")
def served_model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                    num_heads=4, max_position_embeddings=96,
                    intermediate_size=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m, cfg


def _traced_spans(tmp_path, run):
    """[(name, start_ns, end_ns)] of the `serving/*` events of each host
    thread that wrote any, from a profiler session around `run()`."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert found, "the profiler wrote no trace"
    data = jax.profiler.ProfileData.from_file(found[-1])
    threads = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            ev = [(e.name, float(e.start_ns),
                   float(e.start_ns + e.duration_ns))
                  for e in line.events if e.name.startswith("serving/")]
            if ev:
                threads.append(ev)
    return threads


def _tree(spans):
    """[(name, start, end, parent index or None)] by containment."""
    order = sorted(spans, key=lambda s: (s[1], -(s[2] - s[1])))
    out, stack = [], []
    for name, a, b in order:
        while stack and out[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            assert b <= out[stack[-1]][2], \
                f"{name} leaves {out[stack[-1]][0]} before it closes"
        out.append((name, a, b, stack[-1] if stack else None))
        stack.append(len(out) - 1)
    return out


def _check_tree(threads, must_have):
    assert len(threads) == 1, "the engine steps on the caller's thread"
    tree = _tree(threads[0])
    names = {t[0] for t in tree}
    assert names <= set(PARENTS), names - set(PARENTS)
    assert must_have <= names | {"serving/gc"}, must_have - names
    children = {}
    for name, a, b, parent in tree:
        parent_name = tree[parent][0] if parent is not None else None
        assert parent_name in PARENTS[name], (name, parent_name)
        children.setdefault(parent, []).append((a, b, name))
    for sibs in children.values():
        sibs.sort()
        for (_, b0, n0), (a1, _, n1) in zip(sibs, sibs[1:]):
            assert a1 >= b0, f"{n0} and {n1} overlap"
    return tree


def _prompts(cfg, lens, seed=3):
    rng = np.random.RandomState(seed)
    shared = rng.randint(1, cfg.vocab_size, (CAP,)).astype(np.int64)
    out = []
    for i, ln in enumerate(lens):
        p = shared[:ln].copy()
        if i % 2:                   # every other prompt leaves the prefix
            p[-1] = (p[-1] % (cfg.vocab_size - 1)) + 1
        out.append(p)
    return out


@pytest.mark.parametrize("kw", [
    # the benchmark's serving cells: prefix cache on, chunked prefill on
    dict(prefix_cache=True, prefill_chunk=4),
    # one-shot prefill is one window of the whole suffix
    dict(prefix_cache=True),
    # a speculative window takes the plain chunk's children, and every
    # call is read before the next is launched
    dict(prefix_cache=True, prefill_chunk=4, spec_decode=True, spec_k=3),
    # no trie: every admission prefills its whole prompt
    dict(),
], ids=["chunked-prefill", "one-shot-prefill", "spec-decode", "no-trie"])
def test_paged_step_span_tree(served_model, tmp_path, kw):
    m, cfg = served_model
    eng = ServingEngine(m, ServingConfig(
        max_batch=2, prompt_cap=CAP, max_new_tokens=NEW, decode_chunk=2,
        kv_block=4, kv_blocks=96, **kw))
    prompts = _prompts(cfg, [CAP, CAP, 5, CAP, 3])
    eng.submit(prompts[0])
    eng.drain()                     # compile outside the traced steps
    launched0 = dict(eng.metrics.programs_launched)

    def run():
        for p in prompts:
            eng.submit(p)
        done = []
        while eng.busy:
            done += eng.step()
        assert len(done) == len(prompts)
        assert all(r.status == "done" for r in done)

    tree = _check_tree(_traced_spans(tmp_path, run), set(PARENTS))
    steps = [t for t in tree if t[0] == "serving/step"]
    decodes = [i for i, t in enumerate(tree) if t[0] == "serving/decode"]
    assert len(steps) >= len(decodes) >= 2
    # one launch in every call; every chunk launched is read once (the
    # run starts idle and ends drained), a prefill only after its final
    # window; whatever was read is delivered
    count = {n: sum(t[0] == n for t in tree) for n in PARENTS}
    assert count["serving/decode_launch"] == count["serving/decode_read"] \
        == count["serving/decode_prep"] >= 2
    assert count["serving/prefill_launch"] == count["serving/prefill"]
    assert 0 < count["serving/prefill_read"] <= count["serving/prefill"]
    # (a speculative step delivers its prefills and its window apart)
    assert 0 < count["serving/deliver"] <= \
        len(steps) * (2 if kw.get("spec_decode") else 1)
    assert count["serving/bookkeep"] == len(steps)
    # the link from a host span to the device's program: every
    # `*_launch` span enclosed exactly one launch of its program, so the
    # k-th span of a trace caused the k-th `jit_serve_*` event of the
    # device's `XLA Modules` line (a speculative window's is the verify
    # program, or the plain chunk where no row had a draft)
    launched = {k: v - launched0.get(k, 0)
                for k, v in eng.metrics.programs_launched.items()}
    assert launched[PREFILL_PROGRAM] == count["serving/prefill_launch"]
    assert launched.get(DECODE_PROGRAM, 0) + launched.get(VERIFY_PROGRAM, 0) \
        == count["serving/decode_launch"]
    assert launched[STAGE_PROGRAM] == count["serving/decode_prep"]
    # a final window's first token is put where the next chunk picks it
    assert count["serving/prefill_read"] <= launched[PUT_FIRST_PROGRAM] \
        <= launched[PREFILL_PROGRAM]
    if not kw.get("spec_decode"):
        assert VERIFY_PROGRAM not in launched
    # `serving/decode` encloses "launch this step's chunk, then read the
    # step before's": where it has both, the launch comes first, and the
    # plain engine has steps with both (the read waits under a busy chip)
    both = 0
    for d in decodes:
        kids = sorted((a, n) for n, a, b, parent in tree if parent == d)
        names = [n for _, n in kids]
        if "serving/decode_launch" in names and \
                "serving/decode_read" in names:
            both += 1
            assert names.index("serving/decode_launch") \
                < names.index("serving/decode_read")
    assert both >= 2
    if kw.get("spec_decode"):
        assert both == len(decodes)


def test_a_collection_is_a_span_while_an_engine_is_open(served_model,
                                                        tmp_path):
    """`serving/gc` from a collection's start to its stop: a gap the
    pause left in the device's timeline gets a name of its own."""
    import gc
    m, cfg = served_model
    eng = ServingEngine(m, ServingConfig(
        max_batch=2, prompt_cap=CAP, max_new_tokens=NEW, decode_chunk=2,
        kv_block=4))
    eng.submit(_prompts(cfg, [5])[0])
    eng.drain()

    def run():
        eng.submit(_prompts(cfg, [5])[0])
        eng.step()
        gc.collect()
        eng.drain()

    before = eng.metrics.counters["host_gc_pauses"]
    names = [n for th in _traced_spans(tmp_path, run) for n, _, _ in th]
    eng.close()
    # (a collection while the session opens or closes is counted, unseen)
    assert 1 <= names.count("serving/gc") \
        <= eng.metrics.counters["host_gc_pauses"] - before


def test_request_n_produced_counts_delivered_tokens(served_model):
    """`Request.n_produced` is the public face of the engine's running
    count: 0 while queued and while its first launches are unread, rising
    by what each landing delivered, equal to the budget at the end."""
    m, cfg = served_model
    eng = ServingEngine(m, ServingConfig(
        max_batch=2, prompt_cap=CAP, max_new_tokens=NEW, decode_chunk=2,
        kv_block=4))
    req = eng.submit(_prompts(cfg, [5])[0])
    assert req.n_produced == 0
    seen = []
    while eng.busy:
        eng.step()
        seen.append(req.n_produced)
    # the first step only launches (prefill and a chunk); the second
    # reads both
    assert seen == sorted(seen) and seen[0] == 0 and seen[1] >= 1
    assert seen[-1] == req.n_produced == len(req.tokens) == NEW
    with pytest.raises(AttributeError):
        req.n_produced = 3
