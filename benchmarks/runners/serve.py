"""Serve runner: `ServingEngine(paged=True, prefix_cache=True)`, greedy,
behind an open loop (the construction chip_smoke.py:serve_phase proved, at
a deployment's envelope). One thread submits what is due and steps the
engine, as tools/serve_bench.py does; how late each submission was is
recorded, and a request's clock starts when it was due.

After the window closes the engine is stepped until every request that
arrived has its answer (a minute at most): an answer that comes late is
late, not wrong. Then a sample of the finished requests, drawn from the
seed with the longest in it, is judged by the plain reference: how far
each served token's logit lies under the reference's best at its position.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks import harness
from benchmarks import reference as R
from benchmarks.runners.train import gpt_config, install_weights

DRAIN_LIMIT_S = 60.0


def build(cell, seed: int):
    import paddle_tpu as paddle
    from paddle_tpu.inference import ServingConfig, ServingEngine
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.nn import initializer

    config = cell.config
    paddle.seed(seed % (2 ** 31))
    with initializer.fast_init():
        model = GPTForCausalLM(gpt_config(config, {}))
    if config["param_dtype"] != "float32":
        model.to(dtype=config["param_dtype"])
    install_weights(model, config, seed)
    model.eval()
    eng = ServingEngine(
        model, ServingConfig(paged=True, prefix_cache=True,
                             **cell.settings["engine"]),
        clock=time.perf_counter)
    return model, eng


def warm_up(eng, traffic: dict, vocab: int) -> None:
    """Every executable the traffic can reach, on short budgets: a full
    prefill, a block-aligned repeat (copy-on-write), a mid-prefix
    divergence (suffix prefill), each through a decode chunk; then each
    system prompt once, so the window starts with the prefix cache as a
    running service has it."""
    cfg = eng.config
    bs = cfg.kv_block
    aligned = max(bs, min(cfg.prompt_cap, 8 * bs) // bs * bs)
    rng = np.random.default_rng(7)
    p = rng.integers(1, vocab, aligned).astype(np.int64)
    d = p.copy()
    d[bs:] = rng.integers(1, vocab, aligned - bs)
    budget = min(cfg.max_new_tokens, cfg.decode_chunk + 2)

    def serve(prompt):
        r = eng.submit(prompt, max_new_tokens=budget)
        if r.status == "rejected":
            raise RuntimeError(f"warm-up request refused: {r.reason}")
        eng.drain()
    for prompt in (p, p, d):
        serve(prompt)
    harness.say("warm-up: prefill, copy-on-write, suffix prefill and "
                "decode have run")
    for s in traffic["systems"]:
        serve(np.concatenate([s, s[:3]]))
    harness.say(f"warm-up: {len(traffic['systems'])} system prompts cached")


def set_up(cell, seed: int, rec) -> dict:
    model, eng = build(cell, seed)
    harness.say("model and engine built")
    return {"model": model, "eng": eng, "cell": cell, "seed": seed}


def _submit_due(eng, reqs, i, t0, now, rec, live):
    while i < len(reqs) and reqs[i]["at"] <= now:
        r = reqs[i]
        handle = eng.submit(r["prompt"], max_new_tokens=r["max_new_tokens"],
                            enqueue_at=t0 + r["at"])
        rec.sample("arrival_lag_s", now - r["at"])
        live.append((r, handle))
        i += 1
    return i


def window(state: dict, seconds: float, rec) -> dict:
    import jax
    cell, eng = state["cell"], state["eng"]
    if "traffic" not in state:
        # the traffic of this window; warm-up needs its system prompts, so
        # set-up ends here, inside the harness's set-up clock (see run.py)
        raise RuntimeError("serve.prepare() was not called")
    reqs = state["traffic"]["requests"]
    handles: list = []
    i = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        i = _submit_due(eng, reqs, i, t0, now, rec, handles)
        if eng.busy:
            with rec.span("serve/step"):
                eng.step()
            fill = eng.metrics.gauges.get("batch_fill_ratio")
            if fill is not None:
                rec.sample("batch_fill", float(fill))
        elif i < len(reqs):
            time.sleep(max(0.0, min(reqs[i]["at"] - now, 0.002)))
        else:
            time.sleep(0.001)
    window_s = time.perf_counter() - t0
    i = _submit_due(eng, reqs, i, t0, window_s, rec, handles)
    closed_tokens = sum(
        h.n_out if h.status == "done" else int(getattr(h, "_produced", 0))
        for _, h in handles)
    # the window is closed; late answers are waited for and counted late
    with jax.profiler.TraceAnnotation("bench/drain"):
        while eng.busy and time.perf_counter() - t0 < seconds + DRAIN_LIMIT_S:
            eng.step()
    total_s = time.perf_counter() - t0
    state["handles"] = handles
    ttft, tpot, queue, failed = [], [], [], 0
    for r, h in handles:
        due = t0 + r["at"]
        tr = h.trace
        if h.status != "done" or tr.t_first_token is None:
            failed += 1
            ttft.append(total_s - r["at"])
            tpot.append(total_s - r["at"])
            continue
        ttft.append(tr.t_first_token - due)
        if h.n_out >= 2:
            tpot.append((tr.t_finish - tr.t_first_token) / (h.n_out - 1))
        if tr.t_admit is not None:
            queue.append(tr.t_admit - due)
    rec.samples["ttft_s"] = ttft
    rec.samples["tpot_s"] = tpot
    rec.samples["queue_wait_s"] = queue
    summary = eng.summary()
    for k in ("prefix_hit_total", "prefix_miss_total",
              "prefill_tokens_saved_total"):
        rec.counters[f"serve/{k}"] = float(summary.get(k) or 0) \
            - state["base_summary"].get(k, 0.0)
    done = [(int(h.prompt_len), int(h.n_out)) for _, h in handles
            if h.status == "done"]
    sys_len = int(cell.traffic["system_len"]) // eng.config.kv_block \
        * eng.config.kv_block
    hits = rec.counters["serve/prefix_hit_total"]
    rec.counters["serve/prompt_tokens_computed"] = sum(
        p for p, _ in done) - rec.counters["serve/prefill_tokens_saved_total"]
    rec.counters["serve/output_tokens"] = sum(n for _, n in done)
    rec.counters["serve/prefill_pairs"] = sum(
        p * (p + 1) / 2 for p, _ in done) - hits * sys_len * (sys_len + 1) / 2
    rec.counters["serve/decode_steps"] = sum(max(n - 1, 0) for _, n in done)
    rec.counters["serve/decode_kv_rows"] = sum(
        (n - 1) * (p + 1) + (n - 1) * (n - 2) / 2 for p, n in done if n > 1)
    rec.counters["serve/window_output_tokens"] = closed_tokens
    rec.counters["serve/total_s"] = total_s
    rec.counters["serve/requests"] = len(handles)
    rec.counters["serve/unfinished_at_close"] = sum(
        1 for _, h in handles if h.status != "done"
        or h.trace.t_finish - t0 > window_s)
    fills = rec.samples.get("batch_fill") or [0.0]
    harness.say(
        f"{len(handles)} requests, {failed} unanswered, "
        f"{int(rec.counters['serve/unfinished_at_close'])} unfinished at the "
        f"close, last answer at {total_s:.1f}s; ttft p50/p95 "
        f"{1e3 * harness.percentile(ttft, 50):.0f}/"
        f"{1e3 * harness.percentile(ttft, 95):.0f} ms, tpot p50/p95 "
        f"{1e3 * harness.percentile(tpot, 50):.1f}/"
        f"{1e3 * harness.percentile(tpot, 95):.1f} ms, "
        f"{closed_tokens / window_s:.0f} tokens/s in the window, arrival "
        f"lag p95 "
        f"{1e3 * harness.percentile(rec.samples['arrival_lag_s'], 95):.0f} "
        f"ms, batch fill {sum(fills) / len(fills):.2f}")
    return {"window_s": window_s, "attempted": len(handles),
            "failed": failed,
            "end_to_end": {
                "ttft_p95_ms": 1e3 * harness.percentile(ttft, 95),
                "tpot_p95_ms": 1e3 * harness.percentile(tpot, 95),
                "serve_tokens_per_s": closed_tokens / window_s}}


def prepare(state: dict, seconds: float) -> None:
    """The last part of set-up: this window's traffic from the seed, then
    the warm-up over its system prompts."""
    cell = state["cell"]
    state["traffic"] = cell.generator().make(
        cell.traffic, cell.config, state["seed"], seconds)
    vocab = int(cell.traffic.get("real_vocab", cell.config["vocab_size"]))
    warm_up(state["eng"], state["traffic"], vocab)
    s = state["eng"].summary()
    state["base_summary"] = {
        k: float(s.get(k) or 0) for k in (
            "prefix_hit_total", "prefix_miss_total",
            "prefill_tokens_saved_total")}


def release(state: dict) -> None:
    import jax
    state["sample"] = sample_of(state)
    for k in ("model", "eng", "handles", "traffic"):
        state.pop(k, None)
    gc.collect()
    jax.clear_caches()
    gc.collect()


def sample_of(state: dict) -> list:
    """(prompt, served tokens) of the sample, as host arrays."""
    return [(np.asarray(h.prompt), np.asarray(h.tokens)[:h.n_out])
            for h in pick_sample(state)]


def pick_sample(state: dict) -> list:
    """Finished requests drawn from the seed, the longest among them."""
    done = [h for _, h in state["handles"]
            if h.status == "done" and h.n_out >= 1]
    if not done:
        return []
    k = int(state["cell"].settings.get("check_requests", 6))
    rng = np.random.default_rng([int(state["seed"]), 0x636865636B])
    longest = max(done, key=lambda h: h.prompt_len + h.n_out)
    rest = [h for h in done if h is not longest]
    picks = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[j] for j in picks]


def sample_gaps(config, seed, sample, mode="f32", control=False):
    """Widest gap over the sample's served tokens; with `control`, the gap
    of the tokens that `mode` puts first, read in the float32 logits."""
    import jax.numpy as jnp
    width = config["max_position_embeddings"]
    widest, n_tokens, where = 0.0, 0, ""
    for j, (prompt, tokens) in enumerate(sample):
        n = len(tokens)
        ids = np.zeros((1, width), np.int32)
        seq = np.concatenate([prompt, tokens])[:width]
        ids[0, :len(seq)] = seq
        tok = np.zeros((min(512, width),), np.int32)
        n = min(n, tok.shape[0], width - len(prompt) + 1)
        tok[:n] = tokens[:n]
        args = (config, seed, jnp.asarray(ids), jnp.int32(len(prompt)),
                jnp.asarray(tok), jnp.int32(n))
        g, logits = R.served_gaps(*args)
        if control:
            g, _ = R.served_gaps(*args, mode=mode, rank_by=logits)
        g = np.asarray(g)
        if not np.isfinite(g).all():
            return float("inf"), n_tokens, f"request {j}"
        if g.max() >= widest:
            widest, where = float(g.max()), \
                f"request {j} token {int(g.argmax())}"
        n_tokens += n
    return widest, n_tokens, where


def check(cell, seed: int, state: dict, out: dict) -> dict:
    t0 = time.perf_counter()
    limit = float(cell.settings["limits"]["greedy_gap"])
    if not state["sample"]:
        return {"greedy_gap": {"value": float("inf"), "limit": limit,
                               "ok": False, "where": "no request finished"}}
    widest, n, where = sample_gaps(cell.config, seed, state["sample"])
    harness.say(f"reference: {len(state['sample'])} requests, {n} served "
                f"tokens in {time.perf_counter() - t0:.1f}s; widest gap "
                f"{widest:.5f}")
    return {
        "greedy_gap": {"value": widest, "limit": limit,
                       "ok": bool(widest <= limit), "where": where},
        "unanswered": {"value": float(out["failed"]), "limit": 0.0,
                       "ok": out["failed"] == 0}}
