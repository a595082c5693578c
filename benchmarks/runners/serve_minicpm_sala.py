"""Serve runner of the MiniCPM-SALA configurations: the model of
paddle_tpu.models.minicpm_sala behind the same `ServingEngine(paged=True,
prefix_cache=True)`, the same open loop, window, warm-up and sample as
runners/serve.py (taken from it by import). Its own are the model's
construction from the configuration's keys, the weights (made a layer at
a time: the cut is 10.1 GB), the sparse layers' and the state planes'
counters, and the comparison with benchmarks.reference_minicpm_sala that
decides `correct`.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks import harness
from benchmarks import reference_minicpm_sala as R
from benchmarks import weights_minicpm_sala as W
from benchmarks.runners import serve as S
from benchmarks.runners.serve import prepare, release, sample_of  # noqa: F401
# the two compared numbers and their limits are the expert cell's: the
# widest gap (an altered token) and the mean gap over the served tokens
# (the arithmetic: precision, a block left out, a stale state)
from benchmarks.runners.serve_pangu_moe import compared_gaps

# the engine's own counters of the state planes, read beside the model's
ENGINE_COUNTERS = ("state_snapshots_taken", "state_snapshots_restored",
                   "state_snapshot_evictions", "prefix_match_cut_tokens")


def model_config(config: dict):
    from paddle_tpu.models.minicpm_sala import MiniCPMSALAConfig
    c = W.sizes(config)
    return MiniCPMSALAConfig(
        vocab_size=c["V"], hidden_size=c["H"], mixer_types=c["mixers"],
        num_heads=c["nh"], num_kv_heads=c["nkv"], head_dim=c["hd"],
        lightning_heads=c["lnh"], lightning_head_dim=c["lhd"],
        intermediate_size=c["I"], rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        scale_emb=float(config["scale_emb"]),
        scale_depth=float(config["scale_depth"]),
        dim_model_base=config["dim_model_base"],
        published_layers=R.PUBLISHED_DEPTH,
        kernel_size=c["kernel"], kernel_stride=c["stride"],
        block_size=c["block"], topk=c["topk"], init_blocks=c["init_blocks"],
        window_size=c["window"], dense_len=c["dense_len"],
        initializer_range=config["initializer_range"],
        dtype=config["param_dtype"])


def install_weights(model, config: dict, seed: int) -> None:
    """The seed's weights, made a layer at a time, put where the program
    keeps its parameters. A layer's old arrays are let go before its new
    ones are made: beside a full pool there is no room for both."""
    params = dict(model.named_parameters())

    def replace(shapes: dict, make, layer: int = -1):
        held = {k: params.pop(W.program_name(k, layer)) for k in shapes}
        for k, p in held.items():
            if tuple(p.shape) != tuple(shapes[k]):
                raise ValueError(f"{W.program_name(k, layer)}: program "
                                 f"{p.shape}, benchmark {shapes[k]}")
            p._data = p._node = None
        for k, a in make().items():
            held[k]._data = a
    c = W.sizes(config)
    replace({k: fn(c) for k, (fn, _) in W.TOP_LEAVES.items()},
            lambda: W.make_top_only(config, seed))
    for i in range(config["num_hidden_layers"]):
        replace(W.layer_leaves(config, i),
                lambda i=i: W.make_one_layer(config, seed, i), i)
    if params:
        raise ValueError(f"program leaves the benchmark did not make: "
                         f"{sorted(params)}")


def build(cell, seed: int):
    import paddle_tpu as paddle
    from paddle_tpu.inference import ServingConfig, ServingEngine
    from paddle_tpu.models.minicpm_sala import MiniCPMSALAForCausalLM
    from paddle_tpu.nn import initializer

    paddle.seed(seed % (2 ** 31))
    with initializer.fast_init():
        model = MiniCPMSALAForCausalLM(model_config(cell.config))
    install_weights(model, cell.config, seed)
    model.eval()
    eng = ServingEngine(
        model, ServingConfig(paged=True, prefix_cache=True,
                             **cell.settings["engine"]),
        clock=time.perf_counter)
    return model, eng


def set_up(cell, seed: int, rec) -> dict:
    model, eng = build(cell, seed)
    harness.say("model and engine built")
    return {"model": model, "eng": eng, "cell": cell, "seed": seed}


def _counters(state) -> dict:
    s = state["eng"].summary()
    return {k: float(s.get(f"{k}_total") or 0)
            for k in state["model"].step_counter_names + ENGINE_COUNTERS}


def window(state: dict, seconds: float, rec) -> dict:
    """runners/serve.py's window, and what the sparse layers, the state
    planes and the snapshots counted from its first step to its last
    answer."""
    before = _counters(state)
    out = S.window(state, seconds, rec)
    for k, v in _counters(state).items():
        rec.counters[f"serve/{k}"] = v - before[k]
    return out


def sample_gaps(cell, seed, sample, mode="f32", control=False) -> dict:
    """The gaps of the sample's served tokens under the reference's best
    logit: every sequence padded to the engine's longest row, one pass
    each. `widest` over all served tokens with `where`, their `mean`,
    `tokens`. With `control` the tokens are those the reference in `mode`
    puts first at the same positions."""
    import jax.numpy as jnp
    config, engine = cell.config, cell.settings["engine"]
    width = int(engine["prompt_cap"]) + int(engine["max_new_tokens"])
    cap = int(engine["max_new_tokens"])
    out = {"widest": 0.0, "mean": 0.0, "tokens": 0, "where": ""}
    total = 0.0
    for j, (prompt, tokens) in enumerate(sample):
        ids = np.zeros((width,), np.int32)
        seq = np.concatenate([prompt, tokens])[:width]
        ids[:len(seq)] = seq
        n = min(len(tokens), cap, width - len(prompt) + 1)
        tok = np.zeros((cap,), np.int32)
        tok[:n] = tokens[:n]
        args = (config, seed, jnp.asarray(ids), jnp.int32(len(prompt)),
                jnp.asarray(tok), jnp.int32(n))
        g, logits = R.served_gaps(*args)
        if control:
            g, _ = R.served_gaps(*args, mode=mode, rank_by=logits)
        g = np.asarray(g)[:n]
        if not np.isfinite(g).all():
            return dict(out, widest=float("inf"), mean=float("inf"),
                        where=f"request {j}")
        if n and g.max() >= out["widest"]:
            out["widest"], out["where"] = float(g.max()), \
                f"request {j} token {int(g.argmax())}"
        total += float(g.sum(dtype=np.float64))
        out["tokens"] += n
    out["mean"] = total / max(out["tokens"], 1)
    return out


def check(cell, seed: int, state: dict, out: dict) -> dict:
    t0 = time.perf_counter()
    if not state["sample"]:
        gaps = {"widest": float("inf"), "mean": float("inf"), "tokens": 0,
                "where": "no request finished"}
    else:
        gaps = sample_gaps(cell, seed, state["sample"])
        harness.say(f"reference: {len(state['sample'])} requests, "
                    f"{gaps['tokens']} served tokens in "
                    f"{time.perf_counter() - t0:.1f}s; widest gap "
                    f"{gaps['widest']:.5f}, mean {gaps['mean']:.6f}")
    return {**compared_gaps(cell, gaps),
            "unanswered": {"value": float(out["failed"]), "limit": 0.0,
                           "ok": out["failed"] == 0}}
