"""Serve runner of the Jamba configurations: the model of
paddle_tpu.models.jamba behind the same `ServingEngine(paged=True,
prefix_cache=True)`, the same open loop, window, warm-up and sample as
runners/serve.py (taken from it by import). Its own are the model's
construction from the configuration's keys, the weights (made a layer at
a time: the model is 6.06 GB) and the comparison with
benchmarks.reference_jamba that decides `correct`; the counters of the
layers and of the state planes reach the record through
runners/serve_minicpm_sala.py's `window`.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks import harness
from benchmarks import reference_jamba as R
from benchmarks import weights_jamba as W
from benchmarks.runners.serve import prepare, release, sample_of  # noqa: F401
# the window with the model's and the state planes' counters copied into
# the record is the other state model's, as it stands: it asks the model
# for its counters' names
from benchmarks.runners.serve_minicpm_sala import (  # noqa: F401
    ENGINE_COUNTERS, window)
# the two compared numbers and their limits are the expert cell's: the
# widest gap (an altered token) and the mean gap over the served tokens
# (the arithmetic: precision, a stale or lost state)
from benchmarks.runners.serve_pangu_moe import compared_gaps

def model_config(config: dict):
    from paddle_tpu.models.jamba import JambaConfig
    c = W.sizes(config)
    return JambaConfig(
        vocab_size=c["V"], hidden_size=c["H"], num_layers=c["L"],
        attn_layer_period=config["attn_layer_period"],
        attn_layer_offset=config["attn_layer_offset"],
        num_heads=c["nh"], num_kv_heads=c["nkv"], head_dim=c["hd"],
        intermediate_size=c["I"], d_state=c["N"], d_conv=c["K"],
        expand=config["mamba_expand"], dt_rank=c["R"],
        rms_norm_eps=config["rms_norm_eps"],
        initializer_range=config["initializer_range"],
        dtype=config["param_dtype"])


def install_weights(model, config: dict, seed: int) -> None:
    """The seed's weights, made a layer at a time, put where the program
    keeps its parameters, every shape checked against the program's leaf.
    A layer's old arrays are let go before its new ones are made."""
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
    from paddle_tpu.models.jamba import JambaForCausalLM
    from paddle_tpu.nn import initializer

    paddle.seed(seed % (2 ** 31))
    with initializer.fast_init():
        model = JambaForCausalLM(model_config(cell.config))
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
