"""Train runner: `TrainStep.__call__` once per batch on `GPTForCausalLM.loss`,
the construction chip_smoke.py:_build_train proved on the chip (bf16
parameters, AdamW, the fused linear-CE loss at chunk_size=512), with the
configuration's weights made by the benchmark and a fresh batch each step.

Set-up builds the one compiled step with its state and drives it through
its first three steps, through the same `call_step` and feed the window
uses; the window goes on from step four with the same object. After the
window the plain reference follows those three steps from the same seed.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks import harness
from benchmarks import reference as R
from benchmarks import weights as W

CHECK_STEPS = 3


def gpt_config(config: dict, settings: dict):
    from paddle_tpu.models import GPTConfig
    return GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_layers"], num_heads=config["num_heads"],
        intermediate_size=config["intermediate_size"],
        max_position_embeddings=config["max_position_embeddings"],
        layer_norm_epsilon=config["layer_norm_epsilon"],
        initializer_range=config["initializer_range"],
        use_recompute=bool(settings.get("recompute_policy")),
        recompute_policy=(None if settings.get("recompute_policy") == "full"
                          else settings.get("recompute_policy")))


def install_weights(model, config: dict, seed: int) -> None:
    """The seed's weights, made in one jitted call, put where the program
    keeps its parameters."""
    top, layers = W.make_all(config, seed)
    params = dict(model.named_parameters())

    def put(name, arr):
        p = params.pop(name)
        if p._data is not None and tuple(p.shape) != tuple(arr.shape):
            raise ValueError(f"{name}: program {p.shape}, benchmark "
                             f"{arr.shape}")
        p._data = arr
        p._node = None
    for k, a in top.items():
        put(W.program_name(k), a)
    for i in range(config["num_layers"]):
        for k, a in layers.items():
            put(W.program_name(k, i), a[i])
    if params:
        raise ValueError(f"program leaves the benchmark did not make: "
                         f"{sorted(params)}")


def build(cell, seed: int):
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.nn import initializer

    config, settings = cell.config, cell.settings
    hp = config["optimizer"]
    mesh = None
    if settings.get("mesh"):
        n = int(np.prod(list(settings["mesh"].values())))
        mesh = dist.build_mesh(settings["mesh"], devices=jax.devices()[:n])
        dist.set_mesh(mesh)
    paddle.seed(seed % (2 ** 31))
    with initializer.fast_init():
        model = GPTForCausalLM(gpt_config(config, settings))
    if config["param_dtype"] != "float32":
        model.to(dtype=config["param_dtype"])
    install_weights(model, config, seed)
    opt = paddle.optimizer.AdamW(
        learning_rate=hp["learning_rate"], beta1=hp["beta1"],
        beta2=hp["beta2"], epsilon=hp["epsilon"],
        weight_decay=hp["weight_decay"], parameters=model.parameters(),
        moment_dtype=hp["moment_dtype"])
    kw = dict(mesh=mesh, data_axes=("dp",)) if mesh is not None else {}
    chunk = int(settings.get("loss_chunk_size", 512))
    step = TrainStep(model, opt,
                     lambda a, b: model.loss(a, b, chunk_size=chunk), **kw)
    return model, step, mesh


def call_step(step, ids, labels):
    """The one place a step is taken, in set-up and in the window alike."""
    import paddle_tpu as paddle
    return step(paddle.to_tensor(ids), paddle.to_tensor(labels))


def _program_tree(step, what: str, config: dict, layer: int) -> dict:
    """The program's leaves of one layer (or the top leaves), under the
    benchmark's names: parameters, or the optimizer's first moment."""
    by_name = {n: i for i, n in enumerate(step._param_names)}
    names = W.LAYER_LEAVES if layer >= 0 else W.TOP_LEAVES
    out = {}
    for k in names:
        i = by_name[W.program_name(k, layer)]
        if what == "param":
            out[k] = step._params[i]._data
        else:
            st = step._opt_state[i]
            out[k] = (st["moment1_q"], st["moment1_s"]) \
                if "moment1_q" in st else st["moment1"]
    return out


def _layers(config):
    return [-1] + list(range(config["num_layers"]))


def program_grad_norms(step, config) -> dict:
    return R.flatten_norms({
        i: R.grad_norms_from_moment(
            config, _program_tree(step, "moment", config, i),
            _program_tree(step, "param", config, i))
        for i in _layers(config)})


def program_change_norms(step, config, seed) -> dict:
    return R.flatten_norms({
        i: R.delta_norms(config, seed, i, _program_tree(
            step, "param", config, i)) for i in _layers(config)})


def set_up(cell, seed: int, rec) -> dict:
    model, step, mesh = build(cell, seed)
    feed = cell.generator().make(cell.traffic, cell.config, seed)
    first, losses = [], []
    grad_norms = None
    for i in range(CHECK_STEPS):
        ids, labels = feed.next()
        first.append((ids, labels))
        t0 = time.perf_counter()
        losses.append(float(call_step(step, ids, labels)))
        harness.say(f"step {i + 1}: loss {losses[-1]:.5f} "
                    f"({time.perf_counter() - t0:.2f}s)")
        if i == 0:
            grad_norms = program_grad_norms(step, cell.config)
    change = program_change_norms(step, cell.config, seed)
    return {"model": model, "step": step, "mesh": mesh, "feed": feed,
            "first": first, "losses": losses, "grad_norms": grad_norms,
            "change_norms": change, "cell": cell}


def window(state: dict, seconds: float, rec) -> dict:
    import jax
    step, feed = state["step"], state["feed"]
    read_every = int(state["cell"].settings.get("loss_read_every", 10))
    batch_tokens = None
    n, loss, last_read = 0, None, None
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        ids, labels = feed.next()
        batch_tokens = ids.size
        with rec.span("train/dispatch"), \
                jax.profiler.TraceAnnotation("bench/dispatch"):
            loss = call_step(step, ids, labels)
        n += 1
        if n % read_every == 0:
            with jax.profiler.TraceAnnotation("bench/loss_read"):
                last_read = float(loss)
    with jax.profiler.TraceAnnotation("bench/fence"):
        final = float(loss)                 # the fence: all steps are done
    elapsed = time.perf_counter() - t0
    tokens = n * batch_tokens
    rec.counters.update({"train/steps": n, "train/tokens": tokens})
    finite = bool(np.isfinite(final)) and (
        last_read is None or bool(np.isfinite(last_read)))
    harness.say(f"{n} steps, final loss {final:.4f}")
    return {"window_s": elapsed, "attempted": n,
            "failed": 0 if finite else n, "final_loss": final,
            "end_to_end": {"train_tokens_per_s": tokens / elapsed}}


def release(state: dict) -> None:
    import jax
    import paddle_tpu.distributed as dist
    if state.get("mesh") is not None:
        dist.set_mesh(None)
    for k in ("model", "step", "feed"):
        state.pop(k, None)
    gc.collect()
    jax.clear_caches()
    gc.collect()


def compare(program: dict, ref: dict, limits: dict) -> dict:
    """The numbers that decide `correct` for a training cell, each beside
    its limit. `program` and `ref` hold losses, grad_norms, change_norms."""
    loss_gap = max(abs(a - b) / max(abs(b), 1e-30)
                   for a, b in zip(program["losses"], ref["losses"]))
    if not all(np.isfinite(program["losses"])):
        loss_gap = float("inf")
    grad_gap, grad_at = R.leaf_gap(program["grad_norms"], ref["grad_norms"])
    still = R.still_leaves(ref["grad_norms"])
    change_gap, change_at = R.leaf_gap(
        program["change_norms"], ref["change_norms"], skip=still)
    out = {}
    for name, val, where in (("loss_gap", loss_gap, ""),
                             ("grad_norm_gap", grad_gap, grad_at),
                             ("change_norm_gap", change_gap, change_at)):
        if name in limits:
            out[name] = {"value": float(val), "limit": float(limits[name]),
                         "ok": bool(val <= limits[name]), "where": where}
    return out


def reference_run(config: dict, seed: int, batches, mode: str = "f32",
                  rows=None) -> dict:
    ref = R.TrainReference(config, seed, mode=mode, rows=rows)
    losses, grad_norms = [], None
    for i, (ids, labels) in enumerate(batches):
        loss, gn = ref.step(ids, labels)
        losses.append(loss)
        if i == 0:
            grad_norms = gn
    change = ref.change_norms()
    del ref
    gc.collect()
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def check(cell, seed: int, state: dict, out: dict) -> dict:
    t0 = time.perf_counter()
    ref = reference_run(cell.config, seed, state["first"])
    harness.say(f"reference: {CHECK_STEPS} steps in "
                f"{time.perf_counter() - t0:.1f}s; losses "
                + " ".join(f"{x:.5f}" for x in ref["losses"])
                + " against the program's "
                + " ".join(f"{x:.5f}" for x in state["losses"]))
    compared = compare(state, ref, cell.settings["limits"])
    finite = bool(np.isfinite(out["final_loss"]))
    compared["window_loss_finite"] = {
        "value": 0.0 if finite else 1.0, "limit": 0.0, "ok": finite}
    return compared
