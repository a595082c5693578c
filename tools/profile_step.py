"""Trace one training-step executable on TPU and print device-time tables.

Thin CLI over `paddle_tpu.profiler.trace_analysis` (where the
.trace.json.gz parser now lives): builds one of the model configs
(vit / bert / gpt / swin / resnet50), runs a few steps under
jax.profiler.trace, then prints the KernelView / DistributedView tables —
the only trustworthy per-component timing on remote-dispatch runtimes
(host-side timers measure dispatch, not device work).

Usage: python tools/profile_step.py vit [outdir]
"""
import os
import sys

sys.path.insert(0, ".")


def build_step(which):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.jit.train_step import TrainStep
    import paddle_tpu.nn as nn

    if which == "vit":
        from paddle_tpu.models import VisionTransformer, vit_config
        cfg = vit_config("vit-l16", image_size=224, num_classes=1000)
        paddle.seed(0)
        model = VisionTransformer(cfg)
        model.to(dtype="bfloat16")
        ce = nn.CrossEntropyLoss()
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters(),
                                     moment_dtype="bfloat16")
        step = TrainStep(model, opt, lambda x, y: ce(model(x), y))
        B = int(os.environ.get("PADDLE_TPU_BENCH_B", "32"))
        x = paddle.to_tensor(np.random.randn(4, B, 3, 224, 224)
                             .astype("bfloat16"))
        y = paddle.to_tensor(np.random.randint(0, 1000, (4, B))
                             .astype("int64"))
        return step, (x, y)
    if which == "bert":
        from paddle_tpu.models import BertForMaskedLM, bert_config
        cfg = bert_config("bert-base")
        paddle.seed(0)
        model = BertForMaskedLM(cfg)
        model.to(dtype="bfloat16")
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters(),
                                     moment_dtype="bfloat16")
        step = TrainStep(model, opt,
                         lambda ids, lbl: model.loss(ids, lbl,
                                                     chunk_size=256))
        B = int(os.environ.get("PADDLE_TPU_BENCH_B", "32"))
        rng = np.random.RandomState(0)
        ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (4, B, 512))
                               .astype("int32"))
        lbl = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (4, B, 512))
                               .astype("int64"))
        return step, (ids, lbl)
    if which == "gpt":
        from paddle_tpu.models import GPTForCausalLM, gpt_config
        preset = os.environ.get("PADDLE_TPU_BENCH_PRESET", "gpt3-1.3b")
        B = int(os.environ.get("PADDLE_TPU_BENCH_B", "3"))
        S = int(os.environ.get("PADDLE_TPU_BENCH_S", "2048"))
        cfg = gpt_config(preset, max_position_embeddings=max(1024, S))
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        model.to(dtype="bfloat16")
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters(),
                                     moment_dtype="bfloat16")
        step = TrainStep(model, opt,
                         lambda a, b: model.loss(a, b, chunk_size=512))
        rng = np.random.RandomState(0)
        ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (4, B, S))
                               .astype("int32"))
        return step, (ids, ids)
    if which in ("swin", "resnet50"):
        # shared imagenet-train harness; only constructor/opt/batch differ
        paddle.seed(0)
        if which == "swin":
            from paddle_tpu.vision.models import swin_t
            model, default_b = swin_t(num_classes=1000), 32
            opt_fn = lambda ps: paddle.optimizer.AdamW(  # noqa: E731
                learning_rate=1e-4, parameters=ps, moment_dtype="bfloat16")
        else:
            from paddle_tpu.vision.models import resnet50
            model, default_b = resnet50(num_classes=1000), 64
            opt_fn = lambda ps: paddle.optimizer.Momentum(  # noqa: E731
                learning_rate=0.1, parameters=ps)
        model.to(dtype="bfloat16")
        ce = nn.CrossEntropyLoss()
        opt = opt_fn(model.parameters())
        step = TrainStep(model, opt, lambda x, y: ce(model(x), y))
        B = int(os.environ.get("PADDLE_TPU_BENCH_B", str(default_b)))
        x = paddle.to_tensor(np.random.randn(4, B, 3, 224, 224)
                             .astype("bfloat16"))
        y = paddle.to_tensor(np.random.randint(0, 1000, (4, B))
                             .astype("int64"))
        return step, (x, y)
    raise SystemExit(f"unknown model {which}")


def aggregate(outdir, n_steps):
    """Parse + print the capture via profiler.trace_analysis."""
    from paddle_tpu.profiler import trace_analysis as ta
    path = ta.find_trace_file(outdir)
    if path is None:
        raise SystemExit(f"no trace files under {outdir}")
    an = ta.analyze(path, steps=n_steps)
    print(f"\ntrace: {path}")
    print(an.kernel_view())
    print()
    print(an.distributed_view())
    rows = [(r["name"], r["dur_us"]) for r in an.op_totals()]
    return rows, an.total_device_us()


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "vit"
    outdir = sys.argv[2] if len(sys.argv) > 2 else f"/tmp/trace_{which}"
    import jax
    step, args = build_step(which)
    losses = step.run_steps(4, *args)          # compile + warm
    _ = float(losses.numpy()[-1])
    n = 4
    jax.profiler.start_trace(outdir)
    losses = step.run_steps(n, *args)
    _ = float(losses.numpy()[-1])
    jax.profiler.stop_trace()
    aggregate(outdir, n)


if __name__ == "__main__":
    main()
