"""Measured per-component step budgets via ABLATION (real step times).

On the runtime these budgets were taken on (r4-r5) the trace's device
lanes were XLA cost-model estimates (custom-calls read 0), so the only
falsifiable attribution was differential: time
the full training step, then variants with one component replaced by a
stand-in, on the same protocol (fused multi-step scan, host-read fence,
best of N). The delta IS that component's wall contribution, including
whatever overlap XLA does or does not achieve.

Usage:
    python tools/step_budget.py bert   # bert-base MLM B=32 S=512
    python tools/step_budget.py gpt    # gpt3-1.3b B=3 S=2048

Variants:
  full        — the bench step
  no_ce       — LM/MLM head + CE replaced by a mean() surrogate
  no_dropout  — dropout probabilities zeroed (bert only)
  no_attn     — attention context replaced by the value projection input
                (keeps every matmul EXCEPT the S^2 attention math)
  no_ln       — LayerNorm replaced by identity (gpt only; measures the
                mean/var reductions + normalize fwd+bwd)
  sgd_opt     — optimizer swapped for bare SGD (isolates AdamW moments)
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timed(step, iters, *args):
    losses = step.run_steps(iters, *args)
    _ = float(losses.numpy()[-1])
    best = float("inf")
    for _r in range(3):
        t0 = time.perf_counter()
        losses = step.run_steps(iters, *args)
        _ = float(losses.numpy()[-1])
        best = min(best, time.perf_counter() - t0)
    return best / iters * 1e3


def bert_budget():
    import paddle_tpu as paddle
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.models import BertForMaskedLM, bert_config

    B, S, iters = 32, 512, 8
    cfg = bert_config("bert-base", max_position_embeddings=512)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size,
                                       (iters, B, S)).astype("int32"))
    lbl = paddle.to_tensor(rng.randint(0, cfg.vocab_size,
                                       (iters, B, S)).astype("int64"))

    def build(loss_kind="full", drop=True):
        c = bert_config("bert-base", max_position_embeddings=512)
        if not drop:
            c.hidden_dropout = 0.0
            c.attention_dropout = 0.0
        paddle.seed(0)
        m = BertForMaskedLM(c)
        m.to(dtype="bfloat16")
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=m.parameters(),
                                     moment_dtype="bfloat16")
        if loss_kind == "full":
            fn = lambda a, b: m.loss(a, b, chunk_size=256)  # noqa: E731
        else:  # no_ce: encoder + mean surrogate (head+CE ablated)
            def fn(a, b):
                h = m.bert(a)
                if isinstance(h, (tuple, list)):
                    h = h[0]
                return (h.astype("float32") ** 2).mean()
        return TrainStep(m, opt, fn)

    rows = {}
    rows["full"] = timed(build(), iters, ids, lbl)
    rows["no_ce"] = timed(build("no_ce"), iters, ids, lbl)
    rows["no_dropout"] = timed(build(drop=False), iters, ids, lbl)
    print("\nbert-base MLM B=32 S=512 (ms/step):")
    for k, v in rows.items():
        print(f"  {k:12s} {v:8.2f}")
    print(f"  head+CE term      {rows['full'] - rows['no_ce']:8.2f}")
    print(f"  dropout term      {rows['full'] - rows['no_dropout']:8.2f}")


def gpt_budget():
    import paddle_tpu as paddle
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.models import GPTForCausalLM, gpt_config

    B, S, iters = 3, 2048, 8
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, 50304,
                                       (iters, B, S)).astype("int32"))

    def build(loss_kind="full", optimizer="adamw"):
        cfg = gpt_config("gpt3-1.3b", max_position_embeddings=2048)
        paddle.seed(0)
        m = GPTForCausalLM(cfg)
        m.to(dtype="bfloat16")
        if optimizer == "sgd":
            # bare SGD: p -= lr*g reads p+g, writes p — the delta vs
            # AdamW is the measured moment-state traffic + moment math
            opt = paddle.optimizer.SGD(learning_rate=1e-4,
                                       parameters=m.parameters())
        else:
            opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                         parameters=m.parameters(),
                                         moment_dtype="bfloat16")
        if loss_kind == "full":
            fn = lambda a, b: m.loss(a, b, chunk_size=512)  # noqa: E731
        else:
            def fn(a, b):
                h = m.gpt(a)
                return (h.astype("float32") ** 2).mean()
        return TrainStep(m, opt, fn)

    def timed_no_ln():
        # LayerNorm -> identity for the WHOLE build+run: measures the
        # LN mean/var reductions + normalize fwd+bwd as a real step delta
        # (residual adds and every matmul stay)
        from paddle_tpu.nn.layers.norm import LayerNorm
        orig = LayerNorm.forward
        LayerNorm.forward = lambda self, x: x
        try:
            return timed(build(), iters, ids, ids)
        finally:
            LayerNorm.forward = orig

    rows = {}
    rows["full"] = timed(build(), iters, ids, ids)
    rows["no_ce"] = timed(build("no_ce"), iters, ids, ids)
    rows["no_ln"] = timed_no_ln()
    rows["sgd_opt"] = timed(build(optimizer="sgd"), iters, ids, ids)
    print("\ngpt3-1.3b B=3 S=2048 (ms/step):")
    for k, v in rows.items():
        print(f"  {k:12s} {v:8.2f}")
    ce = rows["full"] - rows["no_ce"]
    # FLOP floor of the three head matmuls at the step's own dense-dot
    # efficiency (~90% of 197T measured on the flagship's big dots)
    flops = 3 * 2 * B * S * 2048 * 50304
    print(f"  head+CE term      {ce:8.2f}")
    print(f"  head matmul floor {flops / 197e12 * 1e3:8.2f} (at peak), "
          f"{flops / (0.9 * 197e12) * 1e3:8.2f} (at 90%)")
    print(f"  LayerNorm term    {rows['full'] - rows['no_ln']:8.2f}")
    print(f"  AdamW-vs-SGD term {rows['full'] - rows['sgd_opt']:8.2f}")


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "bert"
    (gpt_budget if which == "gpt" else bert_budget)()
