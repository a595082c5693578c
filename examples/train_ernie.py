"""ERNIE-3.0-class toolkit entrypoint (BASELINE.md config table row 5).

Pretrain-style masked-LM + sequence-classification fine-tune on synthetic
data through the SAME fused TrainStep path the flagship uses. Runs on CPU
in under a minute with the tiny default config; pass a preset name for the
real sizes on a TPU host.

Usage: PYTHONPATH=. python examples/train_ernie.py [ernie-3.0-medium]
Runs on whatever platform JAX selects (JAX_PLATFORMS=cpu for a dry run).
"""
import sys

import numpy as np
import paddle_tpu as paddle


def main():
    from paddle_tpu.models import (ErnieForMaskedLM,
                                   ErnieForSequenceClassification,
                                   ernie_config)
    paddle.seed(0)
    rng = np.random.RandomState(0)

    if len(sys.argv) > 1:
        cfg = ernie_config(sys.argv[1])
        B, S, steps = 8, 512, 20
    else:  # CPU-fast toy config, same code path
        cfg = ernie_config("ernie-3.0-medium", hidden_size=128, num_layers=2,
                           num_heads=2, vocab_size=512,
                           max_position_embeddings=128)
        B, S, steps = 4, 64, 10

    # --- 1) MLM pretrain step (fused chunked loss, no [B,S,V] logits) ---
    mlm = ErnieForMaskedLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=mlm.parameters())
    step = paddle.jit.TrainStep(
        mlm, opt, lambda ids, lbl: mlm.loss(ids, lbl, chunk_size=min(S, 256)))
    ids = rng.randint(0, cfg.vocab_size, (1, B, S)).astype("int32")
    lbl = rng.randint(0, cfg.vocab_size, (1, B, S)).astype("int64")
    losses = step.run_steps(steps, paddle.to_tensor(np.repeat(ids, steps, 0)),
                            paddle.to_tensor(np.repeat(lbl, steps, 0)))
    l = losses.numpy()
    print(f"ERNIE MLM: loss {l[0]:.4f} -> {l[-1]:.4f} over {steps} steps")
    assert np.isfinite(l).all() and l[-1] < l[0]

    # --- 2) sequence-classification fine-tune (toy separable task) ------
    cls = ErnieForSequenceClassification(cfg, num_classes=2)
    copt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                  parameters=cls.parameters())
    import paddle_tpu.nn as nn
    ce = nn.CrossEntropyLoss()
    cstep = paddle.jit.TrainStep(cls, copt,
                                 lambda ids, y: ce(cls(ids), y))
    # label = whether token 7 appears in the first 8 positions
    cids = rng.randint(0, cfg.vocab_size, (steps, B, S)).astype("int32")
    cy = (cids[:, :, :8] == 7).any(-1).astype("int64")
    closs = cstep.run_steps(steps, paddle.to_tensor(cids),
                            paddle.to_tensor(cy)).numpy()
    print(f"ERNIE cls fine-tune: loss {closs[0]:.4f} -> {closs[-1]:.4f}")
    assert np.isfinite(closs).all()
    print("OK")


if __name__ == "__main__":
    main()
