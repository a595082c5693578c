"""Hybrid-parallel GPT training on a device mesh.

Needs eight devices. With JAX_PLATFORMS=cpu these are eight virtual CPU
devices (the flag below only shapes the CPU backend, and has to be set
before jax is imported); on a TPU slice the same code uses the real chips.
Usage:
    JAX_PLATFORMS=cpu PYTHONPATH=. python examples/train_gpt_sharded.py
"""
import os

if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import numpy as np
import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.jit.train_step import TrainStep
from paddle_tpu.models import GPTConfig, GPTForCausalLM, GPTPretrainingCriterion


def main():
    mesh = dist.build_mesh({"dp": 2, "sdp": 2, "mp": 2})
    dist.set_mesh(mesh)
    paddle.seed(0)

    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    intermediate_size=256)
    model = GPTForCausalLM(cfg)          # TP layers annotate mp shardings
    crit = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    dist.shard_optimizer_state(opt, stage=1, axis="sdp")   # ZeRO-1

    step = TrainStep(model, opt, lambda ids, lbl: crit(model(ids), lbl),
                     mesh=mesh, data_axes=("dp",))
    rng = np.random.RandomState(0)
    for i in range(10):
        ids = paddle.to_tensor(rng.randint(0, 256, (8, 32)).astype("int32"))
        loss = step(ids, ids)
        if i % 3 == 0:
            print(f"step {i}: loss={float(loss):.4f} "
                  f"mesh={dict(mesh.shape)}")


if __name__ == "__main__":
    main()
