"""Eager + fused-step training example (seconds, on any platform).

Usage: PYTHONPATH=. python examples/train_eager.py
Runs on whatever platform JAX selects (JAX_PLATFORMS=cpu for a dry run).
"""
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn


def main():
    paddle.seed(0)
    X = np.random.randn(512, 16).astype("float32")
    Y = (np.sin(X[:, :1]) + X[:, 1:2] ** 2).astype("float32")

    model = nn.Sequential(nn.Linear(16, 64), nn.GELU(), nn.Linear(64, 1))
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=model.parameters())
    loader = paddle.io.DataLoader(
        paddle.io.TensorDataset([paddle.to_tensor(X), paddle.to_tensor(Y)]),
        batch_size=64, shuffle=True)

    # eager loop: per-op dispatch, loss.backward() on the tape
    for epoch in range(3):
        for xb, yb in loader:
            loss = nn.MSELoss()(model(xb), yb)
            loss.backward()
            opt.step()
            opt.clear_grad()
        print(f"eager epoch {epoch}: loss={float(loss):.4f}")

    # fused path: the whole step (fwd+bwd+optimizer) is one XLA program
    step = paddle.jit.TrainStep(model, opt,
                                lambda x, y: nn.MSELoss()(model(x), y))
    for i in range(20):
        loss = step(paddle.to_tensor(X[:64]), paddle.to_tensor(Y[:64]))
    print(f"fused step final loss={float(loss):.4f}")


if __name__ == "__main__":
    main()
