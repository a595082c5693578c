"""Train → export → serve: the deployment path.

Usage: PYTHONPATH=. python examples/deploy_inference.py
Runs on whatever platform JAX selects (JAX_PLATFORMS=cpu for a dry run).
"""
import tempfile

import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import inference


def main():
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 4))
    model.eval()

    prefix = tempfile.mkdtemp() + "/model"
    # dynamic batch dim -> one artifact serves any batch size
    paddle.jit.save(model, prefix,
                    input_spec=[paddle.jit.InputSpec([None, 8], "float32")])

    config = inference.Config(prefix)
    predictor = inference.create_predictor(config)
    print("inputs:", predictor.get_input_names())
    for bs in (1, 5, 17):
        (out,) = predictor.run([np.random.randn(bs, 8).astype("float32")])
        print(f"batch {bs}: output {out.shape}")


if __name__ == "__main__":
    main()
