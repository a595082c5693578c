"""PP-YOLOE-class toolkit entrypoint (BASELINE.md config table row 5).

Trains the detector (task-aligned assignment + DFL + varifocal loss) on a
synthetic two-box dataset and runs decode (static-shape masked NMS) —
the full train->eval->decode loop a detection-toolkit user runs. CPU-fast
with the lite preset; `ppyoloe-s` on a TPU host.

Usage: PYTHONPATH=. python examples/train_ppyoloe.py [ppyoloe-s]
Runs on whatever platform JAX selects (JAX_PLATFORMS=cpu for a dry run).
"""
import sys

import numpy as np
import paddle_tpu as paddle


def main():
    from paddle_tpu.vision.models import (yolo_lite, ppyoloe_s, ppyoloe_m,
                                          ppyoloe_l, yolo_loss)
    paddle.seed(0)
    rng = np.random.RandomState(0)

    presets = {"ppyoloe-s": ppyoloe_s, "ppyoloe-m": ppyoloe_m,
               "ppyoloe-l": ppyoloe_l}
    if len(sys.argv) > 1 and sys.argv[1].startswith("ppyoloe"):
        if sys.argv[1] not in presets:
            raise SystemExit(f"unknown preset {sys.argv[1]!r}; "
                             f"choose from {sorted(presets)}")
        model = presets[sys.argv[1]](num_classes=80)
        B, H, steps = 8, 640, 20
    else:
        model = yolo_lite(num_classes=3, width=8)
        B, H, steps = 2, 64, 10
    cfg = model.config

    imgs = rng.randn(B, 3, H, H).astype("float32") * 0.1
    # synthetic ground truth: two boxes per image
    gt_boxes = np.stack([
        np.array([[H * .1, H * .1, H * .5, H * .5],
                  [H * .4, H * .4, H * .9, H * .8]], np.float32)
        for _ in range(B)])
    gt_labels = rng.randint(0, cfg.num_classes, (B, 2)).astype("int64")
    gt_mask = np.ones((B, 2), np.float32)

    opt = paddle.optimizer.Adam(learning_rate=2e-3,
                                parameters=model.parameters())
    losses = []
    for i in range(steps):
        outs = model(paddle.to_tensor(imgs))
        loss = yolo_loss(outs, paddle.to_tensor(gt_boxes),
                         paddle.to_tensor(gt_labels),
                         paddle.to_tensor(gt_mask), cfg)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    print(f"PP-YOLOE train: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"over {steps} steps")
    assert np.isfinite(losses).all() and losses[-1] < losses[0]

    model.eval()
    dets = model.decode(paddle.to_tensor(imgs), score_thresh=0.0, max_dets=10)
    boxes, scores, classes = dets[0]
    print(f"decode: {len(scores)} detections on image 0 "
          f"(top score {float(scores[0]) if len(scores) else 0:.3f})")
    print("OK")


if __name__ == "__main__":
    main()
