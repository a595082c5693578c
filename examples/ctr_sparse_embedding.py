"""Industrial CTR slice: host-RAM sparse embedding PS + dense tower on
device, with optional GeoSGD async mode — the workflow the reference serves
with its brpc parameter server (SURVEY §2.2), redesigned TPU-first
(distributed/ps.py docstring).

Usage: PYTHONPATH=. python examples/ctr_sparse_embedding.py
Runs on whatever platform JAX selects (JAX_PLATFORMS=cpu for a dry run).
"""
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.distributed.ps import DistributedEmbedding, GeoSGDEmbedding


def main(geo: bool = False):
    paddle.seed(0)
    dim, vocab = 16, 100_000  # rows materialize on first touch — no 100k alloc
    emb_cls = GeoSGDEmbedding if geo else DistributedEmbedding
    kwargs = {"geo_step": 8} if geo else {"optimizer": "adagrad"}
    emb = emb_cls(dim=dim, num_shards=4, lr=0.05, **kwargs)

    tower = nn.Sequential(nn.Linear(3 * dim, 64), nn.ReLU(), nn.Linear(64, 1))
    opt = paddle.optimizer.Adam(parameters=tower.parameters(),
                                learning_rate=1e-3)
    bce = nn.BCEWithLogitsLoss()

    rng = np.random.RandomState(0)
    # synthetic CTR: 3 slots (user/item/context), click depends on item ids
    for step in range(60):
        ids = rng.zipf(1.5, (256, 3)).clip(0, vocab - 1).astype("int64")
        clicks = ((ids[:, 1] % 7) < 2).astype("float32").reshape(-1, 1)
        feats = emb(paddle.to_tensor(ids))                  # [256, 3, dim]
        x = paddle.reshape(feats, [256, 3 * dim])
        loss = bce(tower(x), paddle.to_tensor(clicks))
        loss.backward()
        opt.step()
        opt.clear_grad()
        if step % 20 == 0:
            print(f"step {step:3d}  loss {float(loss):.4f}  "
                  f"rows {emb.state_size()}")
    if geo:
        emb.sync()
    print(f"final loss {float(loss):.4f}; touched rows: {emb.state_size()} "
          f"of {vocab} (insert-on-touch)")


def main_heter(steps: int = 120, batch: int = 256):
    """Device-cached tier (distributed/heter.py — the HeterPS answer): hot
    rows live in HBM, prefetch overlaps admission with the step, and the
    only host traffic is the miss set. Prints measured throughput."""
    import time
    from paddle_tpu.distributed.heter import MeshShardedEmbedding

    paddle.seed(0)
    dim, vocab = 16, 100_000
    emb = MeshShardedEmbedding(dim=dim, capacity=1 << 13, lr=0.05)
    tower = nn.Sequential(nn.Linear(3 * dim, 64), nn.ReLU(), nn.Linear(64, 1))
    opt = paddle.optimizer.Adam(parameters=tower.parameters(),
                                learning_rate=1e-3)
    bce = nn.BCEWithLogitsLoss()
    rng = np.random.RandomState(0)

    def batch_ids():
        return rng.zipf(1.5, (batch, 3)).clip(0, vocab - 1).astype("int64")

    ids = batch_ids()
    warmup = min(19, max(0, steps - 2))
    t0 = None
    for step in range(steps):
        nxt = batch_ids()
        emb.prefetch(nxt)                      # overlap admission with step
        feats = emb(paddle.to_tensor(ids))
        x = paddle.reshape(feats, [batch, 3 * dim])
        clicks = ((ids[:, 1] % 7) < 2).astype("float32").reshape(-1, 1)
        loss = bce(tower(x), paddle.to_tensor(clicks))
        loss.backward()
        opt.step()
        opt.clear_grad()
        ids = nxt
        if step == warmup:
            t0 = time.perf_counter()           # skip warmup/compile
    dt = max(time.perf_counter() - t0, 1e-9)
    n = steps - warmup - 1
    print(f"heter tier: loss {float(loss):.4f}  rows {emb.state_size()} "
          f"(resident {emb.resident_rows()})  "
          f"{n * batch / dt:,.0f} examples/s  "
          f"{n * batch * 3 / dt:,.0f} lookups/s")


if __name__ == "__main__":
    print("== sync adagrad PS ==")
    main(geo=False)
    print("== GeoSGD async ==")
    main(geo=True)
    print("== device-cached heter tier ==")
    main_heter()
