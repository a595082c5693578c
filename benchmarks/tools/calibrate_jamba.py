"""Readings that the limits of a Jamba serving cell are set from: the
procedure is tools/calibrate_sala.py's (the cell's engine and load on the
chip, a short window per seed, every case through the runner's
`sample_gaps` and `compared_gaps` as `check` does); the faults are this
model's. One case per `--faults` name, planted in the program (`plant`)
and served again:

- `state_bf16`: the scan state rounded to bfloat16 between steps and
  between windows (the lower-precision control of the state planes: it has
  to fail);
- `snapshot`: a prefix's snapshot restored without its conv state (the
  scan state comes back, the convolution starts from zeros);
- `snapshot_scan`: restored without its scan state (the convolution's
  inputs come back, S starts from zero);
- `sign`: A = +exp(A_log) (the state grows where it should decay);
- `softplus`: dt = W_dt r + b_dt, no softplus;
- `norms`: the three inner RMSNorms of r, B and C left out;
- `token`: one served token in each sampled request replaced by its
  neighbour.

    python3 -m benchmarks.tools.calibrate_jamba --workload <cell> \
        --seeds 201,202,... --seconds 10 --control-seeds 1 \
        --faults state_bf16,snapshot,token --out ...
"""
from __future__ import annotations

import sys

from benchmarks.tools import calibrate_sala as base

FAULTS = ("state_bf16", "snapshot", "snapshot_scan", "sign", "softplus",
          "norms", "token")


def plant(model, eng, fault: str):
    """Plants `fault` in the program; returns the function that takes it
    out again. The model's compiled calls are dropped both times."""
    import jax.numpy as jnp
    from jax import lax
    from paddle_tpu.inference.kv_cache import STATE_LOAD
    from paddle_tpu.models import jamba as M
    undo = []

    def swap(obj, name, new):
        old = getattr(obj, name)
        setattr(obj, name, new)
        undo.append(lambda: setattr(obj, name, old))

    def scans(change):
        """Both forms of the scan called through `change(real, args)`."""
        for name in ("scan_step", "scan_window"):
            real = getattr(M, name)
            swap(M, name, lambda *a, real=real: change(real, a))

    if fault == "state_bf16":
        def rounded(real, a):
            # `reduce_precision`, not a pair of casts: XLA may drop a
            # float32 -> bfloat16 -> float32 round trip (it did, on the
            # chip: the fault read as the sound case to the last digit)
            y, s = real(*a)
            return y, lax.reduce_precision(s, exponent_bits=8,
                                           mantissa_bits=7)
        scans(rounded)
    elif fault == "sign":
        scans(lambda real, a: real(*a[:4], -a[4], *a[5:]))
    elif fault == "softplus":
        f32 = jnp.float32
        swap(M, "_time_step", lambda r, w, b: jnp.matmul(
            r, w.astype(f32), precision="highest") + b.astype(f32))
    elif fault == "norms":
        c = model.config
        inner, rms = {c.dt_rank, c.d_state}, M._rms
        swap(M, "_rms", lambda x, g, eps: x.astype(jnp.float32)
             if g.shape[0] in inner else rms(x, g, eps))
    elif fault in ("snapshot", "snapshot_scan"):
        pool, move = eng._pool, eng._pool.state_move
        n_paged = [len(layer) for layer in pool.layer_block_shapes]
        # a Mamba layer's planes: conv rows, conv snapshots, scan rows, ...
        lost = 0 if fault == "snapshot" else 2

        def without(pools, op, slot, snap):
            pools = move(pools, op, slot, snap)
            if op != STATE_LOAD:
                return pools
            return [tuple(a.at[slot].set(0.0) if j == n + lost
                          and len(layer) > n else a
                          for j, a in enumerate(layer))
                    for layer, n in zip(pools, n_paged)]
        swap(pool, "state_move", without)
    elif fault != "token":
        raise KeyError(fault)
    model._gen_static_cache = None

    def take_out():
        for u in undo:
            u()
        model._gen_static_cache = None
    return take_out


def main(argv=None) -> int:
    """calibrate_sala's procedure with this module's faults in its place
    (that file is the accepted benchmark's and is not edited: its `main`
    looks `plant` and `FAULTS` up in its own module)."""
    saved = base.plant, base.FAULTS
    base.plant, base.FAULTS = plant, FAULTS
    try:
        return base.main(argv)
    finally:
        base.plant, base.FAULTS = saved


if __name__ == "__main__":
    sys.exit(main())
