"""The one-pass 8-bit AdamW update stores what XLA's flat form stores.

`ops/pallas/q8_moments.py` updates a `[R, C]` leaf and its coded moments
in the parameter's own layout; `optimizer._q8_unblock` -> the update ->
`optimizer._q8_block`, viewing each moment as `[nb, 2048]`, stays the
reference. Both must give the same parameter, codes and scales to the bit:
the benchmark reads the stored state in the flat order
(`benchmarks/reference.py` `Q8`). The kernel runs in interpret mode here;
`tests/test_chip_compile.py` compiles it for the chip.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (x64 on, as every user process has it)
from paddle_tpu.optimizer import optimizer as O
from paddle_tpu.ops.pallas import q8_moments as K

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bits(a):
    a = np.asarray(a)
    return a.view(np.int32) if a.dtype == np.float32 else a


def _moment(shape, seed):
    """Float32 values over ~8 decades, signed, as Adam's moments spread."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape) * np.exp(3 * rng.standard_normal(shape))
    return jnp.asarray((x * 1e-3).astype(np.float32))


@pytest.mark.parametrize("shape,tile", [
    ((2560, 10240), 32), ((10240, 2560), 128), ((2560, 7680), 128),
    ((2560, 2560), 128), ((50304, 2560), 128), ((392, 2560), None),
    ((64, 10240), 32), ((2560,), None), ((100, 2560), None),
    ((64, 200), None), ((3, 2048), None)])
def test_row_tile_takes_two_dimensional_whole_blocks(shape, tile):
    """Cell 3's leaves all take the kernel; 1-D leaves, lanes that are not
    whole vregs and row counts that no tile of whole blocks and whole
    bfloat16 sublanes (16 rows) divides keep XLA's form."""
    assert K.row_tile(shape) == tile


# The one-pass update against the flat form's whole update, two steps from
# coded moments over ~8 decades, in children without FMA instructions
# (`--xla_cpu_max_isa=AVX`): XLA's CPU backend contracts multiply-adds
# where its fusions allow, and the interpreted kernel fuses differently;
# without them both compute each value by the same IEEE ops. Rows are cut
# from the 2.7B model's leaves to two or three tiles.
_ONE_PASS = [(96, 10240), (384, 2560), (256, 7680), (256, 2560),
             (640, 2560), (512, 2560), (64, 10240), (32, 2560), (128, 2560)]
_UPDATE_CHILD = r"""
import functools, json, sys
import numpy as np
import jax, jax.numpy as jnp
import paddle_tpu
from paddle_tpu.optimizer import optimizer as O
from paddle_tpu.ops.pallas import q8_moments as K

K.adamw = functools.partial(K.adamw, interpret=True)

def leaves(shape, cls, kernel):
    O.on_tpu = lambda: kernel
    opt = getattr(O, cls)(learning_rate=1e-3, beta2=0.95,
                          moment_dtype="int8", parameters=[])
    rng = np.random.default_rng(sum(shape))
    x = rng.standard_normal(shape) * np.exp(3 * rng.standard_normal(shape))
    v = np.abs(rng.standard_normal(shape)) * 1e-6
    mq, ms = O._q8_encode(jnp.asarray((x * 1e-3).astype(np.float32)))
    vq, vs = O._q8v_encode(jnp.asarray(v.astype(np.float32)))
    st = {"moment1_q": mq, "moment1_s": ms, "moment2_q": vq, "moment2_s": vs}
    p = jnp.asarray(rng.standard_normal(shape) * 0.02, jnp.bfloat16)
    upd = jax.jit(lambda p, g, st, lr, t: opt.update(p, g, st, lr, t, 0.1))
    for t in (3, 4):
        g = jnp.asarray(rng.standard_normal(shape) * 1e-2, jnp.bfloat16)
        p, st = upd(p, g, st, jnp.float32(1e-3), jnp.int32(t))
    return {"param": p, **st}

out = {}
for shape, cls in json.loads(sys.argv[1]):
    flat, kern = (leaves(tuple(shape), cls, k) for k in (False, True))
    out["%dx%d/%s" % (shape[0], shape[1], cls)] = {
        k: [str(kern[k].dtype), list(kern[k].shape),
            int((np.asarray(flat[k]).view(np.uint8)
                 != np.asarray(kern[k]).view(np.uint8)).sum())]
        for k in flat}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def one_pass_leaves():
    """Every case of `_ONE_PASS` for Adam and AdamW, in three children at
    once: {"RxC/cls": {leaf: [dtype, shape, bytes that differ]}}."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_cpu_max_isa=AVX")
    cases = [(s, c) for s in _ONE_PASS for c in ("AdamW", "Adam")]
    kids = [subprocess.Popen(
        [sys.executable, "-c", _UPDATE_CHILD, json.dumps(cases[i::3])],
        env=env, cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True) for i in range(3)]
    res = {}
    try:
        for kid in kids:
            out, err = kid.communicate(timeout=600)
            assert kid.returncode == 0, err[-4000:]
            res.update(json.loads(out.strip().splitlines()[-1]))
    finally:
        for kid in kids:
            kid.kill()
    return res


@pytest.mark.parametrize("shape", _ONE_PASS,
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("cls", ["AdamW", "Adam"])
def test_one_pass_update_stores_the_flat_forms_bits(one_pass_leaves, shape,
                                                    cls):
    """Two updates of a bfloat16 leaf through `pallas_q8_adamw` (interpret
    mode) leave the parameter, both moments' codes and their scales as the
    flat form's decode -> update -> encode does, bit for bit, in the stored
    form (`[nb, 2048]` int8 / uint8, `[nb]` float32). For C = 2560 and 7680
    a block straddles two rows; (32, 2560) is one tile of the whole leaf."""
    got = one_pass_leaves["%dx%d/%s" % (shape + (cls,))]
    nb = shape[0] * shape[1] // K.BLOCK
    assert got == {"param": ["bfloat16", list(shape), 0],
                   "moment1_q": ["int8", [nb, K.BLOCK], 0],
                   "moment1_s": ["float32", [nb], 0],
                   "moment2_q": ["uint8", [nb, K.BLOCK], 0],
                   "moment2_s": ["float32", [nb], 0]}
    assert K.row_tile(shape) is not None


def test_codec_choice_follows_the_leaf(monkeypatch):
    """On the chip a 2-D leaf that `row_tile` takes gets the kernel; a
    1-D leaf falls back, and so does every leaf under a mesh that splits
    something. Off the chip everything is flat."""
    import paddle_tpu.distributed as dist
    assert O.q8_codec((2560, 10240)) == "flat"
    monkeypatch.setattr(O, "on_tpu", lambda: True)
    assert O.q8_codec((2560, 10240)) == "kernel"
    assert O.q8_codec((2560,)) == "flat"
    assert O.q8_codec((100, 2560)) == "flat"
    mesh = dist.build_mesh({"dp": 2}, devices=jax.devices()[:2])
    dist.set_mesh(mesh)
    try:
        assert O.q8_codec((2560, 10240)) == "flat"
    finally:
        dist.set_mesh(None)
    # a 1-D leaf through the optimizer's own functions: the flat form
    v = _moment((2560,), seed=7)
    q, s = O._q8v_encode(v)
    fq, fs = O._q8_block(jnp.sqrt(jnp.maximum(v, 0.0)), True)
    assert (_bits(q) == _bits(fq)).all() and (_bits(s) == _bits(fs)).all()


# A whole training step, kernel path on and off, in a child process: XLA's
# CPU backend contracts multiply-adds into FMAs where its fusions allow,
# and the two paths fuse the update differently; without FMA instructions
# (`--xla_cpu_max_isa=AVX`) both compute each value by the same IEEE ops,
# as the chip does.
_CHILD = r"""
import functools, json, sys
import numpy as np
import jax
import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.optimizer import optimizer as O
from paddle_tpu.ops.pallas import q8_moments as K
from paddle_tpu.profiler.monitor import StepMonitor

def run(kernel):
    if kernel:
        O.on_tpu = lambda: True
        K.adamw = functools.partial(ADAMW, interpret=True)
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(vocab_size=256, hidden_size=128,
                                 num_layers=2, num_heads=4,
                                 max_position_embeddings=64,
                                 intermediate_size=512))
    opt = paddle.optimizer.AdamW(learning_rate=1e-2, beta2=0.95,
                                 weight_decay=0.1,
                                 parameters=m.parameters(),
                                 moment_dtype="int8")
    mon = StepMonitor(track_memory=False)
    step = paddle.jit.TrainStep(m, opt,
                                lambda a, b: m.loss(a, b, chunk_size=16),
                                monitor=mon)
    rng = np.random.default_rng(1)
    losses = []
    for _ in range(3):
        ids = paddle.to_tensor(rng.integers(0, 256, (2, 32)).astype("int64"))
        losses.append(float(step(ids, ids)))
    leaves = [np.asarray(p._data).view(np.uint8).tobytes()
              for p in step._params]
    for st in step._opt_state:
        leaves += [np.asarray(st[k]).view(np.uint8).tobytes()
                   for k in sorted(st)]
    return losses, leaves, step.q8_codec_leaves, mon.report()

ADAMW = K.adamw
flat = run(False)
kern = run(True)
print(json.dumps({
    "losses": [flat[0], kern[0]],
    "leaves": len(flat[1]),
    "differ": [i for i, (a, b) in enumerate(zip(flat[1], kern[1])) if a != b],
    "codec": [flat[2], kern[2]],
    "report": [kern[3]["q8_kernel_leaves"], kern[3]["q8_flat_leaves"]]}))
"""


def test_adamw_int8_steps_are_the_same_on_either_path():
    """Three AdamW steps of a two-layer GPT with 8-bit moments: the kernel
    path (interpret mode) leaves parameters and every state array as the
    flat path does, bit for bit, and the step counts its leaves by path."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_cpu_max_isa=AVX")
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["losses"][0] == res["losses"][1]
    assert res["leaves"] > 0 and res["differ"] == [], res
    flat, kern = res["codec"]
    # wte, wpe, and per layer qkv, out, fc1, fc2: 2-D; biases and norms 1-D
    assert flat == {"kernel": 0, "flat": flat["flat"]} and flat["flat"] > 12
    assert kern["kernel"] == 2 + 2 * 4
    assert kern["kernel"] + kern["flat"] == flat["flat"]
    assert res["report"] == [kern["kernel"], kern["flat"]]


@pytest.mark.parametrize("shape", [(64, 10240), (2560,), (3, 5)])
def test_zero_state_is_what_the_codec_stores_for_zeros(shape):
    """`Adam.init_state` makes the 8-bit state as zeros directly; that is
    what encoding a zero moment stores, codes and scales alike."""
    st = O.AdamW(moment_dtype="int8", parameters=[]).init_state(
        jnp.zeros(shape, jnp.bfloat16))
    z = jnp.zeros(shape, jnp.float32)
    for (kq, ks), (q, s) in ((("moment1_q", "moment1_s"), O._q8_encode(z)),
                             (("moment2_q", "moment2_s"), O._q8v_encode(z))):
        assert st[kq].dtype == q.dtype and st[kq].shape == q.shape
        assert (_bits(st[kq]) == _bits(q)).all()
        assert (_bits(st[ks]) == _bits(s)).all()
