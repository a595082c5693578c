"""Main-path Pallas kernels compile for the chip, at GPT-1.3B widths.

The sandbox has no TPU, but it has the TPU's compiler: a described
`v5e:2x2` topology stands in for the device, and `lower().compile()`
raises what the chip's compiler would raise (Mosaic legalization, the
scoped-VMEM limit). Interpret-mode tests cannot see either, and both
have bitten: `paged_attention.py`'s i64 index-map literals and
`linear_ce.py`'s 16.98M tile plan passed every interpret test and were
refused by the chip. Nothing runs here; results are compared on the
chip by `chip_smoke.py`'s kernel phase.

The kernel entry points are called directly: their gates
(`ops/attention.py`, `use_linear_ce`) see the CPU in this process and
would route to the jnp reference, which compiles anywhere. Every case
asserts the `tpu_custom_call` is in the compiled text.

The topology is described inside a module-scoped fixture — never at
import: only one process may hold libtpu, and every xdist worker imports
every test file.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

import paddle_tpu  # noqa: F401  (turns x64 on, as every user process has it)
import paddle_tpu.distributed as dist
from paddle_tpu.ops import attention as attn
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import fused_mha as fm
from paddle_tpu.ops.pallas import fused_mha_bias as fmb
from paddle_tpu.ops.pallas import int8_matmul as i8
from paddle_tpu.ops.pallas import layer_norm as ln
from paddle_tpu.ops.pallas import linear_ce as lce
from paddle_tpu.ops.pallas import latent_attention as la
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas import selective_scan as pss

# GPT-1.3B geometry (models/gpt.py gpt3-1.3b) and chip_smoke.py's shapes
NH, HD, HIDDEN, VOCAB = 16, 128, 2048, 50304
TRAIN_B, TRAIN_S = 3, 2048
SERVE_B, KV_BLOCK, POOL_BLOCKS, TABLE_SLOTS = 8, 16, 1024, 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # conftest.py asks for 'highest' matmul precision so CPU results match
    # numpy; no user process on the chip has that, and Mosaic refuses an
    # fp32-precision contraction of bf16 tiles ("Bad lhs type")
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    yield topo
    jax.config.update("jax_default_matmul_precision", precision_was)
    jax.config.update("jax_enable_compilation_cache", cache_was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def mesh(topo):
    """dp2 x mp2 over the four described chips, active for the test."""
    m = dist.build_mesh({"dp": 2, "mp": 2}, devices=topo.devices)
    dist.set_mesh(m)
    yield m
    dist.set_mesh(None)


def _compile(fn, sharding, *shapes):
    """Compile fn for the described chip(s) from (shape, dtype) or
    (shape, dtype, sharding) triples and return the optimized HLO text."""
    args = [jax.ShapeDtypeStruct(s[0], s[1],
                                 sharding=s[2] if len(s) > 2 else sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text, *names):
    """Each name stands in the left-hand side of a `tpu_custom_call`
    instruction, wrapped by the transforms the kernel ran under
    (`%transpose_jvp_pallas_flash_dq__.1`): that left-hand side is what a
    device event in a trace is called, and what a metric looks for."""
    assert names
    lhs = [line.split(" = ", 1)[0] for line in text.splitlines()
           if 'custom_call_target="tpu_custom_call"' in line]
    assert len(lhs) >= len(names), "compiled without the Pallas kernel"
    for name in names:
        # the name whole: after a `%` or `_`, before underscores and `.<n>`
        # (benchmarks/readers/named_kernel_roofline.py looks for it so)
        whole = re.compile(r"[%_]" + name + r"_*\.")
        assert any(whole.search(l) for l in lhs), (name, lhs)


# ------------------------------------------------------------ flash attention
# cell 1's call, and cell 3's: 32 heads of 80, which the wrapper pads to
# the 128 lanes. At S = 2048 both run blocks of 1024 whose crossed ones are
# worked in strips (static slices of the block's refs).
_QKV_TRAIN = [pytest.param(((TRAIN_B, TRAIN_S, NH, HD), jnp.bfloat16),
                           id="heads16x128"),
              pytest.param(((TRAIN_B, TRAIN_S, 32, 80), jnp.bfloat16),
                           id="heads32x80")]


@pytest.mark.parametrize("qkv", _QKV_TRAIN)
def test_flash_attention_forward(one_chip, qkv):
    assert fa.sub_tile(fa.DEFAULT_BQ, fa.DEFAULT_BK) is not None
    text = _compile(lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
                    one_chip, qkv, qkv, qkv)
    _assert_kernel(text, fa.FWD_NAME)


@pytest.mark.parametrize("qkv", _QKV_TRAIN)
def test_flash_attention_backward(one_chip, qkv):
    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True)
        return out.astype(jnp.float32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    qkv, qkv, qkv)
    _assert_kernel(text, fa.FWD_NAME, fa.DQ_NAME, fa.DKV_NAME)


def _entry_instructions(text):
    """(opcode, result elements, line) of the entry computation's
    instructions: what the device runs one after the other (a fusion's
    inner instructions are not passes of their own)."""
    entry = text[text.index("\nENTRY "):]
    out = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) ([a-z\-]+)\(", line)
        if m:
            dims = re.findall(r"[a-z]+[0-9]+\[([\d,]*)\]", m.group(1))
            sizes = [math.prod(map(int, d.split(","))) if d else 1
                     for d in dims]
            out.append((m.group(2), max(sizes, default=0), line.strip()))
    return out


def _attention_layer_loss(nh, hd):
    """GPT's training attention between its two projections' matmuls, as
    the model has it: the packed projection in, the context reshaped for
    the output projection, whose product hands back a token-major
    cotangent."""
    from paddle_tpu.models import gpt

    def loss(qkv, w_out):
        ctx = gpt._qkv_attention(qkv, nh, hd)
        y = ctx.reshape(TRAIN_B, TRAIN_S, nh * hd) @ w_out
        return y.astype(jnp.float32).sum()
    return jax.value_and_grad(loss, argnums=(0, 1))


def test_qkv_attention_reads_heads_from_the_packed_projection(
        one_chip, monkeypatch):
    """Cell 1's attention layer, bf16[3, 2048, 6144] at 16 heads of 128:
    the three kernels take the projection itself three times, and no
    instruction of the compiled program copies, transposes, reshapes or
    slices an array of q's size or more. The packed gradient's assembly is
    XLA's: here one pass (a loop fusion, allowed below by its opcode); in
    the whole step it rides as an operand fusion of the projection's
    weight-gradient and input-gradient products and is no pass at all."""
    monkeypatch.setenv("PADDLE_TPU_FLASH", "1")      # the gate sees the CPU
    text = _compile(_attention_layer_loss(NH, HD), one_chip,
                    ((TRAIN_B, TRAIN_S, 3 * NH * HD), jnp.bfloat16),
                    ((NH * HD, HIDDEN), jnp.bfloat16))
    _assert_kernel(text, fa.FWD_NAME, fa.DQ_NAME, fa.DKV_NAME)
    calls = [line for op, _, line in _entry_instructions(text)
             if "tpu_custom_call" in line]
    assert len(calls) == 3
    for line in calls:
        args = re.search(r"custom-call\((.*?)\), custom_call_target",
                         line).group(1).split(", ")
        assert args[0] == args[1] == args[2], line  # q, k, v: one operand
    q_size = TRAIN_B * TRAIN_S * NH * HD
    moved = [line for op, n, line in _entry_instructions(text)
             if op in ("copy", "transpose", "reshape", "slice",
                       "concatenate", "pad") and n >= q_size]
    assert not moved, moved


def test_qkv_attention_at_heads_of_80_keeps_the_three_kernels(
        one_chip, monkeypatch):
    """Cell 3's layer, 32 heads of 80: padded to the lanes and laid
    head-major, bf16[3 * 32, 2048, 128], which to the same three kernels is
    the token-major form of one head a row."""
    monkeypatch.setenv("PADDLE_TPU_FLASH", "1")
    text = _compile(_attention_layer_loss(32, 80), one_chip,
                    ((TRAIN_B, TRAIN_S, 3 * 32 * 80), jnp.bfloat16),
                    ((32 * 80, 32 * 80), jnp.bfloat16))
    _assert_kernel(text, fa.FWD_NAME, fa.DQ_NAME, fa.DKV_NAME)
    calls = [line for _, _, line in _entry_instructions(text)
             if "tpu_custom_call" in line]
    assert len(calls) == 3
    assert all(f"bf16[{TRAIN_B * 32},{TRAIN_S},128]" in c for c in calls)


@pytest.mark.parametrize("nh,hd,operand", [
    (8, 256, f"bf16[{TRAIN_B},{TRAIN_S},{8 * 256}]"),       # token-major
    (8, 192, f"bf16[{TRAIN_B * 8},{TRAIN_S},256]")])        # padded: head-major
def test_qkv_attention_at_heads_over_the_lanes(one_chip, monkeypatch, nh, hd,
                                               operand):
    """Heads wider than the 128 lanes: the same three kernels on blocks as
    wide as the head, whole lanes of it (192 is padded to 256)."""
    monkeypatch.setenv("PADDLE_TPU_FLASH", "1")
    text = _compile(_attention_layer_loss(nh, hd), one_chip,
                    ((TRAIN_B, TRAIN_S, 3 * nh * hd), jnp.bfloat16),
                    ((nh * hd, nh * hd), jnp.bfloat16))
    _assert_kernel(text, fa.FWD_NAME, fa.DQ_NAME, fa.DKV_NAME)
    calls = [line for _, _, line in _entry_instructions(text)
             if "tpu_custom_call" in line]
    assert len(calls) == 3
    assert all(operand in c for c in calls)


# ------------------------------------------------------------------ linear CE
def _ce_shapes(t):
    return (((t, HIDDEN), jnp.bfloat16), ((VOCAB, HIDDEN), jnp.bfloat16),
            ((t,), jnp.int32))


# 6144 = the flagship B=3 S=2048 head; 6144 and 2048 are the two sizes the
# old (bt=1024, bv=256) plan was refused at
@pytest.mark.parametrize("t", [6144, 2048, 1024, 512])
def test_linear_ce_forward(one_chip, t):
    text = _compile(lambda x, w, l: lce.linear_cross_entropy(x, w, l),
                    one_chip, *_ce_shapes(t))
    _assert_kernel(text, lce.FWD_NAME)


def test_linear_ce_backward(one_chip):
    def loss(x, w, l):
        return lce.linear_cross_entropy(x, w, l).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1)), one_chip,
                    *_ce_shapes(TRAIN_B * TRAIN_S))
    _assert_kernel(text, lce.FWD_NAME)     # the backward is XLA's


@pytest.mark.parametrize("h,itemsize", [(768, 2), (1024, 2), (2048, 2),
                                        (2048, 4), (4096, 2), (5120, 4)])
def test_linear_ce_plan_stays_under_the_scoped_limit(h, itemsize):
    """The planner's own arithmetic, no compiler: whatever it picks for a
    width must fit its budget, and the refused plan must not."""
    bt = lce._pick_block_t(8192, h, itemsize)
    bv = lce._pick_block_v(bt, h, itemsize)
    assert lce._plan_bytes(bt, bv, h, itemsize) <= lce._VMEM_BUDGET
    assert lce._plan_bytes(1024, 256, 2048, 2) > 16 * 1024 * 1024


# ------------------------------------------------------------ paged attention
_POOL = ((POOL_BLOCKS, KV_BLOCK, NH, HD), jnp.bfloat16)
_CODES = ((POOL_BLOCKS, KV_BLOCK, NH, HD), jnp.int8)
_SCALES = ((POOL_BLOCKS, KV_BLOCK, NH), jnp.float32)
_TABLES = ((SERVE_B, TABLE_SLOTS), jnp.int32)
_ROWS = ((SERVE_B,), jnp.int32)


def _q(s):
    return ((SERVE_B, s, NH, HD), jnp.bfloat16)


def test_paged_decode(one_chip):
    text = _compile(pa.paged_attention_kernel, one_chip,
                    _q(1), _POOL, _POOL, _TABLES, _ROWS)
    _assert_kernel(text, pa.DECODE_NAME)


def test_paged_decode_at_the_serving_cells_geometry(one_chip):
    """B 32, a table of 128, 3,072 pages of 16 tokens, 16 heads of 128
    (benchmarks/workloads/serve-gpt3-1.3b-chat*.json). The by-shape
    metric (benchmarks/metrics/paged_attention_roofline.*.json) knows the
    kernel by what the compiled call shows: one result [B, heads, D], the
    tables first, the two pools last; the by-name one by DECODE_NAME."""
    b, mb, nb = 32, 128, 3072
    pool = ((nb, KV_BLOCK, NH, HD), jnp.bfloat16)
    text = _compile(pa.paged_attention_kernel, one_chip,
                    ((b, 1, NH, HD), jnp.bfloat16), pool, pool,
                    ((b, mb), jnp.int32), ((b,), jnp.int32))
    _assert_kernel(text, pa.DECODE_NAME)
    call, = [l for l in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    result, operands = re.match(
        r"\s*(?:ROOT )?%\S+ = (\S+) custom-call\((.*?)\), custom_call_target",
        call).groups()
    assert result.startswith(f"bf16[{b},{NH},{HD}]"), result
    shapes = dict(re.findall(r"(%\S+) = (\w+\[[\d,]*\])", text))
    operands = [shapes[o.strip()] if o.strip() in shapes else o.strip()
                for o in operands.split(", ")]
    assert operands[0].startswith(f"s32[{b},{mb}]"), operands
    pool_shape = f"bf16[{nb},{KV_BLOCK},{NH},{HD}]"
    assert [o[:len(pool_shape)] for o in operands[-2:]] == [pool_shape] * 2, \
        operands


@pytest.mark.parametrize("nh,hd", [(32, 80), (12, 64)])
def test_paged_decode_heads_no_dma_can_slice(one_chip, nh, hd):
    """2.7B's heads of 80 and 125M's 12 heads of 64 do not fill the
    (8, 128) tiles the pools are laid out in, and the compiler lets a DMA
    slice whole tiles only: those keep the grid over the table's slots."""
    assert not pa._pages_dma_sliceable(nh, hd)
    pool = ((POOL_BLOCKS, KV_BLOCK, nh, hd), jnp.bfloat16)
    text = _compile(pa.paged_attention_kernel, one_chip,
                    ((SERVE_B, 1, nh, hd), jnp.bfloat16), pool, pool,
                    _TABLES, _ROWS)
    _assert_kernel(text, pa.DECODE_NAME)


def test_paged_decode_int8(one_chip):
    text = _compile(pa.paged_attention_q8_kernel, one_chip,
                    _q(1), _CODES, _SCALES, _CODES, _SCALES, _TABLES, _ROWS)
    _assert_kernel(text, pa.DECODE_Q8_NAME)


# S=128: suffix prefill at prompt_cap; S=4: a speculative verify window
@pytest.mark.parametrize("s", [128, 4])
def test_paged_prefix(one_chip, s):
    text = _compile(pa.paged_prefix_attention_kernel, one_chip,
                    _q(s), _POOL, _POOL, _TABLES, _ROWS)
    _assert_kernel(text, pa.PREFIX_NAME)


@pytest.mark.parametrize("s", [128, 4])
def test_paged_prefix_int8(one_chip, s):
    text = _compile(pa.paged_prefix_attention_q8_kernel, one_chip,
                    _q(s), _CODES, _SCALES, _CODES, _SCALES, _TABLES, _ROWS)
    _assert_kernel(text, pa.PREFIX_Q8_NAME)


# ------------------------------------------------- kernels under the mesh
# Mosaic kernels are never partitioned automatically: without
# distributed.mesh.shard_kernel around them the dp x mp train step does not
# lower for real chips at all ("Please wrap the call in a shard_map").
def test_flash_under_dp_mp_mesh(mesh):
    sh = NamedSharding(mesh, P("dp", None, "mp", None))
    qkv = ((2, TRAIN_S, NH, HD), jnp.bfloat16, sh)

    def loss(q, k, v):
        out = attn._flash(q, k, v, causal=True, scale=None)
        return out.astype(jnp.float32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), None, qkv, qkv, qkv)
    _assert_kernel(text, fa.FWD_NAME, fa.DQ_NAME, fa.DKV_NAME)
    assert "all-gather" not in text and "all-reduce" not in text, \
        "batch rows and heads are independent: no collective belongs here"


def test_flash_qkv_under_dp_mesh(topo):
    """The packed projection under a mesh of dp alone (the route GPT takes
    it by: with an mp axis its heads go the [B, S, H, D] way above): each
    chip runs the kernels on its own batch rows, the lanes (q, k and v
    heads in turn) whole, and nothing is exchanged."""
    m = dist.build_mesh({"dp": 4}, devices=topo.devices)
    dist.set_mesh(m)
    try:
        sh = NamedSharding(m, P("dp", None, None))

        def loss(qkv):
            out = attn._flash_qkv(qkv, NH, causal=True, scale=None)
            return out.astype(jnp.float32).sum()

        text = _compile(jax.grad(loss), None,
                        ((4, TRAIN_S, 3 * NH * HD), jnp.bfloat16, sh))
    finally:
        dist.set_mesh(None)
    _assert_kernel(text, fa.FWD_NAME, fa.DQ_NAME, fa.DKV_NAME)
    assert f"bf16[1,{TRAIN_S},{3 * NH * HD}]" in text    # a chip's rows
    assert "all-gather" not in text and "all-reduce" not in text


def test_linear_ce_under_dp_mp_mesh(mesh):
    """Tokens over dp, the vocab-parallel embedding's rows over mp: the
    kernel runs on each shard's [T/2, H] x [V/2, H], W is never gathered,
    and the shards exchange only [T]-sized vectors."""
    t = 2 * TRAIN_S
    shapes = (((t, HIDDEN), jnp.bfloat16, NamedSharding(mesh, P("dp", None))),
              ((VOCAB, HIDDEN), jnp.bfloat16,
               NamedSharding(mesh, P("mp", None))),
              ((t,), jnp.int32, NamedSharding(mesh, P("dp"))))

    def loss(x, w, l):
        return lce.linear_cross_entropy(x, w, l).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1)), None, *shapes)
    _assert_kernel(text, lce.FWD_NAME)
    assert f"bf16[{VOCAB},{HIDDEN}]" not in text, "W was gathered whole"
    assert "all-gather" not in text


@pytest.mark.parametrize("s", [1, 128])
def test_paged_kernels_under_mp_mesh(mesh, s):
    """ServingConfig(shards=N): pools keep their heads over mp, each shard
    walks the block table over its own heads, nothing is gathered."""
    heads = NamedSharding(mesh, P(None, None, "mp", None))
    rep = NamedSharding(mesh, P())
    kernel = (pa.paged_attention_kernel if s == 1
              else pa.paged_prefix_attention_kernel)
    text = _compile(
        lambda q, k, v, t, rows: attn._paged_kernel(kernel, q, (k, v), t,
                                                    rows),
        None, _q(s) + (heads,), _POOL + (heads,), _POOL + (heads,),
        _TABLES + (rep,), _ROWS + (rep,))
    _assert_kernel(text, pa.DECODE_NAME if s == 1 else pa.PREFIX_NAME)
    assert "all-gather" not in text


# ------------------------------------- kernels of the other bench cells
# Not on chip_smoke.py's path (GPT trains and serves in bf16 without
# them), but they carry the bert, vit, swin and int8-decode cells and had
# never met this compiler either: Mosaic PRNG in fused_mha's dropout,
# SMEM scalars, the int8 tiles.
def test_fused_mha_with_dropout_backward(one_chip):
    def loss(qkv, seed):
        out = fm.fused_mha(qkv, 12, dropout_p=0.1, dropout_seed=seed)
        return out.astype(jnp.float32).sum()

    # the backward kernel works the forward out again from qkv, so the
    # forward kernel survives only where the value is asked for too
    text = _compile(jax.value_and_grad(loss), one_chip,
                    ((32, 512, 3 * 768), jnp.bfloat16), ((), jnp.int32))
    _assert_kernel(text, fm.FWD_NAME, fm.BWD_NAME)


def test_fused_mha_bias_backward(one_chip):
    def loss(qkv, bias):
        return fmb.fused_mha_bias(qkv, 3, bias).astype(jnp.float32).sum()

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1)), one_chip,
                    ((4096, 49, 3 * 96), jnp.bfloat16),
                    ((64, 3, 49, 49), jnp.float32))
    _assert_kernel(text, fmb.FWD_NAME, fmb.BWD_NAME)


@pytest.mark.parametrize("w_layout,wshape", [("kn", (HIDDEN, 4 * HIDDEN)),
                                             ("nk", (VOCAB, HIDDEN))])
def test_int8_matmul(one_chip, monkeypatch, w_layout, wshape):
    # the gate sees the CPU here and would return the XLA fallback, which
    # "passes" without a kernel: force it, as the assertion below checks
    monkeypatch.setenv("PADDLE_TPU_INT8_MATMUL", "1")
    n = wshape[1] if w_layout == "kn" else wshape[0]
    text = _compile(
        lambda x, q, s: i8.int8_matmul(x, q, s, w_layout=w_layout),
        one_chip, ((SERVE_B, HIDDEN), jnp.bfloat16), (wshape, jnp.int8),
        ((n,), jnp.float32))
    _assert_kernel(text, i8.KERNEL_NAME)


def test_layer_norm_backward(one_chip):
    def loss(x, g, b):
        return ln.fused_layer_norm(x, g, b).astype(jnp.float32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    ((TRAIN_B * TRAIN_S, HIDDEN), jnp.bfloat16),
                    ((HIDDEN,), jnp.bfloat16), ((HIDDEN,), jnp.bfloat16))
    _assert_kernel(text, ln.FWD_NAME, ln.BWD_NAME)


def test_latent_decode_at_the_serving_cells_geometry(one_chip):
    """B 128, 128 heads against one latent of 576 a token, a table of 40
    pages of 128 tokens, 6,144 pages [576, 128]
    (benchmarks/workloads/serve-pangu-ultra-moe-agent-over.json): a page
    with its tokens along the lanes is what a DMA can slice, 576 = 4.5
    lane tiles the other way round is not."""
    text = _compile(
        lambda q, p, t, l: la.latent_decode_kernel(q, p, t, l, rank=512,
                                                   scale=192 ** -0.5),
        one_chip, ((128, 128, 576), jnp.bfloat16),
        ((6144, 576, 128), jnp.bfloat16), ((128, 40), jnp.int32),
        ((128,), jnp.int32))
    _assert_kernel(text, la.LATENT_DECODE_NAME)


# ------------------------------------------------ grouped KV heads, page lists
def test_page_list_decode_at_the_sparse_cells_geometry(one_chip):
    """B 128 x 2 KV heads of 16 query heads, lists of 128 pages (a dense
    row's 8,192 tokens; a selection fills 64), 6,144 pages [2, 64, 128]
    (benchmarks/workloads/serve-minicpm-sala-docs32k-over.json): a KV
    head's page is a [64, 128] tile a DMA slices whole, where a
    [64, 2, 128] page would be padded eightfold in the pool."""
    assert pa.grouped_pages_dma_sliceable(64, 128, jnp.bfloat16)
    assert not pa.grouped_pages_dma_sliceable(8, 128, jnp.bfloat16)
    pool = ((6144, 2, 64, 128), jnp.bfloat16)
    text = _compile(
        lambda q, k, v, i, t: pa.grouped_paged_attention_kernel(
            q, k, v, i, t, scale=128 ** -0.5),
        one_chip, ((128, 2, 16, 128), jnp.bfloat16), pool, pool,
        ((128, 2, 128), jnp.int32), ((128, 2), jnp.int32))
    _assert_kernel(text, pa.GROUPED_DECODE_NAME)
    assert "bf16[6144,2,64,128]{3,2,1,0" in text        # the pool as it lies


@pytest.mark.parametrize("nkv", [8, 4], ids=["walk", "slots"])
def test_paged_decode_with_grouped_kv_heads(one_chip, nkv):
    """The GPT-layout kernels under fewer KV heads than query heads
    (pools [NB, bs, Hkv, D]): 8 KV heads still fill the tiles a DMA
    slices (the walk), 4 keep the grid over the table's slots."""
    assert pa._pages_dma_sliceable(nkv, HD) == (nkv == 8)
    pool = ((POOL_BLOCKS, KV_BLOCK, nkv, HD), jnp.bfloat16)
    text = _compile(pa.paged_attention_kernel, one_chip, _q(1), pool, pool,
                    _TABLES, _ROWS)
    _assert_kernel(text, pa.DECODE_NAME)


def test_paged_prefix_with_grouped_kv_heads(one_chip):
    pool = ((POOL_BLOCKS, KV_BLOCK, 4, HD), jnp.bfloat16)
    text = _compile(pa.paged_prefix_attention_kernel, one_chip, _q(4), pool,
                    pool, _TABLES, _ROWS)
    _assert_kernel(text, pa.PREFIX_NAME)


# ------------------------------------- rows that attend nothing (PR 35)
def _attended(lens, done):
    return jnp.where(done, 0, lens + 1)


_IDLE_CALLS = {
    # cell 2 / 4: B 32, a table of 128, 3,072 pages of 16 tokens, 16 x 128
    "paged": (lambda q, k, v, t, l, d: pa.paged_attention_kernel(
        q, k, v, t, _attended(l, d)),
        [((32, 1, NH, HD), jnp.bfloat16)]
        + [((3072, KV_BLOCK, NH, HD), jnp.bfloat16)] * 2
        + [((32, 128), jnp.int32), ((32,), jnp.int32), ((32,), jnp.bool_)],
        pa.DECODE_NAME),
    # cell 5: B 128, 128 heads on a latent of 576, 40 pages of 128 tokens
    "latent": (lambda q, p, t, l, d: la.latent_decode_kernel(
        q, p, t, _attended(l, d), rank=512, scale=192 ** -0.5),
        [((128, 128, 576), jnp.bfloat16), ((6144, 576, 128), jnp.bfloat16),
         ((128, 40), jnp.int32), ((128,), jnp.int32), ((128,), jnp.bool_)],
        la.LATENT_DECODE_NAME),
    # cell 6: B 128 x 2 KV heads of 16 query heads, lists of 128 pages
    "page_list": (lambda q, k, v, i, t, live: (
        pa.grouped_paged_attention_kernel(
            q, k, v, i, jnp.where(live[:, None], t, 0),
            scale=128 ** -0.5)),
        [((128, 2, 16, 128), jnp.bfloat16)]
        + [((6144, 2, 64, 128), jnp.bfloat16)] * 2
        + [((128, 2, 128), jnp.int32), ((128, 2), jnp.int32),
           ((128,), jnp.bool_)],
        pa.GROUPED_DECODE_NAME),
}


@pytest.mark.parametrize("kernel", list(_IDLE_CALLS))
def test_decode_kernels_take_rows_of_length_zero(one_chip, kernel):
    """The three walks at their cells' geometry, called as the models
    call them: a done row's attended length is 0, computed in the
    program from the chunk's own `done`. A row of no tokens is data: the
    kernel steps over it in a loop the chip's compiler has to take."""
    fn, shapes, name = _IDLE_CALLS[kernel]
    _assert_kernel(_compile(fn, one_chip, *shapes), name)


def test_decode_chunk_of_cell_2_holds_one_named_kernel_a_layer(
        one_chip, monkeypatch):
    """The compiled decode chunk at cell 2's engine geometry (two layers
    of GPT-1.3B's widths; benchmarks/workloads/serve-gpt3-1.3b-chat.json)
    holds one `pallas_paged_decode` a layer, and each is the instruction
    `paged_attention_roofline.chat` looks for by its shapes
    (benchmarks/metrics/, read here and never edited): done rows reach
    the kernel through its `lens` operand, nothing was added to it."""
    import json
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.nn import initializer
    from benchmarks import trace

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmarks/metrics/paged_attention_roofline.chat.json")) \
            as f:
        spec = json.load(f)
    with open(os.path.join(
            root, "benchmarks/workloads/serve-gpt3-1.3b-chat.json")) as f:
        eng = json.load(f)["engine"]
    b, nb, bs, chunk = (eng["max_batch"], eng["kv_blocks"], eng["kv_block"],
                        eng["decode_chunk"])
    mb = -(-(eng["prompt_cap"] + eng["max_new_tokens"]) // bs)
    layers = 2
    paddle.seed(0)
    with initializer.fast_init():
        model = GPTForCausalLM(GPTConfig(
            vocab_size=512, hidden_size=HIDDEN, num_layers=layers,
            num_heads=NH, max_position_embeddings=2048,
            intermediate_size=4 * HIDDEN))
    model.to(dtype="bfloat16")
    model.eval()

    class Captured(Exception):
        pass

    def grab(sig, build):               # the chunk's program, not run
        def take(*args):
            raise Captured(build(), args)
        return take

    monkeypatch.setattr(model, "_gen_cache_get", grab)
    monkeypatch.setenv("PADDLE_TPU_PAGED", "1")     # the gate sees the CPU
    pool = jax.ShapeDtypeStruct((nb, bs, NH, HD), jnp.bfloat16)
    with pytest.raises(Captured) as got:
        model.decode_paged([(pool, pool)] * layers,
                           jnp.zeros((b, mb), jnp.int32),
                           jnp.zeros((b,), jnp.int32),
                           jnp.zeros((b,), jnp.int32),
                           jnp.ones((b,), bool), chunk)
    fn, args = got.value.args
    args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), args)
    text = fn.lower(*args).compile().as_text()
    shapes = dict(re.findall(r"(%\S+) = (\w+\[[\d,]*\])", text))
    calls = [l for l in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l
             and re.search(r"[%_]" + pa.DECODE_NAME + r"_*\.",
                           l.split(" = ", 1)[0])]
    assert len(calls) == layers, calls
    patterns = [[part.format(MB=b, KB=nb, BS=bs, NH=NH, D=HD) for part in p]
                for p in spec["patterns"]]
    for call in calls:
        # as the device names the event: operands with their types
        typed = re.sub(r"%[\w.\-]+", lambda m: shapes.get(m.group(0), "")
                       + " " + m.group(0), call.split(" = ", 1)[1])
        assert trace.matches("%k = " + typed, patterns), typed


# -------------------------------------------- the engine's own device programs
@pytest.mark.parametrize("b,chunk", [(32, 8), (128, 8)],
                         ids=["gpt-cells", "pangu-cell"])
def test_engine_step_helpers_at_the_serving_cells_geometry(one_chip, b,
                                                           chunk):
    """The two small programs a paged engine step adds beside the model's
    (inference/serving.py: `paged_stage` picks each row's pending token
    and done flag on the device, `paged_put_first` keeps a prefill's
    first token there), at the serving cells' batch and chunk. Their
    shapes come from the engine's settings alone, so a toy model's engine
    warmed up on the CPU builds the cells' own programs."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.analysis import lint_capture
    from paddle_tpu.inference import ServingConfig, ServingEngine
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=16, num_layers=1, num_heads=2,
        max_position_embeddings=32, intermediate_size=32))
    model.eval()
    eng = ServingEngine(model, ServingConfig(
        max_batch=b, prompt_cap=8, max_new_tokens=chunk + 2,
        decode_chunk=chunk, kv_block=4, prefix_cache=True,
        prefill_chunk=4))
    with lint_capture() as calls:
        eng.submit(np.arange(1, 7))
        assert [r.status for r in eng.drain()] == ["done"]
    for name in ("paged_stage", "paged_put_first"):
        (_, fn, (args, kw)), = [c for c in calls if c[0][0] == name][:1]
        args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                for a in args]
        assert fn.lower(*args, **kw).compile().as_text()


# ------------------------------------------- the selective scan (Jamba, PR 36)
def test_selective_scan_at_the_jamba_cells_window(one_chip):
    """One row's prefill window of 256 tokens at d_inner 5,120 and 16
    states (benchmarks/workloads/serve-jamba2-3b-reason-over.json): ten
    programs of 512 channels, each its [16, 512] state in registers across
    the window. Nothing of [256, 16, 5120] is an operand or a result: the
    call's bytes are its tokens' (x, dt, y: 3 x 5 MB; B, C as columns) and
    one row's state."""
    b, t, din, n = 1, 256, 5120, 16
    f32 = jnp.float32
    compiled = jax.jit(pss.selective_scan_kernel).lower(*[
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
            ((b, t, din), f32), ((b, t, din), f32), ((b, t, n), f32),
            ((b, t, n), f32), ((n, din), f32), ((din,), f32),
            ((b, n, din), f32), ((b,), jnp.int32))]).compile()
    _assert_kernel(compiled.as_text(), pss.SELECTIVE_SCAN_NAME)
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + m.output_size_in_bytes \
        + m.temp_size_in_bytes < 40 * 2 ** 20 < t * n * din * 4


def test_the_jamba_cell_compiles_for_the_chip_and_fits(one_chip, monkeypatch):
    """The decode chunk and the prefill window of cell 7 at the published
    widths and the cell's engine: the memory plan (weights, the paged pool,
    both state planes of 26 layers for 256 slots and 32 snapshots,
    temporaries) is under the chip's 15.75 GiB and is what the cell's
    `engine_note` states; a prefill window holds 26 selective-scan kernels
    and no array of [T, 16, 5120] but the state planes themselves, updated
    in place; a decode chunk walks one KV head's pages in 2 kernels; the
    program's leaves count ISSUE 36's 3,029,337,472 parameters."""
    import json
    import math
    import numpy as np
    from paddle_tpu.inference.kv_cache import BlockPool
    from paddle_tpu.models.jamba import JambaForCausalLM
    from paddle_tpu.nn import initializer
    from paddle_tpu.ops import selective_scan as ss
    from benchmarks.runners import serve_jamba

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks/configs/jamba2-3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(
            root, "benchmarks/workloads/serve-jamba2-3b-reason-over.json")) \
            as f:
        settings = json.load(f)
    eng = settings["engine"]
    b, nb, bs, chunk, snaps, window = (
        eng["max_batch"], eng["kv_blocks"], eng["kv_block"],
        eng["decode_chunk"], eng["state_snapshots"], eng["prefill_chunk"])
    mb = -(-(eng["prompt_cap"] + eng["max_new_tokens"]) // bs)
    with initializer.fast_init():
        model = JambaForCausalLM(serve_jamba.model_config(config))
    model.eval()
    assert sum(math.prod(p.shape) for p in model.parameters()) \
        == 3_029_337_472
    pool = BlockPool.for_model(model, num_blocks=nb, block_size=bs,
                               state_rows=b, snapshot_rows=snaps)
    sds = jax.ShapeDtypeStruct
    pools = [tuple(sds((nb,) + shp, jnp.bfloat16) for shp in paged)
             + tuple(sds((rows,) + shp, jnp.float32) for shp in state
                     for rows in (b, snaps))
             for paged, state in zip(pool.layer_block_shapes,
                                     pool.state_shapes)]

    class Captured(Exception):
        pass

    def grab(sig, build):               # the program, not run
        def take(*args):
            raise Captured(build(), args)
        return take

    monkeypatch.setattr(model, "_gen_cache_get", grab)
    monkeypatch.setenv("PADDLE_TPU_PAGED", "1")     # the gates see the CPU
    monkeypatch.setattr(ss, "on_tpu", lambda: True)

    def compiled(call):
        with pytest.raises(Captured) as got:
            call()
        fn, args = got.value.args
        args = jax.tree.map(lambda a: sds(a.shape, a.dtype,
                                          sharding=one_chip), args)
        c = fn.lower(*args).compile()
        return c.as_text(), c.memory_analysis()

    def kernels(text, name):
        return [l for l in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in l
                and re.search(r"[%_]" + name + r"_*\.", l.split(" = ", 1)[0])]

    zeros = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    text, mem = compiled(lambda: model.decode_paged(
        pools, zeros(b, mb), zeros(b), zeros(b), jnp.ones((b,), bool),
        chunk))
    assert len(kernels(text, pa.GROUPED_DECODE_NAME)) == 2
    gib = lambda x: x / 2 ** 30  # noqa: E731
    plan = gib(mem.argument_size_in_bytes + mem.temp_size_in_bytes)
    assert plan < 15.75
    # every plane is updated in place: what comes out beside them is the
    # chunk's tokens, lengths, flags and counters
    assert mem.alias_size_in_bytes >= pool.state_bytes \
        + nb * pool.bytes_per_block
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 2 ** 20
    note = settings["engine_note"]
    assert f"{gib(mem.argument_size_in_bytes):.2f} GiB of arguments" in note
    assert f"{gib(mem.temp_size_in_bytes):.2f} GiB of temporaries" in note
    assert f"{gib(pool.state_bytes):.2f} GiB" in note

    text, mem = compiled(lambda: model.prefill_paged(
        np.zeros((1, window), np.int64), np.asarray([window], np.int32),
        pools, zeros(1, mb), start=np.asarray([0], np.int32),
        state_slots=np.asarray([0], np.int32)))
    assert len(kernels(text, pss.SELECTIVE_SCAN_NAME)) == 26
    assert gib(mem.argument_size_in_bytes + mem.temp_size_in_bytes) < plan
    assert f"{gib(mem.temp_size_in_bytes):.2f} GiB a prefill window" in note
    # [T, 16, 5120] with T = 256 is also the slots' plane: whatever gives
    # that shape is the plane itself, handed on or written in place
    din, n = config["mamba_expand"] * config["hidden_size"], \
        config["mamba_d_state"]
    made = set(re.findall(
        rf"= f32\[{window},{n},{din}\](?:{{[^}}]*}})? ([a-z\-]+)\(", text))
    assert made and made <= {"parameter", "get-tuple-element", "fusion",
                             "dynamic-update-slice", "bitcast"}, made
    for other in (f"f32[{window},{din},{n}]", f"f32[1,{window},{n},{din}]",
                  f"f32[1,{window},{din},{n}]"):
        assert other not in text


# ------------------------------------------------------- the kernels' names
def test_every_kernel_has_a_name_and_none_holds_another():
    """A metric finds a kernel's events by looking for its name inside the
    event's instruction name, so every `pl.pallas_call` of ops/pallas/
    passes a constant `name=`, all start with `pallas_`, and no name is
    part of another (`..._decode` against `..._decode_q8` would count the
    int8 kernel's time under the bf16 one's)."""
    import inspect
    names, calls = [], 0
    for mod in (fa, fm, fmb, i8, ln, lce, pa, la, pss):
        src = inspect.getsource(mod)
        consts = re.findall(r"^([A-Z0-9_]*NAME) = ", src, re.M)
        used = re.findall(r"^\s+name=(\w+),$", src, re.M)
        calls += src.count("pl.pallas_call(")
        assert sorted(consts) == sorted(used), (mod.__name__, consts, used)
        names += [getattr(mod, c) for c in consts]
    assert len(names) == calls == len(set(names))
    assert all(re.fullmatch(r"pallas_[a-z0-9_]+", n) for n in names), names
    for a in names:
        assert not [b for b in names if a != b and a in b], a
