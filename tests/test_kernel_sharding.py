"""Pallas kernels under a mesh: `shard_kernel` runs them per (dp, mp)
shard, and the answers and gradients match the unsharded reference.

Mosaic kernels are never partitioned automatically, so without the
wrapper a TP/DP train step does not lower for real chips at all
("Please wrap the call in a shard_map"). On the virtual CPU devices the
platform gates never pick the kernels, which is how that stayed hidden;
here the kernels are called directly, in interpret mode, on a dp2 x mp2
mesh of conftest.py's devices. tests/test_chip_compile.py compiles the
same wrapped calls for the described v5e:2x2.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu.distributed as dist
from paddle_tpu.distributed import mesh as _mesh
from paddle_tpu.ops import attention as attn
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import linear_ce as lce
from paddle_tpu.ops.pallas import paged_attention as pa


@pytest.fixture()
def mesh():
    m = dist.build_mesh({"dp": 2, "mp": 2}, devices=jax.devices()[:4])
    dist.set_mesh(m)
    yield m
    dist.set_mesh(None)


def _unfused_ce(x, w, labels):
    logits = jnp.dot(x, w.T, preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return lse - gold


def test_kernel_axes_drops_what_does_not_divide(mesh):
    q = jnp.zeros((2, 8, 4, 16))
    assert _mesh.kernel_axes((q,), (attn._BSHD,)) == {"dp", "mp"}
    odd_heads = jnp.zeros((2, 8, 3, 16))
    assert _mesh.kernel_axes((odd_heads,), (attn._BSHD,)) == {"dp"}
    dist.set_mesh(None)
    assert _mesh.kernel_axes((q,), (attn._BSHD,)) == frozenset()


def test_shard_kernel_sees_local_blocks(mesh):
    seen = []

    def kernel(q):
        seen.append(q.shape)
        return q * 2

    q = jnp.ones((4, 8, 6, 16))
    out = jax.jit(lambda a: _mesh.shard_kernel(
        kernel, (a,), (attn._BSHD,), attn._BSHD))(q)
    assert seen == [(2, 8, 3, 16)]
    np.testing.assert_array_equal(np.asarray(out), 2 * np.asarray(q))


def test_shard_kernel_inside_a_manual_region_is_a_plain_call(mesh):
    def kernel(q):
        return q + 1

    def body(q):            # every axis manual already: nothing to wrap
        return _mesh.shard_kernel(kernel, (q,), (attn._BSHD,), attn._BSHD)

    q = jnp.zeros((4, 8, 4, 16))
    out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                                out_specs=P("dp"), check_vma=False))(q)
    np.testing.assert_array_equal(np.asarray(out), 1.0)


def test_flash_under_mesh_matches_reference(mesh, monkeypatch):
    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=True))
    rng = np.random.RandomState(0)
    shape = (2, 128, 4, 32)          # B over dp, heads over mp
    sh = NamedSharding(mesh, P("dp", None, "mp", None))
    q, k, v = (jax.device_put(jnp.asarray(rng.randn(*shape), jnp.float32)
                              * 0.3, sh) for _ in range(3))

    def kernel_loss(q, k, v):
        out = attn._flash(q, k, v, causal=True, scale=None)
        return jnp.sum(out * out), out

    def ref_loss(q, k, v):
        out = attn.attention_reference(q, k, v, is_causal=True)
        return jnp.sum(out * out), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        kernel_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    dist.set_mesh(None)
    (_, want), want_grads = jax.value_and_grad(
        ref_loss, argnums=(0, 1, 2), has_aux=True)(
            *(np.asarray(a) for a in (q, k, v)))
    assert out.sharding.is_equivalent_to(sh, out.ndim)
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(g), w, atol=5e-5)


def test_flash_qkv_under_mesh_matches_reference(mesh, monkeypatch):
    """The packed projection's batch rows over dp (its lanes are q, k and
    v heads in turn, no axis a mesh could split): each shard runs the
    kernels on its own rows, and the one packed gradient comes back laid
    out as the projection was."""
    monkeypatch.setattr(fa, "flash_attention_qkv", functools.partial(
        fa.flash_attention_qkv, interpret=True))
    rng = np.random.RandomState(3)
    nh = 2
    sh = NamedSharding(mesh, P("dp", None, None))
    qkv = jax.device_put(jnp.asarray(rng.randn(4, 128, 3 * nh * 128),
                                     jnp.float32) * 0.3, sh)

    def kernel_loss(x):
        out = attn._flash_qkv(x, nh, causal=True, scale=None)
        return jnp.sum(out * out), out

    def ref_loss(x):
        q, k, v = (t.reshape(4, 128, nh, 128) for t in jnp.split(x, 3, -1))
        out = attn.attention_reference(q, k, v, is_causal=True)
        return jnp.sum(out * out), out.reshape(4, 128, nh * 128)

    (_, out), grad = jax.jit(jax.value_and_grad(kernel_loss, has_aux=True))(
        qkv)
    dist.set_mesh(None)
    (_, want), want_grad = jax.value_and_grad(ref_loss, has_aux=True)(
        jnp.asarray(np.asarray(qkv)))
    assert out.sharding.is_equivalent_to(sh, out.ndim)
    assert grad.sharding.is_equivalent_to(sh, grad.ndim)
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(grad), want_grad, atol=5e-5)


@pytest.mark.parametrize("w_layout", ["vh", "hv"])
def test_linear_ce_under_mesh_matches_unfused(mesh, w_layout):
    """Tokens over dp, vocab over mp: the per-shard (lse, gold) pairs are
    combined across vocab shards, and the gradients of x and W are those
    of the unsharded loss."""
    rng = np.random.RandomState(1)
    t, h, v = 64, 128, 1024
    x = jnp.asarray(rng.randn(t, h), jnp.float32) * 0.5
    w = jnp.asarray(rng.randn(v, h), jnp.float32) * 0.1
    labels = jnp.asarray(rng.randint(0, v, (t,)), jnp.int32)
    g = jnp.asarray(rng.rand(t), jnp.float32)      # uneven cotangent
    vh = w_layout == "vh"
    xs = jax.device_put(x, NamedSharding(mesh, P("dp", None)))
    ws = jax.device_put(w if vh else w.T, NamedSharding(
        mesh, P("mp", None) if vh else P(None, "mp")))
    ls = jax.device_put(labels, NamedSharding(mesh, P("dp")))

    def kernel_loss(x, w, l):
        per_tok = lce.linear_cross_entropy(x, w, l, w_layout=w_layout,
                                           block_t=16, block_v=128,
                                           interpret=True)
        return jnp.sum(per_tok * g), per_tok

    (_, per_tok), (dx, dw) = jax.jit(jax.value_and_grad(
        kernel_loss, argnums=(0, 1), has_aux=True))(xs, ws, ls)
    dist.set_mesh(None)
    (_, want), (wdx, wdw) = jax.value_and_grad(
        lambda x, w: (jnp.sum(_unfused_ce(x, w, labels) * g),
                      _unfused_ce(x, w, labels)),
        argnums=(0, 1), has_aux=True)(x, w)
    np.testing.assert_allclose(np.asarray(per_tok), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(dx), wdx, atol=2e-5)
    np.testing.assert_allclose(np.asarray(dw if vh else dw.T), wdw,
                               atol=2e-5)
    # the weight gradient comes back in the weight's own layout
    assert dw.sharding.is_equivalent_to(ws.sharding, dw.ndim)


def test_linear_ce_off_mesh_is_the_plain_kernel():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(32, 128), jnp.float32)
    w = jnp.asarray(rng.randn(512, 128), jnp.float32) * 0.1
    labels = jnp.asarray(rng.randint(0, 512, (32,)), jnp.int32)
    got = lce.linear_cross_entropy(x, w, labels, block_t=16, block_v=128,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(got), _unfused_ce(x, w, labels),
                               atol=2e-5)


@pytest.mark.parametrize("int8,walk", [(False, False), (False, True),
                                       (True, False)])
def test_paged_decode_under_mesh_matches_reference(mesh, monkeypatch, int8,
                                                   walk):
    """Sharded serving keeps the pools' head axis over mp: each shard walks
    the block table over its own heads; rows split over dp. `walk`: the
    kernel of heads a DMA can slice (the chip's rule; toy heads fill no
    tile, so the test answers for them), each shard fetching ahead over
    its own rows only."""
    if walk:
        monkeypatch.setattr(pa, "_pages_dma_sliceable", lambda nh, hd: True)
    rng = np.random.RandomState(3)
    b, nh, hd, nb, bs, mb = 4, 4, 16, 12, 4, 3
    q = jnp.asarray(rng.randn(b, 1, nh, hd), jnp.float32) * 0.3
    tables = jnp.asarray(rng.randint(1, nb, (b, mb)), jnp.int32)
    lens = jnp.asarray([1, 4, 9, 12], jnp.int32)
    head_sh = NamedSharding(mesh, P(None, None, "mp", None))
    if int8:
        pools = []
        for _ in range(2):
            pools += [jnp.asarray(rng.randint(-127, 128, (nb, bs, nh, hd)),
                                  jnp.int8),
                      jnp.asarray(rng.rand(nb, bs, nh) * 0.01 + 1e-3,
                                  jnp.float32)]
        kernel, ref = (pa.paged_attention_q8_kernel,
                       attn.paged_attention_reference_q8)
    else:
        pools = [jnp.asarray(rng.randn(nb, bs, nh, hd), jnp.float32) * 0.3
                 for _ in range(2)]
        kernel, ref = pa.paged_attention_kernel, attn.paged_attention_reference
    placed = [jax.device_put(p, head_sh if p.ndim == 4 else NamedSharding(
        mesh, P(None, None, "mp"))) for p in pools]
    got = jax.jit(lambda q, t, l, *p: attn._paged_kernel(
        kernel, q, p, t, l, interpret=True))(q, tables, lens, *placed)
    dist.set_mesh(None)
    want = ref(q, *pools, tables, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
