"""Rehearsal of chip_smoke.py on the CPU.

The chip check runs `python chip_smoke.py` on a TPU; this file keeps that
script from rotting between chip runs. It drives the same phase functions
at a tiny size — kernels in interpret mode, train and serve through the
jnp reference paths the CPU gates pick, the mesh phase on four of
conftest.py's virtual devices — and pins the one thing main() must do
here: refuse, with no `"ok": true`, because JAX found no TPU.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from paddle_tpu.models import GPTConfig  # noqa: E402


_TINY_KERNEL_SHAPES = dict(
    nh=2, hd=64, hidden=128, vocab=1024, flash=(2, 128),
    flash_padded=(2, 40), ce_tokens=64,
    pool_blocks=32, kv_block=8, table_slots=4, serve_batch=8,
    prefix_s=(8, 4), latent=(4, 24, 16, 8, 4, 32))


def _tiny_cfg():
    return GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                     num_heads=4, max_position_embeddings=128,
                     intermediate_size=128)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_without_a_tpu(argv, capsys):
    with pytest.raises(RuntimeError, match="needs a TPU"):
        chip_smoke.main(argv)
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_main_has_no_way_around_the_device_phase():
    """--seed and --chips are all it takes: no size, platform or skip
    option that could let the script end with ok on a CPU."""
    with pytest.raises(SystemExit):
        chip_smoke.main(["--tiny"])
    with pytest.raises(SystemExit):
        chip_smoke.main(["--chips", "2"])


def test_kernels_phase_rehearsal():
    chip_smoke.kernels_phase(_TINY_KERNEL_SHAPES, seed=0, interpret=True)


def test_kernels_phase_names_a_wrong_kernel(monkeypatch):
    from paddle_tpu.ops.pallas import paged_attention as pa
    real = pa.paged_attention_kernel
    monkeypatch.setattr(pa, "paged_attention_kernel",
                        lambda *a, **k: real(*a, **k) * 1.5)
    with pytest.raises(AssertionError, match="paged decode q"):
        chip_smoke.kernels_phase(dict(_TINY_KERNEL_SHAPES, prefix_s=(4,)),
                                 seed=0, interpret=True)


def test_train_phase_rehearsal():
    out = chip_smoke.train_phase(_tiny_cfg(), batch=2, seq=64, seed=0,
                                 expect_kernels=False)
    assert len(out["losses"]) == 12      # warm-up, 3 steps, 2 x run_steps(4)
    assert out["kernels"] == 0           # the CPU gates pick the references


def test_train_phase_fails_when_a_kernel_gate_gives_way():
    """On the chip `expect_kernels` is on: a step compiled without its
    Pallas calls (what a CPU compile is) must fail the phase."""
    with pytest.raises(AssertionError, match="gate gave way"):
        chip_smoke.train_phase(_tiny_cfg(), batch=2, seq=64, seed=0)


def test_serve_phase_rehearsal():
    serve = dict(max_batch=4, prompt_cap=16, max_new_tokens=8, kv_block=4,
                 kv_blocks=64)
    out = chip_smoke.serve_phase(_tiny_cfg(), serve, (16, 5, 3, 9, 12, 1, 7),
                                 seed=0, bf16=False, expect_kernels=False)
    assert out["agree"] == 1.0           # the CPU contract: bit-exact


def test_mesh_phase_rehearsal():
    out = chip_smoke.mesh_phase(_tiny_cfg(), batch=4, seq=32, seed=0,
                                axes={"dp": 2, "mp": 2}, bf16=False,
                                expect_kernels=False)
    assert out["worst"] <= chip_smoke.MESH_TOL
