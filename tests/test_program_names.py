"""Every program the package hands to the chip is named by one table
(`paddle_tpu/jit/api.py`), as every Pallas kernel is by its `*_NAME`
(tests/test_chip_compile.py holds those). A device trace's `XLA Modules`
line has one event per program run, called by the lowered module's name;
the benchmark's by-program readers look for `jit_<name>` there. The tests
lower what each call site built, on the CPU at toy sizes, and read the
module's name: nothing is compiled for a chip and nothing is timed."""
import json
import os
import re

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.analysis import lint_capture
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.inference import kv_cache
from paddle_tpu.jit import api as programs
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the call sites of the table: no bare `jax.jit(` may be left in them
NAMED_FILES = ("models/gpt.py", "models/decoder_parts.py",
               "models/pangu_moe.py", "models/minicpm_sala.py",
               "models/jamba.py", "inference/serving.py",
               "inference/kv_cache.py", "jit/train_step.py")
SERVE_STEP = {programs.PREFILL_PROGRAM, programs.DECODE_PROGRAM,
              programs.STAGE_PROGRAM, programs.PUT_FIRST_PROGRAM}


def _module_name(lowered) -> str:
    return re.match(r"module @(\S+)", lowered.as_text()).group(1)


def _lowered_names(calls) -> set:
    """Module names of the executables a `lint_capture` recorded."""
    seen, out = set(), set()
    for _, fn, (args, kwargs) in calls:
        if id(fn) not in seen:
            seen.add(id(fn))
            out.add(_module_name(fn.lower(*args, **kwargs)))
    return out


def _pool_program_names(eng) -> set:
    """Module names of the block pool's own programs (`state_move`, the
    spilled page's write-back; the cache is the process's, one entry a
    pool geometry), lowered on the engine's pools."""
    out = set()
    for sig, fn in kv_cache._SPILL_SCATTER_CACHE.items():
        pool = eng._pool
        if sig == ("state_move", pool.state_rows, pool.snapshot_rows,
                   pool.state_shapes):
            args = (np.int32(0),) * 3
        elif sig == pool._spill_sig() and eng._spill is not None:
            args = (np.int32(1),) + pool.read_block(eng._pools, 1)
        else:
            continue
        out.add(_module_name(fn.lower(eng._pools, *args)))
    return out


def test_the_table_names_what_it_lowers():
    assert len(set(programs.PROGRAM_NAMES)) == len(programs.PROGRAM_NAMES)
    for name in programs.PROGRAM_NAMES:
        fn = programs.named_program(lambda x: x + 1, name)
        assert _module_name(fn.lower(jnp.zeros((2,)))) == f"jit_{name}"
    with pytest.raises(ValueError):
        programs.named_program(lambda x: x, "run")


def test_a_named_function_keeps_its_own_name():
    def stage(x):
        return x * 2
    fn = programs.named_program(stage, programs.STAGE_PROGRAM,
                                donate_argnums=(0,))
    assert stage.__name__ == "stage"
    assert float(fn(jnp.ones(()))) == 2.0


def test_no_bare_jit_is_left_at_the_call_sites():
    for rel in NAMED_FILES:
        with open(os.path.join(ROOT, "paddle_tpu", rel)) as f:
            src = f.read()
        assert not re.search(r"\bjax\.jit\(", src), rel


@pytest.fixture(scope="module")
def gpt():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                    num_heads=4, max_position_embeddings=96,
                    intermediate_size=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m, cfg


@pytest.mark.parametrize("kw,want", [
    (dict(prefill_chunk=4, spill_host_bytes=1 << 20),
     SERVE_STEP | {programs.PAGE_COPY_PROGRAM, programs.SPILL_PROGRAM}),
    (dict(spec_decode=True, spec_k=3),
     SERVE_STEP | {programs.PAGE_COPY_PROGRAM, programs.VERIFY_PROGRAM}),
], ids=["chunked-spill", "spec-decode"])
def test_gpt_serving_programs(gpt, kw, want):
    m, cfg = gpt
    eng = ServingEngine(m, ServingConfig(
        max_batch=2, prompt_cap=8, max_new_tokens=6, decode_chunk=2,
        kv_block=4, kv_blocks=96, prefix_cache=True, **kw))
    with lint_capture() as calls:
        eng.warmup_prefix_cache(cfg.vocab_size, clear=False)
    names = _lowered_names(calls) | _pool_program_names(eng)
    eng.close()
    assert names == {f"jit_{n}" for n in want}
    launched = eng.metrics.programs_launched
    assert set(launched) == want
    assert launched[programs.DECODE_PROGRAM] \
        + launched.get(programs.VERIFY_PROGRAM, 0) \
        == eng.metrics.counters["decode_chunks"] \
        + eng.metrics.counters["spec_windows"] > 0


def test_gpt_generate_programs(gpt):
    m, cfg = gpt
    ids = paddle.to_tensor(np.arange(1, 9, dtype=np.int64).reshape(2, 4))
    with lint_capture() as calls:
        m.generate_static(ids, max_new_tokens=3)
        m.generate_static_ragged(ids, paddle.to_tensor(
            np.asarray([4, 3], np.int32)), max_new_tokens=3)
    assert len({id(c[1]) for c in calls}) == 2
    assert _lowered_names(calls) == {f"jit_{programs.GENERATE_PROGRAM}"}


def _toy(config_file, runner_name, model_of):
    import importlib
    with open(os.path.join(ROOT, "benchmarks/configs", config_file)) as f:
        config = json.load(f)
    runner = importlib.import_module(f"benchmarks.runners.{runner_name}")
    m = model_of(runner.model_config(config))
    m.eval()
    return m, config


def _family(name):
    if name == "pangu":
        from paddle_tpu.models.pangu_moe import PanguMoEForCausalLM
        return _toy("toy-pangu-moe.json", "serve_pangu_moe",
                    PanguMoEForCausalLM), dict(
            prompt_cap=40, max_new_tokens=8, decode_chunk=3, kv_block=4,
            kv_blocks=64), SERVE_STEP | {programs.PAGE_COPY_PROGRAM}
    state = dict(prompt_cap=96, max_new_tokens=16, decode_chunk=4,
                 kv_block=8, kv_blocks=96, state_snapshots=4)
    want = SERVE_STEP | {programs.STATE_MOVE_PROGRAM}
    if name == "sala":
        from paddle_tpu.models.minicpm_sala import MiniCPMSALAForCausalLM
        return _toy("toy-minicpm-sala.json", "serve_minicpm_sala",
                    MiniCPMSALAForCausalLM), state, want
    from paddle_tpu.models.jamba import JambaForCausalLM
    return _toy("toy-jamba.json", "serve_jamba", JambaForCausalLM), \
        state, want


@pytest.mark.parametrize("family", ["pangu", "sala", "jamba"])
def test_the_other_families_serving_programs(family):
    (m, config), kw, want = _family(family)
    eng = ServingEngine(m, ServingConfig(
        prefix_cache=True, max_batch=3, prefill_chunk=16, **kw))
    with lint_capture() as calls:
        eng.warmup_prefix_cache(int(config["vocab_size"]), clear=False)
    names = _lowered_names(calls) | _pool_program_names(eng)
    eng.close()
    assert names == {f"jit_{n}" for n in want}
    assert set(eng.metrics.programs_launched) == want


def test_the_diagnostic_programs(monkeypatch):
    """`expert_choices` and `selected_blocks` jit in place: the name each
    hands to `named_program` is the table's."""
    from paddle_tpu.models import minicpm_sala, pangu_moe
    asked = []
    real = programs.named_program

    def spy(fn, name, **kw):
        asked.append(name)
        return real(fn, name, **kw)
    monkeypatch.setattr(pangu_moe, "named_program", spy)
    monkeypatch.setattr(minicpm_sala, "named_program", spy)
    ids = np.arange(1, 17, dtype=np.int64).reshape(1, 16)
    (m, _), _, _ = _family("pangu")
    assert m.expert_choices(ids)
    (m, _), _, _ = _family("sala")
    assert m.selected_blocks(ids)
    assert asked == [programs.EXPERT_CHOICES_PROGRAM,
                     programs.SELECTED_BLOCKS_PROGRAM]


def test_train_step_programs():
    import paddle_tpu.nn as nn
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
    opt = paddle.optimizer.Adam(parameters=model.parameters(),
                                learning_rate=1e-2)
    ts = paddle.jit.TrainStep(model, opt,
                              lambda x, y: nn.MSELoss()(model(x), y))
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    y = paddle.to_tensor(np.ones((2, 1), np.float32))
    ts(x, y)
    ts.run_steps(3, paddle.to_tensor(np.ones((3, 2, 4), np.float32)),
                 paddle.to_tensor(np.ones((3, 2, 1), np.float32)))
    ts.loss_and_grad_norm(x, y)
    # a jitted function answers to the name its module is lowered under
    # (`test_the_table_names_what_it_lowers`)
    assert sorted(fn.__name__ for fn in ts._compiled.values()) == sorted(
        [programs.TRAIN_PROGRAM, programs.TRAIN_SCAN_PROGRAM,
         programs.GRAD_PROBE_PROGRAM])
