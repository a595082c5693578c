"""Observability layer (SURVEY §5.1 parity): trace_analysis on a
checked-in miniature device capture, StepMonitor metrics + the
recompilation detector, annotate_layers path naming, scheduler edge cases,
and the device memory telemetry the monitor reads."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import device, profiler
from paddle_tpu.profiler import (ProfilerState, StepMonitor, SummaryView,
                                 make_scheduler, trace_analysis)
import paddle_tpu.nn as nn

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")

# The fixture capture (fixtures/mini_step.trace.json.gz) holds 2 identical
# steps on a /device:TPU:0 lane — per step: fusion.1 300us, convolution.2
# 200us, all-reduce.3 100us (50us of it under convolution.2), copy.4 50us —
# plus an "XLA Modules" envelope lane and a host lane that the parser must
# exclude (both would double-count).


class TestSchedulerStateMachine:
    def test_single_record_slot_returns_immediately(self):
        s = make_scheduler(closed=0, ready=0, record=1, repeat=2)
        S = ProfilerState
        assert [s(i) for i in range(3)] == \
            [S.RECORD_AND_RETURN, S.RECORD_AND_RETURN, S.CLOSED]

    def test_infinite_repeat_cycles(self):
        s = make_scheduler(closed=1, ready=1, record=2, repeat=0)
        S = ProfilerState
        period = [S.CLOSED, S.READY, S.RECORD, S.RECORD_AND_RETURN]
        assert [s(i) for i in range(8)] == period * 2
        assert s(4000 + 2) == S.RECORD  # still cycling far out

    def test_skip_first_shifts_whole_schedule(self):
        s = make_scheduler(closed=1, ready=0, record=1, repeat=1,
                           skip_first=3)
        S = ProfilerState
        assert [s(i) for i in range(6)] == \
            [S.CLOSED, S.CLOSED, S.CLOSED, S.CLOSED, S.RECORD_AND_RETURN,
             S.CLOSED]

    def test_exhausted_repeat_stays_closed(self):
        s = make_scheduler(closed=0, ready=1, record=1, repeat=2)
        assert s(4) == ProfilerState.CLOSED
        assert s(100) == ProfilerState.CLOSED


class TestTraceAnalysis:
    def _an(self, **kw):
        return trace_analysis.analyze(FIXTURES, **kw)

    def test_find_trace_file(self):
        f = trace_analysis.find_trace_file(FIXTURES)
        assert f is not None and f.endswith(".trace.json.gz")

    def test_op_totals_and_exclusions(self):
        an = self._an(steps=2)
        rows = {r["name"]: r for r in an.op_totals()}
        assert rows["fusion.1"]["dur_us"] == 600 and \
            rows["fusion.1"]["calls"] == 2
        assert rows["convolution.2"]["dur_us"] == 400
        assert rows["all-reduce.3"]["dur_us"] == 200
        # module-envelope lane and host lane must NOT be counted
        assert "jit_train_step" not in rows and "dispatch" not in rows
        assert an.total_device_us() == 1300

    def test_categories(self):
        an = self._an()
        cats = {r["name"]: r["category"] for r in an.op_totals()}
        assert cats == {"fusion.1": "fusion", "convolution.2": "compute",
                        "all-reduce.3": "collective", "copy.4": "copy"}

    def test_overlap_ratio(self):
        ov = self._an().overlap()
        # all-reduce [450,550) overlaps convolution [300,500) by 50us/step
        assert ov["collective_us"] == 200
        assert ov["overlapped_us"] == 100
        assert abs(ov["ratio"] - 0.5) < 1e-9

    def test_steady_window_trims_edges(self):
        # first 40% of the 0..1650us span keeps only step-0's four ops
        an = self._an(window=(0.0, 0.4))
        assert all(r["calls"] == 1 for r in an.op_totals())

    def test_views_render(self):
        an = self._an(steps=2)
        kv = an.kernel_view()
        assert "fusion.1" in kv and "ms/step" in kv
        dv = an.device_view()
        assert "/device:TPU:0" in dv and "category split" in dv
        xv = an.distributed_view()
        assert "all-reduce.3" in xv and "overlap ratio 0.50" in xv

    def test_profiler_summary_views_from_capture(self):
        # acceptance surface: summary(views=[KernelView]) renders the
        # per-op device-time table parsed from a real capture
        p = profiler.Profiler(trace_dir=FIXTURES, timer_only=True)
        out = p.summary(views=[SummaryView.KernelView,
                               SummaryView.DistributedView], steps=2)
        assert "fusion.1" in out and "0.300" in out
        assert "overlap ratio" in out

    def test_missing_capture_reports_not_crashes(self, tmp_path):
        p = profiler.Profiler(trace_dir=str(tmp_path), timer_only=True)
        out = p.summary(views=[SummaryView.KernelView])
        assert "no device trace" in out


class TestStepMonitor:
    def test_mfu_and_throughput_math(self):
        mon = StepMonitor(flops_per_step=2e9, peak_flops=1e12,
                          items_per_step=8, track_memory=False)
        for _ in range(3):
            mon.end_step(wall_s=0.004)
        r = mon.report()
        assert r["steps"] == 3
        assert abs(r["step_ms"] - 4.0) < 1e-6
        assert abs(r["mfu"] - 0.5) < 1e-6          # 2e9 / 0.004 / 1e12
        assert abs(r["items_per_s"] - 2000.0) < 1e-6

    def test_recompile_detector_shape_delta(self):
        mon = StepMonitor(track_memory=False)
        sig_a = (((4, 8), "float32"),)
        sig_b = (((6, 8), "float32"),)
        mon.record_compile("train_step", sig_a)
        mon.end_step(wall_s=0.01)
        mon.record_compile("train_step", sig_b, prev_sig=sig_a)
        mon.end_step(wall_s=0.01)
        assert mon.compiles == 2 and mon.recompiles == 1
        ev = mon.recompile_events[0]
        assert "(4, 8)" in ev["delta"] and "(6, 8)" in ev["delta"]

    def test_compile_steps_excluded_from_steady_median(self):
        mon = StepMonitor(track_memory=False)
        mon.record_compile("train_step", ("sig",))
        mon.end_step(wall_s=5.0)          # compile step: huge wall
        for _ in range(3):
            mon.end_step(wall_s=0.01)
        assert abs(mon.report()["step_ms"] - 10.0) < 1e-6

    def test_train_step_integration(self, tmp_path):
        from paddle_tpu.jit.train_step import TrainStep
        paddle.seed(0)
        m = nn.Sequential(nn.Linear(8, 8), nn.Tanh(), nn.Linear(8, 4))
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())
        ce = nn.CrossEntropyLoss()
        jsonl = str(tmp_path / "mon.jsonl")
        mon = StepMonitor(items_per_step=4, jsonl_path=jsonl)
        step = TrainStep(m, opt, lambda x, y: ce(m(x), y), monitor=mon)
        x = paddle.to_tensor(np.random.randn(4, 8).astype(np.float32))
        y = paddle.to_tensor(np.random.randint(0, 4, (4,)).astype("int64"))
        step(x, y)
        step(x, y)
        # batch 4 -> 6: the detector must flag a recompile with the delta
        x2 = paddle.to_tensor(np.random.randn(6, 8).astype(np.float32))
        y2 = paddle.to_tensor(np.random.randint(0, 4, (6,)).astype("int64"))
        step(x2, y2)
        r = mon.report()
        assert r["steps"] == 3
        assert r["compiles"] == 2 and r["recompiles"] == 1
        assert "(4, 8)" in mon.recompile_events[0]["delta"]
        assert r["hbm_peak_bytes"] and r["hbm_peak_bytes"] > 0
        rows = [json.loads(l) for l in open(jsonl)]
        assert len(rows) == 3 and rows[2]["compiled"] is True

    def test_run_steps_records_step_count(self):
        from paddle_tpu.jit.train_step import TrainStep
        paddle.seed(0)
        m = nn.Linear(4, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=m.parameters())
        mon = StepMonitor(track_memory=False)
        step = TrainStep(m, opt, lambda x, y: ((m(x) - y) ** 2).mean(),
                         monitor=mon)
        xs = paddle.to_tensor(np.random.randn(3, 2, 4).astype(np.float32))
        step.run_steps(3, xs, xs)
        assert mon.report()["steps"] == 3
        assert mon.records[0]["steps"] == 3

    def test_on_report_hook_and_metrics_text(self):
        seen = []
        mon = StepMonitor(items_per_step=2, unit="tokens/s",
                          on_report=seen.append, track_memory=False)
        with mon.step():
            pass
        assert len(seen) == 1 and seen[0]["step"] == 1
        text = mon.metrics_text()
        assert "paddle_tpu_steps_total 1" in text
        assert "# TYPE paddle_tpu_throughput gauge" in text

    def test_profiler_callback_drives_monitor(self):
        from paddle_tpu.hapi.callbacks import ProfilerCallback
        mon = StepMonitor(track_memory=False)
        cb = ProfilerCallback(monitor=mon, summary=False)
        cb.on_train_begin()
        for i in range(2):
            cb.on_train_batch_begin(i)
            cb.on_train_batch_end(i)
        cb.on_train_end()
        assert mon.report()["steps"] == 2


class TestAnnotateLayers:
    class _Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.trunk = nn.Sequential(nn.Linear(8, 8), nn.Tanh())
            self.head = nn.Linear(8, 2)

        def forward(self, x):
            return self.head(self.trunk(x))

    def test_qualified_paths_and_parity(self):
        paddle.seed(0)
        m = self._Net()
        x = paddle.to_tensor(np.random.randn(2, 8).astype(np.float32))
        want = m(x).numpy()
        h = profiler.annotate_layers(m)
        assert set(h.paths) == {"_Net", "_Net/trunk", "_Net/trunk/0",
                                "_Net/trunk/1", "_Net/head"}
        np.testing.assert_allclose(m(x).numpy(), want)  # behavior unchanged
        h.remove()
        np.testing.assert_allclose(m(x).numpy(), want)
        assert "forward" not in m.__dict__  # original forward restored

    def test_root_override_and_idempotence(self):
        m = self._Net()
        h1 = profiler.annotate_layers(m, root="gpt")
        assert "gpt/head" in h1.paths
        h2 = profiler.annotate_layers(m, root="gpt")
        assert h2.paths == []           # already annotated: no double wrap
        h1.remove()


class TestDeviceMemoryStats:
    def test_stats_shape_and_peak_monotonic(self):
        s = device.memory_stats()
        assert s["bytes_in_use"] >= 0
        assert device.max_memory_allocated() >= s["bytes_in_use"]

    def test_live_allocation_visible(self):
        before = device.memory_allocated()
        t = paddle.to_tensor(np.zeros((512, 512), np.float32))  # 1 MiB
        after = device.memory_allocated()
        assert after - before >= 512 * 512 * 4
        assert device.max_memory_allocated() >= after
        del t

    def test_chip_peak_flops_known_kinds(self):
        class _D:
            device_kind = "TPU v5e"
        assert device.chip_peak_flops(_D()) == 197e12
        # a kind the table does not know is an error, not a v4: an
        # assumed peak makes every MFU figure wrong without a trace
        _D.device_kind = "weird accelerator"
        with pytest.raises(ValueError, match="weird accelerator"):
            device.chip_peak_flops(_D())

    def test_no_tpu_is_said_not_hidden(self):
        """The tests run on the CPU: on_tpu() says so, and asking for the
        TPU by name raises instead of quietly handing back a CPU device."""
        assert not device.on_tpu()
        assert not paddle.is_compiled_with_tpu()
        with pytest.raises(RuntimeError, match="no 'tpu' device"):
            device.set_device("tpu")
        assert device.set_device("cpu:1").platform == "cpu"
        assert device.get_device() == "cpu:1"
        device._current[0] = None

    def test_compile_cache_follows_the_environment(self, monkeypatch):
        """JAX_COMPILATION_CACHE_DIR set: JAX already uses it, nothing is
        set in code. Unset: one fixed, git-ignored path in the checkout."""
        import jax
        was = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert device.enable_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        try:
            path = device.enable_compile_cache()
            assert path == os.path.join(repo, ".jax_compile_cache")
            assert jax.config.jax_compilation_cache_dir == path
            assert device.enable_compile_cache() == path      # fixed
            with open(os.path.join(repo, ".gitignore")) as f:
                assert ".jax_compile_cache/" in f.read().split()
        finally:
            jax.config.update("jax_compilation_cache_dir", was)
