"""Profiler subsystem: program registry, MFU/HFU gauges, recompile
detection with argument blame, memory accounting, triggered profiling."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import metrics, profiler
from horovod_tpu.profiler import (
    ProfiledStep, describe, instrument, registry, utilization,
)


@pytest.fixture(autouse=True)
def _clean_registry():
    registry.reset()
    hvd.reset_metrics()
    yield
    registry.reset()
    hvd.reset_metrics()


def _counter(name, **labels):
    snap = metrics.snapshot()
    for s in snap["counters"].get(name, []):
        if all(str(s["labels"].get(k)) == str(v)
               for k, v in labels.items()):
            return s["value"]
    return 0


def _gauge(name, **labels):
    snap = metrics.snapshot()
    for s in snap["gauges"].get(name, []):
        if all(str(s["labels"].get(k)) == str(v)
               for k, v in labels.items()):
            return s["value"]
    return None


class TestUtilization:
    def test_r5_split(self):
        # executed 2e12 FLOPs in 0.5s on a 100 TFLOP/s peak: hfu 4%;
        # analytic 1e12 model FLOPs: mfu 2%.
        u = utilization(2e12, 0.5, model_flops=1e12, peak=100.0)
        assert u["hfu"] == pytest.approx(0.04)
        assert u["mfu"] == pytest.approx(0.02)
        assert u["achieved_tflops"] == pytest.approx(4.0)

    def test_no_model_flops_collapses(self):
        u = utilization(2e12, 0.5, peak=100.0)
        assert u["mfu"] == u["hfu"]

    def test_unknown_peak_yields_none(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_PEAK_TFLOPS", raising=False)
        u = utilization(2e12, 0.5)   # CPU: no peak known
        assert u["hfu"] is None and u["mfu"] is None

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_PEAK_TFLOPS", "50")
        assert profiler.peak_tflops() == 50.0
        monkeypatch.setenv("HOROVOD_HBM_GBPS", "123")
        assert profiler.hbm_gbps() == 123.0

    def test_a_tpu_missing_from_the_table_is_an_error(self, monkeypatch):
        # On the chip path a missing peak used to drop hfu/mfu from the
        # record without a word; the CPU mesh still reads None.
        monkeypatch.delenv("HOROVOD_PEAK_TFLOPS", raising=False)
        monkeypatch.delenv("HOROVOD_HBM_GBPS", raising=False)
        assert profiler.peak_tflops("TPU v5 lite") == 197.0
        assert profiler.peak_tflops("cpu") is None
        with pytest.raises(ValueError, match="TPU v9"):
            profiler.peak_tflops("TPU v9")
        with pytest.raises(ValueError, match="HOROVOD_HBM_GBPS"):
            profiler.hbm_gbps("TPU v9")

    def test_failed_cost_analysis_is_an_error_on_tpu(self, monkeypatch):
        class Broken:
            def cost_analysis(self):
                raise RuntimeError("no cost analysis")

        assert profiler.cost_from(Broken())["flops"] == 0.0   # CPU: zeros
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="no cost analysis"):
            profiler.cost_from(Broken())


class TestDescribe:
    def test_arrays_by_shape_dtype(self):
        assert describe(jnp.ones((2, 3))) == "float32[2, 3]"
        assert describe(np.zeros(4, np.int32)) == "int32[4]"

    def test_python_scalars_are_value_free(self):
        # A python scalar is a DYNAMIC arg under jit: its value changing
        # must not read as a recompile.
        assert describe(3) == describe(7)

    def test_pytrees_stable_and_shape_sensitive(self):
        t1 = {"a": jnp.ones((2,)), "b": jnp.ones((3,))}
        t2 = {"a": jnp.ones((2,)), "b": jnp.ones((3,))}
        t3 = {"a": jnp.ones((2,)), "b": jnp.ones((4,))}
        assert describe(t1) == describe(t2)
        assert describe(t1) != describe(t3)


class TestRegistry:
    def test_record_cost_and_gauges(self):
        f = jax.jit(lambda x: x @ x)
        x = jnp.ones((16, 16))
        rec = registry.record_cost("p", f.lower(x).compile())
        assert rec.flops > 0
        assert rec.peak_hbm_bytes > 0
        assert _gauge("program_flops", program="p") == rec.flops
        assert _gauge("program_peak_hbm_bytes", program="p") == \
            rec.peak_hbm_bytes

    def test_observe_step_updates_roofline_gauges(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_PEAK_TFLOPS", "1.0")
        monkeypatch.setenv("HOROVOD_HBM_GBPS", "1.0")
        rec = registry.program("p")
        rec.flops = 1e9
        rec.model_flops = 5e8
        rec.bytes_accessed = 1e6
        registry.observe_step("p", 0.001)
        # 1e9 flops / 1ms = 1 TFLOP/s = peak -> hfu 1.0, mfu 0.5
        assert _gauge("program_hfu", program="p") == pytest.approx(1.0)
        assert _gauge("program_mfu", program="p") == pytest.approx(0.5)
        # 1e6 B / 1ms = 1 GB/s = the whole (overridden) HBM BW
        assert _gauge("hbm_bandwidth_utilization",
                      program="p") == pytest.approx(1.0)
        assert registry.program("p").last_step_seconds == 0.001

    def test_note_trace_counts_and_blames(self):
        st, bl = registry.note_trace("p", {"x": "f32[2]", "k": "2"})
        assert st == "compile" and bl == []
        st, bl = registry.note_trace("p", {"x": "f32[2]", "k": "2"})
        assert st == "steady"
        st, bl = registry.note_trace("p", {"x": "f32[4]", "k": "3"})
        assert st == "recompile" and bl == ["k", "x"]
        assert _counter("recompiles_total", program="p") == 1
        assert _counter("recompile_blame_total", program="p",
                        argument="x") == 1
        rec = registry.program("p")
        assert rec.blame_detail["x"] == ("f32[2]", "f32[4]")

    def test_added_and_removed_args_blamed(self):
        registry.note_trace("p", {"x": "a"})
        _, bl = registry.note_trace("p", {"y": "b"})
        assert bl == ["x", "y"]

    def test_alternating_cached_signatures_are_steady(self):
        # jax.jit caches EVERY signature: alternating train/eval shapes
        # compiles twice total, then executes cached code — revisits must
        # not read as recompiles (they'd flood recompiles_total and the
        # doctor on a healthy job).
        train = {"x": "f32[128]"}
        eval_ = {"x": "f32[64]"}
        assert registry.note_trace("p", train)[0] == "compile"
        assert registry.note_trace("p", eval_)[0] == "recompile"
        for _ in range(3):
            assert registry.note_trace("p", train)[0] == "steady"
            assert registry.note_trace("p", eval_)[0] == "steady"
        rec = registry.program("p")
        assert rec.recompiles == 1 and rec.compiles == 2
        assert _counter("recompiles_total", program="p") == 1
        # a genuinely NEW third signature still counts
        assert registry.note_trace("p", {"x": "f32[32]"})[0] == "recompile"
        assert rec.recompiles == 2


class TestMpDegree:
    """record_cost(mp_degree=...) divides the analytic per-program
    numbers by the tensor-parallel degree: shard_map cost analysis
    counts GLOBAL work, but program_mfu compares against ONE chip's
    peak (ISSUE 14 satellite)."""

    class _FakeMem:
        argument_size_in_bytes = 600.0
        output_size_in_bytes = 200.0
        temp_size_in_bytes = 200.0
        alias_size_in_bytes = 0.0

    class _FakeCompiled:
        def cost_analysis(self):
            return {"flops": 1000.0, "bytes accessed": 400.0}

        def memory_analysis(self):
            return TestMpDegree._FakeMem()

    def test_cost_divided_by_degree(self):
        rec = registry.record_cost("tp_prog", self._FakeCompiled(),
                                   model_flops=800.0, mp_degree=2)
        assert rec.mp_degree == 2
        assert rec.flops == 500.0
        assert rec.bytes_accessed == 200.0
        assert rec.peak_hbm_bytes == 500.0
        assert rec.model_flops == 400.0
        assert rec.snapshot()["mp_degree"] == 2

    def test_degree_one_unchanged(self):
        rec = registry.record_cost("dense_prog", self._FakeCompiled())
        assert rec.mp_degree == 1
        assert rec.flops == 1000.0
        assert rec.peak_hbm_bytes == 1000.0

    def test_mfu_honest_under_mp(self, monkeypatch):
        # 1000 global flops over mp=2 in 1ms = 5e-7 TFLOP/s per chip:
        # against a 1e-6-TFLOPS "chip" that is hfu 0.5 — without the
        # division it would read 1.0, 2x truth.
        monkeypatch.setenv("HOROVOD_PEAK_TFLOPS", "1e-6")
        registry.record_cost("tp_prog", self._FakeCompiled(),
                             mp_degree=2)
        registry.observe_step("tp_prog", 0.001)
        assert _gauge("program_hfu", program="tp_prog") == \
            pytest.approx(0.5)
        """The ISSUE acceptance test: change a static arg, assert
        recompiles_total increments and the blamed argument is named."""
        calls = []

        def fn(x, seq_len):
            calls.append(1)
            return x[:seq_len] * 2.0

        step = instrument(fn, name="train_step", static_argnums=(1,))
        x = jnp.arange(8.0)
        np.testing.assert_allclose(step(x, 8), np.arange(8.0) * 2)
        # cost capture must not compile twice: one trace per signature
        assert len(calls) == 1, calls
        before = _counter("recompiles_total", program="train_step")
        step(x, 8)    # steady: no recompile
        assert _counter("recompiles_total", program="train_step") == before
        np.testing.assert_allclose(step(x, 4), np.arange(4.0) * 2)
        assert _counter("recompiles_total",
                        program="train_step") == before + 1
        rec = step.record()
        assert rec.last_blame == ["seq_len"]
        assert rec.blame_detail["seq_len"] == ("8", "4")
        assert _counter("recompile_blame_total", program="train_step",
                        argument="seq_len") == 1

    def test_shape_change_blames_the_array(self):
        step = instrument(lambda x: x * 1.0, name="p2")
        step(jnp.ones((4,)))
        step(jnp.ones((8,)))
        assert step.record().last_blame == ["x"]

    def test_cost_captured_once_per_signature(self):
        step = instrument(lambda x: x @ x, name="p3")
        step(jnp.ones((8, 8)))
        rec = step.record()
        assert rec.flops > 0
        f8 = rec.flops
        step(jnp.ones((16, 16)))
        assert step.record().flops > f8   # re-captured for the new shape

    def test_decorator_and_timed(self):
        @instrument(name="p4", timed=True)
        def f(x):
            return x + 1
        f(jnp.ones(3))
        rec = registry.program("p4")
        assert rec.steps == 1 and rec.last_step_seconds > 0

    def test_matches_plain_jit_semantics(self):
        step = instrument(lambda a, b: a + b, name="p5")
        out = step(jnp.ones(3), 2.0)
        np.testing.assert_allclose(out, 3.0 * np.ones(3))

    def test_capture_cost_env_off(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_PROFILER_COST", "0")
        step = ProfiledStep(lambda x: x * 2, name="p6")
        step(jnp.ones(3))
        assert registry.program("p6").flops == 0   # fingerprint only

    def test_snapshot_shape(self):
        step = instrument(lambda x: x, name="p7")
        step(jnp.ones(3))
        registry.observe_step("p7", 0.5)
        snap = registry.snapshot()
        assert "p7" in snap
        assert snap["p7"]["compiles"] == 1
        assert "utilization" in snap["p7"]


class TestMemoryAccounting:
    def test_live_buffer_census(self):
        keep = jnp.ones((1024,))   # noqa: F841 — must stay live
        census = profiler.live_buffer_census()
        assert "cpu" in census
        assert census["cpu"]["bytes"] >= 4096
        assert _gauge("device_live_buffer_bytes", platform="cpu") \
            == census["cpu"]["bytes"]

    def test_check_memory_pressure_cpu_is_none(self):
        # CPU devices expose no memory_stats; the check degrades to None
        # without emitting events.
        assert profiler.check_memory_pressure() is None
        assert _counter("memory_pressure_total") == 0


class TestTriggeredProfiling:
    def test_profile_context_manager(self, tmp_path):
        with profiler.profile(str(tmp_path / "cap")) as logdir:
            jnp.ones(4).block_until_ready()
        assert os.path.isdir(logdir)
        # jax wrote an xplane capture under plugins/
        found = [f for _, _, fs in os.walk(logdir) for f in fs]
        assert found, "profile capture produced no files"

    def test_profile_refuses_nesting(self, tmp_path):
        with profiler.profile(str(tmp_path / "a")):
            with pytest.raises(RuntimeError):
                with profiler.profile(str(tmp_path / "b")):
                    pass

    def test_profile_failed_start_releases_flag(self, tmp_path,
                                                monkeypatch):
        # A failed start (unwritable dir, another profiler session) must
        # not wedge _PROFILE_ACTIVE and disable every future capture.
        import jax as _jax

        def boom(logdir):
            raise RuntimeError("profiler busy")
        monkeypatch.setattr(_jax.profiler, "start_trace", boom)
        with pytest.raises(RuntimeError, match="profiler busy"):
            with profiler.profile(str(tmp_path / "x")):
                pass
        monkeypatch.undo()
        assert not profiler._PROFILE_ACTIVE
        with profiler.profile(str(tmp_path / "y")) as logdir:
            jnp.ones(2).block_until_ready()
        assert os.path.isdir(logdir)

    def test_trigger_profile_bounded(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HOROVOD_PROFILE_DIR", str(tmp_path))
        monkeypatch.setenv("HOROVOD_PROFILE_SECONDS", "0.2")
        from horovod_tpu import config
        config.refresh()
        try:
            before = profiler.profile_capture_count()
            d = profiler.trigger_profile("test_reason", seconds=0.2)
            assert d is not None and str(tmp_path) in d
            # While active, a second trigger is refused.
            assert profiler.trigger_profile("again") is None
            deadline = time.monotonic() + 10
            while profiler._PROFILE_ACTIVE and time.monotonic() < deadline:
                time.sleep(0.05)
            assert not profiler._PROFILE_ACTIVE
            assert profiler.profile_capture_count() == before + 1
            assert _counter("profile_capture_total") >= 1
        finally:
            monkeypatch.delenv("HOROVOD_PROFILE_DIR")
            monkeypatch.delenv("HOROVOD_PROFILE_SECONDS")
            config.refresh()

    def test_manual_profile_preempts_background_trigger(self, tmp_path,
                                                        monkeypatch):
        # A watchdog-triggered capture must never crash a user's
        # periodic `with hvd.profile():` window — the manual capture
        # preempts it, and the trigger's stop timer must not clobber
        # the manual capture's state afterwards.
        monkeypatch.setenv("HOROVOD_PROFILE_DIR", str(tmp_path))
        from horovod_tpu import config
        config.refresh()
        try:
            d = profiler.trigger_profile("bg", seconds=30.0)
            assert d is not None
            with profiler.profile(str(tmp_path / "manual")) as logdir:
                jnp.ones(2).block_until_ready()
                assert profiler._PROFILE_ACTIVE
                assert profiler._PROFILE_SOURCE == "manual"
            assert not profiler._PROFILE_ACTIVE
            assert os.path.isdir(logdir)
            # the 30s trigger timer is now a no-op: a fresh capture works
            with profiler.profile(str(tmp_path / "again")):
                pass
        finally:
            monkeypatch.delenv("HOROVOD_PROFILE_DIR")
            config.refresh()

    def test_maybe_trigger_gated_on_knob(self, monkeypatch):
        from horovod_tpu import config
        monkeypatch.delenv("HOROVOD_PROFILE_ON_STALL", raising=False)
        config.refresh()
        assert profiler.maybe_trigger("off") is None


class TestWiring:
    def test_eager_collective_registers_program(self):
        hvd.allreduce(np.ones((8, 3), np.float32), name="prof_wire")
        rec = registry.get("collective:allreduce")
        assert rec is not None
        # count_trace fires on cache MISS only; a repeat dispatch of the
        # same shape must not inflate it.
        n = rec.traces
        hvd.allreduce(np.ones((8, 3), np.float32), name="prof_wire2")
        assert registry.get("collective:allreduce").traces == n

    def test_autotuned_step_feeds_registry(self):
        import optax

        def make_step(threshold):
            opt = hvd.DistributedOptimizer(
                optax.sgd(0.1), fusion_threshold_bytes=threshold)

            @jax.jit
            def step(params, opt_state):
                grads = jax.tree_util.tree_map(jnp.ones_like, params)
                updates, opt_state = opt.update(grads, opt_state, params)
                return optax.apply_updates(params, updates), opt_state
            return step

        from horovod_tpu.autotune import BayesianAutotuner
        tuner = BayesianAutotuner(probes=1, samples_per_probe=1)
        astep = hvd.AutotunedStep(make_step, tuner=tuner)
        params = {"w": jnp.ones((4,))}
        opt = hvd.DistributedOptimizer(optax.sgd(0.1))
        opt_state = opt.init(params)
        for _ in range(4):
            params, opt_state = astep(params, opt_state)
        rec = registry.get("autotuned_step")
        assert rec is not None
        assert rec.expected_recompiles   # tuner churn is by design
        assert rec.steps >= 1            # timed tuning steps fed the gauge

    def test_build_info_carries_profile_knobs(self):
        info = hvd.build_info()
        assert "profile_on_stall" in info and "profile_dir" in info
