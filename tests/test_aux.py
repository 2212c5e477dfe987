"""Timeline, autotune, runner, callbacks tests (SURVEY §5)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import timeline as tl
from horovod_tpu.autotune import Autotuner, autotune_fusion_threshold
from horovod_tpu.callbacks import (
    BroadcastGlobalVariablesCallback, LearningRateScheduleCallback,
    LearningRateWarmupCallback, MetricAverageCallback, warmup_schedule,
)
from horovod_tpu.runner.launcher import (
    build_worker_env, parse_hosts, run as runner_run, worker_commands,
)


class TestTimeline:
    def test_trace_file(self, tmp_path):
        path = str(tmp_path / "tl.json")
        t = tl.init_timeline(path)
        t.marker("epoch_start", epoch=1)
        with t.activity("allreduce", tensor="grads", bytes=1024):
            pass
        tl.shutdown_timeline()
        with open(path) as f:
            data = json.load(f)
        events = data["traceEvents"]
        assert {e["name"] for e in events} == {"epoch_start", "allreduce"}
        span = [e for e in events if e["ph"] == "X"][0]
        assert span["dur"] >= 0 and span["args"]["bytes"] == 1024

    def test_env_var(self, tmp_path, monkeypatch):
        p = str(tmp_path / "t.json")
        monkeypatch.setenv("HOROVOD_TIMELINE", p)
        tl.init_timeline()
        tl.get_timeline().marker("m")
        tl.shutdown_timeline()
        assert os.path.exists(p)

    def test_requires_path(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_TIMELINE", raising=False)
        with pytest.raises(ValueError):
            tl.init_timeline()


class TestAutotune:
    def test_offline_picks_fastest(self):
        import time

        def factory(thr):
            def step():
                time.sleep(0.001 if thr == 4096 else 0.005)
            return step

        res = autotune_fusion_threshold(factory, [1024, 4096, 16384],
                                        steps_per_trial=3, warmup_steps=1)
        assert res.best_threshold_bytes == 4096
        assert len(res.trials) == 3
        assert "best fusion threshold" in res.summary()

    def test_flash_block_autotune_small_shape(self):
        from horovod_tpu.autotune import autotune_flash_blocks
        best, trials = autotune_flash_blocks(
            (1, 64, 2, 8), dtype="float32", causal=True,
            candidates=[(16, 16), (32, 32), (64, 64)],
            steps_per_trial=1, include_backward=False)
        assert best in trials and len(trials) == 3
        assert all(s > 0 for s in trials.values())

    def test_online_converges(self):
        tuner = Autotuner(candidates_bytes=[100, 200], samples_per_candidate=2)
        sim = {100: 0.01, 200: 0.002}
        while not tuner.converged:
            tuner.record(sim[tuner.current_threshold()])
        assert tuner.current_threshold() == 200


@pytest.fixture
def clean_env(monkeypatch):
    import horovod_tpu.config as hconfig
    yield monkeypatch
    monkeypatch.undo()     # undo BEFORE refresh so patches don't re-cache
    hconfig.refresh()


class TestBayesianAutotuner:
    """GP-guided online tuner (upstream horovod/runner/autotune)."""

    @staticmethod
    def _quadratic(thr_bytes, opt_log2=24.5, base=0.01, a=0.002):
        return base + a * (np.log2(thr_bytes) - opt_log2) ** 2

    def test_converges_near_optimum(self):
        from horovod_tpu.autotune import BayesianAutotuner
        tuner = BayesianAutotuner(probes=6, samples_per_probe=3)
        n = 0
        while not tuner.converged:
            tuner.record(self._quadratic(tuner.current_threshold()))
            n += 1
        # deterministic convergence step count — the torch path's rank-0
        # broadcast sync depends on every process converging together
        assert n == 6 * 3
        # optimum is 2^24.5 (~23 MB); the GP should land within one
        # octave either side
        assert 8 * (1 << 20) <= tuner.current_threshold() <= 64 * (1 << 20)
        assert "best" in tuner.summary()

    def test_beats_ladder_probe_count(self):
        """Same objective: the GP reaches a within-noise pick in 6 probes;
        the ladder spends 5 candidates x samples to walk its rungs."""
        from horovod_tpu.autotune import BayesianAutotuner
        tuner = BayesianAutotuner(probes=6, samples_per_probe=1)
        while not tuner.converged:
            tuner.record(self._quadratic(tuner.current_threshold()))
        best_t = self._quadratic(tuner.current_threshold())
        opt_t = self._quadratic(2 ** 24.5)
        assert best_t <= opt_t * 1.5

    def test_median_filters_noise_spikes(self):
        from horovod_tpu.autotune import BayesianAutotuner
        tuner = BayesianAutotuner(probes=6, samples_per_probe=5)
        i = 0
        while not tuner.converged:
            t = self._quadratic(tuner.current_threshold())
            # every 5th sample is a 50x straggler spike
            tuner.record(t * 50 if i % 5 == 4 else t)
            i += 1
        assert 4 * (1 << 20) <= tuner.current_threshold() <= 128 * (1 << 20)

    def test_deterministic_across_processes(self):
        """Identical timing streams -> identical probe sequence and pick
        (SPMD requirement: thresholds feed the negotiation signature)."""
        from horovod_tpu.autotune import BayesianAutotuner
        a = BayesianAutotuner(probes=5, samples_per_probe=2)
        b = BayesianAutotuner(probes=5, samples_per_probe=2)
        while not a.converged:
            assert a.current_threshold() == b.current_threshold()
            t = self._quadratic(a.current_threshold())
            a.record(t)
            b.record(t)
        assert b.converged
        assert a.current_threshold() == b.current_threshold()

    def test_probe_sync_protocol_under_timing_jitter(self):
        """Ranks see DIFFERENT timings, so GP proposals diverge; the
        pending_sync/current_point/set_current_point handshake (rank 0's
        pick broadcast, as the torch synchronize path does) must keep
        every rank probing the same threshold — it feeds the negotiation
        signature."""
        from horovod_tpu.autotune import BayesianAutotuner
        r0 = BayesianAutotuner(probes=6, samples_per_probe=2)
        r1 = BayesianAutotuner(probes=6, samples_per_probe=2)
        rng = np.random.default_rng(7)
        while not r0.converged:
            # emulate the broadcast each rank performs in synchronize()
            for t in (r0, r1):
                if t.pending_sync:
                    t.set_current_point(r0.current_point())
            assert r0.current_threshold() == r1.current_threshold()
            base = self._quadratic(r0.current_threshold())
            r0.record(base * (1 + 0.05 * rng.random()))
            r1.record(base * (1 + 0.05 * rng.random()))
        assert r1.converged
        # final picks come from local argmins and still need the existing
        # converged broadcast; emulate it the way synchronize() does
        r1._best = r0.current_threshold()
        assert r0.current_threshold() == r1.current_threshold()

    def test_tunes_compression_category(self):
        from horovod_tpu.autotune import BayesianAutotuner
        tuner = BayesianAutotuner(probes=8, samples_per_probe=1,
                                  tune_compression=True)
        while not tuner.converged:
            t = self._quadratic(tuner.current_threshold())
            if tuner.current_compression() == "fp16":
                t *= 0.7          # half the wire bytes, 30% faster steps
            tuner.record(t)
        assert tuner.current_compression() == "fp16"

    def test_tunes_wire_precision_axis(self):
        """The per-bucket wire-precision GP axis (PR 6): a bandwidth-bound
        objective where the quantized wires cut step time proportionally
        to their wire bytes must converge onto a 1-byte format."""
        from horovod_tpu.autotune import BayesianAutotuner
        tuner = BayesianAutotuner(probes=10, samples_per_probe=1,
                                  tune_wire=True)
        speed = {"fp32": 1.0, "bf16": 0.75, "int8": 0.55, "fp8": 0.55}
        while not tuner.converged:
            assert tuner.current_wire() in tuner.WIRE_CHOICES
            t = self._quadratic(tuner.current_threshold())
            tuner.record(t * speed[tuner.current_wire()])
        assert tuner.current_wire() in ("int8", "fp8")
        assert "wire=" in tuner.summary()

    def test_wire_axis_off_reports_config_wire(self, clean_env):
        import horovod_tpu.config as hconfig
        from horovod_tpu.autotune import BayesianAutotuner
        clean_env.setenv("HOROVOD_ALLREDUCE_WIRE", "fp8")
        hconfig.refresh()
        try:
            tuner = BayesianAutotuner(probes=3, samples_per_probe=1)
            assert tuner.current_wire() == "fp8"
        finally:
            clean_env.delenv("HOROVOD_ALLREDUCE_WIRE")
            hconfig.refresh()

    def test_wire_axis_sync_protocol(self):
        """6-tuple points (threshold, comp, alg, chunks, wire, topo) must
        ride the same rank-0 broadcast handshake; legacy 4/5-tuples from
        an old coordinator keep the local trailing coordinates."""
        from horovod_tpu.autotune import BayesianAutotuner
        r0 = BayesianAutotuner(probes=6, samples_per_probe=1,
                               tune_algorithm=True, tune_wire=True)
        r1 = BayesianAutotuner(probes=6, samples_per_probe=1,
                               tune_algorithm=True, tune_wire=True)
        while not r0.converged:
            for t in (r0, r1):
                if t.pending_sync:
                    t.set_current_point(r0.current_point())
            assert r0.current_point() == r1.current_point()
            assert len(r0.current_point()) == 6
            t = self._quadratic(r0.current_threshold())
            r0.record(t)
            r1.record(t)
        # legacy shorter points: trailing coordinates preserved locally
        fresh = BayesianAutotuner(probes=6, samples_per_probe=1,
                                  tune_wire=True)
        wire_before = fresh.current_point()[4]
        topo_before = fresh.current_point()[5]
        fresh.set_current_point((0.5, 0, 0, 0))
        assert fresh.current_point() == (0.5, 0, 0, 0, wire_before,
                                         topo_before)
        fresh.set_current_point((0.25, 0, 0, 0, wire_before))
        assert fresh.current_point() == (0.25, 0, 0, 0, wire_before,
                                         topo_before)

    def test_mode_env_selects_bayes(self, clean_env):
        torch = pytest.importorskip("torch")
        import horovod_tpu.config as hconfig
        import horovod_tpu.torch as hvt
        from horovod_tpu.autotune import BayesianAutotuner
        clean_env.setenv("HOROVOD_AUTOTUNE", "1")
        clean_env.setenv("HOROVOD_AUTOTUNE_MODE", "bayes")
        hconfig.refresh()
        model = torch.nn.Linear(4, 1)
        opt = hvt.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1))
        assert isinstance(opt._autotuner, BayesianAutotuner)
        assert hvd.build_info()["autotune_mode"] == "bayes"
        # the drop-in surface drives the existing synchronize loop
        opt._autotuner = BayesianAutotuner(probes=3, samples_per_probe=1)
        for _ in range(6):
            opt.zero_grad()
            model(torch.ones(2, 4)).sum().backward()
            opt.step()
        assert opt._autotuner.converged and opt._autotune_synced

    def test_bayes_compression_probes_live_wire(self, clean_env):
        """The probed compression must be ACTIVE during its probe — the
        GP's compression dimension is fit to these timings."""
        torch = pytest.importorskip("torch")
        import horovod_tpu.config as hconfig
        import horovod_tpu.torch as hvt
        from horovod_tpu.autotune import BayesianAutotuner
        from horovod_tpu.compression import Compression
        clean_env.setenv("HOROVOD_AUTOTUNE", "1")
        clean_env.setenv("HOROVOD_AUTOTUNE_MODE", "bayes-compression")
        hconfig.refresh()
        model = torch.nn.Linear(4, 1)
        opt = hvt.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1))
        assert opt._autotuner._tune_comp
        opt._autotuner = BayesianAutotuner(probes=4, samples_per_probe=1,
                                           tune_compression=True)
        seen = set()
        for _ in range(8):
            opt.zero_grad()
            model(torch.ones(2, 4)).sum().backward()
            opt.step()
            if not opt._autotuner.converged:
                # the live wire format tracks the probed category
                want = opt._autotuner.current_compression()
                got = ("fp16" if opt._compression is Compression.fp16
                       else "none")
                assert got == want
            seen.add(opt._autotuner.current_compression())
        # the fixed design cycles categories, so both were actually probed
        assert seen >= {"none", "fp16"}
        assert opt._autotune_synced

    def test_mode_env_rejects_unknown(self, clean_env):
        pytest.importorskip("torch")
        import torch
        import horovod_tpu.config as hconfig
        import horovod_tpu.torch as hvt
        clean_env.setenv("HOROVOD_AUTOTUNE", "1")
        clean_env.setenv("HOROVOD_AUTOTUNE_MODE", "anneal")
        hconfig.refresh()
        model = torch.nn.Linear(2, 1)
        with pytest.raises(ValueError, match="anneal"):
            hvt.DistributedOptimizer(
                torch.optim.SGD(model.parameters(), lr=0.1))


class TestRunner:
    def test_parse_hosts_string(self):
        specs = parse_hosts("h1:4,h2:2,h3")
        assert [(s.host, s.slots) for s in specs] == [
            ("h1", 4), ("h2", 2), ("h3", 1)]

    def test_parse_hostfile(self, tmp_path):
        f = tmp_path / "hostfile"
        f.write_text("worker0 slots=8\nworker1 slots=8  # comment\n\n")
        specs = parse_hosts(str(f))
        assert [(s.host, s.slots) for s in specs] == [
            ("worker0", 8), ("worker1", 8)]

    def test_worker_env(self):
        env = build_worker_env(2, 4, "c:29500", base_env={})
        assert env == {"HVD_TPU_COORDINATOR": "c:29500",
                       "HVD_TPU_NUM_PROCESSES": "4",
                       "HVD_TPU_PROCESS_ID": "2"}

    def test_worker_commands(self):
        cmds = worker_commands(["python", "train.py"],
                               parse_hosts("h1:8,h2:8"), 1234)
        assert len(cmds) == 2
        assert "HVD_TPU_COORDINATOR=h1:1234" in cmds[0]
        assert "HVD_TPU_PROCESS_ID=1" in cmds[1]

    def test_local_run_spawns(self):
        rc = runner_run(["python", "-c", "import os; "
                         "assert os.environ['HVD_TPU_NUM_PROCESSES']=='2'"],
                        np=2)
        assert rc == 0

    def test_single_local_worker_keeps_the_ambient_platform(
            self, tmp_path, monkeypatch):
        # One worker has nothing to share an accelerator with: the
        # launcher must not put it on the CPU unasked (on a TPU host the
        # ambient platform is the TPU). Several workers are forced there.
        from horovod_tpu.runner.launcher import run
        show = ["python", "-c",
                "import os; print('platform', "
                "os.environ.get('JAX_PLATFORMS'))"]
        monkeypatch.delenv("JAX_PLATFORMS")
        assert run(show, np=1, output_filename=str(tmp_path / "one"),
                   timeout=120) == 0
        assert "platform None" in (
            tmp_path / "one" / "rank.0" / "stdout").read_text()
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        assert run(show, np=2, output_filename=str(tmp_path / "two"),
                   timeout=120) == 0
        for r in range(2):
            assert "platform cpu" in (
                tmp_path / "two" / f"rank.{r}" / "stdout").read_text()

    def test_local_run_failure_raises(self):
        with pytest.raises(RuntimeError):
            runner_run(["python", "-c", "raise SystemExit(3)"], np=2)

    def test_cli_dry_run(self, capsys):
        from horovod_tpu.runner.launcher import main
        rc = main(["-np", "2", "--dry-run", "--", "python", "x.py"])
        assert rc == 0


class TestCallbacks:
    def test_broadcast_callback_idempotent(self):
        cb = BroadcastGlobalVariablesCallback(0)
        state = {"params": {"w": jnp.ones(3)}}
        out = cb.on_train_begin(state)
        out2 = cb.on_train_begin(out)
        np.testing.assert_array_equal(np.asarray(out2["params"]["w"]),
                                      np.ones(3))

    def test_metric_average_single_process(self):
        cb = MetricAverageCallback()
        out = cb.on_epoch_end({"loss": 2.0})
        assert float(out["loss"]) == 2.0

    def test_warmup_schedule(self):
        sched = warmup_schedule(0.1, warmup_epochs=2, steps_per_epoch=5,
                                size=8)
        assert float(sched(0)) == pytest.approx(0.1)
        assert float(sched(10)) == pytest.approx(0.8)

    def test_warmup_callback(self):
        cb = LearningRateWarmupCallback(0.1, warmup_epochs=1,
                                        steps_per_epoch=10)
        assert cb.lr_at(0) == pytest.approx(0.1)
        assert cb.lr_at(100) == pytest.approx(0.1 * hvd.size())

    def test_schedule_callback(self):
        cb = LearningRateScheduleCallback(0.1, multiplier=0.5,
                                          start_epoch=2, end_epoch=4)
        assert cb.lr_at_epoch(1) is None
        assert cb.lr_at_epoch(2) == pytest.approx(0.05)
        assert cb.lr_at_epoch(4) is None


class TestRunFunc:
    def test_run_func_two_processes(self):
        # Programmatic launcher (upstream horovod.run): closures ship via
        # cloudpickle; each worker rendezvouses and returns its result.
        from horovod_tpu.runner import run_func
        base = 100

        def work(offset):
            import jax
            import horovod_tpu as hvd
            out = hvd.allgather_object(jax.process_index())
            return base + offset + sum(out)

        results = run_func(work, args=(7,), np=2)
        assert results == [108, 108]  # 100 + 7 + (0 + 1) on both ranks

    def test_run_func_worker_failure_raises(self):
        from horovod_tpu.runner import run_func

        def boom():
            raise RuntimeError("worker exploded")

        with pytest.raises(RuntimeError):
            run_func(boom, np=1)

    def test_output_filename_writes_per_rank_logs(self, tmp_path):
        from horovod_tpu.runner.launcher import run
        out = str(tmp_path / "logs")
        rc = run(["python", "-c",
                  "import os, sys; print('rank', "
                  "os.environ['HVD_TPU_PROCESS_ID']); "
                  "print('err', file=sys.stderr)"],
                 np=2, output_filename=out, timeout=120)
        assert rc == 0
        for r in range(2):
            text = (tmp_path / "logs" / f"rank.{r}" / "stdout").read_text()
            assert f"rank {r}" in text
            assert "err" in text       # stderr merged, upstream behavior

    def test_run_timeout_kills_wedged_workers(self):
        from horovod_tpu.runner.launcher import run
        import time
        t0 = time.monotonic()
        with pytest.raises(TimeoutError, match="still running"):
            run(["python", "-c", "import time; time.sleep(60)"], np=2,
                timeout=2.0)
        assert time.monotonic() - t0 < 30  # killed promptly, not after 60s


class TestSshLaunch:
    """Remote launch orchestration (upstream gloo_run ssh execution;
    VERDICT r1 missing item 7). ssh is faked with a local shell so the
    supervision/teardown logic runs for real."""

    def test_ssh_mode_executes_and_supervises(self, monkeypatch, tmp_path):
        from horovod_tpu.runner import launcher
        monkeypatch.setattr(launcher, "_ssh_argv",
                            lambda host, line: ["bash", "-c", line])
        script = ("import os, pathlib; "
                  f"pathlib.Path(r'{tmp_path}' + '/out_' + "
                  "os.environ['HVD_TPU_PROCESS_ID']).write_text("
                  "os.environ['HVD_TPU_COORDINATOR'] + ' ' + "
                  "os.environ['HVD_TPU_NUM_PROCESSES'])")
        rc = launcher.run(["python", "-c", script],
                          hosts="hostA:1,hostB:1", ssh=True, timeout=120)
        assert rc == 0
        a = (tmp_path / "out_0").read_text()
        b = (tmp_path / "out_1").read_text()
        assert a == b and a.endswith(" 2")
        assert a.split(":")[0] == "hostA"

    def test_ssh_mode_fail_fast(self, monkeypatch):
        from horovod_tpu.runner import launcher
        monkeypatch.setattr(launcher, "_ssh_argv",
                            lambda host, line: ["bash", "-c", "exit 7"])
        with pytest.raises(RuntimeError, match="exited with code 7"):
            launcher.run(["python", "-c", "pass"],
                         hosts="hostA:1,hostB:1", ssh=True, timeout=60)

    def test_local_ip_is_an_address(self):
        from horovod_tpu.runner.launcher import local_ip
        ip = local_ip()
        assert isinstance(ip, str) and ip.count(".") == 3


class TestAutotunedStep:
    """VERDICT r4 next #10: the Bayesian tuner consumed by the JAX
    (optax) path under jit-recompile discipline."""

    def _make_harness(self, rng, tuner):
        import optax
        builds = []
        X = jnp.asarray(rng.standard_normal((32, 4)), jnp.float32)
        y = jnp.asarray(X @ np.array([1., -2., .5, .8], np.float32))
        opt_holder = {}

        def make_step(threshold):
            builds.append(threshold)
            opt = hvd.DistributedOptimizer(
                optax.sgd(0.05), fusion_threshold_bytes=threshold)
            opt_holder.setdefault("opt", opt)

            @jax.jit
            def step(w, opt_state):
                def loss(w):
                    return jnp.mean((X @ w - y) ** 2)
                l, g = jax.value_and_grad(loss)(w)
                u, opt_state = opt.update(g, opt_state, w)
                return optax.apply_updates(w, u), opt_state, l

            return step

        step = hvd.AutotunedStep(make_step, tuner=tuner)
        w = jnp.zeros((4,))
        ost = opt_holder["opt"].init(w)
        return step, w, ost, builds

    def test_probes_recompile_state_survives_and_converges(self, rng):
        from horovod_tpu.autotune import BayesianAutotuner
        tuner = BayesianAutotuner(probes=3, samples_per_probe=2)
        step, w, ost, builds = self._make_harness(rng, tuner)
        losses = []
        for _ in range(25):
            w, ost, l = step(w, ost)
            losses.append(float(l))
        assert step.converged
        # One build per probe point + the final best rebuild.
        assert len(builds) >= 3
        assert builds[-1] == step.current_threshold()
        # Optimizer state threaded across every recompile: training
        # never reset (loss strictly decreased through every rebuild).
        assert all(b < a for a, b in zip(losses, losses[1:])), losses
        assert losses[-1] < 0.2 * losses[0], losses
        # Post-convergence calls run the winning program untimed.
        before = len(builds)
        w, ost, l = step(w, ost)
        assert len(builds) == before

    def test_converged_threshold_is_a_probed_point(self, rng):
        from horovod_tpu.autotune import BayesianAutotuner
        tuner = BayesianAutotuner(probes=2, samples_per_probe=2)
        step, w, ost, builds = self._make_harness(rng, tuner)
        for _ in range(6):
            w, ost, _ = step(w, ost)
        assert step.converged
        assert step.current_threshold() in builds
