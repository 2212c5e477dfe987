"""bench.py comm-sweep flags: --allreduce-alg / --overlap-chunks /
--sweep-comm must parse, thread through the supervisor to the child, and
the headline JSON line must still emit with the algorithm recorded."""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench():
    sys.path.insert(0, _REPO)
    import bench as b
    yield b
    sys.path.remove(_REPO)


class TestParsing:
    def test_flags_parse(self, bench):
        args = bench._build_parser().parse_args(
            ["--model", "mnist", "--allreduce-alg", "chunked_rs_ag",
             "--overlap-chunks", "8", "--sweep-comm"])
        assert args.allreduce_alg == "chunked_rs_ag"
        assert args.overlap_chunks == 8
        assert args.sweep_comm
        assert args.topology is None

    def test_topology_algorithms_parse(self, bench):
        for alg in ("rs_ag_2d", "chunked_rs_ag_2d",
                    "chunked_rs_ag_2d_int8", "swing"):
            args = bench._build_parser().parse_args(
                ["--allreduce-alg", alg, "--topology", "2x4"])
            assert args.allreduce_alg == alg
            assert args.topology == "2x4"
        assert all(a in bench.SWEEP_ALGS
                   for a in ("rs_ag_2d", "chunked_rs_ag_2d", "swing"))

    def test_bad_algorithm_rejected(self, bench):
        with pytest.raises(SystemExit):
            bench._build_parser().parse_args(
                ["--allreduce-alg", "ring2d"])

    def test_defaults_absent(self, bench):
        args = bench._build_parser().parse_args([])
        assert args.allreduce_alg is None
        assert args.overlap_chunks is None
        assert not args.sweep_comm

    def test_mesh_flag_parses_and_applies(self, bench, monkeypatch):
        args = bench._build_parser().parse_args(
            ["--model", "mnist", "--mesh", "dp2xmp1"])
        assert args.mesh == "dp2xmp1"
        monkeypatch.setenv("HOROVOD_MESH", "pre-test-sentinel")
        bench._apply_comm_flags(args)
        assert os.environ["HOROVOD_MESH"] == "dp2xmp1"

    def test_apply_comm_flags_sets_env(self, bench, monkeypatch):
        # setenv (not delenv) so monkeypatch records the pre-test state
        # even when the variable is absent: _apply_comm_flags writes
        # through plain os.environ, and a leaked HOROVOD_TOPOLOGY=2x4
        # would poison every later hvd.init() whose world it doesn't
        # factor (2-proc smokes, world-4 re-inits).
        keys = ("HOROVOD_ALLREDUCE_ALGORITHM", "HOROVOD_OVERLAP_CHUNKS",
                "HOROVOD_TOPOLOGY")
        for k in keys:
            monkeypatch.setenv(k, "pre-test-sentinel")
        args = bench._build_parser().parse_args(
            ["--allreduce-alg", "chunked_rs_ag", "--overlap-chunks", "3",
             "--topology", "2x4"])
        bench._apply_comm_flags(args)
        assert os.environ["HOROVOD_ALLREDUCE_ALGORITHM"] == \
            "chunked_rs_ag"
        assert os.environ["HOROVOD_OVERLAP_CHUNKS"] == "3"
        assert os.environ["HOROVOD_TOPOLOGY"] == "2x4"


class TestHeadlineStillEmits:
    def test_mnist_line_records_algorithm(self):
        """End-to-end CPU guard: the headline line still emits, with the
        selected algorithm recorded (acceptance criterion — the
        full-size resnet50 variant runs on the TPU container)."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("HOROVOD_ALLREDUCE_ALGORITHM", None)
        r = subprocess.run(
            [sys.executable, os.path.join(_REPO, "bench.py"), "--model",
             "mnist", "--allreduce-alg", "chunked_rs_ag"],
            capture_output=True, text=True, timeout=420, env=env,
            cwd=_REPO)
        assert r.returncode == 0, r.stderr[-2000:]
        lines = [l for l in r.stdout.splitlines() if l.startswith("{")]
        assert lines, r.stdout
        rec = json.loads(lines[-1])
        assert rec["metric"] == "mnist_images_per_sec_per_chip"
        assert rec["value"] is not None
        assert rec["allreduce_alg"] == "chunked_rs_ag"
