"""bench.py --serve: the flag must parse, thread through the supervisor
to the child, and the serving bench must emit a JSON line with TTFT/TPOT
percentiles on CPU (guarded exactly like test_bench_comm_flags.py)."""

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
    def test_serve_flag_parses(self, bench):
        args = bench._build_parser().parse_args(["--serve"])
        assert args.serve
        assert not bench._build_parser().parse_args([]).serve

    def test_serve_bench_tool_parser(self, bench):
        sb = bench._load_serve_bench()
        args = sb._build_parser().parse_args(
            ["--requests", "4", "--rate", "9", "--kv-quant", "int8"])
        assert args.requests == 4 and args.rate == 9.0
        assert args.kv_quant == "int8"
        with pytest.raises(SystemExit):
            sb._build_parser().parse_args(["--kv-quant", "int4"])


class TestServeLineEmits:
    def test_serve_line_records_percentiles(self):
        """End-to-end CPU guard: ``bench.py --serve`` emits one JSON line
        with throughput + ttft/tpot/queue-wait percentiles and the
        paged-cache accounting fields."""
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   HVD_SERVE_BENCH_REQUESTS="6",
                   HVD_SERVE_BENCH_RATE="50",
                   HVD_SERVE_BENCH_SLOTS="3")
        out = subprocess.run(
            [sys.executable, os.path.join(_REPO, "bench.py"), "--serve"],
            capture_output=True, text=True, timeout=900, env=env,
            cwd=_REPO)
        assert out.returncode == 0, out.stderr[-2000:]
        lines = [ln for ln in out.stdout.splitlines()
                 if ln.startswith("{")]
        assert lines, out.stdout
        rec = json.loads(lines[-1])
        assert rec["metric"] == "serve_tokens_per_sec_per_chip"
        assert rec["value"] > 0
        assert rec["completed"] == 6
        for field in ("ttft_s", "tpot_s", "queue_wait_s"):
            assert rec[field]["p50"] is not None, (field, rec)
        assert rec["decode_compiles"] == 1
        assert rec["blocks_peak"] <= rec["dense_equivalent_blocks"]
        # SLO summary rides every line: observed TTFT p99 / error rate
        # vs the declared HOROVOD_SLO_* targets (unset here -> no
        # pass/fail verdict, but the observations are recorded).
        assert rec["slo_ttft_p99_ms"] == 0.0
        assert rec["slo_error_rate"] == 0.0
        assert rec["slo"]["ttft_p99_ms"] > 0
        assert rec["slo"]["error_rate"] == 0.0
        assert rec["slo"]["ttft_p99_ms_target"] is None
        assert rec["slo"]["ttft_ok"] is None and rec["slo"]["errors_ok"] is None
