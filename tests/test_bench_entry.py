"""bench.py's entry and record format: no chip is a failure (never a CPU
number under a chip's metric name), every record names its device, and
hfu/mfu keep their two meanings."""

import os
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench(monkeypatch):
    sys.path.insert(0, _REPO)
    import bench as b
    yield b
    sys.path.remove(_REPO)


def test_no_accelerator_and_no_cpu_request_is_a_failure(bench, monkeypatch,
                                                        capsys):
    # The backend is the CPU and nobody asked for it: nothing may run and
    # nothing may be printed that a parser could take for a result.
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setattr(sys, "argv", ["bench.py", "--model", "mnist"])
    assert bench.main() == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "no accelerator" in out.err


def test_entry_has_no_second_process(bench):
    # The probe child, the bench child and the exit-0-with-null record
    # are gone; main() is the bench.
    for name in ("_supervise", "_probe_backend", "_RC_CPU_FALLBACK",
                 "_inner_main", "_sync"):
        assert not hasattr(bench, name)
    assert "--inner" not in bench._build_parser().format_help()


def test_report_emits_both_hfu_and_mfu(bench, monkeypatch, capsys):
    # VERDICT r4 weak #1: executed FLOPs (remat recompute included) must
    # be labeled hfu; mfu comes from the analytic remat-invariant count.
    monkeypatch.setattr(bench, "_peak_tflops", lambda: 100.0)
    rec = bench._report("m", "u", 1.0, 0.5, 2e12, model_flops=1e12)
    assert rec["hfu"] == pytest.approx(0.04)   # 4 TFLOP/s executed
    assert rec["mfu"] == pytest.approx(0.02)   # 2 TFLOP/s model
    assert rec["achieved_tflops"] == pytest.approx(4.0)
    assert rec["model_tflops"] == pytest.approx(2.0)
    # and the line says where it ran, so a CPU run cannot pass for a chip
    assert (rec["platform"], rec["device_kind"], rec["devices"]) == \
        ("cpu", "cpu", 8)


def test_report_without_model_flops_collapses_to_hfu(bench, monkeypatch,
                                                     capsys):
    # Vision configs run without remat: executed == model by construction.
    monkeypatch.setattr(bench, "_peak_tflops", lambda: 100.0)
    rec = bench._report("m", "u", 1.0, 0.5, 2e12)
    assert rec["mfu"] == rec["hfu"]


def test_lm_model_flops_is_palm_convention(bench):
    # 6 FLOPs per matmul param per token + 12·L·T·d attention.
    got = bench._lm_model_flops(10_000, n_layers=2, seq_len=8, d_attn=4,
                                n_tokens=16)
    assert got == (6 * 10_000 + 12 * 2 * 8 * 4) * 16
