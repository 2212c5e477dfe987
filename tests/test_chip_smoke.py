"""chip_smoke.py is the proof that the system starts on the chip, so the
ways it could pass without proving that are closed: no TPU is a failure,
and a failing phase cannot end in exit 0 or an ``"ok": true`` line."""

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_args, **env):
    return subprocess.run(
        [sys.executable] + code_or_args, cwd=_REPO, capture_output=True,
        text=True, timeout=300, env={**os.environ, **env})


def test_exits_nonzero_and_prints_no_result_without_a_tpu():
    r = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_a_failing_phase_cannot_exit_zero():
    # Even the CPU rehearsal (the only mode that runs here) must die with
    # the phase: the kernels phase is stubbed out, the trainer phase fails.
    r = _run(["-c", (
        "import sys, chip_smoke\n"
        "def boom(ctx):\n"
        "    assert False, 'trainer phase failed'\n"
        "chip_smoke.phase_kernels = lambda ctx: None\n"
        "chip_smoke.phase_trainer = boom\n"
        "sys.argv = ['chip_smoke.py', '--rehearse-cpu']\n"
        "sys.exit(chip_smoke.main())\n")])
    assert r.returncode != 0
    assert "trainer phase failed" in r.stderr
    assert '"ok"' not in r.stdout
    assert "says nothing about the chip" in r.stdout
